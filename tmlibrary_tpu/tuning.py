"""Machine-written tuning defaults (``tuning/TUNING.json``).

The hardware sweep (``scripts/tune_tpu.py``) writes its verdict —
``best_batch`` for the segment+measure chain and ``best_pipeline`` for the
fetch-amortization depth — into ``tuning/TUNING.json``.  This module is the
ONE runtime consumer shared by the production engine (the pipelined batch
executor's default depth, jterator's auto batch size) and ``bench.py``
(which re-exports these loaders so the scripts keep one definition of
the artifact path).

Provenance gate: only a file ``tune_tpu.py write_results`` itself produced
counts.  Hand-seeded or dry-run (``SMOKE``) artifacts never set production
defaults — a tuned default the hardware never measured is worse than a
static one.  ``TMX_TUNING_JSON`` redirects the file (rehearsals, tests).
"""

from __future__ import annotations

import datetime
import json
import os
import time
from pathlib import Path

from tmlibrary_tpu.atomicio import atomic_write_text


def tuning_json_path() -> str:
    """ONE definition of the tuning-results location (and its rehearsal
    redirect) — resolved at call time so env changes take effect without
    re-imports."""
    return os.environ.get(
        "TMX_TUNING_JSON",
        str(Path(__file__).resolve().parent.parent / "tuning" / "TUNING.json"),
    )


def _tuning_dir() -> str:
    return os.path.dirname(os.path.abspath(tuning_json_path()))


def bench_history_path() -> str:
    """Append-only bench history (``tuning/BENCH_HISTORY.jsonl``) — one
    JSON line per emitted bench/sweep record, the regression sentinel's
    input.  ``BENCH_HISTORY`` redirects it (tests, CI smoke); with no
    redirect it follows ``TMX_TUNING_JSON``'s directory so one redirect
    moves the whole artifact family at once."""
    return os.environ.get(
        "BENCH_HISTORY", os.path.join(_tuning_dir(), "BENCH_HISTORY.jsonl")
    )


def recapture_path() -> str:
    """Re-capture queue the regression sentinel writes
    (``tuning/RECAPTURE.json``): the labels to measure again on the
    chip."""
    return os.environ.get(
        "WATCH_RECAPTURE", os.path.join(_tuning_dir(), "RECAPTURE.json")
    )


def append_bench_history(record: dict, path: str | None = None) -> str | None:
    """Append one bench record to the history, stamped with the append
    time.  Returns the path written, or None on any failure — history is
    observability and must never break the bench stdout contract."""
    try:
        path = path or bench_history_path()
        now = time.time()
        line = {
            "recorded_at": datetime.datetime.now(
                datetime.timezone.utc
            ).isoformat(timespec="seconds"),
            "recorded_at_unix": now,
            **record,
        }
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        with open(path, "a") as f:
            f.write(json.dumps(line, sort_keys=True) + "\n")
        return path
    except Exception:
        return None


def load_bench_history(path: str | None = None) -> list[dict]:
    """Parsed history lines, oldest first; corrupt lines are skipped (an
    interrupted append must not poison the whole history)."""
    path = path or bench_history_path()
    out: list[dict] = []
    try:
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue
                if isinstance(rec, dict):
                    out.append(rec)
    except OSError:
        return []
    return out


def load_tuning() -> dict | None:
    """The machine-written tuning verdict, or None when absent, unreadable,
    or failing the provenance gate (no ``written_by``, or a SMOKE dry-run
    methodology)."""
    try:
        with open(tuning_json_path()) as f:
            tuning = json.load(f)
    except (OSError, ValueError):
        return None
    if "SMOKE(" in str(tuning.get("timing_methodology", "")):
        return None  # dry-run sweep artifacts never set production defaults
    return tuning if "written_by" in tuning else None


def _positive_int(value) -> int | None:
    if isinstance(value, (int, float)) and int(value) > 0:
        return int(value)
    return None


def tuned_pipeline_depth() -> int | None:
    """The hardware-swept ``best_pipeline`` in-flight depth, or None."""
    tuning = load_tuning()
    return _positive_int(tuning.get("best_pipeline")) if tuning else None


#: pixels of the site ``best_batch`` was swept at: ``scripts/tune_tpu.py``
#: runs bench.py at its default 256x256 site (``BENCH_SITE_SIZE``)
TUNED_SITE_PIXELS = 256 * 256


def tuned_batch_size() -> int | None:
    """The hardware-swept ``best_batch`` site batch (sites of
    :data:`TUNED_SITE_PIXELS` pixels), or None."""
    tuning = load_tuning()
    return _positive_int(tuning.get("best_batch")) if tuning else None


def tuned_object_capacity(backend: str | None = None) -> int | None:
    """The swept object-capacity bucket verdict for ``backend``, or None.

    A sweep records the winning capacity through
    :func:`record_config_sweep` (``best_capacity``); the jterator step
    uses it as the first-batch routing hint before any on-run object
    counts exist.  Two shapes are accepted: a per-backend dict
    (``{"cpu": 64, "tpu": 1024}``) or a plain value scoped by the file's
    top-level ``backend`` field.  A verdict measured on one backend never
    sets another backend's default, and malformed values degrade to None
    (the static default) rather than erroring."""
    tuning = load_tuning()
    if not tuning:
        return None
    if backend is None:
        import jax

        backend = jax.default_backend()
    entry = tuning.get("object_capacity")
    if isinstance(entry, dict):
        return _positive_int(entry.get(backend))
    if tuning.get("backend") == backend:
        return _positive_int(entry)
    return None


_SCHEDULE_MODES = ("pack", "off")


def tuned_schedule(backend: str | None = None) -> str | None:
    """The swept work-aware scheduling verdict for ``backend``
    (``"pack"`` | ``"off"``), or None.  A sweep records the winner
    through :func:`record_config_sweep` (``best_schedule``); the jterator
    dispatch plane consumes it through
    ``workflow.schedule.resolve_schedule``'s precedence chain.  Same
    provenance and backend-scoping rules as
    :func:`tuned_object_capacity` — a verdict measured on one backend
    never sets another's default, and malformed values degrade to None
    (the default: packing on)."""
    tuning = load_tuning()
    if not tuning:
        return None
    if backend is None:
        import jax

        backend = jax.default_backend()
    entry = tuning.get("schedule")
    if isinstance(entry, dict):
        value = entry.get(backend)
    elif isinstance(entry, str) and tuning.get("backend") == backend:
        value = entry
    else:
        value = None
    return value if value in _SCHEDULE_MODES else None


_ANALYTICS_INDEX_MODES = ("ivf", "brute")


def tuned_analytics_index(backend: str | None = None) -> str | None:
    """The swept analytics kNN index verdict for ``backend``
    (``"ivf"`` | ``"brute"``), or None.  ``bench.py`` BENCH_CONFIG=
    analytics records the winner (``best_index``) when the sweep is
    asked to persist its verdict; same provenance and backend-scoping
    rules as :func:`tuned_object_capacity` — a verdict measured on
    one backend never sets another's default, and malformed values
    degrade to None (the auto size cutover)."""
    tuning = load_tuning()
    if not tuning:
        return None
    if backend is None:
        import jax

        backend = jax.default_backend()
    entry = tuning.get("analytics_index")
    if isinstance(entry, dict):
        value = entry.get(backend)
    elif isinstance(entry, str) and tuning.get("backend") == backend:
        value = entry
    else:
        value = None
    return value if value in _ANALYTICS_INDEX_MODES else None


def record_config_sweep(config: str, entry: dict) -> dict:
    """Merge one per-config sweep verdict into the tuning file.

    ``bench.py``'s analytics mode calls this with a row like
    ``{"backend": ..., "best_index": ..., "rows": [...]}``.  Existing
    keys written by ``tune_tpu.py`` (the top-level
    ``best_batch``/``best_pipeline`` and their provenance stamps) are
    preserved — the sweep only owns ``config_sweeps[config]`` and the
    per-backend verdicts its entry names (``best_capacity``,
    ``best_schedule``, ``best_index``).  Returns the merged document."""
    path = tuning_json_path()
    try:
        with open(path) as f:
            data = json.load(f)
        if not isinstance(data, dict):
            data = {}
    except (OSError, ValueError):
        data = {}
    # provenance: only stamp authorship when this write creates the file;
    # never claim tune_tpu.py's measurements as our own
    data.setdefault("written_by", "bench.py analytics sweep")
    data.setdefault("config_sweeps", {})[str(config)] = entry
    backend = entry.get("backend")
    capacity = _positive_int(entry.get("best_capacity"))
    if backend and capacity:
        caps = data.get("object_capacity")
        if not isinstance(caps, dict):
            caps = {}
        caps[backend] = capacity
        data["object_capacity"] = caps
    sched = entry.get("best_schedule")
    if backend and sched in _SCHEDULE_MODES:
        verdict = data.get("schedule")
        if not isinstance(verdict, dict):
            verdict = {}
        verdict[backend] = sched
        data["schedule"] = verdict
    index_mode = entry.get("best_index")
    if backend and index_mode in _ANALYTICS_INDEX_MODES:
        idx = data.get("analytics_index")
        if not isinstance(idx, dict):
            idx = {}
        idx[backend] = index_mode
        data["analytics_index"] = idx
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    atomic_write_text(
        path, json.dumps(data, indent=2, sort_keys=True) + "\n"
    )
    return data
