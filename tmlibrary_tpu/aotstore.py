"""Content-addressed serialized AOT executable store (cold-start plane).

A steady-state run never pays XLA compile, but every
daemon restart, bucket-ladder escalation and newly joined fleet host
compiles cold on the critical path — tens of seconds before the first
batch lands.  This module makes compiled executables *durable and
shareable*: perf.py's AOT ``lower().compile()`` path exports each
executable (``jax.experimental.serialize_executable``) into an
atomic-write, LRU-capped on-disk store, and imports it back on the next
process — or the next *host*, when the store lives in a shared serve
root — instead of compiling.

Keying contract (stale artifacts can never load):

* the **entry digest** hashes the full program identity — the perf
  program name (which already folds in the description digest +
  ``program_digest_extras`` incl. weight/QC keys), the capacity rung
  and the exact input signature (treedef + leaf shapes/dtypes) — plus
  the **backend fingerprint**;
* the fingerprint is (jax version, jaxlib version, backend name,
  device count, digest of this package's sources): any toolchain,
  topology or code change produces a different digest, so a stale
  artifact is simply never *found*.  The fingerprint
  is additionally re-checked from the meta sidecar at import time
  (defense in depth) and a mismatch refuses LOUDLY.

Store layout (``TMX_AOT_STORE_DIR`` env > ``TM_AOT_STORE_DIR`` config >
process default (serve daemons point this at the shared serve root) >
next to the compile cache, ``<checkout>/.cache/aot``)::

    <dir>/<digest>.bin    pickled {payload, in_tree, out_tree}
    <dir>/<digest>.json   meta sidecar: program/capacity, fingerprint,
                          size, compile_s, timestamps

Writes are tmp-file + ``os.replace`` (the atomicio discipline) so a
concurrent reader never sees a torn entry; a corrupt/undeserializable
payload warns loudly, deletes the entry, and falls back to a cold
compile — the store may never break a run.  ``tmx cache list|gc`` is
the operator surface; ``prune()`` LRU-caps the store after every
export.

Everything here mirrors into ``tmx_compile_{cold,warm,import_hit,
export}_total`` counters and the ``tmx_compile_seconds_saved_total``
gauge (the WARM row in ``tmx top`` / ``tmx serve status``).
"""

from __future__ import annotations

import contextlib
import hashlib
import json
import logging
import os
import pickle
import threading
import time
from typing import Any

from tmlibrary_tpu.atomicio import atomic_write_text

logger = logging.getLogger(__name__)

#: env toggle (beats config): "0"/"false"/... disables the store
ENV_ENABLE = "TMX_AOT_STORE"
#: env override for the store directory (beats config + process default)
ENV_DIR = "TMX_AOT_STORE_DIR"

_FALSE_VALUES = ("0", "false", "no", "off")

#: default LRU cap on total payload bytes (4 GiB).  It has to hold at
#: least one plate's whole bucket ladder: a process walks the rungs in
#: the same order every time, and under least-recently-used eviction a
#: store one rung too small gives the next walk NO hit at all (each
#: export evicts the rung asked for next).  Serialized jterator
#: executables are single-digit MBs on CPU; the config-3 program for one
#: 2160x2160 field is 91 MB on a v5e at every rung (166 MB before the run
#: scans were rewritten), so its 8-rung ladder is 0.73 GB — over the
#: former 1 GiB cap at 166 MB, which is why the second chip run of PR 21
#: imported nothing (PERF.md).
DEFAULT_MAX_BYTES = 4 << 30

_LOCK = threading.Lock()
#: process-default directory (serve daemons point this at the shared
#: serve root so fleet peers import each other's exports); env/config
#: still win — see :func:`store_dir`
_PROCESS_DEFAULT_DIR: str | None = None
#: accumulated compile seconds avoided by import hits (process-wide),
#: mirrored into the tmx_compile_seconds_saved_total gauge
_SECONDS_SAVED = 0.0
#: process-wide compile-event tallies by kind (cold/warm/import_hit/
#: export) — a registry-free mirror of the tmx_compile_*_total counters
#: for consumers without a registry (serve job_done deltas, bench)
_COUNTS: dict = {}


def enabled() -> bool:
    """Whether the executable store is on.  ``TMX_AOT_STORE`` env beats
    the install config (``TM_AOT_STORE`` / INI ``aot_store``); the
    default is ON — tests/conftest.py turns it off so compile-count
    pinning stays deterministic, and opts back in per test."""
    env = os.environ.get(ENV_ENABLE)
    if env is not None:
        return env.strip().lower() not in _FALSE_VALUES
    try:
        from tmlibrary_tpu.config import _setting

        return str(_setting("aot_store", "1")).strip().lower() \
            not in _FALSE_VALUES
    except Exception:
        return True


def speculation_enabled() -> bool:
    """Whether compile-ahead speculation (the background warm thread
    precompiling likely next capacity rungs) is on.  Independent knob
    (``TMX_AOT_SPECULATE`` / ``aot_speculate``) because speculation is
    useful even with the on-disk store off (in-process escalation
    warm-up) and vice versa."""
    env = os.environ.get("TMX_AOT_SPECULATE")
    if env is not None:
        return env.strip().lower() not in _FALSE_VALUES
    try:
        from tmlibrary_tpu.config import _setting

        return str(_setting("aot_speculate", "1")).strip().lower() \
            not in _FALSE_VALUES
    except Exception:
        return True


def set_process_default_dir(directory: str | None) -> None:
    """Set the process-default store directory (serve daemons call this
    with ``<serve_root>/aotstore`` so every fleet host shares one
    store).  Explicit env/config settings still take precedence."""
    global _PROCESS_DEFAULT_DIR
    with _LOCK:
        _PROCESS_DEFAULT_DIR = str(directory) if directory else None


def store_dir(directory: str | None = None) -> str:
    """Resolve the store directory: explicit arg > ``TMX_AOT_STORE_DIR``
    env > config > process default > next to the compile cache
    (``$JAX_COMPILATION_CACHE_DIR/aot`` when that variable is set, else
    ``<checkout>/.cache/aot``)."""
    if directory:
        return str(directory)
    env = os.environ.get(ENV_DIR)
    if env:
        return env
    try:
        from tmlibrary_tpu.config import _setting

        configured = _setting("aot_store_dir", "")
    except Exception:
        configured = ""
    if configured:
        return str(configured)
    with _LOCK:
        if _PROCESS_DEFAULT_DIR:
            return _PROCESS_DEFAULT_DIR
    cache_root = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if cache_root:
        return os.path.join(cache_root, "aot")
    from tmlibrary_tpu.utils import checkout_cache_dir

    return checkout_cache_dir("aot")


def max_store_bytes() -> int:
    """LRU cap on total payload bytes (``TMX_AOT_STORE_MAX_BYTES`` env /
    ``aot_store_max_bytes`` config; <=0 means uncapped)."""
    raw = os.environ.get("TMX_AOT_STORE_MAX_BYTES")
    if raw is None:
        try:
            from tmlibrary_tpu.config import _setting

            raw = _setting("aot_store_max_bytes", str(DEFAULT_MAX_BYTES))
        except Exception:
            raw = str(DEFAULT_MAX_BYTES)
    try:
        return int(raw)
    except (TypeError, ValueError):
        return DEFAULT_MAX_BYTES


# ------------------------------------------------------------- identity

_PACKAGE_DIGEST: str | None = None


def package_digest() -> str:
    """Digest of this package's Python sources.  An executable is the
    compiled form of THIS code: the program name digests the pipeline
    description, not ``ops/``, so without this a store written before an
    edit to an op would hand the edited code its old executable."""
    global _PACKAGE_DIGEST
    if _PACKAGE_DIGEST is None:
        root = os.path.dirname(os.path.abspath(__file__))
        h = hashlib.sha1()
        for directory, subdirs, names in os.walk(root):
            subdirs.sort()
            for name in sorted(n for n in names if n.endswith(".py")):
                path = os.path.join(directory, name)
                h.update(os.path.relpath(path, root).encode())
                with open(path, "rb") as f:
                    h.update(f.read())
        _PACKAGE_DIGEST = h.hexdigest()[:16]
    return _PACKAGE_DIGEST


def fingerprint_info() -> dict:
    """The toolchain/topology/code facts the fingerprint digests.  Device
    count matters: an executable compiled for 8 virtual CPU devices is
    not the one a single-device process wants."""
    import jax
    import jaxlib

    return {
        "jax": getattr(jax, "__version__", "unknown"),
        "jaxlib": getattr(jaxlib, "__version__", "unknown"),
        "backend": jax.default_backend(),
        "device_count": jax.device_count(),
        "package": package_digest(),
    }


def backend_fingerprint(info: dict | None = None) -> str:
    """Stable digest of :func:`fingerprint_info` — part of every entry
    digest, so artifacts from a different jax/jaxlib/backend/topology
    are never even looked up."""
    info = info or fingerprint_info()
    blob = "|".join(
        f"{k}={info.get(k)}"
        for k in ("jax", "jaxlib", "backend", "device_count", "package")
    )
    return hashlib.sha1(blob.encode()).hexdigest()[:16]


def entry_digest(program: str, capacity: int | None,
                 signature: Any, fingerprint: str | None = None) -> str:
    """Content address of one executable: full program identity (the
    perf program name already folds in the description digest and
    ``program_digest_extras``) + capacity rung + input signature +
    backend fingerprint."""
    fp = fingerprint or backend_fingerprint()
    blob = "|".join([str(program), str(capacity), repr(signature), fp])
    return hashlib.sha1(blob.encode()).hexdigest()


def _paths(digest: str, directory: str | None = None) -> tuple[str, str]:
    d = store_dir(directory)
    return os.path.join(d, digest + ".bin"), os.path.join(d, digest + ".json")


# ------------------------------------------------------------ telemetry

def _count(kind: str, program: str | None = None, amount: float = 1.0) -> None:
    """Bump ``tmx_compile_<kind>_total`` (cold/warm/import_hit/export).
    Observability may never break the run."""
    with _LOCK:
        _COUNTS[kind] = _COUNTS.get(kind, 0.0) + float(amount)
    try:
        from tmlibrary_tpu import telemetry

        if telemetry.enabled():
            labels = {"program": str(program)} if program else {}
            telemetry.get_registry().counter(
                f"tmx_compile_{kind}_total", **labels
            ).inc(amount)
    except Exception:
        pass


def note_cold(program: str | None = None) -> None:
    """A real ``lower().compile()`` ran on the critical path."""
    _count("cold", program)


def note_warm(program: str | None = None) -> None:
    """An executable was already waiting (speculative precompile or
    store import) when first requested — no critical-path compile."""
    _count("warm", program)


def _note_saved(seconds: float, program: str | None = None) -> None:
    global _SECONDS_SAVED
    with _LOCK:
        _SECONDS_SAVED += float(seconds)
        total = _SECONDS_SAVED
    try:
        from tmlibrary_tpu import telemetry

        if telemetry.enabled():
            telemetry.get_registry().gauge(
                "tmx_compile_seconds_saved_total"
            ).set(round(total, 4))
    except Exception:
        pass


def seconds_saved() -> float:
    """Compile seconds avoided by import hits so far (process-wide)."""
    with _LOCK:
        return _SECONDS_SAVED


def reset_seconds_saved() -> None:
    """Zero the saved-seconds accumulator (tests)."""
    global _SECONDS_SAVED
    with _LOCK:
        _SECONDS_SAVED = 0.0


def counts_snapshot() -> dict:
    """Process-wide cold/warm/import_hit/export tallies — a registry-free
    mirror of the ``tmx_compile_*_total`` counters, for per-job deltas
    (serve stamps them on ``job_done``) and status surfaces."""
    with _LOCK:
        return dict(_COUNTS)


def reset_counts() -> None:
    """Zero the process tallies (tests)."""
    with _LOCK:
        _COUNTS.clear()


# ---------------------------------------------------------- export/import

def compile_for_store():
    """Context for the compile of an executable the store will take:
    JAX's persistent cache may still serve it, but does not take a second
    copy.  A real field's executable is 91 MB, and under a capped cache
    (192 MiB on the chip machine) the copies evicted every small program
    a second process would have hit (PERF.md, PR 21)."""
    if not enabled():
        return contextlib.nullcontext()
    # the context-manager form of the option has no public name
    from jax._src import config as jax_config

    return jax_config.persistent_cache_min_compile_time_secs(float("inf"))


_CACHE_HITS = threading.local()
_LISTENING = False


def cache_hits_seen() -> int:
    """How many compiles JAX's persistent compilation cache has served on
    this thread since the first call.  A caller reads it before and after
    a ``compile()``: an executable the cache served stays out of the
    store — the cache already holds it, and with jaxlib 0.9.0 an XLA:CPU
    executable that was itself loaded from the cache serializes into a
    payload that loads and then fails to run ("Function ... not
    found")."""
    global _LISTENING
    with _LOCK:
        if not _LISTENING:
            import jax.monitoring

            def on_event(event: str, **_) -> None:
                if event == "/jax/compilation_cache/cache_hits":
                    _CACHE_HITS.n = getattr(_CACHE_HITS, "n", 0) + 1

            jax.monitoring.register_event_listener(on_event)
            _LISTENING = True
    return getattr(_CACHE_HITS, "n", 0)


def export_entry(compiled: Any, *, program: str, step: str = "jterator",
                 capacity: int | None = None,
                 signature: Any = None, compile_s: float | None = None,
                 directory: str | None = None) -> str | None:
    """Serialize ``compiled`` into the store.  Returns the entry digest,
    or None when the store is off or the backend refuses to serialize
    (some backends/executables cannot — graceful, debug-logged, never a
    crash).  Write is atomic (tmp + replace) and the LRU cap is enforced
    after."""
    if not enabled():
        return None
    try:
        from jax.experimental.serialize_executable import serialize

        payload, in_tree, out_tree = serialize(compiled)
        # the devices the executable was compiled for: loading it onto
        # every device of the backend instead makes a one-device program
        # demand one input shard per device
        device_ids = [
            d.id for d in compiled._executable.xla_executable.local_devices()
        ]
        blob = pickle.dumps(
            {"payload": payload, "in_tree": in_tree, "out_tree": out_tree,
             "device_ids": device_ids},
            protocol=pickle.HIGHEST_PROTOCOL,
        )
    except Exception as exc:
        # Host-callback programs (jax.pure_callback routes, e.g. the
        # TMX_NATIVE cpu fallbacks) embed process-local PyCapsule
        # pointers and can never serialize; warn once per program so the
        # operator learns the store is inert for it, then degrade to
        # plain in-process caching.
        from tmlibrary_tpu.log import warn_once

        warn_once(
            logger, f"aot_export:{program}",
            "aotstore: backend refused to serialize %s (%s) — executable "
            "store disabled for this program (host-callback programs "
            "cannot export; on cpu set TMX_NATIVE=0 for a pure-XLA "
            "program)", program, exc)
        return None
    try:
        info = fingerprint_info()
        fp = backend_fingerprint(info)
        digest = entry_digest(program, capacity, signature, fp)
        bin_path, meta_path = _paths(digest, directory)
        if os.path.exists(meta_path):
            return digest  # already exported (peer or earlier run)
        os.makedirs(os.path.dirname(bin_path), exist_ok=True)
        now = time.time()
        tmp = f"{bin_path}.{os.getpid()}.tmp"
        with open(tmp, "wb") as f:
            f.write(blob)
        os.replace(tmp, bin_path)
        atomic_write_text(meta_path, json.dumps({
            "digest": digest,
            "program": str(program),
            "step": str(step),
            "capacity": capacity,
            "signature": repr(signature),
            "fingerprint": fp,
            "fingerprint_info": info,
            "size_bytes": len(blob),
            "compile_s": round(compile_s, 4) if compile_s else None,
            "created_at_unix": now,
            "last_used_unix": now,
        }, indent=2) + "\n")
    except Exception as exc:
        logger.debug("aotstore: export of %s failed: %s", program, exc)
        return None
    _count("export", program)
    try:
        prune(directory=directory)
    except Exception:
        pass
    return digest


def _drop_entry(digest: str, directory: str | None = None) -> None:
    for path in _paths(digest, directory):
        try:
            os.remove(path)
        except OSError:
            pass


def import_entry(*, program: str, capacity: int | None = None,
                 signature: Any = None,
                 directory: str | None = None) -> tuple[Any, dict] | None:
    """Load a serialized executable back.  Returns ``(compiled, meta)``
    on a hit, None on miss/disabled.  A fingerprint mismatch or a
    corrupt/undeserializable artifact refuses LOUDLY (warning log), the
    corrupt entry is deleted, and the caller falls back to a cold
    compile — a poisoned store may never break a run."""
    if not enabled():
        return None
    try:
        fp = backend_fingerprint()
        digest = entry_digest(program, capacity, signature, fp)
        bin_path, meta_path = _paths(digest, directory)
        if not (os.path.exists(bin_path) and os.path.exists(meta_path)):
            return None
        with open(meta_path) as f:
            meta = json.load(f)
        if meta.get("fingerprint") != fp:
            logger.warning(
                "aotstore: entry %s fingerprint %s does not match this "
                "toolchain (%s) — refusing stale artifact, compiling cold",
                digest[:12], meta.get("fingerprint"), fp,
            )
            return None
    except Exception as exc:
        logger.warning("aotstore: unreadable meta for %s: %s — compiling "
                       "cold", program, exc)
        return None
    try:
        from tmlibrary_tpu import telemetry

        with telemetry.span("store_import", program=program,
                            bytes=os.path.getsize(bin_path)):
            with open(bin_path, "rb") as f:
                doc = pickle.loads(f.read())
            from jax.experimental.serialize_executable import (
                deserialize_and_load,
            )

            import jax

            by_id = {d.id: d for d in jax.devices()}
            compiled = deserialize_and_load(
                doc["payload"], doc["in_tree"], doc["out_tree"],
                execution_devices=[by_id[i] for i in doc["device_ids"]],
            )
    except Exception as exc:
        logger.warning(
            "aotstore: corrupt artifact %s for %s (%s) — deleting entry "
            "and compiling cold", digest[:12], program, exc,
        )
        _drop_entry(digest, directory)
        return None
    # LRU touch (best-effort; a concurrent writer losing the race only
    # costs eviction-order precision)
    try:
        meta["last_used_unix"] = time.time()
        atomic_write_text(meta_path, json.dumps(meta, indent=2) + "\n")
    except Exception:
        pass
    _count("import_hit", program)
    saved = meta.get("compile_s")
    if isinstance(saved, (int, float)) and saved > 0:
        _note_saved(float(saved), program)
    return compiled, meta


# ------------------------------------------------------------ operations

def list_entries(directory: str | None = None) -> list[dict]:
    """Meta rows for every store entry, most-recently-used first.  Each
    row adds ``age_s`` (since creation) and ``stale`` (fingerprint vs
    the *current* toolchain — informational; stale entries are inert
    because lookups digest the live fingerprint)."""
    d = store_dir(directory)
    try:
        names = sorted(os.listdir(d))
    except OSError:
        return []
    try:
        fp = backend_fingerprint()
    except Exception:
        fp = None
    now = time.time()
    rows = []
    for name in names:
        if not name.endswith(".json"):
            continue
        try:
            with open(os.path.join(d, name)) as f:
                meta = json.load(f)
        except (OSError, ValueError):
            continue
        if not isinstance(meta, dict) or "digest" not in meta:
            continue
        created = meta.get("created_at_unix")
        meta["age_s"] = round(now - created, 1) \
            if isinstance(created, (int, float)) else None
        meta["stale"] = (fp is not None
                         and meta.get("fingerprint") != fp)
        rows.append(meta)
    rows.sort(key=lambda m: m.get("last_used_unix") or 0.0, reverse=True)
    return rows


def warm_digests(directory: str | None = None, limit: int = 64) -> list[str]:
    """Most-recently-used entry digests (fleet heartbeat payload: what
    this host can warm a peer with)."""
    return [m["digest"] for m in list_entries(directory)[:limit]]


def store_stats(directory: str | None = None) -> dict:
    """One-line store summary for status surfaces and CI manifests."""
    rows = list_entries(directory)
    try:
        fp = backend_fingerprint()
    except Exception:
        fp = None
    return {
        "dir": store_dir(directory),
        "enabled": enabled(),
        "entries": len(rows),
        "total_bytes": sum(int(m.get("size_bytes") or 0) for m in rows),
        "stale_entries": sum(1 for m in rows if m.get("stale")),
        "fingerprint": fp,
        "seconds_saved": round(seconds_saved(), 4),
    }


def prune(directory: str | None = None, max_bytes: int | None = None,
          max_age_s: float | None = None,
          drop_stale_fingerprint: bool = False) -> dict:
    """Evict entries: orphans (payload without meta or vice versa),
    older than ``max_age_s``, stale-fingerprint (opt-in — they are
    harmless but dead weight), then least-recently-used past the
    ``max_bytes`` cap.  Returns ``{"removed": [digests], "kept": n,
    "total_bytes": n}``; never raises."""
    d = store_dir(directory)
    cap = max_store_bytes() if max_bytes is None else int(max_bytes)
    removed: list[str] = []
    try:
        names = set(os.listdir(d))
    except OSError:
        return {"removed": [], "kept": 0, "total_bytes": 0}
    rows = list_entries(d)
    known = {m["digest"] for m in rows}
    for name in names:
        stem, ext = os.path.splitext(name)
        if ext in (".bin", ".json") and stem not in known:
            try:
                os.remove(os.path.join(d, name))
            except OSError:
                pass
    now = time.time()
    keep: list[dict] = []
    for meta in rows:
        digest = meta["digest"]
        too_old = (max_age_s is not None
                   and isinstance(meta.get("created_at_unix"), (int, float))
                   and now - meta["created_at_unix"] > max_age_s)
        if too_old or (drop_stale_fingerprint and meta.get("stale")):
            _drop_entry(digest, d)
            removed.append(digest)
        else:
            keep.append(meta)
    if cap > 0:
        total = sum(int(m.get("size_bytes") or 0) for m in keep)
        # keep is MRU-first: evict from the tail
        while keep and total > cap:
            meta = keep.pop()
            _drop_entry(meta["digest"], d)
            removed.append(meta["digest"])
            total -= int(meta.get("size_bytes") or 0)
    return {
        "removed": removed,
        "kept": len(keep),
        "total_bytes": sum(int(m.get("size_bytes") or 0) for m in keep),
    }
