"""Performance attribution: roofline cost model, compile telemetry, history.

The repo's headline metric is jterator sites/sec/chip, but throughput alone
cannot say *where* the gap to the hardware ceiling lives (ROADMAP item 3:
MFU 0.000246 with no per-program attribution).  This module is the one
place the XLA cost model is read and interpreted:

* :func:`program_cost` / :func:`cost_from_compiled` — FLOPs + bytes
  accessed from ``lowered.compile().cost_analysis()``, hardened so a
  backend that raises or reports nothing yields ``None`` fields instead
  of crashing a bench or a run;
* the **roofline** verdict — arithmetic intensity (FLOPs/byte) against
  the v5e ridge point (:data:`V5E_BF16_PEAK_FLOPS` /
  :data:`V5E_HBM_PEAK_BPS` ≈ 240 FLOPs/byte): programs below the ridge
  are memory-bound, above it compute-bound.  The v5e roofline is the
  *reference target* even when the measurement ran on CPU — the question
  "where would this program sit on the chip" is exactly what a
  CPU-rehearsed profile is for;
* :func:`instrument_batch_fn` — wraps a ``cached_batch_fn`` program so
  its first call per input signature is an AOT ``lower().compile()``
  (timed → compile histogram; cost analysis read off the same compiled
  object, so attribution adds **zero extra compiles**) and subsequent
  calls execute that compiled object directly.  New signatures count as
  recompiles.  A program the compiler refuses raises, as the plain jit
  call would; an executable that fails at call time is reported and
  dropped in favour of jit, and is never retried on inputs it already
  consumed.  The same hook is the
  cold-start plane's beachhead (:mod:`tmlibrary_tpu.aotstore`): before
  compiling it consults the serialized-executable store (an import hit
  skips the compile entirely — ``tmx_compile_import_hit_total``), after
  compiling it exports the executable for the next process/host, and
  :func:`speculate_compile` lets a background thread precompile the
  likely next capacity rung so escalation lands ``warm``;
* a process-wide profile store (:func:`perf_profiles` /
  :func:`perf_snapshot`) keyed by (program, step, capacity),
  mirrored into ``tmx_perf_*`` registry metrics and persisted by the
  engine as ``workflow/perf.json`` for ``tmx perf``;
* the **bench-history sentinel** (:func:`compare_history`) behind
  ``scripts/bench_regression.py`` and ``tmx perf history``: latest
  record vs the best certified one per (metric, config, backend class),
  with distinct exit codes for regression / staleness / missing
  baseline, and re-capture queue labels (what to measure again).

Everything here is observability: zero-cost when telemetry is disabled
(wrappers return the raw fn) and forbidden from perturbing numeric
results — the AOT-executed program is the same executable jit would have
built, pinned by the telemetry-on/off parity test.

jax is imported lazily so ``bench.py``'s parent process (which must not
initialise a backend before choosing one) can import this module.
"""

from __future__ import annotations

import dataclasses
import json
import logging
import os
import re
import threading
import time
from typing import Any, Callable

from tmlibrary_tpu import tuning
from tmlibrary_tpu.atomicio import atomic_write_text

logger = logging.getLogger(__name__)

# ---------------------------------------------------------------------------
# Roofline peaks (moved from bench.py; bench re-exports for compat)

#: MXU peak of one TPU v5e (v5 lite) chip in bf16; the pipeline runs mostly
#: f32 (correctness gate: HIGHEST-precision convs), so MFU against the bf16
#: peak is a conservative lower bound.
V5E_BF16_PEAK_FLOPS = 197e12
#: HBM bandwidth of one v5e chip (public spec: 819 GB/s)
V5E_HBM_PEAK_BPS = 819e9

#: Published per-chip peaks, keyed by ``jax.devices()[0].device_kind``:
#: (bf16 FLOP/s, HBM bytes/s).
DEVICE_PEAKS: dict[str, tuple[float, float]] = {
    # TPU v5e — Google Cloud documentation, "TPU v5e" (system
    # architecture table): 197 TFLOP/s bf16, 819 GB/s HBM per chip
    "TPU v5 lite": (V5E_BF16_PEAK_FLOPS, V5E_HBM_PEAK_BPS),
}


def device_peaks(device_kind: str) -> tuple[float, float]:
    """(peak FLOP/s, peak bytes/s) of one chip of ``device_kind``.  A
    kind that is not in :data:`DEVICE_PEAKS` is an error: a roofline
    share against another chip's peaks is a wrong number, not a
    default."""
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}: add it "
            "to perf.DEVICE_PEAKS with its source"
        ) from None


def attached_device_kind() -> str | None:
    """``device_kind`` of the default device, or None on the CPU
    platform (a CPU run has no device roofline to take a share of)."""
    import jax

    device = jax.devices()[0]
    return None if device.platform == "cpu" else device.device_kind


def ridge_point(peak_flops: float = V5E_BF16_PEAK_FLOPS,
                peak_bps: float = V5E_HBM_PEAK_BPS) -> float:
    """Arithmetic intensity (FLOPs/byte) where the roofline transitions
    from memory- to compute-bound."""
    return peak_flops / peak_bps


# ---------------------------------------------------------------------------
# Cost model

@dataclasses.dataclass
class ProgramCost:
    """XLA cost-model readout for one compiled program.  Fields are None
    when the backend does not report them — never a crash (satellite:
    hardened ``cost_analysis()`` failure path)."""

    flops: float | None = None
    bytes: float | None = None

    @property
    def arithmetic_intensity(self) -> float | None:
        if self.flops and self.bytes:
            return self.flops / self.bytes
        return None

    def bound_by(self, peak_flops: float = V5E_BF16_PEAK_FLOPS,
                 peak_bps: float = V5E_HBM_PEAK_BPS) -> str | None:
        """"memory" below the roofline ridge, "compute" above, None when
        the cost model reported nothing."""
        ai = self.arithmetic_intensity
        if ai is None:
            return None
        return "memory" if ai < peak_flops / peak_bps else "compute"


def cost_from_compiled(compiled: Any) -> ProgramCost:
    """Read FLOPs + bytes accessed off an already-compiled XLA program.

    A backend whose ``cost_analysis()`` raises, returns nothing, or
    reports zeros degrades to None fields."""
    try:
        analysis = compiled.cost_analysis()
        if not isinstance(analysis, dict):
            return ProgramCost()
        flops = float(analysis.get("flops", 0.0))
        nbytes = float(analysis.get("bytes accessed", 0.0))
        return ProgramCost(flops if flops > 0 else None,
                           nbytes if nbytes > 0 else None)
    except Exception:
        return ProgramCost()


def program_cost(jitted_fn: Callable, *args, **kwargs) -> ProgramCost:
    """Compile ``jitted_fn`` for ``args`` and read its cost.  Never raises
    — a backend that cannot lower/compile/analyze yields empty cost."""
    try:
        compiled = jitted_fn.lower(*args, **kwargs).compile()
    except Exception:
        return ProgramCost()
    return cost_from_compiled(compiled)


def cost_flops(jitted_fn: Callable, *args) -> tuple[float | None, float | None]:
    """(total FLOPs, total bytes accessed) of one compiled batch step via
    XLA's cost model — (None, None) if the backend does not report it.
    Tuple form kept for bench.py's call sites."""
    cost = program_cost(jitted_fn, *args)
    return (cost.flops, cost.bytes)


def flops_fields(flops, n_items, best_s, device_kind,
                 item_key="flops_per_site", nbytes=None) -> dict:
    """Roofline record fields from a measured best wall time (the bytes
    side travels with every record because MFU alone is the wrong lens
    for this memory/latency-shaped workload).  ``device_kind`` is
    :func:`attached_device_kind`: None (a CPU run) leaves the
    share-of-peak fields None, a kind without published peaks raises."""
    out = {}
    peak_flops, peak_bps = (device_peaks(device_kind)
                            if device_kind is not None else (None, None))
    if flops:
        achieved = flops / best_s
        out[item_key] = round(flops / n_items)
        out["achieved_tflops_per_sec"] = round(achieved / 1e12, 4)
        out["mfu_vs_v5e_bf16_peak"] = (
            round(achieved / peak_flops, 6) if peak_flops else None
        )
    if nbytes:
        bps = nbytes / best_s
        out["bytes_per_" + item_key.split("_per_")[-1]] = round(
            nbytes / n_items
        )
        out["achieved_gbytes_per_sec"] = round(bps / 1e9, 3)
        out["hbm_frac_vs_v5e_peak"] = (
            round(bps / peak_bps, 6) if peak_bps else None
        )
    if flops and nbytes:
        out["arithmetic_intensity"] = round(flops / nbytes, 3)
        out["bound_by"] = ProgramCost(flops, nbytes).bound_by()
    return out


# ---------------------------------------------------------------------------
# Per-program attribution store + compile telemetry

_LOCK = threading.Lock()
#: (program, step, capacity) -> serializable profile dict
_PROFILES: dict[tuple, dict] = {}
#: same key -> runtime state {"sigs": {signature: compiled|None}, "dead": bool}
_RUNTIME: dict[tuple, dict] = {}
#: beyond this many distinct input signatures per program the AOT path
#: stops caching executables (a shape zoo would churn memory for no
#: attribution value); calls fall through to the plain jit fn
_MAX_SIGNATURES = 8


def reset_profiles() -> None:
    """Drop all recorded program profiles (tests, fresh runs)."""
    with _LOCK:
        _PROFILES.clear()
        _RUNTIME.clear()


def perf_profiles() -> list[dict]:
    """Recorded program profiles, costliest (by FLOPs) first."""
    with _LOCK:
        entries = [dict(e) for e in _PROFILES.values()]
    entries.sort(key=lambda e: (e.get("flops") or 0.0), reverse=True)
    return entries


def perf_snapshot() -> dict:
    """Serializable snapshot for ``workflow/perf.json`` / ``tmx perf``."""
    return {
        "generated_at_unix": time.time(),
        "programs": perf_profiles(),
    }


def record_compile(*, program: str, step: str = "jterator",
                   capacity: int | None = None,
                   backend: str = "unknown", compile_s: float | None = None,
                   cost: ProgramCost | None = None,
                   recompile: bool = False) -> dict:
    """Record one compile event for a program variant: update the profile
    store and mirror ``tmx_perf_*`` metrics (compile counter + compile-time
    histogram per capacity rung, recompile counter, static cost gauges).
    Telemetry failures never propagate."""
    cost = cost or ProgramCost()
    key = (program, step, capacity)
    with _LOCK:
        entry = _PROFILES.setdefault(key, {
            "program": program,
            "step": step,
            "capacity": capacity,
            "backend": backend,
            "flops": None,
            "bytes": None,
            "arithmetic_intensity": None,
            "bound_by": None,
            "compiles": 0,
            "recompiles": 0,
            "compile_seconds_total": 0.0,
            "last_compile_s": None,
        })
        entry["backend"] = backend
        entry["compiles"] += 1
        if recompile:
            entry["recompiles"] += 1
        if compile_s is not None:
            entry["compile_seconds_total"] += compile_s
            entry["last_compile_s"] = round(compile_s, 4)
        if cost.flops is not None:
            entry["flops"] = cost.flops
        if cost.bytes is not None:
            entry["bytes"] = cost.bytes
        ai = cost.arithmetic_intensity
        if ai is not None:
            entry["arithmetic_intensity"] = round(ai, 3)
            entry["bound_by"] = cost.bound_by()
        result = dict(entry)
    try:
        from tmlibrary_tpu import telemetry

        if telemetry.enabled():
            reg = telemetry.get_registry()
            labels = {
                "program": str(program),
                "step": str(step),
                "capacity": str(capacity) if capacity else "none",
            }
            reg.counter("tmx_perf_compiles_total", **labels).inc()
            if recompile:
                reg.counter("tmx_perf_recompiles_total", **labels).inc()
            if compile_s is not None:
                reg.histogram(
                    "tmx_perf_compile_seconds", capacity=labels["capacity"],
                ).observe(compile_s)
            if cost.flops:
                reg.gauge("tmx_perf_program_flops", **labels).set(cost.flops)
            if cost.bytes:
                reg.gauge("tmx_perf_program_bytes", **labels).set(cost.bytes)
            if ai:
                reg.gauge(
                    "tmx_perf_program_arithmetic_intensity", **labels
                ).set(ai)
    except Exception:
        pass  # observability must never break the run
    return result


def _args_signature(args, kwargs):
    """Hashable (treedef, leaf shapes/dtypes) signature of a call — the
    same thing jit keys its executable cache on, minus static/weak-type
    subtleties.  A signature change means XLA recompiled."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten((args, kwargs))
    return (
        treedef,
        tuple(
            (getattr(leaf, "shape", None),
             str(getattr(leaf, "dtype", type(leaf).__name__)))
            for leaf in leaves
        ),
    )


def instrument_batch_fn(fn: Callable, *, program: str,
                        step: str = "jterator",
                        capacity: int | None = None,
                        sub_costs: Callable | None = None) -> Callable:
    """Wrap a jitted batch fn with compile/cost attribution.

    First call per input signature: ``fn.lower(...).compile()`` timed
    (the compile histogram), cost analysis read from the same compiled
    object, and the compiled executable cached and invoked — so the
    instrumented path performs exactly ONE compile, same as plain jit.
    Later signatures count as recompiles.  A ``fn`` without ``lower()``
    runs as it is; an executable that fails at call time is logged with
    its exception and dropped for that signature, and the call goes to
    ``fn`` only while its (possibly donated) inputs are still alive.
    With telemetry disabled the call is a passthrough.

    ``sub_costs``: optional ``(args, kwargs) -> [(name, ProgramCost)]``
    invoked once per new signature; each pair lands as its own roofline
    rung ``{program}:{name}``.  This is how analytically-costed
    sub-programs (the dl configs' conv forward, whose arithmetic
    intensity the whole-program XLA readout averages away under the
    decoder's integer traffic) get their own ``bound_by`` attribution."""
    key = (program, step, capacity)

    def wrapped(*args, **kwargs):
        from tmlibrary_tpu import telemetry

        if not telemetry.enabled():
            return fn(*args, **kwargs)
        return _instrumented_call(fn, key, args, kwargs,
                                  sub_costs=sub_costs)

    wrapped.__wrapped__ = fn
    wrapped.perf_key = key
    return wrapped


def _any_deleted(args, kwargs) -> bool:
    """True when a call consumed (donated) one of its input buffers."""
    import jax

    return any(
        getattr(leaf, "is_deleted", lambda: False)()
        for leaf in jax.tree_util.tree_leaves((args, kwargs))
    )


def _instrumented_call(fn, key, args, kwargs, sub_costs=None):
    from tmlibrary_tpu import aotstore

    program, step, capacity = key
    sig = _args_signature(args, kwargs)
    with _LOCK:
        state = _RUNTIME.setdefault(key, {"sigs": {}, "dead": False})
        known = sig in state["sigs"]
        compiled = state["sigs"].get(sig)
        dead = state["dead"]
        spec_hit = known and sig in state.get("speculative", ())
        if spec_hit:
            state["speculative"].discard(sig)
    if dead and not known:
        return fn(*args, **kwargs)
    if spec_hit:
        # a background speculation thread (or a store import it made)
        # already built this executable: no critical-path compile
        aotstore.note_warm(program)
    if not known:
        # one import or compile a program, however many threads ask at
        # once (the executor's persist workers re-launching fields at
        # the same rung): the others wait and take what the first built
        with _LOCK:
            build_lock = state.setdefault("build_lock", threading.Lock())
        with build_lock:
            with _LOCK:
                known = sig in state["sigs"]
                compiled = state["sigs"].get(sig)
            if not known:
                compiled = _build_signature(fn, key, sig, state, args,
                                            kwargs, sub_costs)
    if compiled is None:
        return fn(*args, **kwargs)
    try:
        return compiled(*args, **kwargs)
    except Exception as exc:
        # the executable and this call disagree (an imported artifact,
        # inputs placed on other devices than it was compiled for):
        # drop it so later calls go through jit, and say so
        with _LOCK:
            state["sigs"][sig] = None
        logger.warning(
            "perf: AOT executable of %s (capacity %s) failed, dropped "
            "in favour of jit: %s: %s", program, capacity,
            type(exc).__name__, exc,
        )
        if _any_deleted(args, kwargs):
            # the failed call already consumed its donated inputs; there
            # is nothing left to run jit on
            raise
    return fn(*args, **kwargs)


def _build_signature(fn, key, sig, state, args, kwargs, sub_costs):
    """The executable of a signature seen for the first time: imported
    from the store, else compiled (and exported).  Called under the
    program's build lock; returns None for a ``fn`` without ``lower()``."""
    from tmlibrary_tpu import aotstore

    program, step, capacity = key
    imported = _try_store_import(key, sig)
    if imported is not None:
        compiled, meta = imported
        with _LOCK:
            if len(state["sigs"]) < _MAX_SIGNATURES:
                state["sigs"][sig] = compiled
        # an import hit is NOT a compile: record_compile is skipped
        # so the zero-new-compiles pinning (warm-start tests / CI
        # smoke) holds; the profile store still learns about it
        record_import(program=program, step=step, capacity=capacity,
                      saved_s=meta.get("compile_s"))
        return compiled
    import jax

    compile_s = None
    compiled = None
    cache_hits = aotstore.cache_hits_seen()
    if hasattr(fn, "lower"):
        # a program the backend's compiler refuses raises here,
        # exactly as the plain jit call would
        t0 = time.perf_counter()
        with aotstore.compile_for_store():
            compiled = fn.lower(*args, **kwargs).compile()
        compile_s = time.perf_counter() - t0
    cost = cost_from_compiled(compiled) if compiled is not None \
        else ProgramCost()
    with _LOCK:
        recompile = bool(state["sigs"])
        if len(state["sigs"]) >= _MAX_SIGNATURES:
            state["dead"] = True
        else:
            state["sigs"][sig] = compiled
    backend = jax.default_backend()
    record_compile(program=program, step=step, capacity=capacity,
                   backend=backend,
                   compile_s=compile_s, cost=cost,
                   recompile=recompile)
    if compiled is not None:
        aotstore.note_cold(program)
        if aotstore.cache_hits_seen() == cache_hits:
            aotstore.export_entry(
                compiled, program=program, step=step,
                capacity=capacity,
                signature=sig, compile_s=compile_s,
            )
    if sub_costs is not None:
        for sub_name, sub_cost in sub_costs(args, kwargs):
            record_compile(
                program=f"{program}:{sub_name}", step=step,
                capacity=capacity,
                backend=backend, cost=sub_cost,
                recompile=recompile,
            )
    return compiled


# ---------------------------------------------------------------------------
# Serialized-executable store hooks + compile-ahead speculation

def _try_store_import(key, sig):
    """Look the (program, capacity, signature) executable up in
    the serialized store.  None on a miss or when the store is off;
    ``aotstore.import_entry`` reports a refused artifact itself."""
    from tmlibrary_tpu import aotstore

    program, _step, capacity = key
    return aotstore.import_entry(program=program, capacity=capacity,
                                 signature=sig)


def record_import(*, program: str, step: str = "jterator",
                  capacity: int | None = None,
                  saved_s: float | None = None) -> dict:
    """Record one store import hit in the profile store.  Deliberately
    does NOT touch the compile counters — an import is the *absence* of
    a compile, and the warm-start tests pin that distinction."""
    key = (program, step, capacity)
    with _LOCK:
        entry = _PROFILES.setdefault(key, {
            "program": program,
            "step": step,
            "capacity": capacity,
            "backend": "unknown",
            "flops": None,
            "bytes": None,
            "arithmetic_intensity": None,
            "bound_by": None,
            "compiles": 0,
            "recompiles": 0,
            "compile_seconds_total": 0.0,
            "last_compile_s": None,
        })
        entry["imports"] = int(entry.get("imports") or 0) + 1
        if isinstance(saved_s, (int, float)) and saved_s > 0:
            entry["compile_seconds_saved"] = round(
                float(entry.get("compile_seconds_saved") or 0.0)
                + float(saved_s), 4,
            )
        return dict(entry)


def adopt_executable(key, sig, compiled) -> bool:
    """Register a speculatively-built executable so the next real call
    with this signature is a hit (and counts as ``warm``, not a
    compile).  False when the signature is already known, the program is
    dead, or the signature cache is full — the speculation thread races
    the real call and the real call always wins."""
    with _LOCK:
        state = _RUNTIME.setdefault(key, {"sigs": {}, "dead": False})
        if (sig in state["sigs"] or state["dead"]
                or len(state["sigs"]) >= _MAX_SIGNATURES):
            return False
        state["sigs"][sig] = compiled
        state.setdefault("speculative", set()).add(sig)
        return True


def abstract_args(args, kwargs):
    """Shape/dtype skeleton of a call: every array leaf becomes a
    ``jax.ShapeDtypeStruct``.  The skeleton has the same
    :func:`_args_signature` as the originals, can be lowered against,
    and holds no buffers — safe to hand to a speculation thread while
    the real (possibly donated) arrays are consumed."""
    import jax

    def conv(leaf):
        if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
            return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype)
        return leaf

    return jax.tree_util.tree_map(conv, (args, kwargs))


def speculate_compile(wrapped_fn, args, kwargs) -> str | None:
    """Precompile one instrumented batch fn off the critical path.

    ``wrapped_fn`` is an :func:`instrument_batch_fn` wrapper (it carries
    ``perf_key`` + ``__wrapped__``); ``args``/``kwargs`` may be real
    arrays or an :func:`abstract_args` skeleton.  Tries the serialized
    store first (an import there counts as an ``import_hit``), then
    compiles and exports.  Returns ``"known"`` (already built),
    ``"imported"``, ``"compiled"``, or None on any failure.  Runs on a
    background thread: every path is exception-proof and the later real
    call counts as ``warm`` instead of a compile."""
    key = getattr(wrapped_fn, "perf_key", None)
    fn = getattr(wrapped_fn, "__wrapped__", None)
    if key is None or fn is None:
        return None
    try:
        sig = _args_signature(args, kwargs)
    except Exception:
        return None
    with _LOCK:
        state = _RUNTIME.setdefault(key, {"sigs": {}, "dead": False})
        if sig in state["sigs"] or state["dead"]:
            return "known"
    imported = _try_store_import(key, sig)
    if imported is not None:
        compiled, meta = imported
        if adopt_executable(key, sig, compiled):
            record_import(program=key[0], step=key[1], capacity=key[2],
                          saved_s=meta.get("compile_s"))
            return "imported"
        return "known"
    from tmlibrary_tpu import aotstore

    compile_s = None
    t0 = time.perf_counter()
    cache_hits = aotstore.cache_hits_seen()
    try:
        with aotstore.compile_for_store():
            compiled = fn.lower(*args, **kwargs).compile()
        compile_s = time.perf_counter() - t0
    except Exception:
        return None
    if not adopt_executable(key, sig, compiled):
        return "known"
    if aotstore.cache_hits_seen() == cache_hits:
        try:
            aotstore.export_entry(
                compiled, program=key[0], step=key[1], capacity=key[2],
                signature=sig, compile_s=compile_s,
            )
        except Exception:
            pass
    return "compiled"


# ---------------------------------------------------------------------------
# Bench-record staleness budget (the history sentinel's)

#: hours after which the latest bench record stops being trustworthy
#: evidence
STALE_HOURS_DEFAULT = 72.0


def stale_hours() -> float:
    try:
        return float(os.environ.get("BENCH_STALE_HOURS", STALE_HOURS_DEFAULT))
    except ValueError:
        return STALE_HOURS_DEFAULT


# ---------------------------------------------------------------------------
# Bench history sentinel

EXIT_OK = 0           # latest matches or improves on the baseline
EXIT_REGRESSION = 1   # latest below baseline by more than the threshold
EXIT_STALE = 2        # latest is fine but older than the staleness budget
EXIT_NO_BASELINE = 3  # nothing comparable to judge against

#: sentinel statuses that exit 0
_OK_STATUSES = ("ok", "improvement")


def _backend_class(backend) -> str:
    """Collapse backend spellings into comparable classes: a forced CPU
    rehearsal (``cpu_forced``) is still a CPU number."""
    b = str(backend or "unknown").lower()
    return "cpu" if b.startswith("cpu") else b


def _record_time(rec: dict) -> float | None:
    for field in ("recorded_at_unix", "measured_at_unix"):
        value = rec.get(field)
        if isinstance(value, (int, float)):
            return float(value)
    return None


def _comparable(rec: dict) -> bool:
    if not isinstance(rec, dict) or rec.get("error"):
        return False
    value = rec.get("value")
    return isinstance(value, (int, float)) and value > 0


def _methodology_class(rec: dict) -> str:
    """Coarse timing-methodology family for like-for-like comparison:
    the specific fetch depth may drift with tuning, but a pipelined
    capture must never be judged against a host-synchronous one (the
    fetch tax makes them different experiments), nor a bucket-routed
    capture against a full-capacity one, nor a
    model-backed capture (the ``dl`` config) against one that ran a
    different checkpoint — the ``model=<digest>`` provenance token
    survives the collapse so the sentinel never compares across
    checkpoints.  Records predating the ``timing_methodology`` field
    form their own ``legacy`` family so old-vs-old still compares."""
    m = str(rec.get("timing_methodology") or "")
    if not m:
        return "legacy"
    if m.startswith("pipelined"):
        cls = "pipelined+bucketed" if "bucketed" in m else "pipelined"
        # work-aware site scheduling changes the dispatch plan (packed
        # rung-homogeneous batches vs directory order) — a packed capture
        # is a different experiment from an unpacked one
        sched = re.search(r"schedule=([a-z]+)", m)
        if sched:
            cls += f"+schedule={sched.group(1)}"
        model = re.search(r"model=([0-9a-f]+)", m)
        if model:
            cls += f"+model={model.group(1)}"
        return cls
    if m.startswith("analytics-tools"):
        # the ``+index=ivf`` token survives (an indexed sublinear sweep
        # is a different experiment from exact brute force), but the
        # measured ``+recall=<x>`` value collapses — two ivf captures
        # with recall 0.971 vs 0.972 are the same family and must keep
        # comparing, while the verbatim record string retains the number
        # as provenance
        return re.sub(r"\+recall=[0-9.]+", "", m)
    return m


def _history_key(rec: dict) -> tuple:
    return (
        str(rec.get("metric", "")),
        str(rec.get("config", "")),
        _backend_class(rec.get("backend")),
        _methodology_class(rec),
    )


def compare_history(history: list[dict], *, baseline: list[dict] | None = None,
                    config: str | None = None, metric: str | None = None,
                    threshold: float = 0.05,
                    stale_hours: float = STALE_HOURS_DEFAULT,
                    now: float | None = None) -> dict:
    """Judge the latest bench record against the best comparable one.

    ``history`` is the parsed ``tuning/BENCH_HISTORY.jsonl``; ``baseline``
    optionally supplies the comparison pool from a separate file (CI's
    committed baseline) instead of earlier history entries.  Records are
    comparable when they share (metric, config, backend class) and carry a
    positive error-free value.  Returns a verdict dict with ``status``
    (improvement/ok/regression/stale/no_baseline), the matching ``exit_code``
    (regression outranks stale: it is the more actionable signal), the
    latest/baseline records, ``delta_frac``, ``age_hours``, and
    ``recapture`` queue labels when action is needed."""
    now = time.time() if now is None else now

    def matches(rec):
        if not _comparable(rec):
            return False
        if config is not None and str(rec.get("config", "")) != str(config):
            return False
        if metric is not None and rec.get("metric") != metric:
            return False
        return True

    pool = [r for r in history if matches(r)]
    if not pool:
        return {"status": "no_baseline", "exit_code": EXIT_NO_BASELINE,
                "reason": "no comparable records in history",
                "latest": None, "baseline": None,
                "delta_frac": None, "age_hours": None, "recapture": []}
    latest = pool[-1]
    key = _history_key(latest)
    if baseline is not None:
        candidates = [r for r in baseline
                      if _comparable(r) and _history_key(r) == key]
    else:
        candidates = [r for r in pool[:-1] if _history_key(r) == key]

    age_hours = None
    ts = _record_time(latest)
    if ts is not None:
        age_hours = round(max(0.0, (now - ts) / 3600.0), 1)
    is_stale = age_hours is not None and age_hours > stale_hours

    label = f"sweep:{latest.get('config')}" if latest.get("sweep") \
        else f"bench:{latest.get('config')}"

    if not candidates:
        return {"status": "no_baseline", "exit_code": EXIT_NO_BASELINE,
                "reason": f"no baseline for {key}",
                "latest": latest, "baseline": None, "delta_frac": None,
                "age_hours": age_hours,
                "recapture": [label] if is_stale else []}

    best = max(candidates, key=lambda r: r["value"])
    delta = (latest["value"] - best["value"]) / best["value"]
    if delta < -threshold:
        status, code = "regression", EXIT_REGRESSION
    elif is_stale:
        status, code = "stale", EXIT_STALE
    elif delta > threshold:
        status, code = "improvement", EXIT_OK
    else:
        status, code = "ok", EXIT_OK
    return {"status": status, "exit_code": code,
            "latest": latest, "baseline": best,
            "delta_frac": round(delta, 4), "age_hours": age_hours,
            "recapture": [label] if code in (EXIT_REGRESSION, EXIT_STALE)
            else []}


# ---------------------------------------------------------------------------
# Re-capture queue (what the sentinel says to measure again on the chip)

def load_recapture(path: str | None = None) -> list[str]:
    """Pending re-capture labels written by the regression sentinel.
    Unknown shapes and unreadable files degrade to an empty list."""
    path = path or tuning.recapture_path()
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, ValueError):
        return []
    items = doc.get("items") if isinstance(doc, dict) else doc
    if not isinstance(items, list):
        return []
    return [str(i) for i in items if isinstance(i, str) and i]


def write_recapture(labels: list[str], path: str | None = None,
                    reason: str = "") -> str:
    """Merge ``labels`` into the re-capture queue file (deduplicated,
    order-preserving).  Returns the path written."""
    path = path or tuning.recapture_path()
    existing = load_recapture(path)
    merged = existing + [l for l in labels if l not in existing]
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    atomic_write_text(
        path,
        json.dumps({"items": merged, "reason": reason,
                    "written_at_unix": time.time()}, indent=2) + "\n",
    )
    return path
