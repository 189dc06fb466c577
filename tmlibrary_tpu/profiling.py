"""Profiling and tracing.

Reference parity: the reference has no built-in profiler (SURVEY.md §6 —
GC3Pie records per-job wall/cpu time in task state; per-job timing lands in
the submission tables).  The TPU rebuild does better: the run ledger already
records per-step/per-batch wall time (``workflow/engine.py``), and this
module adds device-level tracing via ``jax.profiler`` so kernel time on the
TPU can be inspected with TensorBoard/XProf.
"""

from __future__ import annotations

import contextlib
import threading
from pathlib import Path

from tmlibrary_tpu import telemetry

#: pipeline phases in execution order; keys of ``PipelineStats.summary()``
PIPELINE_PHASES = ("prefetch_wait", "dispatch", "device_block", "persist")

#: which resource each phase waits on BY THE HOST'S CLOCK — the basis of
#: ``tmx perf``'s device/host split and the ``tmx_perf_*`` gauges: async
#: launch and literal device wait are device, prefetch/persist host IO —
#: except the ``device_wait`` spans inside ``persist`` (an escalation's
#: re-launch), see :meth:`PipelineStats.record_device_wait`.  Not the
#: device's own busy time: that is in a trace.
PHASE_RESOURCE = {
    "prefetch_wait": "host",
    "dispatch": "device",
    "device_block": "device",
    "persist": "host",
}


class PipelineStats:
    """Per-batch phase timers for the pipelined batch executor.

    Each batch flows through up to four phases — waiting on the prefetch
    worker (``prefetch_wait``), async device dispatch on the main thread
    (``dispatch``), blocking on device arrays (``device_block``) and
    host-side writes (``persist``) — and the executor records each
    duration here.  The summary lands in the ``step_done`` ledger event
    as ``pipeline_stats`` and in ``tmx … status``, so a stalled pipeline
    (device starved on prefetch, or persist eating the window) is
    diagnosable from the ledger alone, without an XProf trace.

    Phase timings are held in bounded-reservoir histograms
    (``telemetry.Histogram``), so the summary carries p50/p95 alongside
    the original ``total_s``/``max_s`` keys (ledger shape stays
    backward-compatible).  When the telemetry registry is enabled the
    same observations are mirrored into ``tmx_pipeline_phase_seconds``
    registry histograms.

    ``persist`` (and ``device_block``) totals are SUMS OVER THE PERSIST
    WORKERS, and a worker that waits for a program it re-launched counts
    the device time of every re-launch queued before its own: with
    several workers the total is a sum of sleeps that can exceed the
    step's wall-clock, not a cost.  ``persist_workers`` (the pool the
    executor resolved) and ``persist_peak_concurrency`` (the most tasks
    inside ``step.persist_batch`` at one instant) say how wide it ran.

    Thread-safe: dispatch timings come from the main thread while
    device-block/persist timings come from persist workers.
    """

    def __init__(self, depth: int, source: str = "explicit", step: str = ""):
        self.depth = int(depth)
        self.source = source
        self.step = step
        self._lock = threading.Lock()
        self._hist = {
            phase: telemetry.Histogram(phase, {}) for phase in PIPELINE_PHASES
        }
        reg = telemetry.get_registry()
        self._reg_hist = {
            phase: reg.histogram(
                "tmx_pipeline_phase_seconds", step=step or "unknown",
                phase=phase,
            )
            for phase in PIPELINE_PHASES
        }
        self._batches = 0
        self._clamps: list[dict] = []
        #: seconds of ``persist`` spent waiting for a re-launched program
        self._persist_device_wait = 0.0
        self._persist_workers = 0
        self._persist_live = 0
        self._persist_peak = 0
        self._reg_persist = {
            name: reg.gauge(f"tmx_pipeline_persist_{name}",
                            step=step or "unknown")
            for name in ("workers", "peak_concurrency")
        }

    def record(self, phase: str, seconds: float) -> None:
        self._hist[phase].observe(seconds)
        self._reg_hist[phase].observe(seconds)

    def record_device_wait(self, seconds: float) -> None:
        """Device wait inside ``persist``: device time in the summary."""
        with self._lock:
            self._persist_device_wait += float(seconds)

    def batch_done(self) -> None:
        with self._lock:
            self._batches += 1

    def note_persist_workers(self, workers: int) -> None:
        """The persist pool the executor resolved for its window."""
        with self._lock:
            self._persist_workers = int(workers)
        self._reg_persist["workers"].set(int(workers))

    @contextlib.contextmanager
    def persisting(self):
        """Around one ``step.persist_batch`` call, on its worker: counts
        how many are inside at once."""
        with self._lock:
            self._persist_live += 1
            self._persist_peak = max(self._persist_peak, self._persist_live)
            self._reg_persist["peak_concurrency"].set(self._persist_peak)
        try:
            yield
        finally:
            with self._lock:
                self._persist_live -= 1

    def record_clamp(self, from_depth: int, to_depth: int) -> None:
        with self._lock:
            self._clamps.append({"from": int(from_depth), "to": int(to_depth)})
            self.depth = int(to_depth)

    def summary(self) -> dict:
        """JSON-ready roll-up for the run ledger.

        ``total_s``/``max_s`` keys are load-bearing (pinned by
        ``tests/test_pipelined.py`` and rendered by ``tmx … status``);
        ``p50_s``/``p95_s``/``count`` are additive.
        """
        with self._lock:
            batches = self._batches
            clamps = list(self._clamps)
            wait = self._persist_device_wait
            workers, peak = self._persist_workers, self._persist_peak
        phases = {}
        for phase in PIPELINE_PHASES:
            hist = self._hist[phase]
            if not hist.count:
                continue
            phases[phase] = {
                "total_s": round(hist.sum, 4),
                "max_s": round(hist.max, 4),
                "p50_s": round(hist.quantile(0.5), 4),
                "p95_s": round(hist.quantile(0.95), 4),
                "count": hist.count,
            }
        out = {
            "depth": self.depth,
            "source": self.source,
            "n_batches": batches,
            "phases": phases,
        }
        if workers:
            out["persist_workers"] = workers
            out["persist_peak_concurrency"] = peak
        device_s = wait + sum(
            p["total_s"] for ph, p in phases.items()
            if PHASE_RESOURCE.get(ph) == "device"
        )
        host_s = -wait + sum(
            p["total_s"] for ph, p in phases.items()
            if PHASE_RESOURCE.get(ph) == "host"
        )
        if phases:
            # additive (ledger shape stays backward-compatible): the
            # device/host attribution consumed by `tmx perf`
            out["device_s"] = round(device_s, 4)
            out["host_s"] = round(host_s, 4)
            if telemetry.enabled():
                reg = telemetry.get_registry()
                label = self.step or "unknown"
                reg.gauge(
                    "tmx_perf_device_seconds_total", step=label
                ).set(round(device_s, 4))
                reg.gauge(
                    "tmx_perf_host_seconds_total", step=label
                ).set(round(host_s, 4))
                if device_s + host_s > 0:
                    reg.gauge("tmx_perf_device_frac", step=label).set(
                        round(device_s / (device_s + host_s), 4)
                    )
        if clamps:
            out["depth_clamps"] = clamps
        return out


@contextlib.contextmanager
def device_trace(log_dir: str | Path | None):
    """Wrap a block in a ``jax.profiler`` trace when ``log_dir`` is set.

    No-op when ``log_dir`` is None so call sites can pass the CLI flag
    straight through.  The trace directory is TensorBoard-compatible
    (``tensorboard --logdir <dir>`` → Profile tab / xprof): telemetry
    spans are ``TraceAnnotation``s in any trace and the programs carry
    ``jax.named_scope`` stage names.
    """
    if log_dir is None:
        yield
        return
    import jax

    path = Path(log_dir)
    path.mkdir(parents=True, exist_ok=True)
    with jax.profiler.trace(str(path)):
        yield
