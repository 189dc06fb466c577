"""Deep pipelined batch executor: multi-batch in-flight depth with
threaded prefetch and persist.

XLA dispatch is asynchronous — a device call returns futures immediately
and only the host fetch blocks — so the old depth-1 generator in
``jterator.py`` already overlapped ONE batch's host IO with device
compute.  The hardware tuning sweep (``tuning/TUNING.json``) shows the
device is still starved at that depth: batch N+1's store reads serialize
against batch N-1's Parquet/polygon persists on the single host thread.
This module generalizes the overlap into an executor any step can use by
exposing the launch/persist split:

- ``prefetch_batch(batch)`` (optional) — pure host-side input loading
  (``store.read_sites``, illumstats, shift tables, mosaic stitching),
  safe to run on a worker thread ahead of dispatch.
- ``launch_batch(batch, prefetched=None) -> (effective_batch, ctx)`` —
  async device dispatch; returns un-fetched device results.  The
  effective batch may differ from the planned one (jterator's cap
  overrides), and is what ``persist_batch`` receives.
- ``block_batch(ctx)`` (optional) — block until the launched device
  arrays are ready, so the device-block phase is timed separately from
  the writes.
- ``persist_batch(effective_batch, ctx) -> result`` — fetch + write
  (feature shards, label stacks, polygons, figures).

Semantics the engine depends on (and the equivalence tests pin down):

- **Ordering**: ``run()`` yields ``(batch, result)`` strictly in
  submission order, so ledger ``batch_done``/``batch_failed`` events keep
  batch-index order and resume replay is unchanged.
- **Window drain**: a launch failure mid-window first persists and
  yields EVERY already-launched batch (not just the previous one), then
  propagates — resume granularity matches the sequential path and no
  completed work loses its ledger event.
- **Depth auto-clamp**: a ``RESOURCE_EXHAUSTED``/OOM failure with
  depth > 1 drains the window, halves the depth, reports a
  ``depth_clamped`` event through ``on_event``, and retries the failed
  batch at the lower depth instead of failing the step — HBM pressure
  from too many in-flight batches degrades throughput, not correctness.
- **Bit-identity**: dispatch happens on the calling thread in batch
  order; persists run on a pool sized like the prefetch stage's
  (``min(depth, 4, batches)``), so several batches persist at once and
  may FINISH out of order, but every persisted artifact is sharded by
  batch (disjoint rows of a label stack, one Parquet shard a batch) and
  results are still yielded in submission order, so the store and the
  ledger are bit-identical to sequential execution.  A step whose
  persist folds state in batch order says so with a true
  ``persist_serial`` attribute and gets one worker.

Fault plans (``faults.py``) targeting ``batch_run``/``ledger_append``
force the engine onto the sequential path *before* this executor is
constructed — those faults must land before a batch persists to mean
anything (DESIGN.md §11).  ``persist``-site plans run through the real
executor: the hook fires in the persist worker, after the device work
and before the batch's outputs are durable.
"""

from __future__ import annotations

import collections
import concurrent.futures
import contextlib
import inspect
import logging
import time
from typing import Any, Callable, Iterable, Iterator

from tmlibrary_tpu import faults, profiling, telemetry
from tmlibrary_tpu.errors import PreemptedError

logger = logging.getLogger(__name__)

#: shared no-op context for disarmed watchdog phases — one object, zero
#: per-batch allocation when the watchdog is off (zero-cost-when-disabled
#: discipline, same as telemetry's shared null instrument)
_NULL_CM = contextlib.nullcontext()

#: messages that signal HBM/host-memory pressure from too-deep pipelining
#: (XLA surfaces these as bare RuntimeError/XlaRuntimeError text)
_RESOURCE_PATTERNS = (
    "resource_exhausted",
    "resource exhausted",
    "out of memory",
)


def is_resource_exhausted(exc: BaseException) -> bool:
    """True when the error smells like memory pressure — the one failure
    class where *reducing the in-flight depth* is the fix, not a retry at
    the same depth."""
    if isinstance(exc, MemoryError):
        return True
    msg = str(exc).lower()
    return any(p in msg for p in _RESOURCE_PATTERNS)


def supports_pipelining(step) -> bool:
    """A step drives through :class:`PipelinedExecutor` when it exposes
    the launch/persist split."""
    return hasattr(step, "launch_batch") and hasattr(step, "persist_batch")


def resolve_pipeline_depth(
    explicit: int | None = None, backend: str | None = None
) -> tuple[int, str]:
    """The in-flight depth to run and where it came from.

    Precedence (highest first): an explicit request (CLI
    ``--pipeline-depth`` / ``Workflow(pipeline_depth=...)``), the
    install config (``TM_PIPELINE_DEPTH`` env / INI ``pipeline_depth``),
    the machine-written tuning sweep's ``best_pipeline`` (device
    backends only — the sweep measured the device), then a safe
    per-backend default: 8 on device, 2 on CPU (dispatch is cheap there
    and a shallow window still overlaps persist IO with compute without
    holding many batches of host arrays).

    Returns ``(depth, source)`` with source in ``cli | config | tuning |
    default`` so the chosen depth's provenance can be logged and
    recorded in the run ledger.
    """
    if explicit is not None and int(explicit) > 0:
        return max(1, int(explicit)), "cli"
    from tmlibrary_tpu.config import _setting

    try:
        configured = int(_setting("pipeline_depth", "0"))
    except ValueError:
        configured = 0
    if configured > 0:
        return configured, "config"
    if backend is None:
        import jax

        backend = jax.default_backend()
    if backend != "cpu":
        from tmlibrary_tpu.tuning import tuned_pipeline_depth

        tuned = tuned_pipeline_depth()
        if tuned:
            return tuned, "tuning"
        return 8, "default"
    return 2, "default"


def prefetch_iter(
    items: Iterable[Any],
    load: Callable[[Any], Any],
    depth: int = 2,
) -> Iterator[Any]:
    """Yield ``load(item)`` for every item IN ORDER, with up to ``depth``
    loads running ahead on worker threads.

    This is the executor's prefetch stage as a standalone primitive, for
    steps whose unit of work is smaller than a batch — corilla's
    chunk-scan loop reads site chunks through it so store IO for chunk
    N+1 hides behind chunk N's device scan.  Order (and therefore any
    order-dependent fold over the results) is preserved exactly; a
    loader exception surfaces at the failing item's position.
    """
    items = list(items)
    depth = max(1, int(depth))
    if len(items) <= 1:
        for item in items:
            yield load(item)
        return
    pool = concurrent.futures.ThreadPoolExecutor(
        max_workers=min(depth, len(items)), thread_name_prefix="tmx-prefetch"
    )
    futures: collections.deque = collections.deque()
    try:
        pos = 0
        while pos < len(items) or futures:
            while pos < len(items) and len(futures) < depth:
                futures.append(pool.submit(load, items[pos]))
                pos += 1
            yield futures.popleft().result()
    finally:
        for f in futures:
            f.cancel()
        pool.shutdown(wait=True)


class PipelinedExecutor:
    """Bounded in-flight window over a step's launch/persist split.

    ``run(batches)`` is a generator of ``(batch, result)`` in submission
    order.  ``on_event(**event)`` receives ``depth_clamped`` events (the
    engine appends them to the run ledger); ``stats`` is an optional
    :class:`tmlibrary_tpu.profiling.PipelineStats` collecting the
    per-batch phase timers.
    """

    def __init__(
        self,
        step,
        depth: int | None = None,
        depth_source: str | None = None,
        persist_workers: int | None = None,
        on_event: Callable[..., None] | None = None,
        stats=None,
        should_stop: Callable[[], bool] | None = None,
        watchdog=None,
        warm_hook: Callable[[], None] | None = None,
    ):
        if depth is None:
            depth, depth_source = resolve_pipeline_depth()
        self.step = step
        self.depth = max(1, int(depth))
        self.depth_source = depth_source or "explicit"
        # None: sized per window as the prefetch stage is
        # (:meth:`_resolve_persist_workers`).  Persist is where a step
        # waits on the device a second time (jterator re-launches a
        # field at the rung its demand selects) and then does its host
        # work (hulls, Parquet, label stacks): on one worker the two
        # alternate and the device idles through every write; on several
        # a field's re-launch runs while the field before it is written.
        # Every persisted artifact is batch-sharded, so the order in
        # which batches FINISH does not reach the store
        self.persist_workers = (
            None if persist_workers is None else max(1, int(persist_workers))
        )
        self.on_event = on_event
        self.stats = stats
        #: graceful drain: polled before each launch — when it flips the
        #: window drains (every launched batch persists + yields) and a
        #: :class:`PreemptedError` carries the drain summary out; both
        #: default to None so the executor costs nothing extra when the
        #: drain/watchdog layers are off
        self.should_stop = should_stop
        #: resilience.PhaseWatchdog (or None): deadlines over the
        #: launch/block/persist phases
        self.watchdog = watchdog
        #: compile-ahead speculation hook (aotstore plane): fired ONCE,
        #: right after the first batch's launch returns — the device is
        #: busy, the prefetch workers own the host IO, and the window is
        #: filling, so this is the prefetch-idle moment to start warming
        #: the likely next capacity rungs on a background thread.  The
        #: hook manages its own thread; a failure is swallowed (warming
        #: is an optimization, never a correctness dependency)
        self.warm_hook = warm_hook
        self._warmed = False

    # ------------------------------------------------------------------ run
    def run(self, batches: Iterable[dict]) -> Iterator[tuple[dict, dict]]:
        batches = list(batches)
        pos = 0
        while pos < len(batches):
            try:
                for out in self._run_window(batches[pos:]):
                    pos += 1
                    yield out
                return
            except Exception as exc:  # noqa: BLE001 — classified below
                if self.depth > 1 and is_resource_exhausted(exc):
                    new_depth = max(1, self.depth // 2)
                    failing = batches[pos]["index"] if pos < len(batches) else None
                    logger.warning(
                        "pipelined executor: %s at depth %d — clamping to "
                        "depth %d and retrying batch %s",
                        exc, self.depth, new_depth, failing,
                    )
                    if self.on_event is not None:
                        self.on_event(
                            event="depth_clamped", from_depth=self.depth,
                            to_depth=new_depth, batch=failing, error=str(exc),
                        )
                    if self.stats is not None:
                        self.stats.record_clamp(self.depth, new_depth)
                    self.depth = new_depth
                    continue  # _run_window drained: pos is the failed batch
                raise

    def _stage_workers(self, n_batches: int) -> int:
        """Threads of a host stage (prefetch, persist) over one window:
        no more than the window is deep, than there are batches, or than
        four — past that the stages contend for the host they overlap."""
        return max(1, min(self.depth, 4, n_batches))

    def _resolve_persist_workers(self, n_batches: int) -> int:
        """The persist pool of one window: an explicit constructor value,
        else one worker for a step that persists in batch order
        (``persist_serial``), else the prefetch stage's size.  Depth 1 —
        the clamp's floor — and a single batch resolve to one worker."""
        if self.persist_workers is not None:
            return self.persist_workers
        if getattr(self.step, "persist_serial", False):
            return 1
        return self._stage_workers(n_batches)

    # --------------------------------------------------------------- window
    def _run_window(self, batches: list[dict]) -> Iterator[tuple[dict, dict]]:
        step = self.step
        stats = self.stats
        step_name = getattr(step, "name", "") or "unknown"
        watchdog = self.watchdog

        def _arm(phase: str, idx):
            # shared null context when no watchdog: zero per-batch cost
            return (_NULL_CM if watchdog is None
                    else watchdog.arm(phase, step=step_name, batch=idx))

        @contextlib.contextmanager
        def _phase(phase: str, idx):
            # a phase on the thread that works: span scope + stats record.
            # Both clocks bracket the work alone: with the stats' clock
            # around the span's own bookkeeping (its buffer's lock, the
            # first import of the profiler) a loaded host put milliseconds
            # between the two sums of one phase
            with telemetry.span_scope(step=step_name, batch=idx), \
                    telemetry.span(
                        phase, resource=profiling.PHASE_RESOURCE[phase]):
                t0 = time.perf_counter()
                yield
                elapsed = time.perf_counter() - t0
            if stats is not None:
                stats.record(phase, elapsed)

        has_prefetch = hasattr(step, "prefetch_batch")
        prefetcher = None
        if has_prefetch and len(batches) > 1:
            prefetcher = concurrent.futures.ThreadPoolExecutor(
                max_workers=self._stage_workers(len(batches)),
                thread_name_prefix="tmx-prefetch",
            )
        persist_workers = self._resolve_persist_workers(len(batches))
        persister = concurrent.futures.ThreadPoolExecutor(
            max_workers=persist_workers, thread_name_prefix="tmx-persist"
        )
        if stats is not None:
            stats.note_persist_workers(persist_workers)
        # launched-but-not-yet-yielded batches, in submission order
        window: collections.deque = collections.deque()
        prefetched: dict[int, concurrent.futures.Future] = {}

        def prefetch_task(batch: dict, idx):
            with telemetry.span_scope(step=step_name, batch=idx):
                return step.prefetch_batch(batch)

        def persist_task(eff: dict, ctx, idx: int) -> dict:
            if hasattr(step, "block_batch"):
                with _phase("device_block", idx), _arm("block", idx):
                    step.block_batch(ctx)
            with _phase("persist", idx), _arm("persist", idx):
                # persist-site faults land here: after the device work,
                # before the outputs are durable (kill-mid-persist,
                # sigterm, hang) — inside the armed phase so an injected
                # hang exercises the watchdog like a real wedged write
                faults.maybe_fire("persist", step=step_name, batch=idx)
                with (_NULL_CM if stats is None else stats.persisting()):
                    result = step.persist_batch(eff, ctx)
            if stats is not None:
                # what the persist spent waiting for a program it re-launched
                if isinstance(result, dict) and result.get("device_wait_s"):
                    stats.record_device_wait(result["device_wait_s"])
                stats.batch_done()
            return result

        def note_inflight() -> None:
            # live window depth for `tmx top` (gauge only — no ledger
            # traffic; this runs on the engine thread either way)
            if telemetry.enabled():
                telemetry.get_registry().gauge(
                    "tmx_pipeline_inflight",
                    step=getattr(step, "name", "") or "unknown",
                ).set(len(window))

        def pop_one() -> tuple[dict, dict]:
            batch, fut = window.popleft()
            note_inflight()
            return batch, fut.result()

        try:
            for i, batch in enumerate(batches):
                if self.should_stop is not None and self.should_stop():
                    # graceful drain: stop admitting batches, let every
                    # already-launched one persist + yield (the caller
                    # ledgers each), then surface the drain summary.  The
                    # ledger boundary is exactly a clean run's after the
                    # same batches: resume continues bit-identically.
                    n0 = len(window)
                    drained = 0
                    while window:
                        yield pop_one()
                        drained += 1
                    raise PreemptedError(
                        f"preempted before batch {batch.get('index', i)}: "
                        f"drained {drained}/{n0} in-flight, abandoned "
                        f"{len(batches) - i} un-launched",
                        step=step_name, in_flight=n0, drained=drained,
                        abandoned=len(batches) - i,
                    )
                if prefetcher is not None:
                    # keep up to `depth` loads ahead of the dispatch point
                    for j in range(i, min(i + self.depth, len(batches))):
                        if j not in prefetched:
                            prefetched[j] = prefetcher.submit(
                                prefetch_task, batches[j],
                                batches[j].get("index", j),
                            )
                bidx = batch.get("index", i)
                try:
                    pre = None
                    if i in prefetched:
                        with _phase("prefetch_wait", bidx):
                            pre = prefetched.pop(i).result()
                    with _phase("dispatch", bidx), _arm("launch", bidx):
                        eff, ctx = step.launch_batch(batch, pre)
                    if self.warm_hook is not None and not self._warmed:
                        self._warmed = True
                        try:
                            # plan-aware warming: a hook that takes a
                            # parameter gets the un-launched tail, so
                            # schedule-planned rungs warm as certainties
                            # rather than ladder guesses; zero-arg hooks
                            # keep their existing contract
                            try:
                                takes_upcoming = bool(
                                    inspect.signature(
                                        self.warm_hook
                                    ).parameters
                                )
                            except (TypeError, ValueError):
                                takes_upcoming = False
                            if takes_upcoming:
                                self.warm_hook(batches[i + 1:])
                            else:
                                self.warm_hook()
                        except Exception:
                            logger.debug("warm hook failed", exc_info=True)
                except Exception:
                    # drain the WHOLE window: every already-launched batch
                    # persists (and the caller ledgers it) before the
                    # failure propagates — with depth > 1 flushing only
                    # the previous batch would drop completed work
                    while window:
                        yield pop_one()
                    raise
                window.append((batch, persister.submit(
                    persist_task, batch if eff is None else eff, ctx, bidx
                )))
                note_inflight()
                while len(window) > self.depth:
                    yield pop_one()
            while window:
                yield pop_one()
        finally:
            for f in prefetched.values():
                f.cancel()
            if prefetcher is not None:
                prefetcher.shutdown(wait=False)
            # wait=True: no persist worker may still be writing while the
            # engine's sequential fallback re-runs the failed batch
            persister.shutdown(wait=True)
