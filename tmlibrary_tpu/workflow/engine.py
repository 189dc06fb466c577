"""Workflow engine: stage/step DAG execution with ledger-backed resume.

Reference parity: ``tmlib/workflow/workflow.py`` (``Workflow`` →
``WorkflowStage`` → ``WorkflowStep`` = init → run → collect, driven through
GC3Pie ``next()`` transitions), ``description.py`` (YAML-serializable
workflow description validated against the step registry),
``dependencies.py`` (canonical stage order) and
``manager.py``/``submission.py`` (DB-backed submission state + ``resume``).

TPU redesign (SURVEY.md §4.1): no process fan-out — stages iterate in one
process dispatching batched device programs; the JSON-lines run ledger
replaces the ``Submission``/``Task`` tables: every init/run/collect event
is appended with timing, and ``resume`` replays the ledger to skip
completed work.  Idempotence still comes from each step's
``delete_previous_output`` + deterministic batch plans, exactly the
reference's contract.
"""

from __future__ import annotations

import dataclasses
import hashlib
import inspect
import json
import logging
import os
import sys
import time
import zlib
from pathlib import Path
from typing import Any

import yaml

from tmlibrary_tpu import faults, telemetry
from tmlibrary_tpu.atomicio import atomic_write_text
from tmlibrary_tpu.errors import FaultInjected, PreemptedError, WorkflowError
from tmlibrary_tpu.log import warn_once
from tmlibrary_tpu.models.store import ExperimentStore
from tmlibrary_tpu.resilience import (
    PERMANENT,
    ResilienceConfig,
    RetryOutcome,
    RetryPolicy,
    classify,
    preemption_reason,
    preemption_requested,
    retry_call,
    watchdog_from_config,
)
from tmlibrary_tpu.profiling import PipelineStats
from tmlibrary_tpu.workflow.pipelined import (
    PipelinedExecutor,
    resolve_pipeline_depth,
    supports_pipelining,
)
from tmlibrary_tpu.workflow.registry import get_step, list_steps

logger = logging.getLogger(__name__)

#: workflow-type stage DAGs (reference ``tmlib/workflow/dependencies.py``:
#: ``CanonicalWorkflowDependencies`` and ``MultiplexingWorkflowDependencies``)
#: — conversion → preprocessing → pyramid → analysis; the multiplexing type
#: adds inter-cycle registration (``align``) to the preprocessing stage.
WORKFLOW_TYPES: dict[str, list[tuple[str, list[str]]]] = {
    "canonical": [
        ("image_conversion", ["metaconfig", "imextract"]),
        ("image_preprocessing", ["corilla"]),
        ("pyramid_creation", ["illuminati"]),
        ("image_analysis", ["jterator"]),
    ],
    "multiplexing": [
        ("image_conversion", ["metaconfig", "imextract"]),
        ("image_preprocessing", ["corilla", "align"]),
        ("pyramid_creation", ["illuminati"]),
        ("image_analysis", ["jterator"]),
    ],
}

#: back-compat alias: the widest stage DAG (multiplexing superset)
CANONICAL_STAGES = WORKFLOW_TYPES["multiplexing"]


@dataclasses.dataclass
class WorkflowStepDescription:
    name: str
    args: dict[str, Any] = dataclasses.field(default_factory=dict)
    active: bool = True


@dataclasses.dataclass
class WorkflowStageDescription:
    name: str
    steps: list[WorkflowStepDescription]


@dataclasses.dataclass
class WorkflowDescription:
    """YAML-serializable workflow plan (reference ``WorkflowDescription``)."""

    stages: list[WorkflowStageDescription]

    def validate(self) -> None:
        known = set(list_steps())
        for stage in self.stages:
            for step in stage.steps:
                if step.name not in known:
                    raise WorkflowError(
                        f"workflow references unknown step '{step.name}' "
                        f"(registered: {sorted(known)})"
                    )

    def active_steps(self) -> list[WorkflowStepDescription]:
        return [s for st in self.stages for s in st.steps if s.active]

    # ------------------------------------------------------------- serialize
    def to_dict(self) -> dict:
        return {
            "stages": [
                {
                    "name": st.name,
                    "steps": [
                        {"name": s.name, "args": s.args, "active": s.active}
                        for s in st.steps
                    ],
                }
                for st in self.stages
            ]
        }

    @classmethod
    def from_dict(cls, d: dict) -> "WorkflowDescription":
        return cls(
            stages=[
                WorkflowStageDescription(
                    name=st["name"],
                    steps=[
                        WorkflowStepDescription(
                            name=s["name"],
                            args=s.get("args", {}) or {},
                            active=bool(s.get("active", True)),
                        )
                        for st_s in [st.get("steps", [])]
                        for s in st_s
                    ],
                )
                for st in d.get("stages", [])
            ]
        )

    @classmethod
    def load(cls, path: Path) -> "WorkflowDescription":
        return cls.from_dict(yaml.safe_load(Path(path).read_text()))

    def save(self, path: Path) -> None:
        Path(path).write_text(yaml.safe_dump(self.to_dict(), sort_keys=False))

    @classmethod
    def for_type(
        cls,
        workflow_type: str,
        step_args: dict[str, dict] | None = None,
    ) -> "WorkflowDescription":
        """Build a description for a registered workflow type
        (``canonical`` | ``multiplexing``); ``step_args`` maps step name →
        args, and only steps with args are active (inactive steps stay in
        the plan so they can be toggled on later)."""
        if workflow_type not in WORKFLOW_TYPES:
            raise WorkflowError(
                f"unknown workflow type '{workflow_type}' "
                f"(registered: {sorted(WORKFLOW_TYPES)})"
            )
        step_args = step_args or {}
        return cls(
            stages=[
                WorkflowStageDescription(
                    name=stage,
                    steps=[
                        WorkflowStepDescription(
                            name=s,
                            args=step_args.get(s, {}),
                            active=s in step_args,
                        )
                        for s in steps
                    ],
                )
                for stage, steps in WORKFLOW_TYPES[workflow_type]
            ]
        )

    @classmethod
    def canonical(cls, step_args: dict[str, dict] | None = None) -> "WorkflowDescription":
        """The four-stage workflow, auto-typed: requesting ``align`` args
        selects the multiplexing variant (the only type that runs
        inter-cycle registration)."""
        wtype = "multiplexing" if "align" in (step_args or {}) else "canonical"
        return cls.for_type(wtype, step_args)


#: separator introducing the per-line checksum :meth:`RunLedger.append`
#: seals every event line with (the last key of the JSON object)
_CRC_SEP = ', "crc": "'


class RunLedger:
    """Append-only JSON-lines event log (replaces the reference's
    ``Submission``/``Task`` tables).

    Crash consistency (DESIGN.md §19): every appended line is *sealed*
    with a CRC-32 of the event body embedded as its last JSON key, so a
    torn write (process killed mid-append) is detectable even when the
    torn prefix happens to be valid JSON.  Readers skip unverifiable
    lines; the *writer* additionally truncates a torn tail back to the
    last intact line boundary before its first append
    (:meth:`recover`), so a crashed run's ledger converges to exactly
    the clean-run prefix.  Seed-era ledgers without CRCs stay fully
    readable — the checksum is only enforced where present.

    ``fsync=True`` makes every append crash-durable at the cost of one
    fsync per event; without it a crash mid-append can leave a truncated
    trailing line, which :meth:`events` skips with a warning instead of
    poisoning every later ``resume``/``status`` call."""

    def __init__(self, path: Path, fsync: bool = False,
                 host: str | None = None):
        self.path = Path(path)
        self.fsync = fsync
        #: fleet attribution: when set, every appended event carries a
        #: ``host`` field so interleaved multi-host ledgers stay
        #: separable in ``registry_from_ledger`` / ``tmx metrics``
        self.host = host
        #: (mtime_ns, size) → parsed events; ``status()`` and
        #: ``completed_batches()`` poll :meth:`events` repeatedly and the
        #: file only grows via :meth:`append`, so re-parsing the whole
        #: JSON-lines file on every call is pure waste
        self._cache: tuple[tuple[int, int], list[dict]] | None = None
        #: torn-tail recovery runs once, lazily, before the first append
        self._recovered = False
        #: per-step completed-batch sets maintained by
        #: :meth:`append_batch_done` so idempotence checks don't re-parse
        #: the whole ledger once per batch
        self._done_cache: dict[str, set[int]] = {}

    # ------------------------------------------------------------- sealing
    @staticmethod
    def _seal(body: str) -> str:
        """Append the CRC-32 of ``body`` as its trailing JSON key.  The
        sealed line is still one valid JSON object, so older checkouts
        (and any JSON-lines tooling) read it unchanged."""
        crc = zlib.crc32(body.encode())
        return f'{body[:-1]}{_CRC_SEP}{crc:08x}"}}'

    @staticmethod
    def _line_ok(line: str) -> bool:
        """True when the line parses — and, if sealed, verifies.  The
        CRC is recomputed over the exact bytes that were sealed (the
        line with its checksum key stripped), not a re-serialization, so
        verification is byte-exact."""
        head, sep, tail = line.rpartition(_CRC_SEP)
        if sep and tail.endswith('"}'):
            if f"{zlib.crc32((head + '}').encode()):08x}" != tail[:-2]:
                return False
            line = head + "}"
        try:
            json.loads(line)
        except json.JSONDecodeError:
            return False
        return True

    def recover(self) -> int:
        """Truncate a torn tail (crash/kill mid-append) back to the last
        intact line boundary; returns the number of bytes dropped.

        WRITER PATH ONLY — called automatically before the first
        :meth:`append`.  Read-only consumers polling a *live* ledger
        from another process (``tmx top``, ``status``) must never
        truncate a file someone else is mid-append on; they skip
        unverifiable lines in :meth:`events` instead."""
        self._recovered = True
        try:
            data = self.path.read_bytes()
        except OSError:
            return 0
        good = len(data)
        while good > 0:
            nl = data.rfind(b"\n", 0, good)
            if nl == good - 1:
                # newline-terminated tail line: keep it if intact,
                # otherwise walk back one more line
                start = data.rfind(b"\n", 0, nl) + 1
                frag = data[start:nl]
                if not frag.strip() or self._line_ok(
                    frag.decode("utf-8", errors="replace")
                ):
                    break
                good = start
            else:
                # unterminated fragment — the signature of a torn append
                good = nl + 1
        dropped = len(data) - good
        if dropped:
            logger.warning(
                "ledger %s: truncating %d bytes of torn tail (crash "
                "mid-append) back to the last intact event boundary",
                self.path, dropped,
            )
            with open(self.path, "rb+") as f:
                f.truncate(good)
            self._cache = None
            self._done_cache.clear()
        return dropped

    def append(self, **event) -> None:
        if not self._recovered:
            self.recover()
        event["ts"] = time.time()
        if self.host is not None:
            event.setdefault("host", self.host)
        # One edit point labels every event (spans, batch_done, job
        # lifecycle, compile) with the ambient trace context: the serve
        # daemon installs trace_id/job/tenant around each execution, so a
        # single trace_id links enqueue → admission → run → phase without
        # threading labels through every emitter.  setdefault keeps
        # explicitly-labeled events (e.g. multi-tenant merges) intact.
        for k, v in telemetry.trace_context().items():
            event.setdefault(k, v)
        telemetry.flight_record(event)
        line = self._seal(json.dumps(event))
        spec = faults.match("ledger_append", step=event.get("step"),
                            event=event.get("event"))
        self._cache = None
        if event.get("event") == "init_done":
            # a re-init invalidates earlier batch completions
            self._done_cache.clear()
        with open(self.path, "a") as f:
            if spec is not None:
                # simulate the process dying mid-write: half a line, no
                # newline, then the injected crash propagates
                f.write(line[: max(1, len(line) // 2)])
                f.flush()
                faults.raise_for(spec, "ledger_append", event)
            f.write(line + "\n")
            if self.fsync:
                f.flush()
                os.fsync(f.fileno())

    def append_batch_done(self, step: str, batch: int, **fields) -> bool:
        """Idempotent ``batch_done``: recording a batch whose completion
        is already in the ledger (a resume that re-ran work which had
        persisted, a drained window re-observed) is a detected no-op, so
        replay-derived state (``completed_batches``, ledger metrics)
        never double-counts.  Returns True when the event was appended."""
        done = self._done_cache.get(step)
        if done is None:
            done = self._done_cache[step] = set(self.completed_batches(step))
        if batch in done:
            logger.info(
                "ledger: batch_done for %s batch %d already recorded — "
                "idempotent no-op", step, batch,
            )
            return False
        self.append(step=step, event="batch_done", batch=batch, **fields)
        done.add(batch)
        return True

    def events(self) -> list[dict]:
        """Parsed ledger events; treat the returned list as read-only
        (it is cached until the file changes on disk).  Sealed lines
        failing their CRC are skipped exactly like unparseable ones; the
        ``crc`` key itself is stripped so consumers see the event as it
        was appended."""
        try:
            st = self.path.stat()
        except OSError:
            return []
        key = (st.st_mtime_ns, st.st_size)
        cached = self._cache
        if cached is not None and cached[0] == key:
            return cached[1]
        out = []
        for lineno, line in enumerate(self.path.read_text().splitlines(), 1):
            if not line.strip():
                continue
            if not self._line_ok(line):
                warn_once(
                    logger, f"{self.path}:{lineno}",
                    "ledger %s line %d is torn or corrupt (invalid JSON "
                    "or failed CRC — crash mid-append?) — skipping it; "
                    "resume treats the event as never recorded",
                    str(self.path), lineno,
                )
                continue
            parsed = json.loads(line)
            parsed.pop("crc", None)
            out.append(parsed)
        self._cache = (key, out)
        return out

    def completed_steps(self) -> set[str]:
        return {e["step"] for e in self.events() if e.get("event") == "step_done"}

    def completed_batches(self, step: str) -> set[int]:
        done = set()
        for e in self.events():
            if e.get("step") != step:
                continue
            if e.get("event") == "batch_done":
                done.add(e["batch"])
            elif e.get("event") == "init_done":
                # a re-init invalidates earlier batch completions
                done.clear()
        return done

    def quarantined_batches(self, step: str) -> set[int]:
        """Batches recorded ``batch_failed`` and not completed since; a
        re-init clears the set like it clears completions."""
        q: set[int] = set()
        for e in self.events():
            if e.get("step") != step:
                continue
            if e.get("event") == "batch_failed":
                q.add(e["batch"])
            elif e.get("event") == "batch_done":
                q.discard(e["batch"])
            elif e.get("event") == "init_done":
                q.clear()
        return q

    def last_description_hash(self) -> str | None:
        h = None
        for e in self.events():
            if e.get("event") == "run_started":
                h = e.get("description_hash", h)
        return h

    def status(self) -> dict[str, Any]:
        steps: dict[str, dict] = {}
        for e in self.events():
            s = e.get("step")
            if not s:
                continue
            entry = steps.setdefault(
                s, {"state": "pending", "batches_done": 0, "n_batches": None,
                    "elapsed": 0.0, "quarantined": []}
            )
            if e["event"] == "init_done":
                entry.update(state="running", n_batches=e.get("n_batches"),
                             batches_done=0, quarantined=[])
            elif e["event"] == "batch_done":
                entry["batches_done"] += 1
                entry["elapsed"] += e.get("elapsed", 0.0)
                if e.get("batch") in entry["quarantined"]:
                    entry["quarantined"].remove(e["batch"])
                # object-capacity bucket routing (capacity.py): the batch
                # summary self-describes its routed capacity and slot
                # occupancy — aggregate so `tmx workflow status` shows
                # padding waste without re-reading any outputs
                result = e.get("result") or {}
                cap = result.get("bucket_capacity")
                if cap is not None:
                    buckets = entry.setdefault(
                        "buckets",
                        {"routed": {}, "escalations": 0,
                         "occupancy_sum": 0.0, "occupancy_n": 0},
                    )
                    key = str(cap)
                    buckets["routed"][key] = buckets["routed"].get(key, 0) + 1
                    buckets["escalations"] += int(
                        result.get("bucket_escalations", 0)
                    )
                    occ = result.get("slot_occupancy")
                    if occ is not None:
                        buckets["occupancy_sum"] += float(occ)
                        buckets["occupancy_n"] += 1
                # QC summary fields are run-cumulative at append time,
                # so last-write-wins mirrors the live registry gauges
                qc = result.get("qc")
                if isinstance(qc, dict):
                    entry["qc"] = {
                        "flagged": qc.get("flagged_total", 0),
                        "nan_columns": qc.get("nan_columns", 0),
                        "worst_focus": qc.get("worst_focus"),
                        "count_z_max": qc.get("count_z_max"),
                    }
            elif e["event"] == "qc_budget_exceeded":
                entry.setdefault("qc", {})["budget_exceeded"] = True
            elif e["event"] == "batch_failed":
                if e.get("batch") not in entry["quarantined"]:
                    entry["quarantined"].append(e.get("batch"))
            elif e["event"] == "step_partial":
                entry["state"] = "partial"
                if e.get("pipeline_stats"):
                    entry["pipeline_stats"] = e["pipeline_stats"]
            elif e["event"] == "step_done":
                entry["state"] = "done"
                if e.get("pipeline_stats"):
                    entry["pipeline_stats"] = e["pipeline_stats"]
            elif e["event"] == "step_failed":
                entry["state"] = "failed"
                entry["error"] = e.get("error")
            elif e["event"] == "depth_clamped":
                entry.setdefault("depth_clamps", []).append(
                    {"from": e.get("from_depth"), "to": e.get("to_depth")}
                )
            elif e["event"] == "watchdog":
                entry["watchdog_fires"] = entry.get("watchdog_fires", 0) + 1
            elif e["event"] == "run_preempted":
                entry["preempted"] = True
        return steps

    def preempted(self) -> dict | None:
        """The trailing ``run_preempted`` event when the most recent run
        ended in a graceful drain; a later ``run_started`` (the resume)
        clears it, so status surfaces PREEMPTED only while it is true."""
        last = None
        for e in self.events():
            if e.get("event") == "run_preempted":
                last = e
            elif e.get("event") == "run_started":
                last = None
        return last


class Workflow:
    """Execute a workflow description against an experiment store.

    Fault tolerance (``resilience.py``): each batch runs under the retry
    policy; a batch that keeps failing is *quarantined* (a
    ``batch_failed`` ledger event) while the step continues, and the
    step only fails once quarantined batches exceed the configured
    budget.  ``resume`` re-attempts quarantined batches first.  A device
    health guard probes the device path before every step; when the
    device does not answer the run stops with a transient error (it is
    never moved to another backend) and ``resume`` picks it up."""

    def __init__(self, store: ExperimentStore,
                 description: WorkflowDescription,
                 resilience: ResilienceConfig | None = None,
                 pipeline_depth: int | None = None,
                 should_stop=None, stop_reason=None):
        from tmlibrary_tpu.config import cfg

        description.validate()
        self.store = store
        self.description = description
        #: cooperative-cancellation hooks, polled at every step and batch
        #: boundary (and inside the pipelined executor's launch loop).
        #: Default: the process-wide preemption flag.  ``tmx serve``
        #: passes a composite that also trips on the per-job deadline,
        #: so an expired job cancels at the next batch boundary with
        #: ``PreemptedError(reason="deadline")`` instead of running to
        #: completion.
        self._should_stop = (should_stop if should_stop is not None
                             else preemption_requested)
        self._stop_reason = (stop_reason if stop_reason is not None
                             else preemption_reason)
        self.ledger = RunLedger(
            store.workflow_dir / "ledger.jsonl",
            fsync=cfg.ledger_fsync,
            # single-host runs keep host-free events (seed-compatible
            # ledgers, bit-identical telemetry-off behaviour); fleet runs
            # attribute every event to this host
            host=(telemetry.host_id() if telemetry.fleet_active() else None),
        )
        self.resilience = (resilience if resilience is not None
                           else ResilienceConfig.from_library_config())
        #: explicit in-flight depth for the pipelined executor; None means
        #: resolve per step (config > tuning > per-backend default)
        self.pipeline_depth = pipeline_depth
        #: resilience.PhaseWatchdog for this run (built in :meth:`run`,
        #: None when disabled — the zero-cost default)
        self._watchdog = None

    # ------------------------------------------------------------- identity
    def description_hash(self) -> str:
        """Stable digest of the whole workflow description, recorded in
        ``run_started`` so resume detects drift anywhere in the plan —
        not just in the per-step ``args`` the batch files capture."""
        canon = json.dumps(self.description.to_dict(), sort_keys=True)
        return hashlib.sha256(canon.encode()).hexdigest()[:16]

    # ------------------------------------------------------------------ run
    def run(self, resume: bool = False) -> dict:
        """Run all active steps in order; with ``resume=True`` skip completed
        steps and completed batches of the interrupted step (reference
        ``resume`` CLI verb backed by DB task state)."""
        if not resume and self.ledger.path.exists():
            self.ledger.path.unlink()
        desc_hash = self.description_hash()
        if resume:
            prev = self.ledger.last_description_hash()
            if prev is not None and prev != desc_hash:
                logger.warning(
                    "resume: workflow description changed since the last "
                    "run (%s -> %s) — steps whose args changed will "
                    "re-plan; review the plan if that is unexpected",
                    prev, desc_hash,
                )
                self.ledger.append(event="description_drift",
                                   previous=prev, current=desc_hash)
        self.ledger.append(event="run_started", description_hash=desc_hash,
                           resume=resume)
        # cold-start attribution: wall clock from run start to the first
        # persisted batch of a device-dispatching step (the time XLA
        # compiles dominate on a cold process — the aotstore warm-start
        # plane exists to shrink it)
        self._run_wall_t0 = time.time()
        self._first_batch_noted = False
        telemetry.get_registry().counter("tmx_runs_total").inc()
        sampler = self._start_sampler()
        guard = self.resilience.guard if self.resilience.enabled else None
        # None when disabled: no monitor thread, no arming, no events
        self._watchdog = watchdog_from_config(
            on_fire=guard.note_watchdog_fire if guard is not None else None
        )
        done_steps = self.ledger.completed_steps() if resume else set()
        summary = {}
        try:
            if guard is not None:
                guard.ensure_backend(where="run")
            telemetry.drain_spans()  # whatever closed before this run
            with telemetry.span("run", emit=self.ledger.append):
                for stage in self.description.stages:
                    for sd in stage.steps:
                        if not sd.active:
                            continue
                        if sd.name in done_steps:
                            logger.info(
                                "resume: skipping completed step %s", sd.name
                            )
                            continue
                        if self._should_stop():
                            # the drain request landed between steps (or
                            # during the previous step's collect): the
                            # boundary is already clean — record it and
                            # stop admitting steps
                            self._note_preempted(PreemptedError(
                                f"preempted before step '{sd.name}'",
                                step=sd.name, reason=self._stop_reason(),
                            ))
                        if guard is not None:
                            guard.ensure_backend(where=sd.name)
                        with telemetry.span_scope(step=sd.name), \
                                telemetry.span("step",
                                               emit=self.ledger.append):
                            summary[sd.name] = self._run_step(sd, resume)
        finally:
            if self._watchdog is not None:
                self._watchdog.stop()
                self._drain_watchdog()
                self._watchdog = None
            if sampler is not None:
                sampler.stop()
            self._drain_spans()
            exc = sys.exc_info()[1]
            if exc is not None and not isinstance(exc, PreemptedError) \
                    and not (isinstance(exc, FaultInjected) and exc.fatal):
                # unhandled crash: preserve the last-N event ring for the
                # post-mortem (preemption dumps in _note_preempted; a
                # FATAL injected fault simulates hard process death — a
                # dead process writes nothing)
                telemetry.flight_dump(
                    telemetry.flightrec_path(self.store.workflow_dir),
                    reason=f"crash:{type(exc).__name__}",
                )
            self._write_metrics_snapshot()
        return summary

    def _drain_watchdog(self, step_name: str | None = None) -> None:
        """Append queued ``watchdog`` events — on the engine thread, the
        only thread allowed to touch the ledger (the monitor thread just
        queues)."""
        wd = self._watchdog
        if wd is None:
            return
        fired = False
        for ev in wd.drain_events():
            if step_name is not None:
                ev.setdefault("step", step_name)
            self.ledger.append(**ev)
            fired = True
        if fired:
            # a watchdog fire is one of the flight-recorder dump triggers:
            # the hang's surrounding events are exactly what a post-mortem
            # needs, and they may be gone from the ring by process exit
            telemetry.flight_dump(
                telemetry.flightrec_path(self.store.workflow_dir),
                reason="watchdog", extra={"step": step_name},
            )

    def _drain_spans(self, step_name: str | None = None) -> None:
        """Append the spans that closed on any thread since the last
        drain — here, because only the engine thread may touch the
        ledger.  A span closed outside any step scope (a compile-ahead
        thread) is booked to the step that is running."""
        exc = sys.exc_info()[1]
        if isinstance(exc, FaultInjected) and exc.fatal:
            return  # simulated hard crash: no further ledger writes
        for record in telemetry.drain_spans():
            if step_name is not None:
                record.setdefault("step", step_name)
            self.ledger.append(**record)

    def _note_preempted(self, exc: PreemptedError) -> None:
        """Record the drain boundary durably (``run_preempted`` event +
        counter) and re-raise — the CLI maps this to the pinned
        ``EXIT_PREEMPTED`` code so schedulers re-launch with ``resume``."""
        self._drain_watchdog(exc.step)
        self.ledger.append(
            event="run_preempted", step=exc.step, reason=exc.reason,
            in_flight=exc.in_flight, drained=exc.drained,
            abandoned=exc.abandoned,
        )
        telemetry.flight_dump(
            telemetry.flightrec_path(self.store.workflow_dir),
            reason=f"preempted:{exc.reason}", extra={"step": exc.step},
        )
        telemetry.get_registry().counter("tmx_preemptions_total").inc()
        logger.warning(
            "run preempted (%s) at step '%s': drained %d/%d in-flight "
            "batches, abandoned %d un-launched — resume with "
            "`tmx workflow resume`", exc.reason, exc.step, exc.drained,
            exc.in_flight, exc.abandoned,
        )
        raise exc

    def _write_metrics_snapshot(self) -> None:
        """Persist the live registry next to the ledger so ``tmx metrics``
        exports the run's exact counters without re-deriving — written on
        failure too (a failed run's metrics are the interesting ones).
        All writes are atomic (tmp + rename, ``atomicio``): a kill
        mid-snapshot leaves the previous snapshot intact, never half a
        JSON file."""
        self._write_qc_profile()
        if not telemetry.enabled():
            return
        try:
            rendered = telemetry.render_json(
                telemetry.get_registry().snapshot()
            )
            # per-host snapshot always (fleet merge input); the legacy
            # single-file name stays for host0 so existing tooling and
            # single-host runs see no change
            atomic_write_text(
                telemetry.snapshot_path(self.store.workflow_dir), rendered
            )
            if telemetry.host_id() == "host0":
                atomic_write_text(
                    self.store.workflow_dir / "metrics.json", rendered
                )
        except OSError:
            logger.debug("metrics snapshot write failed", exc_info=True)
        try:
            # same snapshot, durably: one timestamped sample per series
            # into the per-host tsdb segment (`tmx timeline` feeds on it)
            from tmlibrary_tpu import timeseries

            timeseries.flush_registry(self.store.workflow_dir)
        except Exception:
            logger.debug("tsdb flush failed", exc_info=True)
        try:
            # per-program roofline/compile attribution for `tmx perf`
            from tmlibrary_tpu import perf

            snap = perf.perf_snapshot()
            if snap["programs"]:
                atomic_write_text(
                    self.store.workflow_dir / "perf.json",
                    json.dumps(snap, indent=2) + "\n",
                )
        except OSError:
            logger.debug("perf snapshot write failed", exc_info=True)

    def _write_qc_profile(self) -> None:
        """Persist the run's QC profile (``qc.<host>.json``, plus the
        plain ``qc.json`` convenience copy on host0) — same layout
        discipline as the metrics snapshots.  QC has its own gate, so
        this writes even when telemetry is disabled."""
        from tmlibrary_tpu import qc as qc_mod

        profile = qc_mod.get_session().snapshot()
        if not profile:
            return  # QC off, or nothing observed
        try:
            qc_mod.write_profile(
                qc_mod.profile_path(self.store.workflow_dir), profile
            )
            if telemetry.host_id() == "host0":
                qc_mod.write_profile(
                    self.store.workflow_dir / "qc.json", profile
                )
        except OSError:
            logger.debug("qc profile write failed", exc_info=True)

    def _start_sampler(self):
        """Start the resource sampler thread for this run when telemetry
        is on and a sample period is configured; the heartbeat file lands
        next to the ledger so ``tmx workflow status`` and ``tmx top`` can
        spot a hung run."""
        from tmlibrary_tpu.config import cfg

        period = float(getattr(cfg, "resource_sample_period", 0) or 0)
        if not telemetry.enabled() or period <= 0:
            return None
        return telemetry.ResourceSampler(
            period,
            heartbeat_path=telemetry.heartbeat_path(self.store.workflow_dir),
        ).start()

    def _note_straggler(self, step_name: str, batch_index, result) -> None:
        """Emit a ``straggler`` ledger event when a batch summary carries
        device wall times whose max−min skew crosses the threshold.

        Runs on the engine thread right after the ``batch_done`` append —
        executor worker threads must never touch the ledger, so the device
        timings ride the batch result dict instead of being appended from
        ``block_batch``.  The live-registry counter is already bumped by
        :func:`telemetry.record_device_times` at block time; this only
        records the durable evidence."""
        if not telemetry.enabled() or not isinstance(result, dict):
            return
        times = result.get("device_wall_times")
        skew = result.get("straggler_skew_s")
        if not times or skew is None:
            return
        slowest = max(float(t) for t in times.values())
        if float(skew) <= telemetry.straggler_threshold(slowest):
            return
        extra = {}
        # scheduler's predicted per-shard work rides the same event so
        # the anomaly plane (canary.py) can tell data skew — predicted
        # AND actual both skewed — from a slow device (actual only)
        if result.get("predicted_shard_work"):
            extra["predicted_shard_work"] = [
                float(w) for w in result["predicted_shard_work"]
            ]
            extra["predicted_skew"] = float(result.get("predicted_skew", 0.0))
        self.ledger.append(
            step=step_name, event="straggler", batch=batch_index,
            skew_s=float(skew), device_wall_times=times, **extra,
        )

    def _note_qc(self, step_name: str, batch_index, result) -> int:
        """Emit ``qc_batch`` (+ one ``qc_site`` per flagged site) ledger
        events when a batch summary carries a QC summary.

        Same thread discipline as :meth:`_note_straggler`: the QC
        evidence rides the batch result dict from the persist worker,
        and only the engine thread appends to the ledger.  QC flags are
        observability, not control flow — they reuse the quarantine
        machinery's *ledger* surface without ever failing a batch.
        Returns the number of sites flagged by this batch."""
        if not isinstance(result, dict):
            return 0
        summary = result.get("qc")
        if not isinstance(summary, dict):
            return 0
        flagged = summary.get("flagged_sites") or []
        self.ledger.append(
            step=step_name, event="qc_batch", batch=batch_index,
            summary={k: v for k, v in summary.items()
                     if k != "flagged_sites"},
        )
        for site in flagged:
            self.ledger.append(
                step=step_name, event="qc_site", batch=batch_index,
                **{k: v for k, v in site.items() if k != "step"},
            )
        return len(flagged)

    # ---------------------------------------------------------- batch level
    def _exec_batch(self, step, batch: dict) -> dict:
        faults.maybe_fire("batch_run", step=step.name, batch=batch["index"])
        with telemetry.span_scope(step=step.name, batch=batch["index"]):
            return step.run_batch(batch)

    def _retry_after(self, step, batch: dict, first_exc: Exception,
                     policy: RetryPolicy) -> RetryOutcome:
        """Fold an already-observed failure into the retry budget and run
        the remaining attempts sequentially."""
        cls = classify(first_exc)
        if cls is PERMANENT or policy.max_attempts <= 1:
            return RetryOutcome(error=first_exc, attempts=1,
                                classification=cls)
        remaining = dataclasses.replace(
            policy, max_attempts=policy.max_attempts - 1
        )
        out = retry_call(
            lambda: self._exec_batch(step, batch), remaining,
            describe=f"{step.name} batch {batch['index']}",
        )
        out.attempts += 1
        return out

    def _iter_outcomes(self, step, pending: list[dict],
                       policy: RetryPolicy,
                       pstats: PipelineStats | None = None):
        """Yield ``(batch, RetryOutcome)`` for every pending batch.

        Prefers the deep pipelined executor (``pstats`` carries the
        resolved depth) for steps exposing the launch/persist split, then
        the step's own ``run_batches_pipelined`` generator; after a
        pipeline fault the failing batch is retried and the remainder
        degrades to sequential execution — per-batch isolation beats
        overlap once the device is flaky.  With a fault plan targeting a
        pre-persist site armed the sequential path is used from the
        start, so those faults fire *before* a batch persists (the
        pipelined paths persist a batch before the engine sees it);
        ``persist``-site plans keep the real executor.  Both paths poll
        the preemption flag at batch boundaries and surface a drain as
        :class:`PreemptedError` — never as a batch failure."""
        gen = None
        if pstats is not None and pending:
            executor = PipelinedExecutor(
                step, depth=pstats.depth, depth_source=pstats.source,
                on_event=lambda **ev: self.ledger.append(
                    step=step.name, **ev
                ),
                stats=pstats,
                should_stop=self._should_stop,
                watchdog=self._watchdog,
                # compile-ahead speculation (aotstore plane): steps that
                # expose the hook warm the likely next capacity rungs on
                # a background thread once the window starts filling
                warm_hook=getattr(step, "speculate_ahead", None),
            )
            gen = executor.run(pending)
        elif (hasattr(step, "run_batches_pipelined") and pending
                and not faults.sequential_forced()):
            gen = iter(step.run_batches_pipelined(pending))
        pos = 0
        while pos < len(pending):
            if gen is not None:
                try:
                    batch, result = next(gen)
                except StopIteration:
                    break
                except Exception as e:
                    if isinstance(e, FaultInjected) and e.fatal:
                        raise
                    if isinstance(e, PreemptedError):
                        raise  # drained cleanly — not a batch failure
                    # the pipeline died mid-flight: the first unyielded
                    # batch is the one it was working on
                    logger.warning(
                        "%s: pipelined runner failed at batch %d — "
                        "degrading to sequential execution",
                        step.name, pending[pos]["index"],
                    )
                    gen = None
                    yield pending[pos], self._retry_after(
                        step, pending[pos], e, policy
                    )
                    pos += 1
                    continue
                yield batch, RetryOutcome(value=result, attempts=1)
                pos += 1
            else:
                batch = pending[pos]
                if self._should_stop():
                    raise PreemptedError(
                        f"preempted before batch {batch['index']} of "
                        f"'{step.name}': abandoned {len(pending) - pos} "
                        f"pending batches",
                        step=step.name, abandoned=len(pending) - pos,
                        reason=self._stop_reason(),
                    )
                try:
                    yield batch, RetryOutcome(
                        value=self._exec_batch(step, batch), attempts=1
                    )
                except Exception as e:
                    if isinstance(e, FaultInjected) and e.fatal:
                        raise
                    yield batch, self._retry_after(step, batch, e, policy)
                pos += 1

    @staticmethod
    def _call_collect(step, results: list[dict]):
        """Pass the surviving batch results to ``collect`` when the step
        accepts them (newer signature); legacy ``collect(self)`` steps
        keep working."""
        try:
            params = inspect.signature(step.collect).parameters
        except (TypeError, ValueError):
            params = {}
        if "results" in params:
            return step.collect(results=results)
        return step.collect()

    # ----------------------------------------------------------- step level
    def _run_step(self, sd: WorkflowStepDescription, resume: bool) -> dict:
        step_cls = get_step(sd.name)
        step = step_cls(self.store)
        res = self.resilience
        policy = (res.policy if res.enabled
                  else RetryPolicy(max_attempts=1, base_delay=0.0))
        t0 = time.time()
        current_batch: int | None = None
        try:
            existing = step.list_batches() if resume else []
            quarantined: set[int] = set()
            if existing:
                batches = [step.load_batch(i) for i in existing]
                done = self.ledger.completed_batches(sd.name)
                quarantined = self.ledger.quarantined_batches(sd.name)
                # if the description's args changed since the batches were
                # planned, the old plan is stale — re-init from scratch
                if batches and step.batch_args.resolve(sd.args) != batches[0]["args"]:
                    logger.info("resume: args changed for %s, re-planning", sd.name)
                    existing = []
            if not existing:
                batches = step.init(sd.args)
                batches = [step.load_batch(i) for i in range(len(batches))]
                done = set()
                quarantined = set()
                self.ledger.append(step=sd.name, event="init_done",
                                   n_batches=len(batches))
            # durable schedule-plan provenance: whenever the step planned
            # its batches with the work-model scheduler, the plan digest
            # (and its predicted occupancy/skew deltas) lands in the
            # ledger — on --resume the same event re-appends from the
            # plan side file, so convergence is auditable from the
            # ledger alone (bit-identical digests across attempts)
            plan_info = getattr(step, "schedule_plan_info", None)
            if callable(plan_info):
                try:
                    info = plan_info()
                except Exception:
                    info = None
                if info:
                    self.ledger.append(
                        step=sd.name, event="schedule_plan", **info
                    )
            pending = [b for b in batches if b["index"] not in done]
            # quarantined batches first: the most suspect work re-runs at
            # the start of the resume, while everything else still follows
            pending.sort(key=lambda b: (b["index"] not in quarantined,
                                        b["index"]))
            if quarantined:
                logger.info("resume: re-attempting quarantined batches %s "
                            "of %s first", sorted(quarantined), sd.name)
            results: list[dict] = []
            failed: list[dict] = []
            budget = res.failure_budget(len(batches)) if res.enabled else 0
            # QC flag budget: a warn-only threshold over the step's
            # planned site count (resilience.qc_flag_budget fraction)
            qc_flagged = 0
            qc_budget_noted = False
            qc_sites_total = sum(len(b.get("sites") or []) for b in batches)
            qc_site_budget = (
                int(res.qc_flag_budget * qc_sites_total)
                if res.enabled and qc_sites_total else 0
            )
            pstats = None
            if (pending and supports_pipelining(step)
                    and not faults.sequential_forced()):
                depth, source = resolve_pipeline_depth(
                    explicit=self.pipeline_depth
                )
                pstats = PipelineStats(depth, source, step=sd.name)
                logger.info(
                    "%s: pipelined executor, in-flight depth %d (source: "
                    "%s)", sd.name, depth, source,
                )
            metrics = telemetry.get_registry()
            bt0 = time.time()
            with step.capture_logs("run"):  # per-step log file (§6)
                for batch, outcome in self._iter_outcomes(step, pending,
                                                          policy, pstats):
                    current_batch = batch["index"]
                    self._drain_watchdog(sd.name)
                    self._drain_spans(sd.name)
                    if outcome.ok:
                        b_elapsed = time.time() - bt0
                        if telemetry.enabled():
                            self.ledger.append(
                                step=sd.name, event="span", span="batch",
                                batch=batch["index"], t0=round(bt0, 6),
                                elapsed=round(b_elapsed, 6),
                            )
                        self.ledger.append_batch_done(
                            sd.name, batch["index"],
                            elapsed=b_elapsed,
                            attempts=outcome.attempts,
                            result=outcome.value)
                        # only steps that run batch programs (they
                        # expose the compile-ahead hook — where the XLA
                        # compiles live) count: a metaconfig batch
                        # landing in milliseconds, or an illuminati
                        # channel, would mask the cold-start this
                        # metric exists to expose
                        if (not getattr(self, "_first_batch_noted", True)
                                and getattr(self, "_run_wall_t0", None)
                                and hasattr(step, "speculate_ahead")):
                            self._first_batch_noted = True
                            ttfb = time.time() - self._run_wall_t0
                            # NOT batch= : any step+batch event mints a
                            # batch node in build_span_tree, and this
                            # marker is an instant, not a span
                            self.ledger.append(
                                step=sd.name, event="first_batch",
                                first_batch_index=batch["index"],
                                time_to_first_batch_s=round(ttfb, 6),
                            )
                            metrics.gauge(
                                "tmx_time_to_first_batch_seconds"
                            ).set(round(ttfb, 6))
                        self._note_straggler(sd.name, batch["index"],
                                             outcome.value)
                        qc_flagged += self._note_qc(sd.name, batch["index"],
                                                    outcome.value)
                        if (qc_site_budget and not qc_budget_noted
                                and qc_flagged > qc_site_budget):
                            # the QC flag budget warns, it never fails:
                            # bad inputs are a human decision, not a
                            # scheduler one (quarantine stays reserved
                            # for execution failures)
                            qc_budget_noted = True
                            self.ledger.append(
                                step=sd.name, event="qc_budget_exceeded",
                                flagged=qc_flagged, budget=qc_site_budget,
                            )
                            metrics.counter(
                                "tmx_qc_budget_exceeded_total",
                                step=sd.name).inc()
                            logger.warning(
                                "%s: QC flagged %d sites — more than the "
                                "configured budget (%d); inspect with "
                                "`tmx qc`", sd.name, qc_flagged,
                                qc_site_budget,
                            )
                        metrics.counter("tmx_batches_done_total",
                                        step=sd.name).inc()
                        metrics.histogram("tmx_batch_seconds",
                                          step=sd.name).observe(b_elapsed)
                        if outcome.attempts > 1:
                            metrics.counter("tmx_batch_retries_total",
                                            step=sd.name).inc(
                                                outcome.attempts - 1)
                        results.append(outcome.value)
                        bt0 = time.time()
                        continue
                    failure = {
                        "batch": batch["index"],
                        "error": str(outcome.error),
                        "exception": type(outcome.error).__name__,
                        "attempts": outcome.attempts,
                        "classification": outcome.classification,
                    }
                    self.ledger.append(step=sd.name, event="batch_failed",
                                       **failure)
                    metrics.counter("tmx_batches_failed_total",
                                    step=sd.name).inc()
                    metrics.counter("tmx_batches_quarantined_total",
                                    step=sd.name).inc()
                    failed.append(failure)
                    bt0 = time.time()
                    if len(failed) > budget:
                        raise WorkflowError(
                            f"step '{sd.name}': {len(failed)} failed "
                            f"batches exceeds the quarantine budget "
                            f"({budget} of {len(batches)})"
                        ) from outcome.error
                    logger.error(
                        "%s: batch %d quarantined after %d attempt(s) "
                        "(%s: %s) — step continues (%d/%d budget used)",
                        sd.name, batch["index"], outcome.attempts,
                        failure["exception"], failure["error"],
                        len(failed), budget,
                    )
                # collect is part of the step execution the log file
                # covers; it sees only the surviving results
                collected = self._call_collect(step, results)
            self._drain_spans(sd.name)
            metrics.histogram("tmx_step_seconds", step=sd.name).observe(
                time.time() - t0
            )
            extra = ({"pipeline_stats": pstats.summary()}
                     if pstats is not None else {})
            if failed:
                # no step_done: resume re-attempts the quarantined
                # batches first, then re-collects
                self.ledger.append(
                    step=sd.name, event="step_partial",
                    elapsed=time.time() - t0, collected=collected,
                    quarantined=sorted(f["batch"] for f in failed),
                    **extra,
                )
                metrics.counter("tmx_steps_partial_total",
                                step=sd.name).inc()
                return {"n_batches": len(batches), "collected": collected,
                        "quarantined": sorted(f["batch"] for f in failed)}
            self.ledger.append(step=sd.name, event="step_done",
                               elapsed=time.time() - t0, collected=collected,
                               **extra)
            metrics.counter("tmx_steps_done_total", step=sd.name).inc()
            return {"n_batches": len(batches), "collected": collected}
        except PreemptedError as e:
            # a drain, not a failure: the ledger boundary is clean, so no
            # step_failed — record the drain summary and surface the
            # pinned-exit-code path (cli → EXIT_PREEMPTED → resume)
            if e.step is None:
                e.step = sd.name
            if e.reason == "signal":
                # the executor's drain path doesn't know which signal
                # (or deadline) tripped the flag — the stop-reason hook
                # does
                e.reason = self._stop_reason()
            self._note_preempted(e)
        except FaultInjected as e:
            if e.fatal:
                raise  # simulated hard crash: no further ledger writes
            self.ledger.append(step=sd.name, event="step_failed",
                               error=str(e), exception=type(e).__name__,
                               batch=current_batch)
            telemetry.get_registry().counter("tmx_steps_failed_total",
                                             step=sd.name).inc()
            raise WorkflowError(f"step '{sd.name}' failed: {e}") from e
        except WorkflowError as e:
            # e.g. the quarantine budget overflow above; keep the original
            # exception class visible in the ledger via __cause__
            self.ledger.append(step=sd.name, event="step_failed",
                               error=str(e),
                               exception=type(e.__cause__ or e).__name__,
                               batch=current_batch)
            telemetry.get_registry().counter("tmx_steps_failed_total",
                                             step=sd.name).inc()
            raise
        except Exception as e:
            self.ledger.append(step=sd.name, event="step_failed",
                               error=str(e), exception=type(e).__name__,
                               batch=current_batch)
            telemetry.get_registry().counter("tmx_steps_failed_total",
                                             step=sd.name).inc()
            raise WorkflowError(f"step '{sd.name}' failed: {e}") from e
