"""Unit + engine-level tests for the fault-tolerance layer
(``resilience.py`` / ``faults.py`` / engine quarantine semantics).

The full-pipeline chaos suite (real steps, convergence under injected
faults) lives in ``test_chaos.py``; here a registered dummy step keeps
the engine paths fast and surgical.
"""

import json

import pytest

from tmlibrary_tpu import faults
from tmlibrary_tpu.errors import (
    FaultInjected,
    PipelineError,
    ProbeTimeoutError,
    TransientDeviceError,
    VendorConflictError,
    WorkflowError,
)
from tmlibrary_tpu.models.experiment import Experiment
from tmlibrary_tpu.models.store import ExperimentStore
from tmlibrary_tpu.resilience import (
    PERMANENT,
    TRANSIENT,
    CircuitBreaker,
    DeviceHealthGuard,
    ResilienceConfig,
    RetryPolicy,
    call_with_timeout,
    classify,
    retry_call,
)
from tmlibrary_tpu.workflow.api import Step
from tmlibrary_tpu.workflow.engine import (
    RunLedger,
    Workflow,
    WorkflowDescription,
    WorkflowStageDescription,
    WorkflowStepDescription,
)
from tmlibrary_tpu.workflow.registry import register_step


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.clear()
    yield
    faults.clear()


# --------------------------------------------------------------- dummy step
@register_step("chaosdummy")
class ChaosDummy(Step):
    """Four trivial batches; each writes a marker file (idempotent)."""

    N_BATCHES = 4

    def create_batches(self, args):
        return [{} for _ in range(self.N_BATCHES)]

    def run_batch(self, batch):
        out = self.step_dir / f"out_{batch['index']:03d}.txt"
        out.write_text("ok")
        return {"i": batch["index"]}


@register_step("chaoscollect")
class ChaosCollect(ChaosDummy):
    """Collect override that accepts the surviving results."""

    last_results = None

    def collect(self, results=None):
        ChaosCollect.last_results = results
        return {"n_results": len(results or [])}


@register_step("chaospipelined")
class ChaosPipelined(ChaosDummy):
    """Pipelined runner that dies when it reaches ``FAIL_AT`` (set by the
    test); ``run_batch`` still works, so the engine's sequential
    degradation must recover every batch."""

    FAIL_AT: int | None = None

    def run_batches_pipelined(self, batches):
        for b in batches:
            if b["index"] == ChaosPipelined.FAIL_AT:
                raise TransientDeviceError("pipeline blew up")
            yield b, self.run_batch(b)


def dummy_description(step="chaosdummy"):
    return WorkflowDescription(
        stages=[WorkflowStageDescription(
            name="test", steps=[WorkflowStepDescription(name=step)]
        )]
    )


def fast_resilience(max_batch_failures=0.5, attempts=3):
    return ResilienceConfig(
        policy=RetryPolicy(max_attempts=attempts, base_delay=0.0, jitter=0.0),
        max_batch_failures=max_batch_failures,
        guard=None,
    )


@pytest.fixture
def store(tmp_path):
    placeholder = Experiment(
        name="res", plates=[], channels=[], site_height=1, site_width=1
    )
    return ExperimentStore.create(tmp_path / "exp", placeholder)


# ------------------------------------------------------------- RetryPolicy
def test_retry_policy_deterministic_backoff():
    p = RetryPolicy(max_attempts=5, base_delay=0.5, max_delay=8.0,
                    jitter=0.25, seed=7)
    first = [p.delay(a) for a in range(1, 6)]
    again = [p.delay(a) for a in range(1, 6)]
    assert first == again  # seeded jitter: replays sleep identically
    # exponential envelope with symmetric jitter
    for a, d in enumerate(first, 1):
        nominal = min(8.0, 0.5 * 2 ** (a - 1))
        assert 0.75 * nominal - 1e-9 <= d <= 1.25 * nominal + 1e-9
    assert RetryPolicy(seed=8).delay(1) != RetryPolicy(seed=9).delay(1)
    assert RetryPolicy(jitter=0.0, base_delay=1.0).delay(3) == 4.0


# -------------------------------------------------------------- classifier
@pytest.mark.parametrize("exc,expected", [
    (TransientDeviceError("device gone"), TRANSIENT),
    (TimeoutError("x"), TRANSIENT),
    (OSError("disk hiccup"), TRANSIENT),
    (MemoryError(), TRANSIENT),
    (RuntimeError("UNAVAILABLE: socket closed"), TRANSIENT),
    (RuntimeError("RESOURCE_EXHAUSTED: out of memory allocating"), TRANSIENT),
    (VendorConflictError("two containers on one well"), PERMANENT),
    (PipelineError("bad pipe"), PERMANENT),
    (ValueError("bad arg"), PERMANENT),
    (RuntimeError("some genuine bug"), PERMANENT),
    (FaultInjected("x", transient=True), TRANSIENT),
    (FaultInjected("x", transient=False), PERMANENT),
])
def test_classify(exc, expected):
    assert classify(exc) == expected


# -------------------------------------------------------------- retry_call
def test_retry_call_recovers_after_transient_failures():
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise TransientDeviceError("flake")
        return "ok"

    slept = []
    out = retry_call(flaky, RetryPolicy(max_attempts=4, base_delay=0.5,
                                        jitter=0.0),
                     sleep=slept.append)
    assert out.ok and out.value == "ok" and out.attempts == 3
    assert slept == [0.5, 1.0]  # exponential backoff between attempts


def test_retry_call_permanent_fails_fast():
    calls = []

    def broken():
        calls.append(1)
        raise ValueError("corrupt data")

    out = retry_call(broken, RetryPolicy(max_attempts=5, base_delay=0.0))
    assert not out.ok and out.attempts == 1 and len(calls) == 1
    assert out.classification == PERMANENT


def test_retry_call_exhausts_attempts():
    out = retry_call(
        lambda: (_ for _ in ()).throw(TransientDeviceError("down")),
        RetryPolicy(max_attempts=3, base_delay=0.0), sleep=lambda s: None,
    )
    assert not out.ok and out.attempts == 3
    assert out.classification == TRANSIENT


def test_retry_call_respects_deadline():
    calls = []

    def flaky():
        calls.append(1)
        raise TransientDeviceError("down")

    out = retry_call(
        flaky,
        RetryPolicy(max_attempts=50, base_delay=100.0, jitter=0.0,
                    deadline=1.0),
        sleep=lambda s: None,
    )
    # the first 100 s backoff would blow the 1 s deadline: stop after try 1
    assert not out.ok and len(calls) == 1


def test_retry_call_never_absorbs_fatal_faults():
    def crash():
        raise FaultInjected("crash", transient=False, fatal=True)

    with pytest.raises(FaultInjected):
        retry_call(crash, RetryPolicy(max_attempts=3, base_delay=0.0))


# -------------------------------------------------------- call_with_timeout
def test_call_with_timeout_paths():
    import time as _time

    assert call_with_timeout(lambda: 42, 1.0) == 42
    with pytest.raises(ValueError):
        call_with_timeout(lambda: (_ for _ in ()).throw(ValueError("x")), 1.0)
    with pytest.raises(ProbeTimeoutError):
        call_with_timeout(lambda: _time.sleep(5), 0.05)


# ----------------------------------------------------------- CircuitBreaker
def test_circuit_breaker_lifecycle():
    clock = {"t": 0.0}
    br = CircuitBreaker(failure_threshold=2, cooldown=10.0,
                        clock=lambda: clock["t"])
    assert br.state == "closed" and br.allow()
    br.record_failure()
    assert br.state == "closed"  # under threshold
    br.record_failure()
    assert br.state == "open" and not br.allow()
    clock["t"] = 10.0
    assert br.state == "half-open" and br.allow()
    br.record_failure()  # failed half-open probe: re-open, doubled cooldown
    assert br.state == "open" and br.cooldown == 20.0
    clock["t"] = 30.0
    assert br.allow()
    br.record_success()
    assert br.state == "closed" and br.cooldown == 10.0 and br.failures == 0


# -------------------------------------------------------- DeviceHealthGuard
def test_guard_raises_transient_on_hanging_probe():
    import time as _time

    guard = DeviceHealthGuard(probe=lambda: _time.sleep(5), timeout=0.05,
                              failure_threshold=1, cooldown=3600.0)
    with pytest.raises(TransientDeviceError, match="device path is down"):
        guard.ensure_backend(where="run")
    assert classify(TransientDeviceError("x")) == TRANSIENT
    # subsequent calls fail at once without re-probing (circuit open)
    t0 = _time.monotonic()
    with pytest.raises(TransientDeviceError):
        guard.ensure_backend()
    assert _time.monotonic() - t0 < 0.05


def test_guard_healthy_path_caches_probe():
    calls = []
    guard = DeviceHealthGuard(probe=lambda: calls.append(1), timeout=1.0,
                              probe_ttl=3600.0)
    guard.ensure_backend()
    guard.ensure_backend()
    assert len(calls) == 1  # TTL cache: one probe


# ----------------------------------------------------------------- ledger
def test_ledger_survives_truncated_trailing_line(tmp_path):
    path = tmp_path / "ledger.jsonl"
    ledger = RunLedger(path)
    ledger.append(step="a", event="init_done", n_batches=2)
    ledger.append(step="a", event="batch_done", batch=0)
    # crash mid-append: half a JSON object, no newline
    with open(path, "a") as f:
        f.write('{"step": "a", "event": "batch_do')
    events = ledger.events()  # must not raise
    assert [e["event"] for e in events] == ["init_done", "batch_done"]
    assert ledger.completed_batches("a") == {0}
    assert ledger.completed_steps() == set()
    # the resuming process's writer truncates the torn tail before its
    # first append, so later events land on a clean line boundary and
    # are NOT lost
    resumed = RunLedger(path)
    resumed.append(step="a", event="batch_done", batch=1)
    resumed.append(step="a", event="step_done")
    assert resumed.completed_steps() == {"a"}
    assert resumed.completed_batches("a") == {0, 1}
    raw = path.read_text()
    assert '"event": "batch_do{' not in raw  # the torn fragment is gone
    assert raw.endswith("\n")


def test_ledger_crc_detects_tampered_line(tmp_path):
    """A line whose payload no longer matches its CRC (bit rot, a torn
    write that happens to stay valid JSON) is skipped like a torn one."""
    path = tmp_path / "ledger.jsonl"
    ledger = RunLedger(path)
    ledger.append(step="a", event="init_done", n_batches=2)
    ledger.append(step="a", event="batch_done", batch=0)
    ledger.append(step="a", event="batch_done", batch=1)
    lines = path.read_text().splitlines()
    assert all('"crc": "' in ln for ln in lines)  # every line sealed
    # corrupt the middle line's payload without touching its CRC: still
    # valid JSON, but the checksum proves it is not what was written
    lines[1] = lines[1].replace('"batch": 0', '"batch": 9')
    path.write_text("\n".join(lines) + "\n")
    fresh = RunLedger(path)
    assert fresh.completed_batches("a") == {1}  # tampered line dropped
    # the reader strips the checksum key from surviving events
    assert all("crc" not in e for e in fresh.events())


def test_ledger_reads_seed_era_crc_less_lines(tmp_path):
    """Ledgers written before line sealing (no ``crc`` key) stay fully
    readable — the checksum is only enforced where present."""
    path = tmp_path / "ledger.jsonl"
    path.write_text(
        '{"event": "run_started", "description_hash": "x"}\n'
        '{"step": "a", "event": "init_done", "n_batches": 1}\n'
        '{"step": "a", "event": "batch_done", "batch": 0}\n'
        '{"step": "a", "event": "step_done"}\n'
    )
    ledger = RunLedger(path)
    assert ledger.completed_steps() == {"a"}
    assert ledger.completed_batches("a") == {0}
    # a new-writer append seals its own line without disturbing the old
    ledger.append(step="b", event="init_done", n_batches=1)
    raw = path.read_text().splitlines()
    assert '"crc": "' not in raw[0] and '"crc": "' in raw[-1]
    assert len(RunLedger(path).events()) == 5


def test_ledger_idempotent_batch_done(tmp_path):
    """Re-recording an already-completed batch is a detected no-op: one
    ``batch_done`` event per (step, batch), however often persist-side
    replay re-observes it."""
    ledger = RunLedger(tmp_path / "l.jsonl")
    ledger.append(step="s", event="init_done", n_batches=2)
    assert ledger.append_batch_done("s", 0, elapsed=0.1) is True
    assert ledger.append_batch_done("s", 0, elapsed=0.2) is False
    assert ledger.append_batch_done("s", 1) is True
    done = [e for e in ledger.events() if e.get("event") == "batch_done"]
    assert [e["batch"] for e in done] == [0, 1]
    # a second writer instance resolves idempotence from disk
    again = RunLedger(ledger.path)
    assert again.append_batch_done("s", 1) is False
    # a re-init invalidates completions, so the same index records anew
    ledger.append(step="s", event="init_done", n_batches=2)
    assert ledger.append_batch_done("s", 0) is True


def test_ledger_fsync_flag(tmp_path):
    ledger = RunLedger(tmp_path / "ledger.jsonl", fsync=True)
    ledger.append(step="a", event="init_done", n_batches=1)
    assert ledger.events()[0]["event"] == "init_done"


def test_ledger_quarantine_bookkeeping(tmp_path):
    ledger = RunLedger(tmp_path / "l.jsonl")
    ledger.append(step="s", event="init_done", n_batches=3)
    ledger.append(step="s", event="batch_failed", batch=1, error="x",
                  exception="TransientDeviceError", attempts=3)
    ledger.append(step="s", event="batch_done", batch=0)
    assert ledger.quarantined_batches("s") == {1}
    # a later completion clears the quarantine
    ledger.append(step="s", event="batch_done", batch=1)
    assert ledger.quarantined_batches("s") == set()
    # a re-init clears everything
    ledger.append(step="s", event="batch_failed", batch=2, error="x",
                  exception="OSError", attempts=1)
    ledger.append(step="s", event="init_done", n_batches=3)
    assert ledger.quarantined_batches("s") == set()


# -------------------------------------------------------------- fault plan
def test_fault_plan_matching_and_times():
    plan = faults.FaultPlan([
        faults.FaultSpec(site="batch_run", kind="device_loss", step="s",
                         batch=1, times=2),
    ])
    assert plan.match("batch_run", step="s", batch=0) is None
    assert plan.match("batch_run", step="other", batch=1) is None
    assert plan.match("batch_run", step="s", batch=1) is not None
    assert plan.match("batch_run", step="s", batch=1) is not None
    assert plan.match("batch_run", step="s", batch=1) is None  # times spent
    assert plan.fire_counts() == {"batch_run/device_loss": 2}


def test_fault_plan_probability_is_seed_deterministic():
    def draws(seed):
        plan = faults.FaultPlan(
            [faults.FaultSpec(site="batch_run", kind="io_error",
                              probability=0.5, times=10**6)],
            seed=seed,
        )
        return [plan.match("batch_run", step="s", batch=b) is not None
                for b in range(64)]

    assert draws(3) == draws(3)  # replayable
    assert draws(3) != draws(4)  # but seed-sensitive
    assert any(draws(3)) and not all(draws(3))


def test_fault_plan_from_json_roundtrip():
    plan = faults.FaultPlan.from_json(json.dumps({
        "seed": 11,
        "faults": [{"site": "batch_run", "kind": "io_error", "step": "s",
                    "batch": 2, "times": 3}],
    }))
    assert plan.seed == 11
    assert plan.specs[0].kind == "io_error" and plan.specs[0].times == 3
    with pytest.raises(ValueError):
        faults.FaultPlan([faults.FaultSpec(site="x", kind="nope")])


# --------------------------------------------------- engine: quarantine
def test_engine_quarantines_failing_batch(store):
    faults.install(faults.FaultPlan([
        faults.FaultSpec(site="batch_run", kind="device_loss",
                         step="chaosdummy", batch=1, times=99),
    ]))
    wf = Workflow(store, dummy_description(), resilience=fast_resilience())
    summary = wf.run()
    assert summary["chaosdummy"]["quarantined"] == [1]
    events = wf.ledger.events()
    bf = [e for e in events if e.get("event") == "batch_failed"]
    assert len(bf) == 1
    assert bf[0]["batch"] == 1
    assert bf[0]["exception"] == "TransientDeviceError"
    assert bf[0]["attempts"] == 3  # full retry budget burned
    assert bf[0]["classification"] == "transient"
    # step is partial, not done — resume will revisit it
    assert any(e.get("event") == "step_partial" for e in events)
    assert not any(e.get("event") == "step_done" for e in events)
    assert wf.ledger.quarantined_batches("chaosdummy") == {1}
    # the other batches ran to completion
    assert wf.ledger.completed_batches("chaosdummy") == {0, 2, 3}


def test_engine_retry_recovers_single_flake(store):
    faults.install(faults.FaultPlan([
        faults.FaultSpec(site="batch_run", kind="device_loss",
                         step="chaosdummy", batch=2, times=1),
    ]))
    wf = Workflow(store, dummy_description(), resilience=fast_resilience())
    summary = wf.run()
    assert "quarantined" not in summary["chaosdummy"]
    done = {e["batch"]: e for e in wf.ledger.events()
            if e.get("event") == "batch_done"}
    assert set(done) == {0, 1, 2, 3}
    assert done[2]["attempts"] == 2  # one retry
    assert done[0]["attempts"] == 1


def test_engine_permanent_fault_skips_retries(store):
    faults.install(faults.FaultPlan([
        faults.FaultSpec(site="batch_run", kind="crash",
                         step="chaosdummy", batch=0, times=99),
    ]))
    wf = Workflow(store, dummy_description(), resilience=fast_resilience())
    wf.run()
    bf = [e for e in wf.ledger.events() if e.get("event") == "batch_failed"]
    assert bf[0]["attempts"] == 1  # permanent: no retry
    assert bf[0]["classification"] == "permanent"
    assert bf[0]["exception"] == "FaultInjected"


def test_engine_failure_budget_aborts_step(store):
    faults.install(faults.FaultPlan([
        faults.FaultSpec(site="batch_run", kind="device_loss",
                         step="chaosdummy", batch=b, times=99)
        for b in (0, 1, 2)
    ]))
    # budget 0.5 of 4 batches = 2 quarantines allowed; the 3rd aborts
    wf = Workflow(store, dummy_description(), resilience=fast_resilience())
    with pytest.raises(WorkflowError, match="quarantine budget"):
        wf.run()
    sf = [e for e in wf.ledger.events() if e.get("event") == "step_failed"]
    assert sf and sf[0]["batch"] == 2  # failing batch index recorded
    # the root cause class, not the WorkflowError wrapper
    assert sf[0]["exception"] == "TransientDeviceError"


def test_engine_zero_budget_restores_fail_fast(store):
    faults.install(faults.FaultPlan([
        faults.FaultSpec(site="batch_run", kind="device_loss",
                         step="chaosdummy", batch=0, times=99),
    ]))
    wf = Workflow(store, dummy_description(),
                  resilience=fast_resilience(max_batch_failures=0))
    with pytest.raises(WorkflowError):
        wf.run()


def test_engine_resume_reattempts_quarantined_first(store):
    faults.install(faults.FaultPlan([
        faults.FaultSpec(site="batch_run", kind="io_error",
                         step="chaosdummy", batch=2, times=99),
    ]))
    wf = Workflow(store, dummy_description(), resilience=fast_resilience())
    assert wf.run()["chaosdummy"]["quarantined"] == [2]
    n_events = len(wf.ledger.events())

    faults.clear()
    wf2 = Workflow(store, dummy_description(), resilience=fast_resilience())
    summary = wf2.run(resume=True)
    assert "quarantined" not in summary["chaosdummy"]
    new = wf2.ledger.events()[n_events:]
    ran = [e["batch"] for e in new if e.get("event") == "batch_done"]
    assert ran == [2]  # ONLY the quarantined batch re-ran
    assert any(e.get("event") == "step_done" for e in new)
    assert wf2.ledger.quarantined_batches("chaosdummy") == set()


def test_engine_pipelined_degrades_to_sequential(store):
    ChaosPipelined.FAIL_AT = 2
    try:
        wf = Workflow(store, dummy_description("chaospipelined"),
                      resilience=fast_resilience())
        summary = wf.run()
        assert "quarantined" not in summary["chaospipelined"]
        done = {e["batch"]: e for e in wf.ledger.events()
                if e.get("event") == "batch_done"}
        assert set(done) == {0, 1, 2, 3}
        # batch 2's first (pipelined) try failed, the sequential retry won
        assert done[2]["attempts"] == 2
    finally:
        ChaosPipelined.FAIL_AT = None


def test_engine_collect_receives_surviving_results(store):
    faults.install(faults.FaultPlan([
        faults.FaultSpec(site="batch_run", kind="device_loss",
                         step="chaoscollect", batch=1, times=99),
    ]))
    ChaosCollect.last_results = None
    wf = Workflow(store, dummy_description("chaoscollect"),
                  resilience=fast_resilience())
    summary = wf.run()
    assert summary["chaoscollect"]["collected"] == {"n_results": 3}
    assert [r["i"] for r in ChaosCollect.last_results] == [0, 2, 3]


# ------------------------------------------------- engine: run identity
def test_run_started_event_and_description_drift(store):
    wf = Workflow(store, dummy_description(), resilience=fast_resilience())
    wf.run()
    events = wf.ledger.events()
    started = [e for e in events if e.get("event") == "run_started"]
    assert started and started[0]["description_hash"] == wf.description_hash()
    assert started[0]["resume"] is False

    # same description resumed: no drift event
    wf2 = Workflow(store, dummy_description(), resilience=fast_resilience())
    wf2.run(resume=True)
    assert not any(e.get("event") == "description_drift"
                   for e in wf2.ledger.events())

    # whole-description drift beyond any step's args: an extra (inactive)
    # step changes the hash but not the per-step batch plans
    drifted = dummy_description()
    drifted.stages[0].steps.append(
        WorkflowStepDescription(name="chaoscollect", active=False)
    )
    wf3 = Workflow(store, drifted, resilience=fast_resilience())
    wf3.run(resume=True)
    drift = [e for e in wf3.ledger.events()
             if e.get("event") == "description_drift"]
    assert len(drift) == 1
    assert drift[0]["previous"] == wf.description_hash()
    assert drift[0]["current"] == wf3.description_hash()


def test_crash_mid_append_then_resume(store):
    """Satellite regression: a simulated process death halfway through a
    ``batch_done`` append leaves a torn line; resume must skip it, treat
    the batch as never finished, and converge."""
    faults.install(faults.FaultPlan([
        faults.FaultSpec(site="ledger_append", kind="crash_append",
                         step="chaosdummy", event="batch_done", times=1),
    ]))
    wf = Workflow(store, dummy_description(), resilience=fast_resilience())
    with pytest.raises(FaultInjected):
        wf.run()  # the simulated crash propagates like a real one
    raw = wf.ledger.path.read_text()
    assert not raw.endswith("\n")  # torn trailing line on disk

    faults.clear()
    wf2 = Workflow(store, dummy_description(), resilience=fast_resilience())
    summary = wf2.run(resume=True)
    assert "quarantined" not in summary["chaosdummy"]
    assert wf2.ledger.completed_batches("chaosdummy") == {0, 1, 2, 3}
    assert wf2.ledger.completed_steps() == {"chaosdummy"}
    # every batch output exists exactly once
    from tmlibrary_tpu.workflow.registry import get_step

    step = get_step("chaosdummy")(store)
    outs = sorted(p.name for p in step.step_dir.glob("out_*.txt"))
    assert outs == [f"out_{i:03d}.txt" for i in range(4)]


def test_workflow_guard_integration_fails_loudly_then_resumes(store):
    """A hanging device probe trips the breaker; the run stops with the
    transient error instead of hanging for hours or carrying on on
    another backend, runs no batch, and a resume with the device back
    completes."""
    import time as _time

    res = fast_resilience()
    res.guard = DeviceHealthGuard(probe=lambda: _time.sleep(5),
                                  timeout=0.05, failure_threshold=1,
                                  cooldown=3600.0)
    wf = Workflow(store, dummy_description(), resilience=res)
    with pytest.raises(TransientDeviceError, match="device path is down"):
        wf.run()
    events = [e["event"] for e in wf.ledger.events()]
    assert "backend_degraded" not in events and "batch_done" not in events
    assert wf.ledger.completed_steps() == set()
    res.guard = DeviceHealthGuard(probe=lambda: True, timeout=1.0)
    summary = Workflow(store, dummy_description(),
                       resilience=res).run(resume=True)
    assert summary["chaosdummy"]["n_batches"] == 4
    assert wf.ledger.completed_steps() == {"chaosdummy"}


def test_cli_resilience_knobs(store, tmp_path):
    """The workflow verbs surface the retry/quarantine knobs."""
    from tmlibrary_tpu.cli import main

    desc = dummy_description()
    desc.save(store.workflow_dir / "workflow.yaml")
    faults.install(faults.FaultPlan([
        faults.FaultSpec(site="batch_run", kind="device_loss",
                         step="chaosdummy", batch=0, times=99),
    ]))
    # quarantine disabled: first failure aborts (non-zero exit)
    assert main(["workflow", "submit", "--root", str(store.root),
                 "--max-batch-failures", "0", "--retry-attempts", "1",
                 "--retry-delay", "0"]) == 1
    # with the default budget the run completes, quarantining batch 0
    assert main(["workflow", "submit", "--root", str(store.root),
                 "--max-batch-failures", "0.5", "--retry-attempts", "1",
                 "--retry-delay", "0"]) == 0
    ledger = RunLedger(store.workflow_dir / "ledger.jsonl")
    assert ledger.quarantined_batches("chaosdummy") == {0}
