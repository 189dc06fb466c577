"""Chaos suite: the full canonical pipeline under deterministic injected
faults (``tmlibrary_tpu.faults``).

The property these tests pin down is *convergence*: a run that loses
batches to injected device/IO faults, quarantines them, and is then
resumed must end in exactly the fault-free final state — same label
stacks, same feature tables.  That is the contract that makes quarantine
safe to enable by default.

Marked ``chaos`` (registered in pyproject); the suite stays fast enough
to live inside the tier-1 gate.
"""

import numpy as np
import pytest

from test_resilience import dummy_description, fast_resilience
from test_workflow import make_description, source_dir, synth_site_image  # noqa: F401 — fixture re-export

from tmlibrary_tpu import faults
from tmlibrary_tpu.models.experiment import Experiment
from tmlibrary_tpu.models.store import ExperimentStore
from tmlibrary_tpu.resilience import DeviceHealthGuard, RetryPolicy
from tmlibrary_tpu.workflow.engine import Workflow

pytestmark = pytest.mark.chaos


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.clear()
    yield
    faults.clear()


def _make_store(tmp_path, name):
    placeholder = Experiment(
        name=name, plates=[], channels=[], site_height=1, site_width=1
    )
    return ExperimentStore.create(tmp_path / name, placeholder)


def _chaos_description(source_dir, store):
    """The canonical test workflow with jterator re-batched to 4 batches
    of 4 sites, so two quarantines sit exactly at the 0.5 budget."""
    desc = make_description(source_dir, store)
    for stage in desc.stages:
        for step in stage.steps:
            if step.name == "jterator":
                step.args["batch_size"] = 4
    return desc


def test_faulted_run_plus_resume_converges(tmp_path, source_dir):
    """Device loss on jterator batch 1 and an IO fault on batch 3 (both
    outlasting every retry) quarantine those batches; clearing the fault
    plan and resuming must reproduce the fault-free run bit-for-bit."""
    ref = _make_store(tmp_path, "reference")
    Workflow(ref, _chaos_description(source_dir, ref),
             resilience=fast_resilience()).run()
    ref_labels = ref.read_labels(None, "nuclei")
    ref_feats = ref.read_features("nuclei")

    chaotic = _make_store(tmp_path, "chaotic")
    faults.install(faults.FaultPlan([
        faults.FaultSpec(site="batch_run", kind="device_loss",
                         step="jterator", batch=1, times=99),
        faults.FaultSpec(site="batch_run", kind="io_error",
                         step="jterator", batch=3, times=99),
    ], seed=7))
    res = fast_resilience(max_batch_failures=0.5, attempts=2)
    summary = Workflow(chaotic, _chaos_description(source_dir, chaotic),
                       resilience=res).run()
    # 4 jterator batches, budget floor(0.5 * 4) = 2: the run survives
    assert summary["jterator"]["quarantined"] == [1, 3]
    ledger = Workflow(chaotic, _chaos_description(source_dir, chaotic),
                      resilience=res).ledger
    failures = {e["batch"]: e for e in ledger.events()
                if e.get("event") == "batch_failed"}
    assert failures[1]["exception"] == "TransientDeviceError"
    assert failures[3]["exception"] == "OSError"
    assert faults.active().fire_counts() == {
        "batch_run/device_loss": 2,  # attempts=2: first try + one retry
        "batch_run/io_error": 2,
    }

    # the faults clear (device back, disk back) — resume converges
    faults.clear()
    summary = Workflow(chaotic, _chaos_description(source_dir, chaotic),
                       resilience=res).run(resume=True)
    assert "quarantined" not in summary["jterator"]

    assert np.array_equal(chaotic.read_labels(None, "nuclei"), ref_labels)
    key = ["site_index", "label"]
    got = chaotic.read_features("nuclei").sort_values(key).reset_index(drop=True)
    want = ref_feats.sort_values(key).reset_index(drop=True)
    import pandas.testing

    pandas.testing.assert_frame_equal(got, want)


def test_hung_device_probe_fails_submit_instead_of_hanging(tmp_path,
                                                          monkeypatch):
    """An unreachable device can make the probe *hang*, not error.  The
    guard's timeout converts the hang into breaker failures; the breaker
    trips and ``tmx workflow submit`` exits non-zero with no
    ``backend_degraded`` event and no batch run — never an indefinite
    hang, never a quiet run on another backend."""
    import test_resilience  # registers the dummy step  # noqa: F401
    from tmlibrary_tpu.cli import main

    faults.install(faults.FaultPlan([
        faults.FaultSpec(site="device_probe", kind="hang", seconds=3.0,
                         times=99),
    ]))
    store = _make_store(tmp_path, "devicedown")
    dummy_description().save(store.workflow_dir / "workflow.yaml")
    # default probe (jax.devices() behind the fault hook), short deadline
    assert main(["workflow", "submit", "--root", str(store.root),
                 "--probe-timeout", "0.05"]) == 1
    ledger = Workflow(store, dummy_description()).ledger
    events = [e["event"] for e in ledger.events()]
    assert "run_started" in events
    assert "backend_degraded" not in events and "batch_done" not in events

    # the device answers again: resume completes every batch
    faults.clear()
    assert main(["workflow", "submit", "--root", str(store.root),
                 "--resume"]) == 0
    assert ledger.completed_batches("chaosdummy") == {0, 1, 2, 3}


def test_pipelined_quarantine_resume_converges(tmp_path, source_dir,
                                               monkeypatch):
    """Depth > 1 does not weaken the fault model: with ``TMX_FAULT_PLAN``
    armed the engine forces the sequential path (injected faults must
    land before a batch persists), quarantines the faulted batches, and
    a resume at ``pipeline_depth=4`` — now genuinely pipelined — still
    converges bit-for-bit to the fault-free reference."""
    ref = _make_store(tmp_path, "pipe_reference")
    Workflow(ref, _chaos_description(source_dir, ref),
             resilience=fast_resilience()).run()
    ref_labels = ref.read_labels(None, "nuclei")
    ref_feats = ref.read_features("nuclei")

    plan_file = tmp_path / "pipe_plan.json"
    plan_file.write_text(
        '{"seed": 11, "faults": ['
        '{"site": "batch_run", "kind": "device_loss",'
        ' "step": "jterator", "batch": 1, "times": 99},'
        '{"site": "batch_run", "kind": "io_error",'
        ' "step": "jterator", "batch": 3, "times": 99}]}'
    )
    monkeypatch.setenv("TMX_FAULT_PLAN", str(plan_file))
    faults._ENV_CHECKED = False  # re-arm the lazy env check
    assert faults.active() is not None

    chaotic = _make_store(tmp_path, "pipe_chaotic")
    res = fast_resilience(max_batch_failures=0.5, attempts=2)
    summary = Workflow(chaotic, _chaos_description(source_dir, chaotic),
                       resilience=res, pipeline_depth=4).run()
    assert summary["jterator"]["quarantined"] == [1, 3]
    wf = Workflow(chaotic, _chaos_description(source_dir, chaotic),
                  resilience=res, pipeline_depth=4)
    partial = [e for e in wf.ledger.events()
               if e.get("event") == "step_partial"
               and e.get("step") == "jterator"]
    # the armed plan forced the sequential path: no executor, no stats
    assert partial and "pipeline_stats" not in partial[0]

    # faults clear (device back): resume runs the quarantined batches
    # through the REAL pipelined executor at depth 4 and converges
    monkeypatch.delenv("TMX_FAULT_PLAN")
    faults.clear()
    summary = wf.run(resume=True)
    assert "quarantined" not in summary["jterator"]
    done = [e for e in wf.ledger.events()
            if e.get("event") == "step_done" and e.get("step") == "jterator"]
    assert done and done[-1]["pipeline_stats"]["depth"] == 4
    assert done[-1]["pipeline_stats"]["source"] == "cli"

    assert np.array_equal(chaotic.read_labels(None, "nuclei"), ref_labels)
    key = ["site_index", "label"]
    got = chaotic.read_features("nuclei").sort_values(key).reset_index(drop=True)
    want = ref_feats.sort_values(key).reset_index(drop=True)
    import pandas.testing

    pandas.testing.assert_frame_equal(got, want)


def test_fault_plan_env_activation(tmp_path, monkeypatch):
    """``TMX_FAULT_PLAN`` arms the harness without code changes — the
    path ``scripts/chaos_run.py`` and operators use."""
    plan_file = tmp_path / "plan.json"
    plan_file.write_text(
        '{"seed": 3, "faults": [{"site": "batch_run", "kind": "device_loss",'
        ' "step": "chaosdummy", "batch": 0, "times": 99}]}'
    )
    monkeypatch.setenv("TMX_FAULT_PLAN", str(plan_file))
    # reset the lazy env check that clear() disarmed
    faults._ENV_CHECKED = False
    plan = faults.active()
    assert plan is not None and plan.seed == 3
    assert plan.specs[0].step == "chaosdummy"

    store = _make_store(tmp_path, "envplan")
    summary = Workflow(store, dummy_description(),
                       resilience=fast_resilience()).run()
    assert summary["chaosdummy"]["quarantined"] == [0]
