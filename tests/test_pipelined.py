"""Deep pipelined batch executor (``workflow/pipelined.py``).

Two layers of guarantees:

- Executor mechanics on a fake step: yields stay in submission order, a
  mid-window launch failure drains the WHOLE window before propagating
  (regression: flushing only the previous batch dropped completed
  batches' ledger events at depth > 1), HBM exhaustion halves the depth
  and retries instead of failing, and the depth/source resolution obeys
  the cli > config > tuning > default precedence.  The persist stage
  runs on a pool sized like the prefetch stage's: N sleeping persists
  take ceil(N / workers) sleeps, a persist that raises lets every other
  launched batch persist first, two live ``persist`` arms of the
  watchdog and two live ``persist`` spans keep to their own batch.
- Bit-identity on the real jterator step: the pipelined executor at
  depths 2/4/8 must persist exactly the sequential path's label stacks
  and feature tables, for BOTH the sites and the spatial layout — the
  property that makes deep pipelining safe to enable by default.
"""

import json
import threading
import time

import numpy as np
import pytest

from test_workflow import (  # noqa: F401 — fixture re-export
    make_description,
    source_dir,
    store,
    synth_site_image,
)

from tmlibrary_tpu.profiling import PipelineStats
from tmlibrary_tpu.workflow.engine import Workflow
from tmlibrary_tpu.workflow.pipelined import (
    PipelinedExecutor,
    is_resource_exhausted,
    prefetch_iter,
    resolve_pipeline_depth,
    supports_pipelining,
)


# --------------------------------------------------------------- fake step
class FakeStep:
    """Minimal launch/persist step: records call order and thread names,
    optionally failing a launch (once or forever) to exercise the drain
    and clamp paths."""

    name = "fake"

    def __init__(self, fail_at=None, fail_exc=None, fail_times=1):
        self.fail_at = fail_at
        self.fail_exc = fail_exc or ValueError("launch failed")
        self.fail_remaining = fail_times
        self.launched: list[int] = []
        self.persisted: list[int] = []
        self.prefetch_threads: list[str] = []

    def prefetch_batch(self, batch):
        self.prefetch_threads.append(threading.current_thread().name)
        return {"loaded": batch["index"]}

    def launch_batch(self, batch, prefetched=None):
        i = batch["index"]
        if i == self.fail_at and self.fail_remaining > 0:
            self.fail_remaining -= 1
            raise self.fail_exc
        self.launched.append(i)
        if prefetched is not None:
            assert prefetched == {"loaded": i}
        return batch, {"payload": i * 10}

    def persist_batch(self, batch, ctx):
        self.persisted.append(batch["index"])
        return {"value": ctx["payload"], "index": batch["index"]}


def _batches(n):
    return [{"index": i} for i in range(n)]


def test_supports_pipelining_detection():
    assert supports_pipelining(FakeStep())

    class Legacy:
        def run_batch(self, batch):
            return {}

    assert not supports_pipelining(Legacy())


def test_executor_yields_in_order_with_prefetch():
    step = FakeStep()
    ex = PipelinedExecutor(step, depth=4)
    out = list(ex.run(_batches(10)))
    assert [b["index"] for b, _ in out] == list(range(10))
    assert [r["value"] for _, r in out] == [i * 10 for i in range(10)]
    # dispatch stays on the calling thread in batch order
    assert step.launched == list(range(10))
    # every batch persisted once; with a pool the workers may enter
    # persist_batch in any order, the YIELDS above are what is ordered
    assert sorted(step.persisted) == list(range(10))
    # prefetch really ran on the worker pool, once per batch
    assert len(step.prefetch_threads) == 10
    assert all(t.startswith("tmx-prefetch") for t in step.prefetch_threads)


def test_midwindow_launch_failure_drains_whole_window():
    """Regression: with depth 4 the window holds batches 0 and 1 un-yielded
    when batch 2's launch dies; BOTH must come out (so the engine ledgers
    their ``batch_done``) before the failure propagates — the old code
    flushed only the immediately-previous batch."""
    step = FakeStep(fail_at=2, fail_exc=ValueError("boom"), fail_times=99)
    ex = PipelinedExecutor(step, depth=4)
    gen = ex.run(_batches(6))
    yielded = []
    with pytest.raises(ValueError, match="boom"):
        for b, r in gen:
            yielded.append(b["index"])
    assert yielded == [0, 1]
    assert sorted(step.persisted) == [0, 1]
    # nothing past the failure launched
    assert step.launched == [0, 1]


def test_oom_clamps_depth_and_retries():
    """RESOURCE_EXHAUSTED at depth > 1 is a pressure signal, not a step
    failure: the window drains, the depth halves, a ``depth_clamped``
    event fires, and the failed batch retries at the lower depth."""
    step = FakeStep(
        fail_at=3,
        fail_exc=RuntimeError("RESOURCE_EXHAUSTED: out of memory (HBM)"),
        fail_times=1,
    )
    from tmlibrary_tpu import telemetry

    events = []
    stats = PipelineStats(8, "cli")
    ex = PipelinedExecutor(
        step, depth=8, depth_source="cli",
        on_event=lambda **ev: events.append(ev), stats=stats,
    )
    telemetry.drain_spans()
    out = list(ex.run(_batches(6)))
    assert [b["index"] for b, _ in out] == list(range(6))
    assert sorted(step.persisted) == list(range(6))
    # the control-flow events are exactly one depth clamp; the phase
    # spans do not ride that callback, they wait in the process buffer
    assert events == [{
        "event": "depth_clamped", "from_depth": 8, "to_depth": 4,
        "batch": 3, "error": "RESOURCE_EXHAUSTED: out of memory (HBM)",
    }]
    spans = telemetry.drain_spans()
    assert {e["span"] for e in spans} >= {"dispatch", "persist"}
    assert {e["batch"] for e in spans} == set(range(6))
    assert {e["step"] for e in spans} == {step.name}
    summary = stats.summary()
    assert summary["depth"] == 4
    assert summary["depth_clamps"] == [{"from": 8, "to": 4}]
    assert summary["n_batches"] == 6


def test_oom_at_depth_one_propagates():
    """Depth 1 has nothing left to clamp: memory pressure is a real
    failure and must surface to the engine's retry/quarantine path."""
    step = FakeStep(fail_at=1, fail_exc=MemoryError("host OOM"),
                    fail_times=99)
    ex = PipelinedExecutor(step, depth=1)
    yielded = []
    with pytest.raises(MemoryError):
        for b, _ in ex.run(_batches(4)):
            yielded.append(b["index"])
    assert yielded == [0]


def test_non_oom_failure_never_clamps():
    step = FakeStep(fail_at=2, fail_exc=OSError("disk gone"), fail_times=99)
    events = []
    ex = PipelinedExecutor(step, depth=4,
                           on_event=lambda **ev: events.append(ev))
    with pytest.raises(OSError):
        list(ex.run(_batches(5)))
    assert events == []


def test_is_resource_exhausted_classifier():
    assert is_resource_exhausted(MemoryError())
    assert is_resource_exhausted(RuntimeError("RESOURCE_EXHAUSTED: ..."))
    assert is_resource_exhausted(RuntimeError("Resource exhausted: HBM"))
    assert is_resource_exhausted(RuntimeError("ran Out of Memory on chip"))
    assert not is_resource_exhausted(ValueError("bad geometry"))
    assert not is_resource_exhausted(OSError("connection reset"))


# ------------------------------------------------------------ persist pool
class SleepyStep(FakeStep):
    """Persists by sleeping: what a field's re-launch looks like to the
    executor (the worker waits on the device, then writes)."""

    def __init__(self, sleep=0.2, persist_fail_at=None, serial=False):
        super().__init__()
        self.sleep = sleep
        self.persist_fail_at = persist_fail_at
        if serial:
            self.persist_serial = True
        self.persist_threads: set[str] = set()

    def persist_batch(self, batch, ctx):
        from tmlibrary_tpu import telemetry

        self.persist_threads.add(threading.current_thread().name)
        with telemetry.span("inner", own=batch["index"]):
            time.sleep(self.sleep)
        if batch["index"] == self.persist_fail_at:
            raise OSError("disk gone mid-persist")
        return super().persist_batch(batch, ctx)


@pytest.mark.parametrize(
    "depth, n, kwargs, serial, workers",
    [
        (4, 8, {}, False, 4),     # the prefetch stage's rule: min(depth, 4, n)
        (8, 8, {}, False, 4),     # capped at four however deep the window
        (2, 8, {}, False, 2),     # the CPU backend's default depth
        (8, 3, {}, False, 3),     # fewer batches than workers
        (1, 3, {}, False, 1),     # depth 1, the clamp's floor: one worker
        (4, 1, {}, False, 1),     # a single batch
        (4, 4, {"persist_workers": 1}, False, 1),  # the explicit argument
        (4, 4, {"persist_workers": 3}, False, 3),
        (4, 4, {}, True, 1),      # a step that persists in batch order
    ],
)
def test_persist_pool_is_sized_like_the_prefetch_stage(
        depth, n, kwargs, serial, workers):
    """N sleeping persists finish in about ceil(N / workers) sleeps, are
    yielded in submission order, and ``persist_peak_concurrency`` reads
    the pool's size."""
    sleep = 0.2
    step = SleepyStep(sleep=sleep, serial=serial)
    stats = PipelineStats(depth, "cli", step="fake")
    ex = PipelinedExecutor(step, depth=depth, stats=stats, **kwargs)
    t0 = time.perf_counter()
    out = list(ex.run(_batches(n)))
    elapsed = time.perf_counter() - t0
    assert [b["index"] for b, _ in out] == list(range(n))
    assert [r["value"] for _, r in out] == [i * 10 for i in range(n)]
    rounds = -(-n // workers)
    assert elapsed >= rounds * sleep * 0.98
    # a loaded test machine stretches a sleep; a queue of one would take
    # n sleeps, and no stretch comes near that for the wide cases
    assert elapsed < rounds * sleep + 0.6
    summary = stats.summary()
    assert summary["persist_workers"] == workers
    assert summary["persist_peak_concurrency"] == workers
    assert len(step.persist_threads) == workers
    assert all(t.startswith("tmx-persist") for t in step.persist_threads)


@pytest.mark.parametrize("n, fail_at, launched", [
    (6, 2, 6),   # the failure surfaces in the final drain: all six launched
    (8, 2, 7),   # it surfaces while batch 7 is still to launch
    (6, 0, 5),   # the very first pop: batch 5 never launched
])
def test_persist_failure_lets_every_launched_batch_persist(
        n, fail_at, launched):
    """A persist that raises in the middle: the batches before it are
    yielded, the error surfaces at its place in the order, and by then
    every other launched batch has persisted (``shutdown(wait=True)``) —
    no worker is still writing when the engine's sequential path re-runs
    the failed batch."""
    step = SleepyStep(sleep=0.05, persist_fail_at=fail_at)
    ex = PipelinedExecutor(step, depth=4)
    yielded = []
    with pytest.raises(OSError, match="mid-persist"):
        for b, _ in ex.run(_batches(n)):
            yielded.append(b["index"])
    assert yielded == list(range(fail_at))
    assert step.launched == list(range(launched))
    assert sorted(step.persisted) == [
        i for i in range(launched) if i != fail_at]


def test_clamp_to_depth_one_resolves_one_persist_worker():
    """The depth clamp's floor is today's behaviour: after 2 -> 1 the
    remaining batches persist on one worker."""
    step = FakeStep(
        fail_at=2, fail_exc=RuntimeError("RESOURCE_EXHAUSTED: HBM"),
        fail_times=1,
    )
    stats = PipelineStats(2, "cli")
    ex = PipelinedExecutor(step, depth=2, stats=stats)
    assert ex._resolve_persist_workers(6) == 2
    out = list(ex.run(_batches(6)))
    assert [b["index"] for b, _ in out] == list(range(6))
    assert ex.depth == 1
    assert ex._resolve_persist_workers(4) == 1
    assert stats.summary()["persist_workers"] == 1


@pytest.mark.parametrize("qc_on, workers", [(True, 1), (False, 4)])
def test_jterator_persists_in_order_only_while_qc_is_on(qc_on, workers):
    """The QC session folds running statistics in the order batches are
    observed, so a QC-on jterator says ``persist_serial`` and gets one
    worker; by what the step says of itself, not by a setting of the
    executor's."""
    from tmlibrary_tpu import qc
    from tmlibrary_tpu.workflow.steps.jterator import ImageAnalysisRunner

    class Step(FakeStep):
        persist_serial = ImageAnalysisRunner.persist_serial

    qc.set_enabled(qc_on)
    try:
        ex = PipelinedExecutor(Step(), depth=8)
        assert ex._resolve_persist_workers(9) == workers
    finally:
        qc.set_enabled(None)


def test_spans_under_two_live_persists_keep_their_own_batch():
    """A span opened inside worker 2's ``persist`` carries that
    ``persist`` as ``parent`` and its own ``batch``: the span stack and
    the ambient scope are per thread."""
    from tmlibrary_tpu import telemetry

    telemetry.drain_spans()
    step = SleepyStep(sleep=0.15)
    stats = PipelineStats(4, "cli", step="fake")
    list(PipelinedExecutor(step, depth=4, stats=stats).run(_batches(4)))
    assert stats.summary()["persist_peak_concurrency"] == 4
    spans = telemetry.drain_spans()
    inner = [e for e in spans if e["span"] == "inner"]
    persist = {e["batch"]: e for e in spans if e["span"] == "persist"}
    assert sorted(e["own"] for e in inner) == [0, 1, 2, 3]
    assert sorted(persist) == [0, 1, 2, 3]
    for e in inner:
        assert e["parent"] == "persist"
        assert e["batch"] == e["own"]
        assert e["step"] == "fake"
        outer = persist[e["own"]]
        assert outer["t0"] <= e["t0"]
        assert e["t0"] + e["elapsed"] <= outer["t0"] + outer["elapsed"] + 1e-3
    # the four persists really were alive together
    starts = [e["t0"] for e in persist.values()]
    ends = [e["t0"] + e["elapsed"] for e in persist.values()]
    assert max(starts) < min(ends)


@pytest.mark.parametrize("slow", [1, 2])
def test_watchdog_keeps_two_live_persist_arms_apart(slow):
    """``arm("persist", batch=1)`` and ``arm("persist", batch=2)`` alive
    together: leaving one does not disarm the other, and only the one
    that overruns fires."""
    from tmlibrary_tpu.resilience import PhaseWatchdog, WatchdogTimeout

    wd = PhaseWatchdog({"persist": 0.25}, poll=0.02)
    both_armed = threading.Barrier(2)
    outcome = {}

    def persist(batch):
        try:
            with wd.arm("persist", step="fake", batch=batch):
                both_armed.wait(timeout=5)
                time.sleep(0.7 if batch == slow else 0.02)
            outcome[batch] = "ok"
        except WatchdogTimeout as exc:
            outcome[batch] = str(exc)

    threads = [threading.Thread(target=persist, args=(b,)) for b in (1, 2)]
    try:
        for t in threads:
            t.start()
        time.sleep(0.1)
        # the fast arm has left, the slow one is still armed
        with wd._lock:
            armed = [(e["phase"], e["batch"]) for e in wd._armed.values()]
        assert armed == [("persist", slow)]
        for t in threads:
            t.join()
    finally:
        wd.stop()
    fast = 3 - slow
    assert outcome[fast] == "ok"
    assert f"batch {slow} overran" in outcome[slow]
    events = wd.drain_events()
    assert [(e["phase"], e["batch"]) for e in events] == [("persist", slow)]
    assert not wd._armed


# ----------------------------------------------------------- prefetch_iter
def test_prefetch_iter_preserves_order():
    done = []

    def load(i):
        # later items finish FIRST: order must still be preserved
        time.sleep(0.02 * (5 - i))
        done.append(i)
        return i * 2

    assert list(prefetch_iter(range(5), load, depth=5)) == [0, 2, 4, 6, 8]


def test_prefetch_iter_exception_surfaces_in_position():
    def load(i):
        if i == 3:
            raise OSError("read failed")
        return i

    got = []
    with pytest.raises(OSError, match="read failed"):
        for v in prefetch_iter(range(6), load, depth=4):
            got.append(v)
    assert got == [0, 1, 2]


def test_prefetch_iter_single_item_short_circuits():
    # no pool spin-up for a single chunk
    assert list(prefetch_iter([7], lambda x: x + 1)) == [8]
    assert list(prefetch_iter([], lambda x: x)) == []


# --------------------------------------------------------- depth resolution
@pytest.fixture
def _clean_depth_env(monkeypatch, tmp_path):
    """Hermetic resolution: no ambient env/INI/tuning artifacts."""
    monkeypatch.delenv("TM_PIPELINE_DEPTH", raising=False)
    monkeypatch.setenv("TM_CONFIG_FILE", str(tmp_path / "absent.cfg"))
    monkeypatch.setenv("TMX_TUNING_JSON", str(tmp_path / "absent.json"))
    return tmp_path


def _write_tuning(path, methodology="median-of-3 steady-state", **extra):
    path.write_text(json.dumps({
        "best_batch": 128, "best_pipeline": 16,
        "written_by": "scripts/tune_tpu.py write_results",
        "timing_methodology": methodology, **extra,
    }))


def test_resolve_depth_explicit_wins(_clean_depth_env, monkeypatch):
    monkeypatch.setenv("TM_PIPELINE_DEPTH", "5")
    assert resolve_pipeline_depth(explicit=3, backend="tpu") == (3, "cli")


def test_resolve_depth_config_beats_tuning(_clean_depth_env, monkeypatch):
    tuning = _clean_depth_env / "TUNING.json"
    _write_tuning(tuning)
    monkeypatch.setenv("TMX_TUNING_JSON", str(tuning))
    monkeypatch.setenv("TM_PIPELINE_DEPTH", "5")
    assert resolve_pipeline_depth(backend="tpu") == (5, "config")


def test_resolve_depth_tuning_on_device_backend(_clean_depth_env, monkeypatch):
    tuning = _clean_depth_env / "TUNING.json"
    _write_tuning(tuning)
    monkeypatch.setenv("TMX_TUNING_JSON", str(tuning))
    assert resolve_pipeline_depth(backend="tpu") == (16, "tuning")
    # the sweep measured the device: CPU keeps its own safe default
    assert resolve_pipeline_depth(backend="cpu") == (2, "default")


def test_resolve_depth_defaults_without_tuning(_clean_depth_env):
    assert resolve_pipeline_depth(backend="tpu") == (8, "default")
    assert resolve_pipeline_depth(backend="cpu") == (2, "default")


def test_resolve_depth_rejects_smoke_tuning(_clean_depth_env, monkeypatch):
    """Dry-run (SMOKE) sweep artifacts never set production defaults."""
    tuning = _clean_depth_env / "TUNING.json"
    _write_tuning(tuning, methodology="SMOKE(dry-run, 1 repeat)")
    monkeypatch.setenv("TMX_TUNING_JSON", str(tuning))
    assert resolve_pipeline_depth(backend="tpu") == (8, "default")


def test_resolve_depth_rejects_unprovenanced_tuning(
    _clean_depth_env, monkeypatch
):
    tuning = _clean_depth_env / "TUNING.json"
    tuning.write_text(json.dumps({"best_pipeline": 16}))  # hand-seeded
    monkeypatch.setenv("TMX_TUNING_JSON", str(tuning))
    assert resolve_pipeline_depth(backend="tpu") == (8, "default")


# ---------------------------------------------------- bit-identity: sites
def _run_prep_steps(desc, store):
    from tmlibrary_tpu.workflow.registry import get_step

    for name in ("metaconfig", "imextract", "corilla"):
        sd = next(s for stage in desc.stages for s in stage.steps
                  if s.name == name)
        step = get_step(name)(store)
        step.init(sd.args)
        for j in step.list_batches():
            step.run(j)


def _read_features_sorted(store, name):
    return (store.read_features(name)
            .sort_values(["site_index", "label"])
            .reset_index(drop=True))


def test_sites_layout_bit_identical_across_depths(source_dir, store):
    """The engine executor at depths 2/4/8 persists exactly the sequential
    path's label stacks AND feature tables (16 sites in 8 batches of 2)."""
    import pandas.testing

    from tmlibrary_tpu.workflow.registry import get_step

    desc = make_description(source_dir, store)
    _run_prep_steps(desc, store)
    jd = next(s for stage in desc.stages for s in stage.steps
              if s.name == "jterator")
    args = {**jd.args, "batch_size": 2}  # 16 sites -> 8 batches

    jt = get_step("jterator")(store)
    jt.init(args)
    for j in jt.list_batches():
        jt.run(j)
    ref_labels = store.read_labels(None, "nuclei").copy()
    ref_feats = _read_features_sorted(store, "nuclei")

    for depth in (2, 4, 8):
        jt2 = get_step("jterator")(store)
        jt2.delete_previous_output()
        jt2.init(args)
        batches = [jt2.load_batch(i) for i in jt2.list_batches()]
        out = list(PipelinedExecutor(jt2, depth=depth).run(batches))
        assert [b["index"] for b, _ in out] == list(range(8))
        assert all(r["n_sites"] == 2 for _, r in out)
        assert np.array_equal(store.read_labels(None, "nuclei"), ref_labels), \
            f"labels diverged at depth {depth}"
        pandas.testing.assert_frame_equal(
            _read_features_sorted(store, "nuclei"), ref_feats
        )


# -------------------------------------------------- bit-identity: spatial
@pytest.fixture
def spatial_store(tmp_path, devices):
    """Two wells of 2x2 50px sites (site indices 0-3 and 4-7), each well a
    100x100 mosaic with blobs straddling site seams."""
    from tmlibrary_tpu.models.experiment import grid_experiment
    from tmlibrary_tpu.models.store import ExperimentStore

    exp = grid_experiment(
        "pipespatial", well_rows=1, well_cols=2, sites_per_well=(2, 2),
        channel_names=("DAPI",), site_shape=(50, 50),
    )
    st = ExperimentStore.create(tmp_path / "pipespatial_exp", exp)
    rng = np.random.default_rng(23)
    yy, xx = np.mgrid[0:100, 0:100]
    tiles, sites = [], []
    for w, centers in enumerate(
        [[(50, 50), (20, 24), (80, 70)], [(48, 52), (75, 20), (25, 80)]]
    ):
        mosaic = rng.normal(300, 15, (100, 100))
        for cy, cx in centers:
            mosaic += 4000 * np.exp(
                -((yy - cy) ** 2 + (xx - cx) ** 2) / (2 * 3.5**2)
            )
        mosaic = np.clip(mosaic, 0, 65535).astype(np.uint16)
        tiles += [mosaic[0:50, 0:50], mosaic[0:50, 50:100],
                  mosaic[50:100, 0:50], mosaic[50:100, 50:100]]
        sites += [w * 4 + i for i in range(4)]
    st.write_sites(np.stack(tiles), sites, channel=0)
    return st


def test_spatial_layout_bit_identical_across_depths(spatial_store):
    """One batch per well: the pipelined executor overlaps well B's stitch
    with well A's device segmentation, and the persisted global-id label
    stacks must stay bit-identical to the sequential run."""
    import pandas.testing

    from tmlibrary_tpu.workflow.registry import get_step

    st = spatial_store
    args = {"layout": "spatial", "n_devices": 8}
    jt = get_step("jterator")(st)
    jt.init(args)
    for j in jt.list_batches():
        jt.run(j)
    ref_labels = st.read_labels(None, "mosaic_cells").copy()
    ref_feats = _read_features_sorted(st, "mosaic_cells")
    assert ref_labels.max() > 0  # segmentation found the blobs

    for depth in (2, 4):
        jt2 = get_step("jterator")(st)
        jt2.delete_previous_output()
        jt2.init(args)
        batches = [jt2.load_batch(i) for i in jt2.list_batches()]
        out = list(PipelinedExecutor(jt2, depth=depth).run(batches))
        assert [b["index"] for b, _ in out] == [0, 1]
        assert all(r["layout"] == "spatial" for _, r in out)
        assert np.array_equal(
            st.read_labels(None, "mosaic_cells"), ref_labels
        ), f"mosaic labels diverged at depth {depth}"
        pandas.testing.assert_frame_equal(
            _read_features_sorted(st, "mosaic_cells"), ref_feats
        )


# ------------------------------------------------------------ engine wiring
def test_engine_records_pipeline_stats_in_ledger(source_dir, store):
    """A full engine run drives jterator through the pipelined executor
    and lands the phase timers in the ``step_done`` ledger event (and
    ``status()``), with the explicitly requested depth marked ``cli``."""
    desc = make_description(source_dir, store)
    wf = Workflow(store, desc, pipeline_depth=2)
    wf.run()

    done = [e for e in wf.ledger.events()
            if e.get("event") == "step_done" and e.get("step") == "jterator"]
    assert len(done) == 1
    ps = done[0]["pipeline_stats"]
    assert ps["depth"] == 2
    assert ps["source"] == "cli"
    assert ps["n_batches"] == 2  # 16 sites / batch_size 8
    assert set(ps["phases"]) >= {"dispatch", "device_block", "persist"}
    for phase in ps["phases"].values():
        assert phase["total_s"] >= 0.0
        assert phase["max_s"] <= phase["total_s"] + 1e-9

    status = wf.ledger.status()
    assert status["jterator"]["pipeline_stats"]["depth"] == 2
    # steps without the launch/persist split carry no stats
    assert "pipeline_stats" not in status["metaconfig"]


def test_engine_ledger_batch_order_preserved(source_dir, store):
    """Pipelined ``batch_done`` events keep batch-index order — resume
    replay depends on it."""
    desc = make_description(source_dir, store)
    for stage in desc.stages:
        for step in stage.steps:
            if step.name == "jterator":
                step.args["batch_size"] = 4  # 4 batches
    wf = Workflow(store, desc, pipeline_depth=4)
    wf.run()
    order = [e["batch"] for e in wf.ledger.events()
             if e.get("event") == "batch_done" and e.get("step") == "jterator"]
    assert order == [0, 1, 2, 3]
