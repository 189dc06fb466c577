"""illuminati's channels through the pipelined executor
(``workflow/steps/illuminati.py``, ``workflow/pipelined.py``).

A five-channel experiment of two wells (2x2 fields of 96x96 each, so a
192x384 mosaic holds eight sites, two tiles and two levels) runs
metaconfig, imextract, corilla and illuminati through ``Workflow.run``

- on the executor (five channels in flight, four persist workers),
- with the engine's sequential path forced (``run_batch`` a channel),
- on the executor, killed at the ``persist`` fault site of channel 2 and
  resumed,
- on the executor with memory for one channel in flight,

and every file under ``pyramids/`` is compared byte for byte, with the
``batch_done`` results and their order in the ledger.  The benchmark's
``correct`` does not read ``pyramids/``: this is the guard.
"""

import hashlib
import os
import threading

import numpy as np
import pytest

from tmlibrary_tpu import faults
from tmlibrary_tpu.errors import FaultInjected, WorkflowError
from tmlibrary_tpu.models.experiment import Experiment
from tmlibrary_tpu.models.store import ExperimentStore
from tmlibrary_tpu.resilience import ResilienceConfig
from tmlibrary_tpu.workflow.engine import Workflow, WorkflowDescription
from tmlibrary_tpu.workflow.steps import illuminati
from tmlibrary_tpu.workflow.steps.illuminati import PyramidBuilder

CHANNELS = ("DAPI", "Actin", "Tubulin", "ER", "Mito")
WELLS, FIELDS, SIZE = ("A01", "A02"), 4, 96
#: bytes of the plate's float32 mosaic: one row of two wells, 2x2 fields
MOSAIC_BYTES = 4 * (2 * SIZE) * (2 * 2 * SIZE)


@pytest.fixture(scope="module")
def source(tmp_path_factory):
    import cv2

    src = tmp_path_factory.mktemp("illuminati_pipelined_src")
    rng = np.random.default_rng(35)
    yy, xx = np.mgrid[0:SIZE, 0:SIZE]
    for well in WELLS:
        for field in range(FIELDS):
            for c, chan in enumerate(CHANNELS):
                img = rng.normal(300 + 40 * c, 20, (SIZE, SIZE))
                for _ in range(5):
                    y, x = rng.uniform(6, SIZE - 6, 2)
                    img += 2500 * np.exp(
                        -((yy - y) ** 2 + (xx - x) ** 2) / (2 * 2.5 ** 2))
                assert cv2.imwrite(
                    str(src / f"{well}_s{field}_{chan}.tif"),
                    np.clip(img, 0, 65535).astype(np.uint16))
    return src


def _workflow(root, source):
    store = ExperimentStore.create(root, Experiment(
        name="wf", plates=[], channels=[], site_height=1, site_width=1))
    desc = WorkflowDescription.canonical({
        "metaconfig": {"source_dir": str(source), "sites_per_well_x": 2},
        "imextract": {},
        "corilla": {"n_devices": 1},
        "illuminati": {},
    })
    return store, desc


def _submit(root, source, resume_after=None):
    """One run into a fresh root; with ``resume_after`` (an exception type)
    the first run has to end in it and a second one resumes."""
    store, desc = _workflow(root, source)
    wf = Workflow(store, desc, pipeline_depth=8)
    if resume_after is None:
        wf.run()
    else:
        with pytest.raises(resume_after):
            wf.run()
        faults.clear()
        wf = Workflow(store, desc, pipeline_depth=8)
        wf.run(resume=True)
    return store, wf.ledger.events()


@pytest.fixture
def launched_before_persisted(monkeypatch):
    """Hold every persist until the step's last channel is launched: the
    persist workers then stand inside ``persist_batch`` together."""
    state = {"launched": 0, "all": threading.Event()}
    launch, persist = PyramidBuilder.launch_batch, PyramidBuilder.persist_batch

    def launch_batch(self, batch, prefetched=None):
        out = launch(self, batch, prefetched)
        state["launched"] += 1
        if state["launched"] == len(CHANNELS):
            state["all"].set()
        return out

    def persist_batch(self, batch, ctx):
        assert state["all"].wait(timeout=120)
        return persist(self, batch, ctx)

    monkeypatch.setattr(PyramidBuilder, "launch_batch", launch_batch)
    monkeypatch.setattr(PyramidBuilder, "persist_batch", persist_batch)


@pytest.fixture(scope="module")
def on_executor(tmp_path_factory, source):
    return _submit(tmp_path_factory.mktemp("on_executor") / "exp", source)


def _sequential(root, source):
    # a plan that targets a pre-persist site forces the engine's
    # sequential path for the whole run; this one never fires
    faults.install(faults.FaultPlan([
        faults.FaultSpec(site="batch_run", step="no-such-step")]))
    try:
        return _submit(root, source)
    finally:
        faults.clear()


def _killed_and_resumed(root, source):
    # a fatal fault stands for the process's death: nothing unwinds into
    # the ledger, and the second run has only the ledger to go by
    faults.install(faults.FaultPlan([
        faults.FaultSpec(site="persist", kind="crash_append",
                         step="illuminati", batch=2)]))
    try:
        return _submit(root, source, resume_after=FaultInjected)
    finally:
        faults.clear()


def _tree(store) -> dict:
    root = store.root / "pyramids"
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file()}


def _done(events) -> list:
    return [e for e in events if e.get("event") == "batch_done"
            and e.get("step") == "illuminati"]


def _stats(events) -> dict:
    (done,) = [e for e in events if e.get("event") == "step_done"
               and e.get("step") == "illuminati"]
    return done["pipeline_stats"]


def test_the_executor_ran_the_channels_and_says_so(on_executor):
    store, events = on_executor
    tree = _tree(store)
    # a channel: layer.json, the native level's two tiles, one above
    assert sum(name.endswith("layer.json") for name in tree) == len(CHANNELS)
    assert len(tree) == len(CHANNELS) * 4
    stats = _stats(events)
    assert stats["depth"] == 8
    assert stats["persist_workers"] == 4
    assert stats["persist_peak_concurrency"] >= 1
    # no `block_batch`: the first `level_fetch` is the wait for the device
    assert set(stats["phases"]) == {"prefetch_wait", "dispatch", "persist"}
    for phase in stats["phases"].values():
        assert phase["total_s"] >= phase["max_s"] >= 0.0
    assert stats["phases"]["persist"]["count"] == len(CHANNELS)
    assert not [e for e in events if e.get("event") == "depth_clamped"]


@pytest.mark.parametrize("other", [_sequential, _killed_and_resumed],
                         ids=["sequential", "killed_at_persist_and_resumed"])
def test_tiles_and_results_are_the_executors_byte_for_byte(
        on_executor, other, tmp_path, source):
    store, events = on_executor
    other_store, other_events = other(tmp_path / "exp", source)
    assert _tree(other_store) == _tree(store)
    done, other_done = _done(events), _done(other_events)
    assert [e["batch"] for e in done] == list(range(len(CHANNELS)))
    # a resumed run's ledger holds the first run's batches, then the rest
    assert sorted(e["batch"] for e in other_done) == list(range(len(CHANNELS)))
    by_batch = {e["batch"]: e["result"] for e in other_done}
    assert [by_batch[e["batch"]] for e in done] == [e["result"] for e in done]
    if other is _sequential:
        assert [e["batch"] for e in other_done] == list(range(len(CHANNELS)))
        assert not [e for e in other_events if e.get("event") == "step_done"
                    and e.get("pipeline_stats")]
    else:
        # channels 0 and 1 were in the ledger when the run died; the
        # resumed run re-ran what was not, channel 2 among it, in order
        first_run = [e["batch"] for e in other_done][:2]
        assert first_run == [0, 1]
        resumed = [e["batch"] for e in other_done][2:]
        assert resumed == sorted(resumed) and 2 in resumed


def test_persist_workers_stand_in_persist_together(
        launched_before_persisted, tmp_path, source, on_executor):
    store, events = _submit(tmp_path / "exp", source)
    assert _stats(events)["persist_peak_concurrency"] >= 2
    assert _tree(store) == _tree(on_executor[0])


@pytest.mark.parametrize("fit, peak", [(1, 1), (5, 4)],
                         ids=["room_for_one", "room_for_five"])
def test_channels_in_flight_follow_the_memory_found_free(
        fit, peak, tmp_path, source, on_executor, monkeypatch, request):
    # the device's share decides: half of what is free, over 19/12 of the
    # mosaic a channel; the host has room for any number
    device_free = 2 * (MOSAIC_BYTES * 19 // 12) * fit + 1000
    monkeypatch.setattr(illuminati, "free_memory",
                        lambda: (device_free, 1 << 40))
    assert illuminati.channels_in_flight(
        MOSAIC_BYTES, device_free, 1 << 40) == fit
    if fit > 1:
        request.getfixturevalue("launched_before_persisted")
    in_flight = {"now": 0, "most": 0, "reads_ahead": 0}
    lock = threading.Lock()
    launch, persist = PyramidBuilder._launch, PyramidBuilder._persist

    def _launch(self, batch, pre):
        with lock:
            in_flight["now"] += 1
            in_flight["most"] = max(in_flight["most"], in_flight["now"])
            in_flight["reads_ahead"] += pre["stacks"] is not None
        return launch(self, batch, pre)

    def _persist(self, batch, ctx):
        try:
            return persist(self, batch, ctx)
        finally:
            with lock:
                in_flight["now"] -= 1

    monkeypatch.setattr(PyramidBuilder, "_launch", _launch)
    monkeypatch.setattr(PyramidBuilder, "_persist", _persist)
    store, events = _submit(tmp_path / "exp", source)
    stats = _stats(events)
    assert in_flight["most"] == min(fit, len(CHANNELS))
    assert stats["persist_peak_concurrency"] == peak
    # planes are read ahead only where every channel fits in flight
    assert in_flight["reads_ahead"] == (len(CHANNELS) if fit >= 5 else 0)
    assert not [e for e in events if e.get("event") == "depth_clamped"]
    assert _tree(store) == _tree(on_executor[0])


def test_one_png_pool_serves_the_channels_in_flight(
        launched_before_persisted, tmp_path, source, monkeypatch):
    import cv2

    alive = []
    imwrite = cv2.imwrite

    def counting_imwrite(path, tile):
        alive.append(sum(
            t.name.startswith(illuminati.ENCODE_THREAD_PREFIX)
            for t in threading.enumerate()))
        return imwrite(path, tile)

    monkeypatch.setattr(cv2, "imwrite", counting_imwrite)
    _, events = _submit(tmp_path / "exp", source)
    assert _stats(events)["persist_peak_concurrency"] >= 2
    assert 1 <= max(alive) <= min(8, os.cpu_count() or 1)
    # closed with the last channel
    assert not [t for t in threading.enumerate()
                if t.name.startswith(illuminati.ENCODE_THREAD_PREFIX)]


def test_a_failed_encode_fails_the_step_after_the_launched_channels_persisted(
        launched_before_persisted, tmp_path, source, monkeypatch):
    import cv2

    imwrite = cv2.imwrite
    monkeypatch.setattr(
        cv2, "imwrite",
        lambda path, tile: "channel01" not in path and imwrite(path, tile))
    store, desc = _workflow(tmp_path / "exp", source)
    # no quarantine budget: the first failed batch fails the step
    wf = Workflow(store, desc, pipeline_depth=8,
                  resilience=ResilienceConfig(enabled=False))
    with pytest.raises(WorkflowError, match="illuminati"):
        wf.run()
    events = wf.ledger.events()
    (failed,) = [e for e in events if e.get("event") == "batch_failed"]
    assert failed["batch"] == 1 and "PNG tile encode failed" in failed["error"]
    assert [e["batch"] for e in _done(events)] == [0]
    # every channel was launched, so every other one left its pyramid
    for channel in (0, 2, 3, 4):
        assert (store.root / "pyramids" / f"channel{channel:02d}"
                / "layer.json").exists()
    assert not (store.root / "pyramids" / "channel01" / "layer.json").exists()
    assert not [t for t in threading.enumerate()
                if t.name.startswith(illuminati.ENCODE_THREAD_PREFIX)]
