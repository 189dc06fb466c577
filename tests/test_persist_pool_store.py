"""The persist pool does not reach the store (``workflow/pipelined.py``).

The executor persists several batches at once, so batches FINISH in an
order the clock decides.  What a run leaves behind must not depend on
it: a small experiment (16 fields of 1-12 nuclei in 8 batches, ladder
2 / 4 / 8 with the ceiling at 8, so every batch is launched at rung 2
and re-launched once at the rung its demand selects, and the densest
fields saturate the ceiling and are re-segmented by collect) is
submitted through the engine

- with the pool the executor resolves (four workers),
- with ``persist_workers=1`` (the queue of one it replaced),
- and once more with the pool, a resubmission into a fresh root,

and the three stores are compared byte for byte: every label stack,
every Parquet shard, ``saturation.json`` and ``cap_overrides.json``,
and the ``batch_done`` results
of the run ledger apart from times.  These are the benchmark's
``repeatability`` and ``durability`` guarantees (resubmissions identical,
every stack and row on disk when ``run`` returns), held on the CPU
before the chip is asked.

Every batch is launched before the first persist starts — the shape of
the benchmark's cells, where a well's nine fields fit the window — so
all eight route from a cold history and the re-launch each persist makes
is a function of its own demand alone.  (Where launches and persists
interleave, WHICH rung a batch is first launched at follows the clock,
with one worker as with four; the bit-identity contract in
``capacity.py`` keeps that out of the labels and features, and
``tests/test_buckets.py`` pins it.)
"""

import copy
import functools
import hashlib
import json
import threading

import numpy as np
import pytest
import yaml

from test_workflow import PIPE_YAML, make_description

from tmlibrary_tpu import capacity
from tmlibrary_tpu.models.experiment import Experiment
from tmlibrary_tpu.models.store import ExperimentStore
from tmlibrary_tpu.workflow import engine as engine_mod
from tmlibrary_tpu.workflow.engine import Workflow
from tmlibrary_tpu.workflow.pipelined import PipelinedExecutor
from tmlibrary_tpu.workflow.steps.jterator import ImageAnalysisRunner

#: nuclei planted in each of the 16 fields, in site order: every rung of
#: the ladder (2, 4, 8) is some field's, and 9+ saturate the ceiling
BLOBS = (1, 3, 5, 7, 9, 2, 4, 6, 12, 1, 3, 3, 5, 7, 2, 9)
#: batch_done.result keys that are clock readings
TIMES = ("device_wait_s", "device_wall_times", "straggler_skew_s")


def _site_image(rng, n_blobs):
    """``n_blobs`` Gaussian nuclei in random cells of a 4x4 grid: apart
    enough to stay apart, and in other cells from field to field, so the
    illumination statistics see no pattern."""
    yy, xx = np.mgrid[0:64, 0:64]
    img = rng.normal(300, 20, (64, 64))
    for k in rng.permutation(16)[:n_blobs]:
        y, x = 8 + 16 * (k // 4), 8 + 16 * (k % 4)
        img += 4000 * np.exp(-((yy - y) ** 2 + (xx - x) ** 2) / (2 * 2.0**2))
    return np.clip(img, 0, 65535).astype(np.uint16)


@pytest.fixture
def ladder_source(tmp_path, rng):
    import cv2

    src = tmp_path / "microscope"
    src.mkdir()
    blobs = iter(BLOBS)
    for well in ("A01", "A02", "B01", "B02"):
        for site in range(4):
            cv2.imwrite(str(src / f"{well}_s{site}_DAPI.png"),
                        _site_image(rng, next(blobs)))
    return src


@pytest.fixture(autouse=True)
def _isolate_routing(tmp_path, monkeypatch):
    monkeypatch.setenv("TMX_TUNING_JSON", str(tmp_path / "no_tuning.json"))
    monkeypatch.delenv("TMX_OBJECT_BUCKETS", raising=False)


@pytest.fixture
def launched_before_persisted(monkeypatch):
    """Hold every persist until the step's last batch is launched."""
    state = {"n": 0, "launched": 0, "all": threading.Event()}
    launch, persist = (ImageAnalysisRunner.launch_batch,
                       ImageAnalysisRunner.persist_batch)

    def launch_batch(self, batch, prefetched=None):
        out = launch(self, batch, prefetched)
        state["launched"] += 1
        if state["launched"] == state["n"]:
            state["all"].set()
        return out

    def persist_batch(self, batch, ctx):
        assert state["all"].wait(timeout=120)
        return persist(self, batch, ctx)

    monkeypatch.setattr(ImageAnalysisRunner, "launch_batch", launch_batch)
    monkeypatch.setattr(ImageAnalysisRunner, "persist_batch", persist_batch)

    def expect(n):
        state.update(n=n, launched=0)
        state["all"].clear()

    return expect


def _submit(root, source, expect):
    """One submit into a fresh root, as the benchmark's plate driver makes
    it: router history dropped, the whole workflow through the engine."""
    capacity.reset_routing_history()
    st = ExperimentStore.create(root, Experiment(
        name="wf", plates=[], channels=[], site_height=1, site_width=1))
    desc = make_description(source, st)
    # planted nuclei are to be counted as planted: no illumination
    # correction (sixteen fields are too few for its statistics)
    pipe = copy.deepcopy(PIPE_YAML)
    pipe["input"]["channels"][0]["correct"] = False
    (st.root / "nuclei.pipe.yaml").write_text(yaml.safe_dump(pipe))
    for stage in desc.stages:
        for sd in stage.steps:
            if sd.name == "jterator":
                sd.args.update(batch_size=2, max_objects=8,
                               object_buckets="2,4")
    expect(8)
    wf = Workflow(st, desc, pipeline_depth=8)
    wf.run()
    return st, wf.ledger.events()


def _artifacts(st) -> dict:
    """sha256 of every file the jterator step is answerable for."""
    root = st.root
    step_dir = root / "workflow" / "jterator"
    files = sorted([*root.glob("segmentations/*.npy"),
                    *root.glob("features/*/*.parquet"),
                    step_dir / "saturation.json",
                    step_dir / "cap_overrides.json"])
    return {str(p.relative_to(root)):
            hashlib.sha256(p.read_bytes()).hexdigest() for p in files}


def _batch_results(events) -> list:
    out = []
    for e in events:
        if e.get("step") == "jterator" and e.get("event") == "batch_done":
            result = {k: v for k, v in e["result"].items() if k not in TIMES}
            out.append((e["batch"], json.dumps(result, sort_keys=True)))
    return out


def _pipeline_stats(events) -> dict:
    return next(e["pipeline_stats"] for e in events
                if e.get("step") == "jterator"
                and e.get("event") == "step_done")


def test_store_is_byte_identical_whatever_the_persist_pool(
        tmp_path, ladder_source, launched_before_persisted, monkeypatch):
    pooled, pooled_events = _submit(
        tmp_path / "pooled", ladder_source, launched_before_persisted)
    stats = _pipeline_stats(pooled_events)
    assert stats["persist_workers"] == 4
    assert stats["persist_peak_concurrency"] >= 2

    with monkeypatch.context() as patch:
        patch.setattr(engine_mod, "PipelinedExecutor", functools.partial(
            PipelinedExecutor, persist_workers=1))
        single, single_events = _submit(
            tmp_path / "single", ladder_source, launched_before_persisted)
    stats = _pipeline_stats(single_events)
    assert (stats["persist_workers"], stats["persist_peak_concurrency"]) \
        == (1, 1)

    again, again_events = _submit(
        tmp_path / "again", ladder_source, launched_before_persisted)

    want = _artifacts(pooled)
    # two label stacks' worth is not the point: every batch's shard is
    assert len([f for f in want if f.endswith(".parquet")]) == 8
    assert "workflow/jterator/saturation.json" in want
    assert _artifacts(single) == want
    assert _artifacts(again) == want

    results = _batch_results(pooled_events)
    assert [b for b, _ in results] == list(range(8))  # submission order
    assert _batch_results(single_events) == results
    assert _batch_results(again_events) == results

    # the experiment exercised what it set out to: every field launched
    # cold and re-launched once, at three different rungs, and the dense
    # fields saturated the ceiling
    parsed = [json.loads(r) for _, r in results]
    assert all(r["bucket_escalations"] == 1 for r in parsed)
    assert {r["bucket_capacity"] for r in parsed} == {4, 8}
    # ... which the persists recorded in saturation.json, four at once,
    # and collect then re-segmented at a doubled ceiling and cleared
    saturated = [b for b, r in enumerate(parsed) if r.get("saturated")]
    assert saturated == [2, 4, 7]
    step_dir = pooled.root / "workflow" / "jterator"
    assert sorted(json.loads((step_dir / "cap_overrides.json").read_text())) \
        == ["2", "4", "7"]
    assert json.loads((step_dir / "saturation.json").read_text()) == {}

    # durability: every stack and row is on disk when run() returns
    labels = pooled.read_labels(None, "nuclei")
    rows = pooled.read_features("nuclei").groupby("site_index").size()
    for s in range(16):
        in_stack = np.count_nonzero(np.bincount(labels[s].ravel())[1:])
        assert in_stack == rows.get(s, 0) == BLOBS[s]
