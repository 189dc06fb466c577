"""A 64x64 five-step workflow (the ``cp3-plate`` configuration: five
channels on disk, config-3 pipeline) through ``Workflow.run``: its ledger
holds every span the steps promise, each child inside its parent's
interval with serial children summing to no more than the parent, and
``batch_done.result.h2d_bytes`` equals the bytes of the arrays uploaded,
times one launch plus one per escalation.  Then the lowered batch program:
every pipeline module and op stage is named in its text, and a build with
``jax.named_scope`` patched out computes the same outputs bit for bit.
"""

import contextlib
import importlib.util
import json
from pathlib import Path

import numpy as np
import pytest
import yaml

REPO = Path(__file__).resolve().parents[1]
CONFIG = json.loads(
    (REPO / "benchmark" / "configs" / "cp3-plate.json").read_text())
SIZE, CAPACITY, FIELDS = 64, 16, 9

#: per step, the spans its batches must record: (parent, span)
EXPECTED = {
    "imextract": {("step", "decode"), ("step", "write")},
    "corilla": {("step", "read_wait"), ("step", "scan"),
                ("step", "finalize"), ("step", "write")},
    # a channel through the executor: its reads on a prefetch worker (no
    # enclosing span there), correction and layout under the engine
    # thread's dispatch, the levels under a persist worker's persist
    "illuminati": {("step", "prefetch_wait"), ("step", "dispatch"),
                   (None, "persist"),
                   (None, "stats_read"), (None, "read"),
                   ("dispatch", "prep"), ("dispatch", "mosaic"),
                   ("dispatch", "pyramid"), ("persist", "level_fetch"),
                   ("persist", "encode")},
    "jterator": {("step", "prefetch_wait"), ("step", "dispatch"),
                 (None, "device_block"), (None, "persist"),
                 (None, "load"), ("dispatch", "upload"),
                 ("persist", "escalate"), ("escalate", "load"),
                 ("escalate", "upload"), ("escalate", "device_wait"),
                 ("persist", "fetch"), ("persist", "solidity"),
                 ("persist", "write_labels"), ("persist", "write_features"),
                 ("persist", "write_polygons")},
}
#: spans that run one after the other on one thread inside their parent
SERIAL = {"persist", "escalate", "dispatch"}


def _recorder():
    """``scripts/record_stage_trace.py``: the same seeded grid fields the
    chip fixture was recorded from (twelve nuclei a field, so rung 8 of
    the ladder saturates and every batch escalates once)."""
    spec = importlib.util.spec_from_file_location(
        "record_stage_trace", REPO / "scripts" / "record_stage_trace.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def ledger_events(tmp_path_factory):
    from tmlibrary_tpu import telemetry
    from tmlibrary_tpu.models.experiment import Experiment
    from tmlibrary_tpu.models.store import ExperimentStore
    from tmlibrary_tpu.workflow.engine import Workflow, WorkflowDescription

    tmp = tmp_path_factory.mktemp("inside_spans")
    src = tmp / "src"
    _recorder().write_grid_plate(str(src), FIELDS, SIZE, CONFIG["channels"],
                                 25)
    store = ExperimentStore.create(tmp / "exp", Experiment(
        name="wf", plates=[], channels=[], site_height=1, site_width=1))
    # the configuration's pipeline plus measure_morphology, so the
    # host-side solidity pass runs too
    pipe = json.loads(json.dumps(CONFIG["pipeline"]))
    pipe["pipeline"].append({"handles": {
        "module": "measure_morphology",
        "input": [{"name": "objects_image", "type": "LabelImage",
                   "key": "nuclei"}],
        "output": [{"name": "measurements", "type": "Measurement",
                    "objects": "nuclei"}]}})
    (store.root / "p.pipe.yaml").write_text(yaml.safe_dump(pipe))
    desc = WorkflowDescription.canonical({
        "metaconfig": {"source_dir": str(src), "sites_per_well_x": 3},
        "imextract": {},
        "corilla": {"n_devices": 1},
        "illuminati": {},
        # three batches of three fields: a prefetch worker loads ahead
        "jterator": {"pipe": "p.pipe.yaml", "max_objects": CAPACITY,
                     "n_devices": 1, "batch_size": 3,
                     "as_polygons": True},
    })
    telemetry.reset_registry(enabled=True)
    wf = Workflow(store, desc, pipeline_depth=2)
    wf.run()
    telemetry.reset_registry()
    return wf.ledger.events()


def _spans(events, step):
    return [e for e in events
            if e.get("event") == "span" and e.get("step") == step
            and e["span"] not in ("step", "batch")]


@pytest.mark.parametrize("step", sorted(EXPECTED))
def test_ledger_holds_every_span_of_the_step(ledger_events, step):
    spans = _spans(ledger_events, step)
    have = {(e.get("parent"), e["span"]) for e in spans}
    assert EXPECTED[step] <= have, sorted(EXPECTED[step] - have, key=str)
    for e in spans:
        assert e["batch"] is not None and e["t0"] > 0 and e["elapsed"] >= 0


def test_numeric_attributes_ride_the_spans(ledger_events):
    plane = SIZE * SIZE * 2
    for e in _spans(ledger_events, "imextract"):
        if e["span"] == "decode":
            assert e["files"] == FIELDS
            assert e["pixels"] == FIELDS * SIZE * SIZE
    for e in _spans(ledger_events, "illuminati"):
        if e["span"] == "prep":
            assert e["bytes"] == FIELDS * plane
        if e["span"] == "encode":
            assert e["tiles"] >= 1 and e["bytes"] >= e["tiles"]
    jt = _spans(ledger_events, "jterator")
    assert {e["capacity"] for e in jt if e["span"] == "escalate"} == {16}
    # three fields of two channels a batch; no shifts, no correction
    assert {e["bytes"] for e in jt if e["span"] == "upload"} \
        == {3 * 2 * plane}
    assert all(e["bytes"] > 0 for e in jt if e["span"] == "fetch")


@pytest.mark.parametrize("step", sorted(EXPECTED))
def test_children_lie_inside_their_parent_and_serial_ones_fit(
        ledger_events, step):
    spans = _spans(ledger_events, step)
    children_of: dict = {}
    for e in spans:
        parent = e.get("parent")
        if parent in (None, "step"):
            continue
        homes = [p for p in spans
                 if p["span"] == parent and p["batch"] == e["batch"]
                 and p["t0"] - 2e-3 <= e["t0"]
                 and e["t0"] + e["elapsed"]
                 <= p["t0"] + p["elapsed"] + 2e-3]
        assert homes, f"{step}: {e['span']} lies in no {parent} span"
        children_of.setdefault(id(homes[-1]), (homes[-1], []))[1].append(e)
    for parent, children in children_of.values():
        if parent["span"] in SERIAL:
            # compile-path spans nest among themselves: leave them out
            serial = [c for c in children if not c["span"].startswith(
                ("jit_", "cache_load"))]
            assert sum(c["elapsed"] for c in serial) \
                <= parent["elapsed"] + 2e-3


def test_spans_precede_their_batch_done(ledger_events):
    seen_done = set()
    for e in ledger_events:
        if e.get("event") == "batch_done":
            seen_done.add((e["step"], e["batch"]))
        elif (e.get("event") == "span" and e.get("batch") is not None
              and e["span"] != "batch"):
            assert (e["step"], e["batch"]) not in seen_done, e


def test_h2d_bytes_is_uploaded_nbytes_times_launches(ledger_events):
    plane = SIZE * SIZE * 2     # uint16
    results = [e["result"] for e in ledger_events
               if e.get("event") == "batch_done"
               and e.get("step") == "jterator"]
    assert len(results) == 3
    for r in results:
        # two channels of three fields; no shifts, no correction
        uploaded = r["n_sites"] * 2 * plane
        assert r["bucket_escalations"] == 1
        assert r["h2d_bytes"] == uploaded * (1 + r["bucket_escalations"])
        assert r["device_wait_s"] >= 0.0
    uploads = [e for e in _spans(ledger_events, "jterator")
               if e["span"] == "upload"]
    assert sum(e["bytes"] for e in uploads) \
        == sum(r["h2d_bytes"] for r in results)


def test_pipeline_stats_books_the_escalations_wait_as_device_time(
        ledger_events):
    (done,) = [e for e in ledger_events if e.get("event") == "step_done"
               and e.get("step") == "jterator"]
    stats = done["pipeline_stats"]
    phases = stats["phases"]
    wait = sum(e["result"]["device_wait_s"] for e in ledger_events
               if e.get("event") == "batch_done"
               and e.get("step") == "jterator")
    assert stats["device_s"] == pytest.approx(
        phases["dispatch"]["total_s"] + phases["device_block"]["total_s"]
        + wait, abs=2e-3)
    assert stats["host_s"] == pytest.approx(
        phases["prefetch_wait"]["total_s"] + phases["persist"]["total_s"]
        - wait, abs=2e-3)


def test_first_batch_is_the_batch_programs_not_an_illuminati_channel(
        ledger_events):
    """illuminati has ``launch_batch`` too (it runs before jterator), but
    ``first_batch`` / ``tmx_time_to_first_batch_seconds`` stay the cold
    start of the step that runs batch programs — live and replayed."""
    from tmlibrary_tpu import telemetry

    (first,) = [e for e in ledger_events if e.get("event") == "first_batch"]
    assert first["step"] == "jterator" and first["first_batch_index"] == 0
    order = [(e.get("event"), e.get("step")) for e in ledger_events]
    assert order.index(("step_done", "illuminati")) \
        < order.index(("first_batch", "jterator"))
    prom = telemetry.render_prometheus(
        telemetry.registry_from_ledger(ledger_events).snapshot())
    (sample,) = [v for n, _, v in telemetry.parse_prometheus(prom)
                 if n == "tmx_time_to_first_batch_seconds"]
    # the exposition keeps six significant digits (``:g``): a host loaded
    # enough to put the first batch past 10 s loses the fifth decimal
    assert sample == pytest.approx(first["time_to_first_batch_s"], rel=1e-5)


def test_illuminati_channels_ran_on_the_executor_beside_each_other(
        ledger_events):
    (done,) = [e for e in ledger_events if e.get("event") == "step_done"
               and e.get("step") == "illuminati"]
    stats = done["pipeline_stats"]
    # five channels at depth 2: two stage workers
    assert stats["n_batches"] == 5 and stats["persist_workers"] == 2
    threads: dict = {}
    for e in _spans(ledger_events, "illuminati"):
        threads.setdefault(e["span"], set()).add(e["thread"])
    assert all(t.startswith("tmx-persist") for t in threads["encode"])
    assert all(t.startswith("tmx-prefetch") for t in threads["read"])
    assert threads["mosaic"] == threads["dispatch"]
    assert len(threads["mosaic"]) == 1


# ------------------------------------------------- scopes: names, not values
def _batch_fn():
    from tmlibrary_tpu.jterator.description import PipelineDescription
    from tmlibrary_tpu.jterator.pipeline import ImageAnalysisPipeline

    desc = PipelineDescription.from_dict(CONFIG["pipeline"])
    return desc, ImageAnalysisPipeline(
        desc, max_objects=CAPACITY).build_batch_fn()


def _inputs():
    import jax.numpy as jnp

    rng = np.random.default_rng(25)
    fields = [_recorder().grid_field(rng, SIZE, ("DAPI", "Actin"))
              for _ in range(2)]
    raw = {c: jnp.asarray(np.stack([f[c] for f in fields]))
           for c in ("DAPI", "Actin")}
    return raw, {}, jnp.zeros((2, 2), jnp.int32)


def test_lowered_batch_program_names_every_module_and_stage():
    desc, fn = _batch_fn()
    text = fn.lower(*_inputs()).as_text(debug_info=True)
    for module in {m.module for m in desc.modules}:
        assert f"({module})/" in text or f"/{module}/" in text, module
    # the ops the modules are made of; on this backend CC, fill and the
    # flood are native callbacks, named like the XLA twins they stand for
    for stage in ("smooth", "otsu", "fill_holes", "label", "filter_area",
                  "watershed", "measure_intensity"):
        assert f"/{stage}/" in text, stage


def test_outputs_equal_a_build_with_scopes_patched_out(monkeypatch):
    import jax

    _, named = _batch_fn()
    with_scopes = jax.device_get(named(*_inputs()))
    text = named.lower(*_inputs()).as_text(debug_info=True)
    assert "/watershed/" in text

    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    _, bare = _batch_fn()
    assert "/watershed/" not in bare.lower(*_inputs()).as_text(
        debug_info=True)
    without = jax.device_get(bare(*_inputs()))
    a, tree_a = jax.tree_util.tree_flatten(with_scopes)
    b, tree_b = jax.tree_util.tree_flatten(without)
    assert tree_a == tree_b
    assert int(np.max(with_scopes.counts["nuclei"])) == 12
    for x, y in zip(a, b):
        assert x.dtype == y.dtype and x.shape == y.shape
        assert x.tobytes() == y.tobytes()     # bit for bit, NaNs included

