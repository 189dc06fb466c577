"""Adaptive object-capacity bucketing (``capacity.py`` + jterator routing).

Three layers of guarantees:

- Ladder resolution and routing policy as pure functions: spec parsing
  (auto / off / explicit lists, loud failures on malformed input), the
  strict-inequality capacity pick (a count AT the cap may have been
  clipped there), and the tuning-verdict hint loader.
- The bit-identity contract that makes bucketing safe to enable: the
  persisted label stacks and feature tables are byte-identical across
  bucket specs, through the pipelined executor at depth > 1, for both
  the sites and the spatial layout — including when an undersized
  bucket saturates and the router escalates before persisting.
- Surfacing: ``bucket_capacity``/``slot_occupancy`` ride the batch
  summaries into the run ledger, ``status()`` aggregates them, and the
  ledger→metrics derivation exports the routing counters and the
  occupancy gauge.
"""

import numpy as np
import pytest

from test_pipelined import (  # noqa: F401 — fixture re-export
    _read_features_sorted,
    _run_prep_steps,
    spatial_store,
)
from test_workflow import (  # noqa: F401 — fixture re-export
    make_description,
    source_dir,
    store,
    synth_site_image,
)

from tmlibrary_tpu import telemetry
from tmlibrary_tpu.capacity import (
    resolve_bucket_ladder,
    select_capacity,
    slot_occupancy,
)
from tmlibrary_tpu.workflow.engine import Workflow
from tmlibrary_tpu.workflow.pipelined import PipelinedExecutor
from tmlibrary_tpu.workflow.registry import get_step


@pytest.fixture(autouse=True)
def _isolate_tuning(tmp_path, monkeypatch):
    """Routing must not pick up a ``tuned_object_capacity`` hint from the
    repo's TUNING.json — tests pin the first-batch bucket explicitly."""
    monkeypatch.setenv("TMX_TUNING_JSON", str(tmp_path / "no_tuning.json"))
    monkeypatch.delenv("TMX_OBJECT_BUCKETS", raising=False)


# ------------------------------------------------------------ pure policy
def test_auto_ladder_is_pow2_up_to_ceiling():
    assert resolve_bucket_ladder(64, "auto") == (8, 16, 32, 64)
    assert resolve_bucket_ladder(64, None) == (8, 16, 32, 64)
    # non-pow2 ceiling is kept as the final rung, not rounded
    assert resolve_bucket_ladder(100, "auto") == (8, 16, 32, 64, 100)
    # ceiling at or below the minimum bucket collapses to a single rung
    assert resolve_bucket_ladder(6, "auto") == (6,)
    assert resolve_bucket_ladder(8, "auto") == (8,)


def test_off_spec_disables_bucketing():
    for spec in ("off", "none", "0", "false", "no", "OFF"):
        assert resolve_bucket_ladder(64, spec) == (64,)


def test_explicit_ladder_sorted_deduped_ceiling_appended():
    assert resolve_bucket_ladder(64, "8,32") == (8, 32, 64)
    assert resolve_bucket_ladder(64, "32, 8, 32") == (8, 32, 64)
    # rungs above the ceiling are dropped, ceiling always present
    assert resolve_bucket_ladder(16, "8,32,64") == (8, 16)


def test_malformed_specs_fail_loudly():
    for spec in ("8,banana", "-4", "8;16"):
        with pytest.raises(ValueError):
            resolve_bucket_ladder(64, spec)
    with pytest.raises(ValueError):
        resolve_bucket_ladder(0, "auto")


def test_select_capacity_strict_inequality():
    ladder = (8, 16, 64)
    # a count AT the cap may have been clipped there -> go one rung up
    assert select_capacity(7, ladder) == 8
    assert select_capacity(8, ladder) == 16
    assert select_capacity(16, ladder) == 64
    assert select_capacity(200, ladder) == 64  # ceiling is the fallback
    assert select_capacity(0, ladder) == 8


def test_slot_occupancy_guards_zero_slots():
    assert slot_occupancy(6, 24) == 0.25
    assert slot_occupancy(0, 0) == 0.0


def test_tuned_object_capacity_loader(tmp_path, monkeypatch):
    import json

    from tmlibrary_tpu.tuning import tuned_object_capacity

    path = tmp_path / "TUNING.json"
    path.write_text(json.dumps({
        "backend": "cpu",
        "written_by": "scripts/tune_tpu.py write_results",
        "object_capacity": {"cpu": 16},
    }))
    monkeypatch.setenv("TMX_TUNING_JSON", str(path))
    assert tuned_object_capacity("cpu") == 16
    assert tuned_object_capacity("tpu") is None
    monkeypatch.setenv("TMX_TUNING_JSON", str(tmp_path / "missing.json"))
    assert tuned_object_capacity("cpu") is None


# ------------------------------------------- bit-identity: sites layout
def test_sites_bit_identical_across_bucket_specs(source_dir, store):
    """Labels and features persisted with bucketing on (routed at
    capacity 8, far below the 64 ceiling) are byte-identical to the
    unbucketed run, through the pipelined executor at depth 4."""
    import pandas.testing

    desc = make_description(source_dir, store)
    _run_prep_steps(desc, store)
    jd = next(s for stage in desc.stages for s in stage.steps
              if s.name == "jterator")
    args = {**jd.args, "batch_size": 2, "object_buckets": "off"}

    jt = get_step("jterator")(store)
    jt.init(args)
    summaries = [jt.run(j) for j in jt.list_batches()]
    assert all(s["bucket_capacity"] == 64 for s in summaries)
    ref_labels = store.read_labels(None, "nuclei").copy()
    ref_feats = _read_features_sorted(store, "nuclei")
    # the synthetic sites are sparse: peak count fits the smallest bucket
    peak = int(max(lab.max() for lab in ref_labels))
    assert 0 < peak < 8

    # "8" routes at the smallest rung, "16,32" at a mid-ladder rung —
    # two genuinely different compiled capacities vs the 64 reference
    # ("auto" resolves to the same rung as "8"; the ladder unit tests
    # above pin that resolution)
    for spec in ("8", "16,32"):
        jt2 = get_step("jterator")(store)
        jt2.delete_previous_output()
        jt2.init({**args, "object_buckets": spec})
        batches = [jt2.load_batch(i) for i in jt2.list_batches()]
        out = list(PipelinedExecutor(jt2, depth=4).run(batches))
        caps = [r["bucket_capacity"] for _, r in out]
        # routing engaged: every batch ran below the 64-slot ceiling
        assert all(c < 64 for c in caps), (spec, caps)
        assert all("bucket_escalations" not in r for _, r in out)
        occs = [r["slot_occupancy"] for _, r in out]
        assert all(0.0 < o <= 1.0 for o in occs)
        assert np.array_equal(store.read_labels(None, "nuclei"),
                              ref_labels), f"labels diverged: {spec}"
        pandas.testing.assert_frame_equal(
            _read_features_sorted(store, "nuclei"), ref_feats
        )


def test_saturated_bucket_escalates_then_matches(source_dir, store):
    """An undersized first rung (capacity 2 for ~6-object sites) clips
    the on-device counts, so the router must relaunch one rung up before
    persisting — and the escalated results still match the unbucketed
    run exactly."""
    import pandas.testing

    desc = make_description(source_dir, store)
    _run_prep_steps(desc, store)
    jd = next(s for stage in desc.stages for s in stage.steps
              if s.name == "jterator")
    args = {**jd.args, "batch_size": 4, "object_buckets": "off"}

    jt = get_step("jterator")(store)
    jt.init(args)
    for j in jt.list_batches():
        jt.run(j)
    ref_labels = store.read_labels(None, "nuclei").copy()
    ref_feats = _read_features_sorted(store, "nuclei")

    jt2 = get_step("jterator")(store)
    jt2.delete_previous_output()
    jt2.init({**args, "object_buckets": "2"})  # ladder (2, 64)
    batches = [jt2.load_batch(i) for i in jt2.list_batches()]
    out = list(PipelinedExecutor(jt2, depth=2).run(batches))

    # the first batch routed at 2, saturated, escalated to the ceiling;
    # batches inside the initial launch window (depth 2 keeps up to
    # depth+1 dispatches ahead of the first persist) may pay the same
    # relaunch before the routing history exists
    first = out[0][1]
    assert first["bucket_capacity"] == 64
    assert first.get("bucket_escalations", 0) >= 1
    assert all(r["bucket_capacity"] == 64 for _, r in out)
    # batches past the initial window learn from history and route at
    # the ceiling directly — no repeated relaunch tax
    assert all("bucket_escalations" not in r for _, r in out[3:])

    assert np.array_equal(store.read_labels(None, "nuclei"), ref_labels)
    pandas.testing.assert_frame_equal(
        _read_features_sorted(store, "nuclei"), ref_feats
    )


# -------------------------------------- routing by demand (the jump)
def _handle(name, kind, **rest):
    return {"name": name, "type": kind, **rest}


def _pipe(segmenter_modules, correct=True):
    """``PIPE_YAML`` with another segmentation: the modules given, which
    leave their labels under the key ``nuclei``, then intensity."""
    from test_workflow import PIPE_YAML

    return {
        **PIPE_YAML,
        "input": {"channels": [
            {"name": "DAPI", "correct": correct, "align": False}]},
        "pipeline": [{"handles": m} for m in segmenter_modules]
        + [PIPE_YAML["pipeline"][-1]],
    }


#: smooth, Otsu, label, area filter: the filter clips at the capacity and
#: reports no demand, so the router sees "at least cap" and nothing more
FALLBACK_PIPE = _pipe([
    {"module": "smooth",
     "input": [_handle("intensity_image", "IntensityImage", key="DAPI"),
               _handle("sigma", "Numeric", value=1.5)],
     "output": [_handle("smoothed_image", "IntensityImage", key="sm")]},
    {"module": "threshold_otsu",
     "input": [_handle("intensity_image", "IntensityImage", key="sm")],
     "output": [_handle("mask", "BinaryImage", key="mask")]},
    {"module": "label",
     "input": [_handle("mask", "BinaryImage", key="mask")],
     "output": [_handle("label_image", "LabelImage", key="raw")]},
    {"module": "filter",
     "input": [_handle("label_image", "LabelImage", key="raw"),
               _handle("feature", "Character", value="area"),
               _handle("lower_threshold", "Numeric", value=10)],
     "output": [_handle("filtered_label_image", "SegmentedObjects",
                        key="nuclei", objects="nuclei")]},
])


def _jterator_runs(source_dir, store, pipe, spec, batch_size, depth=2):
    """The jterator step with ``object_buckets=off`` and then with
    ``spec``, on one store: ``(reference labels, reference rows, the
    bucketed run's batch summaries)``; the store holds the bucketed
    run's outputs on return."""
    import yaml

    desc = make_description(source_dir, store)
    if pipe is not None:
        (store.root / "nuclei.pipe.yaml").write_text(yaml.safe_dump(pipe))
    _run_prep_steps(desc, store)
    jd = next(s for stage in desc.stages for s in stage.steps
              if s.name == "jterator")
    args = {**jd.args, "batch_size": batch_size, "object_buckets": "off"}
    jt = get_step("jterator")(store)
    jt.init(args)
    for j in jt.list_batches():
        jt.run(j)
    ref_labels = store.read_labels(None, "nuclei").copy()
    ref_feats = _read_features_sorted(store, "nuclei")

    jt2 = get_step("jterator")(store)
    jt2.delete_previous_output()
    jt2.init({**args, "object_buckets": spec})
    batches = [jt2.load_batch(i) for i in jt2.list_batches()]
    out = [r for _, r in PipelinedExecutor(jt2, depth=depth).run(batches)]
    return ref_labels, ref_feats, out


@pytest.mark.parametrize("pipe,relaunches,skipped", [
    pytest.param(None, 1, 1, id="segmenter-reports-jump"),
    pytest.param(FALLBACK_PIPE, 2, 0, id="nobody-reports-climb"),
])
def test_relaunch_goes_to_the_rung_the_demand_selects(
        source_dir, store, pipe, relaunches, skipped):
    """About 6 objects a site, first rung 2 of the ladder 2, 4, 8, 16, 64.
    ``segment_primary`` reports the component count it found at rung 2,
    so ONE re-launch goes to rung 8 and rung 4 is skipped; a pipeline
    whose clipping module reports nothing reads "at least 2", "at least
    4", and climbs 2 -> 4 -> 8 as it always has.  Either way what is
    persisted equals the unbucketed run."""
    import pandas.testing

    ref_labels, ref_feats, out = _jterator_runs(
        source_dir, store, pipe, "2,4,8,16", batch_size=8)
    per_site = ref_feats.groupby("site_index").size()
    assert len(out) == 2  # both inside the first launch window
    for i, res in enumerate(out):
        peak = int(per_site.iloc[8 * i:8 * (i + 1)].max())
        assert 4 <= peak < 8, "the fixture's sites hold about 6 objects"
        assert res["bucket_capacity"] == 8
        assert res["bucket_escalations"] == relaunches
        assert res.get("bucket_rungs_skipped", 0) == skipped
        # no debris in these fields: demand is the persisted peak count
        assert res["bucket_demand"] == peak
    assert np.array_equal(store.read_labels(None, "nuclei"), ref_labels)
    pandas.testing.assert_frame_equal(
        _read_features_sorted(store, "nuclei"), ref_feats
    )


@pytest.fixture
def debris_source_dir(tmp_path):
    """One well, four 64x64 fields: three 2x2 specks in the top rows
    (first in scan order, each under ``min_area``) and two 8x8 nuclei
    below them.  Five components, two objects."""
    import cv2

    src = tmp_path / "microscope_debris"
    src.mkdir()
    for site in range(4):
        img = np.full((64, 64), 300, np.uint16)
        for k in range(3):
            img[2:4, 6 + 12 * k + site:8 + 12 * k + site] = 5000
        img[20:28, 10 + site:18 + site] = 5000
        img[40:48, 30 + site:38 + site] = 5000
        cv2.imwrite(str(src / f"A01_s{site}_DAPI.png"), img)
    return src


def test_debris_before_the_filter_cannot_pass_for_a_fit(
        debris_source_dir, store):
    """Raw count 5 over the first rung 4, filtered count 2 under it: at
    rung 4 the clip drops the fifth component (a nucleus), the filter
    drops the three specks, and ONE object survives — below the cap.
    The count after the filter says the rung held; the demand (5, taken
    before the clip) says it did not, and the batch is re-launched."""
    import pandas.testing

    pipe = _pipe([
        {"module": "segment_primary",
         "input": [_handle("intensity_image", "IntensityImage", key="DAPI"),
                   _handle("threshold_method", "Character", value="manual"),
                   _handle("threshold_value", "Numeric", value=1000),
                   _handle("smooth_sigma", "Numeric", value=0.0),
                   _handle("min_area", "Numeric", value=10)],
         "output": [_handle("objects", "SegmentedObjects", key="nuclei",
                            objects="nuclei")]},
    ], correct=False)
    ref_labels, ref_feats, out = _jterator_runs(
        debris_source_dir, store, pipe, "4", batch_size=4)
    assert [int(lab.max()) for lab in ref_labels] == [2, 2, 2, 2]
    assert np.array_equal(store.read_labels(None, "nuclei"), ref_labels)
    pandas.testing.assert_frame_equal(
        _read_features_sorted(store, "nuclei"), ref_feats
    )
    (res,) = out
    assert res["bucket_demand"] == 5
    assert res["bucket_capacity"] == 64
    assert res["bucket_escalations"] == 1


@pytest.mark.parametrize("declump", [False, True],
                         ids=["components", "declump-seeds"])
def test_demand_is_a_function_of_the_field(rng, declump):
    """The same site at capacities 2, 8 and 64 reports the same demand,
    whatever the capacity did to its labels and counts."""
    import copy

    import jax.numpy as jnp
    from test_workflow import PIPE_YAML

    from tmlibrary_tpu.jterator.description import PipelineDescription
    from tmlibrary_tpu.jterator.pipeline import ImageAnalysisPipeline

    pipe = copy.deepcopy(PIPE_YAML)
    pipe["input"]["channels"][0]["correct"] = False
    pipe["pipeline"][1]["handles"]["input"].append(
        _handle("declump", "Boolean", value=declump))
    desc = PipelineDescription.from_dict(pipe)
    sites = np.stack([synth_site_image(rng) for _ in range(2)])
    seen = {}
    for cap in (2, 8, 64):
        fn = ImageAnalysisPipeline(desc, max_objects=cap).build_batch_fn()
        res = fn({"DAPI": jnp.asarray(sites)}, {},
                 jnp.zeros((2, 2), jnp.int32))
        seen[cap] = (np.asarray(res.demand).tolist(),
                     np.asarray(res.counts["nuclei"]).tolist())
    demand, counts = seen[64]
    assert all(2 < d < 8 for d in demand)
    assert demand == seen[2][0] == seen[8][0]
    assert seen[2][1] == [2, 2] and seen[8][1] == counts
    # the area filter only takes away: demand never under the count
    assert all(d >= c for d, c in zip(demand, counts))


# ----------------------------------------- bit-identity: spatial layout
def test_spatial_layout_bit_identical_with_buckets(spatial_store,
                                                   monkeypatch):
    """The spatial (mosaic) layout routes through the same persist path;
    bucketing via the environment spec must leave its global-id label
    stacks untouched at depth 2."""
    import pandas.testing

    st = spatial_store
    args = {"layout": "spatial", "n_devices": 8, "object_buckets": "off"}
    jt = get_step("jterator")(st)
    jt.init(args)
    for j in jt.list_batches():
        jt.run(j)
    ref_labels = st.read_labels(None, "mosaic_cells").copy()
    ref_feats = _read_features_sorted(st, "mosaic_cells")
    assert ref_labels.max() > 0

    monkeypatch.setenv("TMX_OBJECT_BUCKETS", "8")
    jt2 = get_step("jterator")(st)
    jt2.delete_previous_output()
    # arg left at its "auto" default -> the env spec decides the ladder
    jt2.init({"layout": "spatial", "n_devices": 8})
    batches = [jt2.load_batch(i) for i in jt2.list_batches()]
    out = list(PipelinedExecutor(jt2, depth=2).run(batches))
    assert len(out) == 2
    assert np.array_equal(st.read_labels(None, "mosaic_cells"), ref_labels)
    pandas.testing.assert_frame_equal(
        _read_features_sorted(st, "mosaic_cells"), ref_feats
    )


# ------------------------------------------------- ledger + metrics path
def test_engine_ledger_aggregates_buckets_and_exports_metrics(
        source_dir, store, monkeypatch, tmp_path, capsys):
    """A full engine run with bucketing on lands ``bucket_capacity`` /
    ``slot_occupancy`` in the ``batch_done`` events, ``status()`` rolls
    them up, and ``tmx metrics --source ledger`` exports the routing
    counter and occupancy gauge."""
    from tmlibrary_tpu.cli import main

    monkeypatch.setenv("TMX_OBJECT_BUCKETS", "8")
    desc = make_description(source_dir, store)
    wf = Workflow(store, desc, pipeline_depth=2)
    wf.run()

    events = wf.ledger.events()
    done = [e for e in events if e.get("event") == "batch_done"
            and e.get("step") == "jterator"]
    assert done, "no jterator batch_done events"
    for e in done:
        res = e.get("result") or {}
        assert res.get("bucket_capacity") == 8
        assert 0.0 < res.get("slot_occupancy", 0.0) <= 1.0

    buckets = wf.ledger.status()["jterator"]["buckets"]
    assert buckets["routed"] == {"8": len(done)}
    assert buckets["escalations"] == 0
    assert buckets["occupancy_n"] == len(done)
    assert buckets["occupancy_sum"] > 0.0

    reg = telemetry.registry_from_ledger(events)
    prom = telemetry.render_prometheus(reg.snapshot())
    assert 'tmx_jterator_bucket_routed_total{capacity="8"}' in prom
    assert "tmx_jterator_slot_occupancy" in prom

    prom_file = tmp_path / "metrics.prom"
    assert main(["metrics", "--root", str(store.root), "--source",
                 "ledger", "--out", str(prom_file)]) == 0
    samples = telemetry.parse_prometheus(prom_file.read_text())
    by_key = {(n, lbl.get("capacity")): v for n, lbl, v in samples}
    assert by_key.get(("tmx_jterator_bucket_routed_total", "8")) == \
        float(len(done))
    assert ("tmx_jterator_slot_occupancy", None) in by_key

    # the status CLI renders the same aggregate as a buckets line
    # (same run — a second engine run would only re-prove the above)
    assert main(["workflow", "status", "--root", str(store.root)]) == 0
    text = capsys.readouterr().out
    assert "buckets:" in text
    assert "cap8x" in text
    assert "slot occupancy" in text
