import json

import numpy as np
import pytest
import yaml

from tmlibrary_tpu.models.experiment import Experiment
from tmlibrary_tpu.models.store import ExperimentStore
from tmlibrary_tpu.workflow.engine import (
    RunLedger,
    Workflow,
    WorkflowDescription,
)

PIPE_YAML = {
    "description": "nuclei segmentation + intensity",
    "input": {"channels": [{"name": "DAPI", "correct": True, "align": False}]},
    "pipeline": [
        {
            "handles": {
                "module": "smooth",
                "input": [
                    {"name": "intensity_image", "type": "IntensityImage", "key": "DAPI"},
                    {"name": "sigma", "type": "Numeric", "value": 1.5},
                ],
                "output": [
                    {"name": "smoothed_image", "type": "IntensityImage", "key": "sm"}
                ],
            }
        },
        {
            "handles": {
                "module": "segment_primary",
                "input": [
                    {"name": "intensity_image", "type": "IntensityImage", "key": "sm"},
                    {"name": "threshold_method", "type": "Character", "value": "otsu"},
                    {"name": "smooth_sigma", "type": "Numeric", "value": 0.0},
                    {"name": "min_area", "type": "Numeric", "value": 10},
                ],
                "output": [
                    {
                        "name": "objects",
                        "type": "SegmentedObjects",
                        "key": "nuclei",
                        "objects": "nuclei",
                    }
                ],
            }
        },
        {
            "handles": {
                "module": "measure_intensity",
                "input": [
                    {"name": "objects_image", "type": "LabelImage", "key": "nuclei"},
                    {"name": "intensity_image", "type": "IntensityImage", "key": "DAPI"},
                ],
                "output": [
                    {
                        "name": "measurements",
                        "type": "Measurement",
                        "objects": "nuclei",
                        "channel": "DAPI",
                    }
                ],
            }
        },
    ],
    "output": {"objects": [{"name": "nuclei"}]},
}


def synth_site_image(rng, n_blobs=6, margin=8):
    """One synthetic uint16 site: noisy background + Gaussian nuclei blobs."""
    yy, xx = np.mgrid[0:64, 0:64]
    img = rng.normal(300, 20, (64, 64))
    for _ in range(n_blobs):
        y, x = rng.integers(margin, 64 - margin, 2)
        img += 4000 * np.exp(-((yy - y) ** 2 + (xx - x) ** 2) / (2 * 3.0**2))
    return np.clip(img, 0, 65535).astype(np.uint16)


@pytest.fixture
def source_dir(tmp_path, rng):
    """Synthetic 1-plate 2x2-well 2x2-site single-channel experiment on disk."""
    import cv2

    src = tmp_path / "microscope"
    src.mkdir()
    for well in ("A01", "A02", "B01", "B02"):
        for site in range(4):
            path = src / f"{well}_s{site}_DAPI.png"
            cv2.imwrite(str(path), synth_site_image(rng))
    return src


@pytest.fixture
def store(tmp_path):
    placeholder = Experiment(
        name="wf", plates=[], channels=[], site_height=1, site_width=1
    )
    return ExperimentStore.create(tmp_path / "exp", placeholder)


def make_description(source_dir, store):
    pipe_path = store.root / "nuclei.pipe.yaml"
    pipe_path.write_text(yaml.safe_dump(PIPE_YAML))
    return WorkflowDescription.canonical(
        {
            "metaconfig": {"source_dir": str(source_dir)},
            "imextract": {},
            "corilla": {"chunk_size": 8, "n_devices": 1},
            "jterator": {
                "pipe": "nuclei.pipe.yaml",
                "batch_size": 8,
                "max_objects": 64,
                "n_devices": 1,
            },
        }
    )


def test_full_workflow_end_to_end(source_dir, store):
    desc = make_description(source_dir, store)
    summary = Workflow(store, desc).run()
    assert set(summary) == {"metaconfig", "imextract", "corilla", "jterator"}

    # manifest was configured from filenames
    exp = ExperimentStore.open(store.root).experiment
    assert exp.n_sites == 16
    assert [c.name for c in exp.channels] == ["DAPI"]
    assert exp.site_height == 64

    # pixels ingested
    pixels = store.read_sites(None, channel=0)
    assert pixels.shape == (16, 64, 64)
    assert pixels.max() > 1000

    # corilla stats exist and are sane
    stats = store.read_illumstats(channel=0)
    assert stats["mean_log"].shape == (64, 64)
    assert float(stats["n"]) == 16

    # segmentations + features persisted
    labels = store.read_labels(None, "nuclei")
    assert labels.shape == (16, 64, 64)
    assert labels.max() > 0
    feats = store.read_features("nuclei")
    assert len(feats) > 20
    assert "Intensity_mean_DAPI" in feats.columns
    assert (feats["label"] >= 1).all()
    # every site produced at least one object (6 blobs planted per site)
    assert set(feats["site_index"].unique()) == set(range(16))


def test_illuminati_static_mapobjects(source_dir, store):
    """The pyramid step's collect phase registers the static
    Plates/Wells/Sites mapobject types with grid outlines (reference:
    auto-created MapobjectType rows for the viewer overlay)."""
    from tmlibrary_tpu.models.mapobject import MapobjectTypeRegistry
    from tmlibrary_tpu.workflow.registry import get_step

    desc = make_description(source_dir, store)
    Workflow(store, desc).run()

    step = get_step("illuminati")(store)
    step.init({"correct": False, "align": False, "batch_size": 8})
    for i in step.list_batches():
        step.run(i)
    out = step.collect()
    assert out["static_mapobjects"] == {"Plates": 1, "Wells": 4, "Sites": 16}

    reg = MapobjectTypeRegistry(store.root)
    assert {"Plates", "Wells", "Sites"} <= set(reg.names())
    assert reg.get("Wells").ref_type == "well"
    import pandas as pd

    wells = pd.read_parquet(store.root / "segmentations" /
                            "Wells_polygons_plate00.parquet")
    assert len(wells) == 4
    assert {"name", "contour_y", "contour_x"} <= set(wells.columns)
    # pyramid tiles exist too
    assert (store.root / "pyramids" / "channel00" / "layer.json").exists()


def test_workflow_resume_skips_completed(source_dir, store):
    desc = make_description(source_dir, store)
    wf = Workflow(store, desc)
    wf.run()
    # step-scoped events only: every run (including a no-op resume)
    # appends a run_started marker carrying the description hash
    events_before = len([e for e in wf.ledger.events() if e.get("step")])
    # resume after completion: no step re-runs
    wf2 = Workflow(store, desc)
    summary = wf2.run(resume=True)
    assert summary == {}
    assert len([e for e in wf2.ledger.events() if e.get("step")]) == events_before


def test_workflow_resume_after_failure(source_dir, store):
    desc = make_description(source_dir, store)
    # break jterator by pointing at a missing pipe file
    for stage in desc.stages:
        for s in stage.steps:
            if s.name == "jterator":
                s.args["pipe"] = "missing.pipe.yaml"
    from tmlibrary_tpu.errors import WorkflowError

    with pytest.raises(WorkflowError):
        Workflow(store, desc).run()
    status = RunLedger(store.workflow_dir / "ledger.jsonl").status()
    assert status["jterator"]["state"] == "failed"
    assert status["corilla"]["state"] == "done"

    # fix and resume: earlier steps skipped, jterator runs
    desc2 = make_description(source_dir, store)
    summary = Workflow(store, desc2).run(resume=True)
    assert list(summary) == ["jterator"]
    assert store.read_labels(None, "nuclei").max() > 0


def test_workflow_rejects_unknown_step():
    from tmlibrary_tpu.errors import WorkflowError
    from tmlibrary_tpu.workflow.engine import (
        WorkflowStageDescription,
        WorkflowStepDescription,
    )

    desc = WorkflowDescription(
        stages=[
            WorkflowStageDescription(
                name="x", steps=[WorkflowStepDescription(name="nope")]
            )
        ]
    )
    with pytest.raises(WorkflowError):
        desc.validate()


def test_description_yaml_roundtrip(tmp_path, source_dir, store):
    desc = make_description(source_dir, store)
    path = tmp_path / "wf.yaml"
    desc.save(path)
    loaded = WorkflowDescription.load(path)
    assert loaded.to_dict() == desc.to_dict()


def test_cli_end_to_end(source_dir, tmp_path, capsys):
    from tmlibrary_tpu.cli import main

    root = str(tmp_path / "cli_exp")
    assert main(["create", "--root", root, "--name", "cli"]) == 0
    assert (
        main(
            [
                "metaconfig", "init", "--root", root,
                "--source-dir", str(source_dir),
            ]
        )
        == 0
    )
    assert main(["metaconfig", "run", "--root", root]) == 0
    assert main(["imextract", "init", "--root", root]) == 0
    assert main(["imextract", "run", "--root", root]) == 0
    assert main(["corilla", "init", "--root", root, "--n-devices", "1"]) == 0
    assert main(["corilla", "run", "--root", root]) == 0
    store = ExperimentStore.open(root)
    assert store.experiment.n_sites == 16
    assert store.has_illumstats(channel=0)
    # error path: run without init
    assert main(["jterator", "run", "--root", root, "--job", "0"]) == 1
    err = capsys.readouterr().err
    assert "run init first" in err


def test_jterator_pipelined_matches_sequential(source_dir, store):
    """run_batches_pipelined (async-dispatch overlap) must produce the
    same persisted outputs and ledger batch events as one-at-a-time runs."""
    from tmlibrary_tpu.workflow.registry import get_step

    desc = make_description(source_dir, store)
    # run everything up to jterator sequentially
    for name in ("metaconfig", "imextract", "corilla"):
        sd = next(s for stage in desc.stages for s in stage.steps if s.name == name)
        step = get_step(name)(store)
        step.init(sd.args)
        for j in step.list_batches():
            step.run(j)

    from tmlibrary_tpu.workflow.registry import get_step as _get

    jd = next(s for stage in desc.stages for s in stage.steps if s.name == "jterator")
    jt = _get("jterator")(store)
    jt.init({**jd.args, "batch_size": 4})  # 16 sites -> 4 batches
    batches = [jt.load_batch(i) for i in jt.list_batches()]

    seen = []
    for batch, result in jt.run_batches_pipelined(batches):
        seen.append((batch["index"], result["n_sites"]))
    assert [i for i, _ in seen] == [0, 1, 2, 3]
    assert all(n == 4 for _, n in seen)
    labels_pipelined = store.read_labels(None, "nuclei").copy()

    # sequential re-run over fresh output must persist identical labels
    jt2 = _get("jterator")(store)
    jt2.delete_previous_output()
    jt2.init({**jd.args, "batch_size": 4})
    for j in jt2.list_batches():
        jt2.run(j)
    labels_seq = store.read_labels(None, "nuclei")
    assert np.array_equal(labels_pipelined, labels_seq)


def test_jterator_figures_artifacts(source_dir, store):
    """figures=True writes per-site segmentation overlay PNGs
    (reference: jterator module Figure artifacts)."""
    import cv2

    from tmlibrary_tpu.workflow.registry import get_step

    desc = make_description(source_dir, store)
    for name in ("metaconfig", "imextract", "corilla"):
        sd = next(s for stage in desc.stages for s in stage.steps if s.name == name)
        step = get_step(name)(store)
        step.init(sd.args)
        for j in step.list_batches():
            step.run(j)

    jd = next(s for stage in desc.stages for s in stage.steps if s.name == "jterator")
    jt = get_step("jterator")(store)
    jt.init({**jd.args, "batch_size": 16, "figures": True})
    jt.run(0)
    figs = sorted((store.root / "figures").glob("nuclei_site*.png"))
    assert len(figs) == 16
    img = cv2.imread(str(figs[0]), cv2.IMREAD_UNCHANGED)
    assert img.shape == (64, 64, 3)
    # boundaries are colored: the overlay is not pure grayscale
    assert not (img[..., 0] == img[..., 1]).all()


def test_jterator_applies_intersection_crop(source_dir, store):
    """With cycle alignment, every channel is cropped to the stored
    intersection window inside the fused program, and persisted labels /
    centroids are mapped back to the site frame (reference
    SiteIntersection semantics)."""
    from tmlibrary_tpu.workflow.registry import get_step

    desc = make_description(source_dir, store)
    for name in ("metaconfig", "imextract", "corilla"):
        sd = next(s for stage in desc.stages for s in stage.steps if s.name == name)
        step = get_step(name)(store)
        step.init(sd.args)
        for j in step.list_batches():
            step.run(j)

    # simulate an align run: +3px dy shift everywhere, stored window
    n = store.n_sites
    store.write_shifts(np.tile([[3, 0]], (n, 1)).astype(np.int32), cycle=0)
    store.write_intersection({"top": 3, "bottom": 0, "left": 0, "right": 0})

    pipe_yaml = yaml.safe_load(yaml.safe_dump(PIPE_YAML))
    pipe_yaml["input"]["channels"][0]["align"] = True
    pipe_yaml["pipeline"].append({"handles": {
        "module": "measure_morphology",
        "input": [
            {"name": "objects_image", "type": "LabelImage", "key": "nuclei"},
        ],
        "output": [
            {"name": "measurements", "type": "Measurement", "objects": "nuclei"},
        ],
    }})
    (store.root / "aligned.pipe.yaml").write_text(yaml.safe_dump(pipe_yaml))

    jd = next(s for stage in desc.stages for s in stage.steps if s.name == "jterator")
    jt = get_step("jterator")(store)
    jt.init({**jd.args, "pipe": "aligned.pipe.yaml", "batch_size": 16})
    jt.run(0)

    labels = store.read_labels(None, "nuclei")
    assert labels.shape == (16, 64, 64)  # site frame preserved
    # cropped top margin maps back to rows 0..2 == empty after padding
    assert labels[:, :3, :].max() == 0
    assert labels.max() > 0
    feats = store.read_features("nuclei")
    # centroids are site-frame: none can sit inside the cropped margin
    assert (feats["Morphology_centroid_y"] >= 3).all()


def test_cli_export_features(source_dir, store, tmp_path, capsys):
    """tmx export writes the combined feature table as CSV/Parquet."""
    import pandas as pd

    from tmlibrary_tpu.cli import main
    from tmlibrary_tpu.workflow.registry import get_step

    desc = make_description(source_dir, store)
    for name in ("metaconfig", "imextract", "corilla", "jterator"):
        sd = next(s for stage in desc.stages for s in stage.steps if s.name == name)
        step = get_step(name)(store)
        step.init(sd.args)
        for j in step.list_batches():
            step.run(j)

    out_csv = tmp_path / "nuclei.csv"
    rc = main(["export", "--root", str(store.root), "--objects", "nuclei",
               "--out", str(out_csv)])
    assert rc == 0
    df = pd.read_csv(out_csv)
    assert len(df) > 20
    assert {"site_index", "label", "Intensity_mean_DAPI"} <= set(df.columns)

    out_pq = tmp_path / "nuclei.parquet"
    assert main(["export", "--root", str(store.root), "--objects", "nuclei",
                 "--out", str(out_pq)]) == 0
    assert len(pd.read_parquet(out_pq)) == len(df)

    # unknown object type is a clean error, not a traceback
    assert main(["export", "--root", str(store.root), "--objects", "nope",
                 "--out", str(tmp_path / "x.csv")]) == 1
    assert "no feature shards" in capsys.readouterr().err


def test_jterator_sharded_matches_single_device(source_dir, store):
    """The step's sharded run_batch (site axis over a 4-device mesh) must
    persist the same labels and counts as a single-device run."""
    from tmlibrary_tpu.workflow.registry import get_step

    desc = make_description(source_dir, store)
    for name in ("metaconfig", "imextract", "corilla"):
        sd = next(s for stage in desc.stages for s in stage.steps if s.name == name)
        step = get_step(name)(store)
        step.init(sd.args)
        for j in step.list_batches():
            step.run(j)

    jd = next(s for stage in desc.stages for s in stage.steps if s.name == "jterator")

    jt1 = get_step("jterator")(store)
    jt1.init({**jd.args, "batch_size": 16, "n_devices": 1})
    r1 = jt1.run(0)
    labels_1dev = store.read_labels(None, "nuclei").copy()

    jt4 = get_step("jterator")(store)
    jt4.delete_previous_output()
    jt4.init({**jd.args, "batch_size": 16, "n_devices": 4})
    r4 = jt4.run(0)
    labels_4dev = store.read_labels(None, "nuclei")

    assert r1["objects"] == r4["objects"]
    assert np.array_equal(labels_1dev, labels_4dev)


def test_step_log_capture_and_cli(source_dir, store, capsys):
    """Per-batch/step log files are captured and surfaced by `tmx log
    --step` (reference per-job stdout files, SURVEY §6)."""
    from tmlibrary_tpu.cli import main
    from tmlibrary_tpu.workflow.registry import get_step

    mc = get_step("metaconfig")(store)
    mc.init({"source_dir": str(source_dir)})
    mc.run(0)
    log_file = store.workflow_dir / "metaconfig" / "logs" / "batch_000.log"
    assert log_file.exists()
    # INFO-level framework logging is captured even at default verbosity
    import logging as _logging

    _logging.getLogger("tmlibrary_tpu.test").info("marker-not-captured")
    with mc.capture_logs("probe"):
        _logging.getLogger("tmlibrary_tpu.test").info("marker-captured")
    probe = (store.workflow_dir / "metaconfig" / "logs" / "probe.log").read_text()
    assert "marker-captured" in probe
    assert "marker-not-captured" not in probe
    # re-running truncates instead of appending
    mc.run(0)
    assert log_file.read_text().count("planned") <= 1

    rc = main(["log", "--root", str(store.root), "--step", "metaconfig",
               "--job", "0"])
    assert rc == 0
    # engine-driven runs also produce a per-step run log
    desc = make_description(source_dir, store)
    Workflow(store, desc).run()
    assert (store.workflow_dir / "jterator" / "logs" / "run.log").exists()
    capsys.readouterr()
    assert main(["log", "--root", str(store.root), "--step", "nope"]) == 1


def test_cli_cleanup_verb(source_dir, store, tmp_path):
    from tmlibrary_tpu.cli import main

    root = str(store.root)
    assert main(["metaconfig", "init", "--root", root,
                 "--source-dir", str(source_dir)]) == 0
    assert main(["metaconfig", "run", "--root", root]) == 0
    assert main(["imextract", "init", "--root", root]) == 0
    assert main(["imextract", "run", "--root", root]) == 0
    assert main(["imextract", "cleanup", "--root", root]) == 0
    from tmlibrary_tpu.workflow.registry import get_step

    assert get_step("imextract")(store).list_batches() == []


def test_cli_export_geojson(source_dir, store, tmp_path):
    """GeoJSON polygon export (reference: tmserver's mapobject GeoJSON)."""
    from tmlibrary_tpu.cli import main
    from tmlibrary_tpu.workflow.registry import get_step

    desc = make_description(source_dir, store)
    for name in ("metaconfig", "imextract", "corilla"):
        sd = next(s for stage in desc.stages for s in stage.steps if s.name == name)
        step = get_step(name)(store)
        step.init(sd.args)
        for j in step.list_batches():
            step.run(j)
    jd = next(s for stage in desc.stages for s in stage.steps if s.name == "jterator")
    jt = get_step("jterator")(store)
    jt.init({**jd.args, "batch_size": 16, "as_polygons": True})
    jt.run(0)

    out = tmp_path / "nuclei.geojson"
    assert main(["export", "--root", str(store.root), "--objects", "nuclei",
                 "--out", str(out)]) == 0
    doc = json.loads(out.read_text())
    assert doc["type"] == "FeatureCollection"
    assert len(doc["features"]) > 10
    f0 = doc["features"][0]
    assert f0["geometry"]["type"] == "Polygon"
    ring = f0["geometry"]["coordinates"][0]
    assert ring[0] == ring[-1]  # closed
    assert {"site", "label"} <= set(f0["properties"])

    # --simplify drops collinear/near-collinear vertices but keeps shape
    out2 = tmp_path / "nuclei_simple.geojson"
    assert main(["export", "--root", str(store.root), "--objects", "nuclei",
                 "--out", str(out2), "--simplify", "1.0"]) == 0
    doc2 = json.loads(out2.read_text())
    assert len(doc2["features"]) == len(doc["features"])
    n_full = sum(len(f["geometry"]["coordinates"][0]) for f in doc["features"])
    n_simp = sum(len(f["geometry"]["coordinates"][0]) for f in doc2["features"])
    assert n_simp < n_full

    # --join-features attaches measurement columns by (site, label)
    out3 = tmp_path / "nuclei_joined.geojson"
    assert main(["export", "--root", str(store.root), "--objects", "nuclei",
                 "--out", str(out3),
                 "--join-features", "Intensity_mean_DAPI"]) == 0
    doc3 = json.loads(out3.read_text())
    vals = [f["properties"]["Intensity_mean_DAPI"] for f in doc3["features"]]
    assert all(isinstance(v, float) and v > 0 for v in vals)
    feats_table = store.read_features("nuclei")
    f0 = doc3["features"][0]["properties"]
    row = feats_table[(feats_table["site_index"] == f0["site"])
                      & (feats_table["label"] == f0["label"])]
    assert np.isclose(float(row["Intensity_mean_DAPI"].iloc[0]),
                      f0["Intensity_mean_DAPI"])
    # unknown column is a clean error
    assert main(["export", "--root", str(store.root), "--objects", "nuclei",
                 "--out", str(out3), "--join-features", "nope"]) == 1


def test_cli_args_schema(capsys):
    """tmx <step> args prints the argument schema (reference: the args
    introspection tmserver renders as UI forms)."""
    from tmlibrary_tpu.cli import main

    assert main(["jterator", "args"]) == 0
    schema = json.loads(capsys.readouterr().out)
    names = {a["name"] for a in schema}
    assert {"pipe", "batch_size", "max_objects", "figures"} <= names
    # pipe stopped being schema-required when --layout spatial landed
    # (the spatial path needs no module chain); sites-layout still
    # enforces it at init time
    pipe = next(a for a in schema if a["name"] == "pipe")
    assert pipe["required"] is False
    assert "layout" in names


def test_workflow_types_registry():
    """Reference dependencies.py defines two workflow types: canonical
    (no inter-cycle registration) and multiplexing (adds align)."""
    from tmlibrary_tpu.errors import WorkflowError
    from tmlibrary_tpu.workflow.engine import WORKFLOW_TYPES, WorkflowDescription

    assert set(WORKFLOW_TYPES) == {"canonical", "multiplexing"}
    canon = WorkflowDescription.for_type("canonical", {"jterator": {}})
    steps = [s.name for st in canon.stages for s in st.steps]
    assert "align" not in steps
    multi = WorkflowDescription.for_type("multiplexing", {"jterator": {}})
    steps = [s.name for st in multi.stages for s in st.steps]
    assert "align" in steps
    # stage order is identical four-stage DAG in both
    assert [st.name for st in canon.stages] == [st.name for st in multi.stages]

    with pytest.raises(WorkflowError):
        WorkflowDescription.for_type("nope")


def test_canonical_autoselects_multiplexing_for_align():
    from tmlibrary_tpu.workflow.engine import WorkflowDescription

    d = WorkflowDescription.canonical({"align": {"ref_cycle": 0}})
    steps = [s.name for st in d.stages for s in st.steps]
    assert "align" in steps
    d2 = WorkflowDescription.canonical({"jterator": {}})
    assert "align" not in [s.name for st in d2.stages for s in st.steps]


def test_cli_workflow_template(store, capsys):
    from tmlibrary_tpu.cli import main

    root = str(store.root)
    assert main(["workflow", "template", "--root", root,
                 "--type", "multiplexing"]) == 0
    wf_yaml = store.workflow_dir / "workflow.yaml"
    d = WorkflowDescription.load(wf_yaml)
    steps = [s.name for st in d.stages for s in st.steps]
    assert "align" in steps and "jterator" in steps
    assert not any(s.active for st in d.stages for s in st.steps)
    # refuses to clobber an existing description
    capsys.readouterr()
    assert main(["workflow", "template", "--root", root]) == 1


@pytest.fixture
def multiplex_source_dir(tmp_path, rng):
    """2-cycle experiment: cycle 1 is cycle 0 rolled down 4 px (known
    inter-cycle stage drift for the align step to recover)."""
    import cv2

    src = tmp_path / "mx"
    src.mkdir()
    for well in ("A01", "A02"):
        for site in range(2):
            img = synth_site_image(rng, n_blobs=5, margin=10)
            cv2.imwrite(str(src / f"{well}_s{site}_c0_DAPI.png"), img)
            cv2.imwrite(str(src / f"{well}_s{site}_c1_DAPI.png"),
                        np.roll(img, 4, axis=0))
    return src


def test_multiplexing_workflow_end_to_end(multiplex_source_dir, store):
    """The multiplexing workflow type runs align for real: per-site
    phase-correlation shifts of cycle 1 against cycle 0 recover the
    planted 4-px drift, and collect stores the intersection window."""
    desc = WorkflowDescription.for_type(
        "multiplexing",
        {
            "metaconfig": {"source_dir": str(multiplex_source_dir)},
            "imextract": {},
            "align": {"ref_cycle": 0, "batch_size": 4},
        },
    )
    summary = Workflow(store, desc).run()
    assert set(summary) == {"metaconfig", "imextract", "align"}

    exp = ExperimentStore.open(store.root).experiment
    assert exp.n_cycles == 2
    shifts = store.read_shifts(cycle=1)
    assert shifts.shape == (4, 2)
    # stored shifts are CORRECTIONS: content drifted 4 px down, so the
    # stored roll that re-aligns cycle 1 is dy=-4 at every site
    np.testing.assert_array_equal(shifts, np.tile([[-4, 0]], (4, 1)))
    # rolling up by 4 exposes invalid rows at the bottom -> bottom margin
    # of the intersection; what is stored, and cropped to, is its largest
    # margin widened to the next multiple of 16 on every side
    assert summary["align"]["collected"]["intersection"] == {
        "top": 0, "bottom": 4, "left": 0, "right": 0}
    window = store.read_intersection()
    assert window == {"top": 16, "bottom": 16, "left": 16, "right": 16}


def test_workflow_resume_skips_completed_batches(source_dir, store):
    """Mid-step crash recovery: batches the ledger already records as done
    are not re-run on resume (reference: GC3Pie task-level resume)."""
    from tmlibrary_tpu.workflow.registry import get_step

    desc = make_description(source_dir, store)
    # run everything up to jterator
    for name in ("metaconfig", "imextract", "corilla"):
        sd = next(s for stage in desc.stages for s in stage.steps if s.name == name)
        step = get_step(name)(store)
        step.init(sd.args)
        for j in step.list_batches():
            step.run(j)

    # simulate a crash after jterator batch 0: plan 4 batches of 4 sites,
    # run only the first, and record what the engine would have logged
    jd = next(s for stage in desc.stages for s in stage.steps
              if s.name == "jterator")
    jd.args["batch_size"] = 4
    jt = get_step("jterator")(store)
    jt.init(jd.args)
    assert len(jt.list_batches()) == 4
    jt.run(0)
    ledger = RunLedger(store.workflow_dir / "ledger.jsonl")
    ledger.append(step="metaconfig", event="step_done")
    ledger.append(step="imextract", event="step_done")
    ledger.append(step="corilla", event="step_done")
    ledger.append(step="jterator", event="init_done", n_batches=4)
    ledger.append(step="jterator", event="batch_done", batch=0)

    summary = Workflow(store, desc).run(resume=True)
    assert list(summary) == ["jterator"]
    events = ledger.events()
    done = [e["batch"] for e in events
            if e.get("step") == "jterator" and e.get("event") == "batch_done"]
    # batch 0 was recorded once (the simulated pre-crash run), 1..3 ran now
    assert sorted(done) == [0, 1, 2, 3]
    # all 16 sites have labels regardless
    assert (store.read_labels(None, "nuclei") > 0).any(axis=(1, 2)).all()


def test_workflow_resume_replans_on_args_change(source_dir, store):
    """Resume with changed step args discards the stale batch plan and
    re-inits (engine re-init invalidation)."""
    from tmlibrary_tpu.workflow.registry import get_step

    desc = make_description(source_dir, store)
    Workflow(store, desc).run()

    # change jterator's batching and resume: step re-runs from a new plan
    desc2 = make_description(source_dir, store)
    jd = next(s for stage in desc2.stages for s in stage.steps
              if s.name == "jterator")
    jd.args["batch_size"] = 4
    # forget the step_done so jterator is considered interrupted
    ledger = RunLedger(store.workflow_dir / "ledger.jsonl")
    events = [e for e in ledger.events()
              if not (e.get("step") == "jterator"
                      and e.get("event") == "step_done")]
    ledger.path.write_text("".join(json.dumps(e) + "\n" for e in events))

    summary = Workflow(store, desc2).run(resume=True)
    assert list(summary) == ["jterator"]
    jt = get_step("jterator")(store)
    assert len(jt.list_batches()) == 4  # re-planned at the new batch size
    # the new plan actually RAN in full: 4 fresh batch_done events after
    # the last init_done, and every site has labels
    after = ledger.events()
    last_init = max(i for i, e in enumerate(after)
                    if e.get("step") == "jterator"
                    and e.get("event") == "init_done")
    ran = [e["batch"] for e in after[last_init:]
           if e.get("step") == "jterator" and e.get("event") == "batch_done"]
    assert sorted(ran) == [0, 1, 2, 3]
    assert (store.read_labels(None, "nuclei") > 0).any(axis=(1, 2)).all()


def test_cli_workflow_resume_verb(source_dir, store):
    """'tmx workflow resume' is the reference's resume verb: shorthand
    for submit --resume (skips completed steps)."""
    from tmlibrary_tpu.cli import main

    desc = make_description(source_dir, store)
    desc.save(store.workflow_dir / "workflow.yaml")
    root = str(store.root)
    assert main(["workflow", "submit", "--root", root]) == 0
    ledger = RunLedger(store.workflow_dir / "ledger.jsonl")
    events_before = len([e for e in ledger.events() if e.get("step")])
    assert main(["workflow", "resume", "--root", root]) == 0
    events_after = len([e for e in ledger.events() if e.get("step")])
    assert events_after == events_before  # nothing re-ran


def test_cli_workflow_cleanup(source_dir, store):
    """workflow cleanup wipes every step's outputs, plans and the ledger;
    a fresh submit afterwards rebuilds everything."""
    from tmlibrary_tpu.cli import main
    from tmlibrary_tpu.workflow.registry import get_step

    desc = make_description(source_dir, store)
    desc.save(store.workflow_dir / "workflow.yaml")
    root = str(store.root)
    assert main(["workflow", "submit", "--root", root]) == 0
    store = ExperimentStore.open(store.root)  # CLI refreshed the manifest
    assert store.read_labels(None, "nuclei").max() > 0

    assert main(["workflow", "cleanup", "--root", root]) == 0
    assert not (store.workflow_dir / "ledger.jsonl").exists()
    assert get_step("jterator")(store).list_batches() == []
    from tmlibrary_tpu.errors import StoreError
    from tmlibrary_tpu.models.mapobject import MapobjectTypeRegistry
    from tmlibrary_tpu.workflow.steps.metaconfig import MetadataConfigurator

    with pytest.raises(StoreError):
        store.read_labels(None, "nuclei")
    # metaconfig's persisted mapping and the mapobject registrations are
    # gone too — nothing advertises artifacts that no longer exist
    mc = get_step("metaconfig")(store)
    assert not (mc.step_dir / MetadataConfigurator.MAPPING_FILE).exists()
    assert MapobjectTypeRegistry(store.root).names() == []

    assert main(["workflow", "submit", "--root", root]) == 0
    assert store.read_labels(None, "nuclei").max() > 0


def test_object_cap_saturation_is_loud(tmp_path, caplog):
    """A site with more objects than max_objects must produce a visible
    saturation signal (batch summary -> ledger, collect warning) instead
    of silently losing the overflow (round-2 VERDICT weak-spot #4)."""
    import logging

    from tmlibrary_tpu.models.experiment import grid_experiment
    from tmlibrary_tpu.workflow.registry import get_step

    exp = grid_experiment(
        "sat", well_rows=1, well_cols=1, sites_per_well=(1, 1),
        channel_names=("DAPI",), site_shape=(64, 64),
    )
    st = ExperimentStore.create(tmp_path / "sat_exp", exp)
    # 7x7 grid of bright 3x3 squares = 49 objects, comfortably over cap 16
    img = np.full((64, 64), 300, np.uint16)
    for gy in range(7):
        for gx in range(7):
            y, x = 4 + 8 * gy, 4 + 8 * gx
            img[y:y + 3, x:x + 3] = 40000
    st.write_sites(img[None], [0], channel=0)

    pipe = dict(PIPE_YAML)
    pipe["input"] = {"channels": [{"name": "DAPI", "correct": False, "align": False}]}
    (st.root / "sat.pipe.yaml").write_text(yaml.safe_dump(pipe))

    jt = get_step("jterator")(st)
    jt.init({"pipe": "sat.pipe.yaml", "batch_size": 4, "max_objects": 16,
             "n_devices": 1, "auto_resegment": False})
    with caplog.at_level(logging.WARNING):
        result = jt.run(0)
    assert result["saturated"] == {"nuclei": 1}
    assert result["objects"]["nuclei"] == 16  # capped, and visibly so
    assert any("max_objects" in r.message for r in caplog.records)

    caplog.clear()
    # collect from a FRESH instance: the per-verb CLI runs init/run/collect
    # in separate processes, so the signal must survive process boundaries
    jt_collect = get_step("jterator")(st)
    with caplog.at_level(logging.WARNING):
        collected = jt_collect.collect()
    assert collected["saturated_sites"] == {"nuclei": 1}
    assert any("--max-objects" in r.message for r in caplog.records)

    # a clean re-run of the same batch (same init) must CLEAR its entry
    clean = np.full((64, 64), 300, np.uint16)
    clean[10:13, 10:13] = 40000
    st.write_sites(clean[None], [0], channel=0)
    result2 = jt.run(0)
    assert "saturated" not in result2
    assert "saturated_sites" not in get_step("jterator")(st).collect()

    # cleanup (init implies delete_previous_output) clears the stale signal
    st.write_sites(img[None], [0], channel=0)
    jt2 = get_step("jterator")(st)
    jt2.init({"pipe": "sat.pipe.yaml", "batch_size": 4, "max_objects": 16,
              "n_devices": 1, "auto_resegment": False})
    jt2.run(0)
    assert get_step("jterator")(st).collect()["saturated_sites"] == {"nuclei": 1}
    jt2.init({"pipe": "sat.pipe.yaml", "batch_size": 4, "max_objects": 64,
              "n_devices": 1, "auto_resegment": False})
    assert "saturated_sites" not in get_step("jterator")(st).collect()


def test_collect_auto_resegments_saturated_batches(tmp_path, caplog):
    """The default flow closes the saturation loop with NO manual step
    (round-3 VERDICT next-step #7): a 300-object site at max_objects=64
    ends with the correct counts after collect, via bounded doublings
    (64 -> 128 -> 256 -> 512), the raised cap written back into the
    batch file, and the escalation recorded in the collect summary."""
    import json as _json
    import logging

    from tmlibrary_tpu.models.experiment import grid_experiment
    from tmlibrary_tpu.workflow.registry import get_step

    exp = grid_experiment(
        "autoreseg", well_rows=1, well_cols=1, sites_per_well=(1, 1),
        channel_names=("DAPI",), site_shape=(256, 256),
    )
    st = ExperimentStore.create(tmp_path / "ar_exp", exp)
    # 18x17 grid of bright 3x3 squares, first 300 = 300 objects
    img = np.full((256, 256), 300, np.uint16)
    n_obj = 0
    for gy in range(18):
        for gx in range(17):
            if n_obj == 300:
                break
            y, x = 4 + 14 * gy, 4 + 14 * gx
            img[y:y + 3, x:x + 3] = 40000
            n_obj += 1
    st.write_sites(img[None], [0], channel=0)

    pipe = dict(PIPE_YAML)
    pipe["input"] = {"channels": [{"name": "DAPI", "correct": False,
                                   "align": False}]}
    (st.root / "ar.pipe.yaml").write_text(yaml.safe_dump(pipe))

    jt = get_step("jterator")(st)
    jt.init({"pipe": "ar.pipe.yaml", "batch_size": 4, "max_objects": 64,
             "n_devices": 1})
    result = jt.run(0)
    assert result["saturated"] == {"nuclei": 1}

    # collect from a FRESH instance (per-verb CLI process boundary)
    with caplog.at_level(logging.WARNING):
        collected = get_step("jterator")(st).collect()
    assert collected["resegmented"] == {"0": 512}
    assert "saturated_sites" not in collected
    assert collected["objects_total"]["nuclei"] == 300
    feats = st.read_features("nuclei")
    assert len(feats) == 300
    labels = st.read_labels(None, "nuclei")
    assert labels.max() == 300
    # the raised cap persisted in the SIDE override file — NOT the batch
    # file, whose args must keep matching the planned description or the
    # engine's resume staleness check would re-plan and wipe everything
    jt_fresh = get_step("jterator")(st)
    batch = _json.loads(
        (jt_fresh.step_dir / "batch_000.json").read_text()
    )
    assert batch["args"]["max_objects"] == 64
    overrides = _json.loads(
        (jt_fresh.step_dir / "cap_overrides.json").read_text()
    )
    assert overrides == {"0": 512}
    # engine resume comparison (engine._run_step): planned args still
    # resolve identically, so resume keeps the completed batches
    assert jt_fresh.batch_args.resolve(
        {"pipe": "ar.pipe.yaml", "batch_size": 4, "max_objects": 64,
         "n_devices": 1}
    ) == batch["args"]
    # and a resumed re-run of the batch applies the override
    rerun = jt_fresh.run(0)
    assert rerun["objects"]["nuclei"] == 300
    assert any("auto-resegmenting" in r.message for r in caplog.records)


def test_no_saturation_signal_below_cap(tmp_path):
    """An unsaturated run must NOT emit the signal (no false alarms)."""
    from tmlibrary_tpu.models.experiment import grid_experiment
    from tmlibrary_tpu.workflow.registry import get_step

    exp = grid_experiment(
        "nosat", well_rows=1, well_cols=1, sites_per_well=(1, 1),
        channel_names=("DAPI",), site_shape=(64, 64),
    )
    st = ExperimentStore.create(tmp_path / "nosat_exp", exp)
    rng = np.random.default_rng(3)
    st.write_sites(synth_site_image(rng, n_blobs=4)[None], [0], channel=0)
    pipe = dict(PIPE_YAML)
    pipe["input"] = {"channels": [{"name": "DAPI", "correct": False, "align": False}]}
    (st.root / "nosat.pipe.yaml").write_text(yaml.safe_dump(pipe))
    jt = get_step("jterator")(st)
    jt.init({"pipe": "nosat.pipe.yaml", "batch_size": 4, "max_objects": 64,
             "n_devices": 1})
    result = jt.run(0)
    assert "saturated" not in result
    assert "saturated_sites" not in jt.collect()


def test_spatial_layout_mosaic_segmentation(tmp_path, devices):
    """`--layout spatial`: the well mosaic is row-sharded over the 8-CPU
    mesh, segmented with distributed CC, and exported — an object crossing
    a site border keeps ONE global id, and the labels are bit-identical
    to the same chain on the unsharded mosaic (scipy scan order)."""
    import jax.numpy as jnp
    import scipy.ndimage as ndi

    from tmlibrary_tpu.models.experiment import grid_experiment
    from tmlibrary_tpu.ops.smooth import gaussian_smooth
    from tmlibrary_tpu.ops.threshold import otsu_value
    from tmlibrary_tpu.workflow.registry import get_step

    exp = grid_experiment(
        "spatial", well_rows=1, well_cols=1, sites_per_well=(2, 2),
        channel_names=("DAPI",), site_shape=(64, 64),
    )
    st = ExperimentStore.create(tmp_path / "spatial_exp", exp)
    rng = np.random.default_rng(11)
    mosaic = rng.normal(300, 20, (128, 128))
    yy, xx = np.mgrid[0:128, 0:128]
    # one blob dead on the 4-corner junction (spans ALL four sites) plus
    # a few ordinary ones
    for cy, cx in [(64, 64), (20, 30), (100, 20), (30, 100), (90, 95)]:
        mosaic += 4000 * np.exp(-((yy - cy) ** 2 + (xx - cx) ** 2) / (2 * 4.0**2))
    mosaic = np.clip(mosaic, 0, 65535).astype(np.uint16)
    tiles = np.stack([
        mosaic[0:64, 0:64], mosaic[0:64, 64:128],
        mosaic[64:128, 0:64], mosaic[64:128, 64:128],
    ])
    st.write_sites(tiles, [0, 1, 2, 3], channel=0)

    jt = get_step("jterator")(st)
    jt.init({"layout": "spatial", "n_devices": 8})
    result = jt.run(0)
    assert result["layout"] == "spatial"
    assert result["objects"]["mosaic_cells"] == 5

    labels = st.read_labels(None, "mosaic_cells")
    # junction blob: same id in all four site stacks
    ids = {int(labels[0][-1, -1]), int(labels[1][-1, 0]),
           int(labels[2][0, -1]), int(labels[3][0, 0])}
    assert len(ids) == 1 and ids != {0}

    # bit-identity vs the unsharded chain (scipy scan order)
    sm = np.asarray(gaussian_smooth(jnp.asarray(mosaic, jnp.float32), 1.5))
    mask = sm > float(np.asarray(otsu_value(jnp.asarray(sm))))
    golden, n = ndi.label(mask, structure=np.ones((3, 3)))
    assert n == 5
    restitched = np.zeros((128, 128), np.int32)
    restitched[0:64, 0:64] = labels[0]
    restitched[0:64, 64:128] = labels[1]
    restitched[64:128, 0:64] = labels[2]
    restitched[64:128, 64:128] = labels[3]
    np.testing.assert_array_equal(restitched, golden)

    # ragged feature table: one row per global object
    feats = st.read_features("mosaic_cells")
    assert len(feats) == 5
    assert set(feats["label"]) == {1, 2, 3, 4, 5}
    assert (feats["Morphology_area"] > 0).all()
    assert ((feats["Morphology_solidity"] > 0)
            & (feats["Morphology_solidity"] <= 1.0)).all()
    # intensity stats over the segmentation channel, per GLOBAL object
    for lab in (1, 2):
        sel = mosaic[restitched == lab].astype(np.float64)
        row = feats.loc[feats["label"] == lab].iloc[0]
        np.testing.assert_allclose(row["Intensity_mean_DAPI"], sel.mean(),
                                   rtol=1e-6)
        np.testing.assert_allclose(row["Intensity_max_DAPI"], sel.max())
    assert (feats["Morphology_bbox_height"] > 0).all()
    # the junction blob's bbox spans both site rows/cols of the mosaic
    junction = feats.loc[
        feats["Morphology_centroid_y"].sub(64).abs().idxmin()
    ]
    assert junction["Morphology_bbox_height"] > 8

    collected = get_step("jterator")(st).collect()
    assert collected["objects_total"]["mosaic_cells"] == 5


def test_spatial_layout_applies_cycle_shifts(tmp_path, devices):
    """Stored align-step shifts move each site into the aligned frame
    during stitching, so a multiplexing cycle's mosaic segments exactly
    like the pre-shift golden."""
    import jax.numpy as jnp
    import scipy.ndimage as ndi

    from tmlibrary_tpu.models.experiment import grid_experiment
    from tmlibrary_tpu.ops.smooth import gaussian_smooth
    from tmlibrary_tpu.ops.threshold import otsu_value
    from tmlibrary_tpu.workflow.registry import get_step
    from tmlibrary_tpu.workflow.steps.jterator import _host_shift

    exp = grid_experiment(
        "spatsh", well_rows=1, well_cols=1, sites_per_well=(2, 2),
        channel_names=("DAPI",), site_shape=(32, 32), n_cycles=2,
    )
    st = ExperimentStore.create(tmp_path / "spatsh_exp", exp)
    rng = np.random.default_rng(23)
    yy, xx = np.mgrid[0:64, 0:64]
    mosaic = rng.normal(300, 15, (64, 64))
    for cy, cx in [(16, 16), (40, 48)]:
        mosaic += 4000 * np.exp(-((yy - cy) ** 2 + (xx - cx) ** 2) / 18.0)
    mosaic = np.clip(mosaic, 0, 65535).astype(np.uint16)
    tiles = np.stack([mosaic[0:32, 0:32], mosaic[0:32, 32:64],
                      mosaic[32:64, 0:32], mosaic[32:64, 32:64]])
    # cycle-1 acquisition drifted by (+2, -3) per site
    drift = np.stack([_host_shift(t, -2, 3) for t in tiles])
    st.write_sites(drift, [0, 1, 2, 3], cycle=1, channel=0)
    shifts = np.tile(np.asarray([[2, -3]], np.int32), (4, 1))
    st.write_shifts(shifts, cycle=1)

    jt = get_step("jterator")(st)
    jt.init({"layout": "spatial", "n_devices": 8, "cycle": 1})
    result = jt.run(0)
    assert result["objects"]["mosaic_cells"] == 2

    labels = st.read_labels(None, "mosaic_cells")
    restitched = np.zeros((64, 64), np.int32)
    for i, (sy, sx) in enumerate([(0, 0), (0, 1), (1, 0), (1, 1)]):
        restitched[sy * 32:(sy + 1) * 32, sx * 32:(sx + 1) * 32] = labels[i]
    # golden: the same chain on the ALIGNED stitched mosaic (per-site
    # un-drift, zero-filled edges — what _stitched_channel builds), with
    # the Otsu cut computed over the VALID pixels only (the shift's zero
    # stripes must not feed the histogram)
    aligned = np.zeros((64, 64), np.float32)
    valid = np.zeros((64, 64), bool)
    ones = np.ones((32, 32), np.float32)
    for i, (sy, sx) in enumerate([(0, 0), (0, 1), (1, 0), (1, 1)]):
        aligned[sy * 32:(sy + 1) * 32, sx * 32:(sx + 1) * 32] = _host_shift(
            drift[i].astype(np.float32), 2, -3
        )
        valid[sy * 32:(sy + 1) * 32, sx * 32:(sx + 1) * 32] = (
            _host_shift(ones, 2, -3) > 0
        )
    sm = np.asarray(gaussian_smooth(jnp.asarray(aligned), 1.5))
    golden, n = ndi.label(
        sm > float(np.asarray(otsu_value(jnp.asarray(sm[valid])))),
        structure=np.ones((3, 3)),
    )
    assert n == 2
    np.testing.assert_array_equal(restitched, golden)


def test_spatial_layout_grid_mesh(tmp_path, devices):
    """spatial_grid='auto' picks a 2-D rows x cols tile grid when it
    keeps more devices busy (100-row mosaic on 8 devices: 1-D shrinks to
    5, a 4x2 grid uses all 8) and stays bit-identical to the unsharded
    chain; 'rows' forces the 1-D layout with identical results."""
    import jax.numpy as jnp
    import scipy.ndimage as ndi

    from tmlibrary_tpu.models.experiment import grid_experiment
    from tmlibrary_tpu.ops.smooth import gaussian_smooth
    from tmlibrary_tpu.ops.threshold import otsu_value
    from tmlibrary_tpu.workflow.registry import get_step

    exp = grid_experiment(
        "spatialg", well_rows=1, well_cols=1, sites_per_well=(2, 2),
        channel_names=("DAPI",), site_shape=(50, 50),
    )
    st = ExperimentStore.create(tmp_path / "spatialg_exp", exp)
    rng = np.random.default_rng(17)
    mosaic = rng.normal(300, 20, (100, 100))
    yy, xx = np.mgrid[0:100, 0:100]
    # one blob dead on the four-site junction plus ordinary ones
    for cy, cx in [(50, 50), (18, 70), (82, 25)]:
        mosaic += 4000 * np.exp(-((yy - cy) ** 2 + (xx - cx) ** 2) / (2 * 4.0**2))
    mosaic = np.clip(mosaic, 0, 65535).astype(np.uint16)
    tiles = np.stack([mosaic[0:50, 0:50], mosaic[0:50, 50:100],
                      mosaic[50:100, 0:50], mosaic[50:100, 50:100]])
    st.write_sites(tiles, [0, 1, 2, 3], channel=0)

    jt = get_step("jterator")(st)
    jt.init({"layout": "spatial", "n_devices": 8})
    result = jt.run(0)
    assert result["mesh_shape"] == [4, 2]  # auto chose the grid
    assert result["objects"]["mosaic_cells"] == 3

    labels = st.read_labels(None, "mosaic_cells")
    restitched = np.zeros((100, 100), np.int32)
    restitched[0:50, 0:50] = labels[0]
    restitched[0:50, 50:100] = labels[1]
    restitched[50:100, 0:50] = labels[2]
    restitched[50:100, 50:100] = labels[3]
    sm = np.asarray(gaussian_smooth(jnp.asarray(mosaic, jnp.float32), 1.5))
    golden, n = ndi.label(
        sm > float(np.asarray(otsu_value(jnp.asarray(sm)))),
        structure=np.ones((3, 3)),
    )
    assert n == 3
    np.testing.assert_array_equal(restitched, golden)
    # junction blob: one global id across all four sites
    ids = {int(labels[0][-1, -1]), int(labels[1][-1, 0]),
           int(labels[2][0, -1]), int(labels[3][0, 0])}
    assert len(ids) == 1 and ids != {0}

    # forcing 1-D must give the same labels (and report a rows mesh)
    st2 = ExperimentStore.create(tmp_path / "spatialg_rows", exp)
    st2.write_sites(tiles, [0, 1, 2, 3], channel=0)
    jt2 = get_step("jterator")(st2)
    jt2.init({"layout": "spatial", "n_devices": 8, "spatial_grid": "rows"})
    r2 = jt2.run(0)
    assert r2["mesh_shape"] == [5, 1]
    lab2 = st2.read_labels(None, "mosaic_cells")
    np.testing.assert_array_equal(np.stack(labels), np.stack(lab2))


def test_spatial_layout_secondary_objects(tmp_path, devices):
    """--spatial-secondary-channel: cells grow from mosaic nuclei through
    the actin channel via distributed watershed, keep the nuclei's GLOBAL
    ids, and match the single-device segment_secondary chain exactly."""
    import jax.numpy as jnp

    from tmlibrary_tpu.models.experiment import grid_experiment
    from tmlibrary_tpu.ops.segment_secondary import watershed_from_seeds
    from tmlibrary_tpu.ops.threshold import threshold_otsu
    from tmlibrary_tpu.workflow.registry import get_step

    exp = grid_experiment(
        "spatsec", well_rows=1, well_cols=1, sites_per_well=(2, 2),
        channel_names=("DAPI", "Actin"), site_shape=(50, 50),
    )
    st = ExperimentStore.create(tmp_path / "spatsec_exp", exp)
    rng = np.random.default_rng(19)
    yy, xx = np.mgrid[0:100, 0:100]
    dapi = rng.normal(300, 15, (100, 100))
    actin = rng.normal(400, 15, (100, 100))
    # nuclei (one dead on the 4-site junction) with larger actin halos
    for cy, cx in [(50, 50), (20, 24), (80, 70)]:
        dapi += 4000 * np.exp(-((yy - cy) ** 2 + (xx - cx) ** 2) / (2 * 3.0**2))
        actin += 3000 * np.exp(-((yy - cy) ** 2 + (xx - cx) ** 2) / (2 * 7.0**2))
    dapi = np.clip(dapi, 0, 65535).astype(np.uint16)
    actin = np.clip(actin, 0, 65535).astype(np.uint16)
    for ch, mosaic in ((0, dapi), (1, actin)):
        tiles = np.stack([mosaic[0:50, 0:50], mosaic[0:50, 50:100],
                          mosaic[50:100, 0:50], mosaic[50:100, 50:100]])
        st.write_sites(tiles, [0, 1, 2, 3], channel=ch)

    jt = get_step("jterator")(st)
    jt.init({"layout": "spatial", "n_devices": 8,
             "spatial_secondary_channel": "Actin", "figures": True})
    result = jt.run(0)
    assert result["mesh_shape"] == [4, 2]  # the 2-D watershed branch
    n = result["objects"]["mosaic_cells"]
    assert n == 3
    assert result["objects"]["mosaic_secondary"] == n

    nuc = st.read_labels(None, "mosaic_cells")
    cells = st.read_labels(None, "mosaic_secondary")
    re_nuc = np.zeros((100, 100), np.int32)
    re_cells = np.zeros((100, 100), np.int32)
    for i, (sy, sx) in enumerate([(0, 0), (0, 1), (1, 0), (1, 1)]):
        re_nuc[sy * 50:(sy + 1) * 50, sx * 50:(sx + 1) * 50] = nuc[i]
        re_cells[sy * 50:(sy + 1) * 50, sx * 50:(sx + 1) * 50] = cells[i]

    # single-device golden: same chain on the gathered mosaics
    mask = np.asarray(threshold_otsu(jnp.asarray(actin, jnp.float32)))
    golden = np.asarray(watershed_from_seeds(
        jnp.asarray(actin, jnp.float32), jnp.asarray(re_nuc),
        jnp.asarray(mask), n_levels=32, method="xla",
    ))
    np.testing.assert_array_equal(re_cells, golden)
    # cells contain their nuclei and share ids
    assert ((re_cells == re_nuc) | (re_nuc == 0)).all()
    assert (np.bincount(re_cells.ravel())[1:] >=
            np.bincount(re_nuc.ravel(), minlength=n + 1)[1:]).all()
    # secondary features landed with the same label ids
    feats = st.read_features("mosaic_secondary")
    assert sorted(feats["label"]) == [1, 2, 3]
    # --figures wrote one whole-well overlay per object family
    import cv2
    for fam in ("mosaic_cells", "mosaic_secondary"):
        fig = st.root / "figures" / f"{fam}_well_plate00_00_00.png"
        assert fig.exists()
        img = cv2.imread(str(fig))
        assert img is not None and img.shape == (100, 100, 3)
        assert (img.max(axis=-1) != img.min(axis=-1)).any()  # colored edges
    assert (feats["Morphology_area"].to_numpy() >=
            st.read_features("mosaic_cells")["Morphology_area"].to_numpy()).all()


def test_spatial_layout_divisor_fallback_and_polygons(tmp_path, devices):
    """Mosaic rows not divisible by the requested mesh must shrink the
    mesh (not pad, which would corrupt the Otsu cut), stay bit-identical
    to the unsharded chain, and --as-polygons writes mosaic-frame rings."""
    import jax.numpy as jnp
    import pandas as pd
    import scipy.ndimage as ndi

    from tmlibrary_tpu.models.experiment import grid_experiment
    from tmlibrary_tpu.ops.smooth import gaussian_smooth
    from tmlibrary_tpu.ops.threshold import otsu_value
    from tmlibrary_tpu.workflow.registry import get_step

    exp = grid_experiment(
        "spatial2", well_rows=1, well_cols=1, sites_per_well=(2, 2),
        channel_names=("DAPI",), site_shape=(50, 50),  # 100 rows: 8 -> 5 devs
    )
    st = ExperimentStore.create(tmp_path / "spatial2_exp", exp)
    rng = np.random.default_rng(13)
    mosaic = rng.normal(300, 20, (100, 100))
    yy, xx = np.mgrid[0:100, 0:100]
    for cy, cx in [(50, 50), (20, 75), (80, 20)]:
        mosaic += 4000 * np.exp(-((yy - cy) ** 2 + (xx - cx) ** 2) / (2 * 4.0**2))
    mosaic = np.clip(mosaic, 0, 65535).astype(np.uint16)
    tiles = np.stack([mosaic[0:50, 0:50], mosaic[0:50, 50:100],
                      mosaic[50:100, 0:50], mosaic[50:100, 50:100]])
    st.write_sites(tiles, [0, 1, 2, 3], channel=0)

    jt = get_step("jterator")(st)
    jt.init({"layout": "spatial", "n_devices": 8, "as_polygons": True})
    result = jt.run(0)
    assert result["objects"]["mosaic_cells"] == 3

    labels = st.read_labels(None, "mosaic_cells")
    restitched = np.zeros((100, 100), np.int32)
    restitched[0:50, 0:50] = labels[0]
    restitched[0:50, 50:100] = labels[1]
    restitched[50:100, 0:50] = labels[2]
    restitched[50:100, 50:100] = labels[3]
    sm = np.asarray(gaussian_smooth(jnp.asarray(mosaic, jnp.float32), 1.5))
    golden, n = ndi.label(
        sm > float(np.asarray(otsu_value(jnp.asarray(sm)))),
        structure=np.ones((3, 3)),
    )
    assert n == 3
    np.testing.assert_array_equal(restitched, golden)

    polys = pd.read_parquet(
        st.root / "segmentations"
        / "mosaic_cells_polygons_well_plate00_00_00.parquet"
    )
    assert sorted(polys["label"]) == [1, 2, 3]
    assert (polys["site"] == -1).all()


def test_spatial_layout_applies_illumination_correction(tmp_path, devices):
    """When corilla statistics exist, the spatial layout must segment the
    corrected pixels — same op as the sites layout's preprocess."""
    import jax
    import jax.numpy as jnp
    import scipy.ndimage as ndi

    from tmlibrary_tpu.models.experiment import grid_experiment
    from tmlibrary_tpu.ops import image_ops
    from tmlibrary_tpu.ops.smooth import gaussian_smooth
    from tmlibrary_tpu.ops.threshold import otsu_value
    from tmlibrary_tpu.workflow.registry import get_step

    exp = grid_experiment(
        "spatial3", well_rows=1, well_cols=1, sites_per_well=(2, 2),
        channel_names=("DAPI",), site_shape=(64, 64),
    )
    st = ExperimentStore.create(tmp_path / "spatial3_exp", exp)
    rng = np.random.default_rng(17)
    mosaic = rng.normal(300, 20, (128, 128))
    yy, xx = np.mgrid[0:128, 0:128]
    for cy, cx in [(64, 64), (30, 90)]:
        mosaic += 4000 * np.exp(-((yy - cy) ** 2 + (xx - cx) ** 2) / (2 * 4.0**2))
    mosaic = np.clip(mosaic, 0, 65535).astype(np.uint16)
    tiles = np.stack([mosaic[0:64, 0:64], mosaic[0:64, 64:128],
                      mosaic[64:128, 0:64], mosaic[64:128, 64:128]])
    st.write_sites(tiles, [0, 1, 2, 3], channel=0)
    # synthetic vignetting field in the log domain
    fy, fx = np.mgrid[0:64, 0:64]
    mean_log = (2.5 + 0.002 * (fy + fx)).astype(np.float32)
    std_log = np.full((64, 64), 0.3, np.float32)
    st.write_illumstats({"mean_log": mean_log, "std_log": std_log,
                         "n": np.int64(4)}, channel=0)

    jt = get_step("jterator")(st)
    jt.init({"layout": "spatial", "n_devices": 8})
    jt.run(0)

    labels = st.read_labels(None, "mosaic_cells")
    restitched = np.zeros((128, 128), np.int32)
    restitched[0:64, 0:64] = labels[0]
    restitched[0:64, 64:128] = labels[1]
    restitched[64:128, 0:64] = labels[2]
    restitched[64:128, 64:128] = labels[3]

    corrected = np.asarray(jax.jit(jax.vmap(
        lambda im: image_ops.correct_illumination(
            jnp.asarray(im, jnp.float32),
            jnp.asarray(mean_log), jnp.asarray(std_log))
    ))(jnp.asarray(tiles)))
    golden_mosaic = np.zeros((128, 128), np.float32)
    golden_mosaic[0:64, 0:64] = corrected[0]
    golden_mosaic[0:64, 64:128] = corrected[1]
    golden_mosaic[64:128, 0:64] = corrected[2]
    golden_mosaic[64:128, 64:128] = corrected[3]
    sm = np.asarray(gaussian_smooth(jnp.asarray(golden_mosaic), 1.5))
    golden, n = ndi.label(
        sm > float(np.asarray(otsu_value(jnp.asarray(sm)))),
        structure=np.ones((3, 3)),
    )
    assert n >= 2
    np.testing.assert_array_equal(restitched, golden)


def test_spatial_layout_sparse_well(tmp_path, devices):
    """A well with a missing site (acquisition skip) still segments: the
    absent tile stays zero in the mosaic and contributes no objects."""
    from tmlibrary_tpu.models.experiment import Experiment, Plate, Site, Well
    from tmlibrary_tpu.models.experiment import Channel as Ch
    from tmlibrary_tpu.workflow.registry import get_step

    # 2x2 site grid with (1,1) never acquired
    sites = (Site(y=0, x=0), Site(y=0, x=1), Site(y=1, x=0))
    exp = Experiment(
        name="sparse",
        plates=[Plate(name="p0", wells=(Well(row=0, column=0, sites=sites),))],
        channels=[Ch(index=0, name="DAPI")],
        site_height=64, site_width=64,
    )
    st = ExperimentStore.create(tmp_path / "sparse_exp", exp)
    rng = np.random.default_rng(19)
    tiles = []
    for _ in range(3):
        img = rng.normal(300, 20, (64, 64))
        yy, xx = np.mgrid[0:64, 0:64]
        img += 4000 * np.exp(-((yy - 32) ** 2 + (xx - 32) ** 2) / (2 * 4.0**2))
        tiles.append(np.clip(img, 0, 65535).astype(np.uint16))
    st.write_sites(np.stack(tiles), [0, 1, 2], channel=0)

    jt = get_step("jterator")(st)
    jt.init({"layout": "spatial", "n_devices": 8})
    result = jt.run(0)
    assert result["objects"]["mosaic_cells"] == 3
    labels = st.read_labels(None, "mosaic_cells")
    assert labels.shape == (3, 64, 64)
    assert all(labels[b].max() > 0 for b in range(3))


def test_spatial_layout_engine_resume(tmp_path, devices):
    """Engine resume skips completed spatial batches like site batches."""
    from tmlibrary_tpu.models.experiment import grid_experiment
    from tmlibrary_tpu.workflow.engine import RunLedger
    from tmlibrary_tpu.workflow.registry import get_step

    exp = grid_experiment(
        "sres", well_rows=1, well_cols=2, sites_per_well=(1, 2),
        channel_names=("DAPI",), site_shape=(64, 64),
    )
    st = ExperimentStore.create(tmp_path / "sres_exp", exp)
    rng = np.random.default_rng(23)
    imgs = []
    for _ in range(4):
        img = rng.normal(300, 20, (64, 64))
        yy, xx = np.mgrid[0:64, 0:64]
        img += 4000 * np.exp(-((yy - 20) ** 2 + (xx - 40) ** 2) / (2 * 4.0**2))
        imgs.append(np.clip(img, 0, 65535).astype(np.uint16))
    st.write_sites(np.stack(imgs), [0, 1, 2, 3], channel=0)

    jt = get_step("jterator")(st)
    batches = jt.init({"layout": "spatial", "n_devices": 8})
    assert len(batches) == 2  # one per well
    # run batch 0, record it in a ledger, then resume-style: only batch 1
    ledger = RunLedger(st.workflow_dir / "ledger.jsonl")
    r0 = jt.run(0)
    ledger.append(step="jterator", event="batch_done", batch=0, result=r0)
    done = ledger.completed_batches("jterator")
    pending = [i for i in jt.list_batches() if i not in done]
    assert pending == [1]
    r1 = jt.run(1)
    assert r1["layout"] == "spatial"
    assert st.read_labels(None, "mosaic_cells").shape[0] == 4


def test_spatial_layout_multichannel_intensity(tmp_path, devices):
    """All channels get per-global-object intensity columns, not just the
    segmentation channel."""
    from tmlibrary_tpu.models.experiment import grid_experiment
    from tmlibrary_tpu.workflow.registry import get_step

    exp = grid_experiment(
        "spatmc", well_rows=1, well_cols=1, sites_per_well=(2, 2),
        channel_names=("DAPI", "GFP"), site_shape=(64, 64),
    )
    st = ExperimentStore.create(tmp_path / "spatmc_exp", exp)
    rng = np.random.default_rng(31)
    yy, xx = np.mgrid[0:128, 0:128]
    dapi = rng.normal(300, 20, (128, 128))
    dapi += 4000 * np.exp(-((yy - 64) ** 2 + (xx - 64) ** 2) / (2 * 4.0**2))
    dapi = np.clip(dapi, 0, 65535).astype(np.uint16)
    gfp = rng.integers(100, 900, (128, 128)).astype(np.uint16)
    for ch, mos in ((0, dapi), (1, gfp)):
        st.write_sites(np.stack([mos[:64, :64], mos[:64, 64:],
                                 mos[64:, :64], mos[64:, 64:]]),
                       [0, 1, 2, 3], channel=ch)

    jt = get_step("jterator")(st)
    jt.init({"layout": "spatial", "n_devices": 8})
    jt.run(0)
    feats = st.read_features("mosaic_cells")
    assert len(feats) == 1
    labels = st.read_labels(None, "mosaic_cells")
    full = np.zeros((128, 128), np.int32)
    full[:64, :64] = labels[0]; full[:64, 64:] = labels[1]
    full[64:, :64] = labels[2]; full[64:, 64:] = labels[3]
    row = feats.iloc[0]
    for ch_name, mos in (("DAPI", dapi), ("GFP", gfp)):
        sel = mos[full == 1].astype(np.float64)
        np.testing.assert_allclose(
            row[f"Intensity_mean_{ch_name}"], sel.mean(), rtol=1e-6
        )
        np.testing.assert_allclose(row[f"Intensity_max_{ch_name}"], sel.max())
        np.testing.assert_allclose(row[f"Intensity_min_{ch_name}"], sel.min())
    # Zernike shape moments present and sane (Z_00 of a blob ~ 1/pi)
    assert abs(row["Zernike_0_0"] - 1.0 / np.pi) < 0.05
