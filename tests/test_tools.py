import numpy as np
import pandas as pd
import pytest

from tmlibrary_tpu.errors import NotSupportedError, RegistryError
from tmlibrary_tpu.models.experiment import grid_experiment
from tmlibrary_tpu.models.store import ExperimentStore
from tmlibrary_tpu.tools import ToolRequestManager, get_tool, list_tools


@pytest.fixture
def store_with_features(tmp_path, rng):
    """Store with a synthetic two-population feature table."""
    exp = grid_experiment(name="tools", well_rows=1, well_cols=1,
                          sites_per_well=(2, 2), site_shape=(16, 16))
    store = ExperimentStore.create(tmp_path / "exp", exp)
    rows = []
    for site in range(4):
        for label in range(1, 21):
            # population A: small dim objects; population B: large bright
            pop_b = label > 10
            rows.append(
                {
                    "site_index": site,
                    "plate": "plate00",
                    "well_row": 0,
                    "well_col": 0,
                    "site_y": site // 2,
                    "site_x": site % 2,
                    "label": label,
                    "Morphology_area": rng.normal(400 if pop_b else 80, 10),
                    "Intensity_mean_DAPI": rng.normal(3000 if pop_b else 500, 50),
                }
            )
    store.append_features("nuclei", pd.DataFrame(rows), shard="batch_000")
    return store


def test_registry():
    assert set(list_tools()) >= {"classification", "clustering", "heatmap"}
    with pytest.raises(RegistryError):
        get_tool("nope")


def test_clustering_separates_populations(store_with_features):
    mgr = ToolRequestManager(store_with_features)
    result = mgr.submit("clustering", {"objects_name": "nuclei", "k": 2})
    assert result.layer_type == "categorical"
    v = result.values
    a = v[v["label"] <= 10]["value"]
    b = v[v["label"] > 10]["value"]
    # each true population lands in one cluster
    assert a.nunique() == 1 and b.nunique() == 1
    assert a.iloc[0] != b.iloc[0]
    # result persisted
    results = mgr.list_results()
    assert len(results) == 1 and results[0]["tool"] == "clustering"


@pytest.mark.parametrize("method", ["logreg", "svm", "randomforest"])
def test_classification_methods(store_with_features, method):
    mgr = ToolRequestManager(store_with_features)
    examples = [
        {"site_index": 0, "label": 1, "class": "dim"},
        {"site_index": 0, "label": 2, "class": "dim"},
        {"site_index": 1, "label": 3, "class": "dim"},
        {"site_index": 0, "label": 11, "class": "bright"},
        {"site_index": 0, "label": 12, "class": "bright"},
        {"site_index": 1, "label": 13, "class": "bright"},
    ]
    result = mgr.submit(
        "classification",
        {"objects_name": "nuclei", "method": method, "training_examples": examples},
    )
    v = result.values
    classes = result.attributes["classes"]
    # population A (labels 1..10) should classify 'dim', B 'bright'
    pred_a = [classes[i] for i in v[v["label"] <= 10]["value"]]
    pred_b = [classes[i] for i in v[v["label"] > 10]["value"]]
    assert np.mean([p == "dim" for p in pred_a]) > 0.95
    assert np.mean([p == "bright" for p in pred_b]) > 0.95


def test_classification_requires_examples(store_with_features):
    mgr = ToolRequestManager(store_with_features)
    with pytest.raises(NotSupportedError):
        mgr.submit("classification", {"objects_name": "nuclei"})


def test_heatmap(store_with_features):
    mgr = ToolRequestManager(store_with_features)
    result = mgr.submit(
        "heatmap", {"objects_name": "nuclei", "feature": "Intensity_mean_DAPI"}
    )
    assert result.layer_type == "continuous"
    assert result.attributes["max"] > result.attributes["min"]
    assert len(result.values) == 80


def test_heatmap_unknown_feature(store_with_features):
    mgr = ToolRequestManager(store_with_features)
    with pytest.raises(NotSupportedError, match="not found"):
        mgr.submit("heatmap", {"objects_name": "nuclei", "feature": "Bogus"})


def test_tool_cli(store_with_features, capsys):
    """tmx tool submit/list/available (reference tm_tool CLI)."""
    import json

    from tmlibrary_tpu.cli import main

    root = str(store_with_features.root)
    assert main(["tool", "available"]) == 0
    out = capsys.readouterr().out
    assert "clustering" in out and "classification" in out

    assert main([
        "tool", "submit", "--root", root, "--name", "clustering",
        "--payload", '{"objects_name": "nuclei", "k": 2}',
    ]) == 0
    submitted = json.loads(capsys.readouterr().out)
    assert submitted["tool"] == "clustering"
    assert submitted["n_objects"] == 80

    assert main(["tool", "list", "--root", root]) == 0
    listed = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    assert len(listed) == 1 and listed[0]["tool"] == "clustering"


def test_device_trace_writes_profile(tmp_path):
    """device_trace produces a TensorBoard-compatible trace directory."""
    import jax.numpy as jnp

    from tmlibrary_tpu.profiling import device_trace

    with device_trace(tmp_path / "prof"):
        (jnp.arange(64.0) ** 2).sum().block_until_ready()
    files = list((tmp_path / "prof").rglob("*"))
    assert any(f.is_file() for f in files)


def test_device_trace_none_is_noop():
    from tmlibrary_tpu.profiling import device_trace

    with device_trace(None):
        pass


def test_request_lifecycle_sync(store_with_features):
    """Synchronous submit still records the full request lifecycle."""
    mgr = ToolRequestManager(store_with_features)
    mgr.submit("clustering", {"objects_name": "nuclei", "k": 2})
    reqs = mgr.list_requests()
    assert len(reqs) == 1
    req = reqs[0]
    assert req["state"] == "done"
    assert req["tool"] == "clustering"
    assert req["n_objects"] == 80
    assert req["finished_at"] >= req["started_at"] >= req["submitted_at"]
    # status() round-trips by id and keeps the payload
    full = mgr.status(req["request"])
    assert full["payload"] == {"objects_name": "nuclei", "k": 2}


def test_request_lifecycle_failed(store_with_features):
    mgr = ToolRequestManager(store_with_features)
    with pytest.raises(Exception):
        mgr.submit("heatmap", {"objects_name": "nuclei", "feature": "Bogus"})
    (req,) = mgr.list_requests()
    assert req["state"] == "failed"
    assert "Bogus" in req["error"]
    # unknown tool fails at submit, before any request dir exists
    with pytest.raises(RegistryError):
        mgr.create_request("nope", {})
    assert len(mgr.list_requests()) == 1


@pytest.mark.parametrize("holds,expected", [(True, "cpu"), (False, "tpu")])
def test_background_child_takes_cpu_when_caller_holds_the_chip(
        store_with_features, monkeypatch, holds, expected):
    """A chip belongs to one process: a caller that holds it starts the
    detached child on the CPU platform, a caller that holds none leaves
    the child its inherited platform."""
    import subprocess

    from tmlibrary_tpu.tools import base

    spawned = {}
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    monkeypatch.setattr(base, "_caller_holds_accelerator", lambda: holds)
    monkeypatch.setattr(
        subprocess, "Popen",
        lambda argv, **kw: spawned.update(argv=argv, env=kw["env"]))
    request_id = ToolRequestManager(store_with_features).submit_async(
        "clustering", {"objects_name": "nuclei", "k": 2})
    assert spawned["argv"][-1] == request_id
    assert spawned["env"]["JAX_PLATFORMS"] == expected


def test_caller_holds_accelerator_is_false_on_the_cpu_suite():
    import jax

    from tmlibrary_tpu.tools.base import _caller_holds_accelerator

    jax.devices()  # backend initialised, but it is the CPU's
    assert _caller_holds_accelerator() is False


def test_request_background_end_to_end(store_with_features):
    """--background spawns a detached job whose state transitions to done
    (reference ToolJob fan-out)."""
    import time

    mgr = ToolRequestManager(store_with_features)
    request_id = mgr.submit_async("clustering", {"objects_name": "nuclei", "k": 2})
    assert mgr.status(request_id)["state"] in ("submitted", "running", "done")
    deadline = time.time() + 120
    while time.time() < deadline:
        state = mgr.status(request_id)["state"]
        if state in ("done", "failed"):
            break
        time.sleep(1)
    final = mgr.status(request_id)
    assert final["state"] == "done", final
    assert final["n_objects"] == 80
    # the detached job captured its log
    assert (store_with_features.tools_dir / request_id / "tool.log").exists()
    # and the result itself is loadable
    results = mgr.list_results()
    assert any(r["request"] == request_id for r in results)


def test_cli_tool_status_and_workflow_status(store_with_features, capsys):
    import json as _json

    from tmlibrary_tpu.cli import main

    root = str(store_with_features.root)
    assert main([
        "tool", "submit", "--root", root, "--name", "clustering",
        "--payload", '{"objects_name": "nuclei", "k": 2}',
    ]) == 0
    capsys.readouterr()
    assert main(["tool", "list", "--root", root]) == 0
    (line,) = capsys.readouterr().out.strip().splitlines()
    entry = _json.loads(line)
    assert entry["state"] == "done"
    assert main(["tool", "status", "--root", root,
                 "--request", entry["request"]]) == 0
    status = _json.loads(capsys.readouterr().out)
    assert status["state"] == "done" and "payload" in status


def test_same_millisecond_requests_get_distinct_ids(store_with_features,
                                                   monkeypatch):
    import time as _time

    mgr = ToolRequestManager(store_with_features)
    monkeypatch.setattr(_time, "time", lambda: 1234.567)
    a = mgr.create_request("clustering", {"k": 2})
    b = mgr.create_request("clustering", {"k": 3})
    assert a != b
    assert mgr.status(a)["payload"] == {"k": 2}
    assert mgr.status(b)["payload"] == {"k": 3}


def test_status_of_pre_ledger_result_dir(store_with_features):
    d = store_with_features.tools_dir / "clustering_legacy"
    d.mkdir(parents=True)
    (d / "result.json").write_text('{"tool": "clustering"}')
    mgr = ToolRequestManager(store_with_features)
    assert mgr.status("clustering_legacy") == {
        "request": "clustering_legacy", "state": "done"
    }


def test_tools_on_spatial_mosaic_features(tmp_path, devices):
    """Tools compose with the spatial layout's ragged per-well feature
    tables (site_index -1, global labels): heatmap + k-means clustering
    run unchanged on mosaic_cells features."""
    import numpy as np

    from tmlibrary_tpu.models.experiment import grid_experiment
    from tmlibrary_tpu.models.store import ExperimentStore
    from tmlibrary_tpu.tools.base import ToolRequestManager
    from tmlibrary_tpu.workflow.registry import get_step

    exp = grid_experiment(
        "tools_sp", well_rows=1, well_cols=1, sites_per_well=(2, 2),
        channel_names=("DAPI",), site_shape=(32, 32),
    )
    st = ExperimentStore.create(tmp_path / "tools_sp_exp", exp)
    rng = np.random.default_rng(5)
    yy, xx = np.mgrid[0:64, 0:64]
    mosaic = rng.normal(300, 15, (64, 64))
    # two small dim nuclei + two large bright ones -> 2 k-means clusters
    for cy, cx, amp, s2 in [(16, 16, 5000, 4.0), (48, 16, 5000, 4.0),
                            (16, 48, 5000, 30.0), (48, 48, 5000, 30.0)]:
        mosaic += amp * np.exp(-((yy - cy) ** 2 + (xx - cx) ** 2) / (2 * s2))
    mosaic = np.clip(mosaic, 0, 65535).astype(np.uint16)
    st.write_sites(np.stack([mosaic[:32, :32], mosaic[:32, 32:],
                             mosaic[32:, :32], mosaic[32:, 32:]]),
                   [0, 1, 2, 3], channel=0)
    jt = get_step("jterator")(st)
    jt.init({"layout": "spatial", "n_devices": 8})
    assert jt.run(0)["objects"]["mosaic_cells"] == 4

    mgr = ToolRequestManager(st)
    heat = mgr.submit("heatmap", {"objects_name": "mosaic_cells",
                                  "feature": "Morphology_area"})
    assert heat.layer_type == "continuous"
    assert len(heat.values) == 4
    assert (heat.values["site_index"] == -1).all()  # mosaic frame
    assert heat.attributes["max"] > heat.attributes["min"]

    clus = mgr.submit("clustering", {
        "objects_name": "mosaic_cells", "k": 2,
        "features": ["Morphology_area", "Intensity_mean_DAPI"],
    })
    labels_by_obj = dict(zip(clus.values["label"], clus.values["value"]))
    # the two big/bright objects cluster together, apart from the small
    feats = st.read_features("mosaic_cells").sort_values("label")
    order = np.argsort(feats["Morphology_area"].to_numpy())
    small = [int(feats.iloc[i]["label"]) for i in order[:2]]
    big = [int(feats.iloc[i]["label"]) for i in order[2:]]
    assert labels_by_obj[small[0]] == labels_by_obj[small[1]]
    assert labels_by_obj[big[0]] == labels_by_obj[big[1]]
    assert labels_by_obj[small[0]] != labels_by_obj[big[0]]


def test_classification_reports_training_metrics(store_with_features):
    """Training accuracy and per-class counts land in
    ToolResult.attributes (round-3 VERDICT next-step #8) so degenerate
    training sets are visible in the result."""
    mgr = ToolRequestManager(store_with_features)
    examples = [
        {"site_index": 0, "label": 1, "class": "dim"},
        {"site_index": 0, "label": 2, "class": "dim"},
        {"site_index": 0, "label": 11, "class": "bright"},
        {"site_index": 1, "label": 13, "class": "bright"},
        {"site_index": 2, "label": 14, "class": "bright"},
    ]
    result = mgr.submit(
        "classification",
        {"objects_name": "nuclei", "training_examples": examples},
    )
    attrs = result.attributes
    assert attrs["training_accuracy"] == 1.0  # well-separated populations
    assert attrs["class_counts"]["training"] == {"dim": 2, "bright": 3}
    pred = attrs["class_counts"]["predicted"]
    assert pred["dim"] + pred["bright"] == 80
    assert 35 <= pred["dim"] <= 45  # 40 true dims across 4 sites


def test_classification_select_k_best(store_with_features, rng):
    """select_k_best keeps the most class-separating features: with two
    informative columns and one pure-noise column, k=2 must drop the
    noise and still classify perfectly."""
    # add a noise feature column to the persisted table
    table = store_with_features.read_features("nuclei")
    table["Noise_feature"] = rng.normal(0, 1, len(table))
    store_with_features.append_features("nuclei", table, shard="batch_000")

    mgr = ToolRequestManager(store_with_features)
    examples = [
        {"site_index": 0, "label": l, "class": "dim"} for l in (1, 2, 3)
    ] + [
        {"site_index": 0, "label": l, "class": "bright"} for l in (11, 12, 13)
    ]
    result = mgr.submit(
        "classification",
        {"objects_name": "nuclei", "training_examples": examples,
         "select_k_best": 2},
    )
    kept = result.attributes["features"]
    assert len(kept) == 2 and "Noise_feature" not in kept
    assert result.attributes["training_accuracy"] == 1.0


def test_feature_matrix_sanitizes_nan(store_with_features):
    """A NaN feature value (degenerate-object solidity) must not poison
    the standardized matrix."""
    table = store_with_features.read_features("nuclei")
    table.loc[0, "Morphology_area"] = np.nan
    store_with_features.append_features("nuclei", table, shard="batch_000")
    tool = get_tool("classification")(store_with_features)
    ids, x, cols = tool.load_feature_matrix("nuclei")
    assert np.isfinite(x).all()
    # imputed with the column finite mean -> z of ~0, not an outlier
    assert abs(x[0, cols.index("Morphology_area")]) < 0.05


def test_label_layer_export_site_values(store_with_features):
    """Viewer-style per-site export: values image carries each object's
    mapped value on its pixels, background 0."""
    # persist tiny label images: site 0 has objects 1 and 11
    labels = np.zeros((1, 16, 16), np.int32)
    labels[0, 2:5, 2:5] = 1
    labels[0, 9:12, 9:12] = 11
    store_with_features.write_labels(labels, [0], "nuclei")

    mgr = ToolRequestManager(store_with_features)
    result = mgr.submit(
        "classification",
        {"objects_name": "nuclei", "training_examples": [
            {"site_index": 0, "label": 1, "class": "dim"},
            {"site_index": 0, "label": 11, "class": "bright"},
        ]},
    )
    layer = result.label_layer()
    out = layer.export_site_values(
        store_with_features, store_with_features.root / "layer_export"
    )
    by_site = {p.name: p for p in out}
    assert "site_00000.npz" in by_site
    data = np.load(by_site["site_00000.npz"])
    np.testing.assert_array_equal(data["labels"], labels[0])
    v = result.values
    want_1 = float(v[(v["site_index"] == 0) & (v["label"] == 1)]["value"].iloc[0])
    want_11 = float(v[(v["site_index"] == 0) & (v["label"] == 11)]["value"].iloc[0])
    assert data["values"][3, 3] == want_1
    assert data["values"][10, 10] == want_11
    # class id 0 is a real value, so background is NaN, not 0
    assert {want_1, want_11} == {0.0, 1.0}
    assert np.isnan(data["values"][0, 0])


def test_kbest_keeps_perfect_separator():
    """A feature constant within each class but different between them
    is a PERFECT separator (F = inf), never scored below noise."""
    from tmlibrary_tpu.tools.classification import _kbest_anova

    rng = np.random.default_rng(5)
    n = 20
    y = np.repeat(np.asarray([0, 1], np.int32), n // 2)
    perfect = y.astype(np.float64)  # zero within-class variance
    noise = rng.normal(0, 1, (n, 2))
    x = np.column_stack([noise[:, 0], perfect, noise[:, 1]])
    keep = _kbest_anova(x, y, 2, 1)
    assert list(keep) == [1]
    # a fully constant column still scores 0 (not selected over noise)
    x2 = np.column_stack([np.ones(n), perfect])
    assert list(_kbest_anova(x2, y, 2, 1)) == [1]


def test_heatmap_plate_plot_and_robust_window(store_with_features):
    mgr = ToolRequestManager(store_with_features)
    result = mgr.submit(
        "heatmap", {"objects_name": "nuclei", "feature": "Morphology_area"}
    )
    attrs = result.attributes
    assert attrs["n_objects"] == 80
    assert attrs["min"] <= attrs["p01"] < attrs["p99"] <= attrs["max"]
    (plot,) = result.plots
    assert plot.type == "plate_heatmap"
    wells = plot.figure["wells"]
    assert len(wells) == 1  # one well in the fixture
    table = store_with_features.read_features("nuclei")
    np.testing.assert_allclose(
        wells[0]["mean"], table["Morphology_area"].mean()
    )


def test_heatmap_emits_all_nan_well_with_null_mean(tmp_path, rng):
    """An all-NaN well (every object's feature degenerate) stays in the
    plate_heatmap wells list with ``mean: null`` — dropping it would be
    indistinguishable from a well outside the plate (round-4 advisor)."""
    exp = grid_experiment(name="nanwell", well_rows=1, well_cols=2,
                          sites_per_well=(1, 1), site_shape=(16, 16))
    store = ExperimentStore.create(tmp_path / "exp", exp)
    rows = []
    for well_col in (0, 1):
        for label in range(1, 4):
            rows.append({
                "site_index": well_col,
                "plate": "plate00",
                "well_row": 0,
                "well_col": well_col,
                "site_y": 0,
                "site_x": 0,
                "label": label,
                "Morphology_area": np.nan if well_col else 100.0 + label,
            })
    store.append_features("nuclei", pd.DataFrame(rows), shard="batch_000")
    result = ToolRequestManager(store).submit(
        "heatmap", {"objects_name": "nuclei", "feature": "Morphology_area"}
    )
    (plot,) = result.plots
    wells = {w["well_col"]: w["mean"] for w in plot.figure["wells"]}
    assert wells[1] is None
    np.testing.assert_allclose(wells[0], 102.0)
    # and the serialized payload is strict JSON (no literal NaN)
    import json

    json.loads(json.dumps(plot.figure))


def test_clustering_reports_sizes_and_inertia(store_with_features):
    mgr = ToolRequestManager(store_with_features)
    result = mgr.submit("clustering", {"objects_name": "nuclei", "k": 2})
    attrs = result.attributes
    sizes = attrs["cluster_sizes"]
    assert sorted(sizes.values()) == [40, 40]  # two equal populations
    assert attrs["inertia"] > 0
