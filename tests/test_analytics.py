"""Analytics tier: feature store, ops, spatial stats, query serving.

Covers ISSUE 15 end to end: the columnar feature store (build, digest,
staleness rebuild), the device ops against brute-force references, the
integral-image spatial index, the digest-keyed query cache (one-shot CLI
and the serve daemon's ``kind: query`` jobs), ``ToolResult`` save/load
round-trips, the deterministic k-means++ seeding rewrite, and the
classic tools (classification, heatmap) reading through the store.
"""

import json

import numpy as np
import pandas as pd
import pytest

from tmlibrary_tpu import serve, telemetry
from tmlibrary_tpu.analytics import ops, spatial
from tmlibrary_tpu.analytics.query import query_key, run_query
from tmlibrary_tpu.analytics.store import FeatureStore, analytics_dir
from tmlibrary_tpu.errors import NotSupportedError, RegistryError
from tmlibrary_tpu.models.experiment import grid_experiment
from tmlibrary_tpu.models.store import ExperimentStore
from tmlibrary_tpu.tools import ToolRequestManager
from tmlibrary_tpu.tools.base import Plot, ToolResult
from tmlibrary_tpu.tools.clustering import kmeans
from tmlibrary_tpu.workflow.admission import JobSpec
from tmlibrary_tpu.workflow.engine import RunLedger


@pytest.fixture(autouse=True)
def _fresh_registry():
    telemetry.reset_registry(enabled=True)
    yield
    telemetry.reset_registry()


@pytest.fixture
def astore(tmp_path, rng):
    """Experiment store with a two-population feature table including
    measured centroids (so spatial queries have positions)."""
    exp = grid_experiment(name="analytics", well_rows=1, well_cols=1,
                          sites_per_well=(2, 2), site_shape=(16, 16))
    store = ExperimentStore.create(tmp_path / "exp", exp)
    store.append_features("nuclei", _feature_table(rng), shard="batch_000")
    return store


def _feature_table(rng, sites=range(4), labels=range(1, 21)):
    rows = []
    for site in sites:
        for label in labels:
            pop_b = label > 10
            rows.append({
                "site_index": site,
                "plate": "plate00",
                "well_row": 0,
                "well_col": 0,
                "site_y": site // 2,
                "site_x": site % 2,
                "label": label,
                "Morphology_area": rng.normal(400 if pop_b else 80, 10),
                "Intensity_mean_DAPI":
                    rng.normal(3000 if pop_b else 500, 50),
                # bright objects sit in the right half of the site
                "Morphology_centroid_y": rng.uniform(2, 14),
                "Morphology_centroid_x":
                    rng.uniform(9, 15) if pop_b else rng.uniform(1, 7),
            })
    return pd.DataFrame(rows)


# ============================================================ feature store
def test_store_build_views_and_reuse(astore):
    fs = FeatureStore.ensure(astore, "nuclei")
    assert fs.n_objects == 80
    assert set(fs.features) == {
        "Morphology_area", "Intensity_mean_DAPI",
        "Morphology_centroid_y", "Morphology_centroid_x",
    }
    assert fs.matrix().shape == (80, 4)
    assert fs.matrix().dtype == np.float32
    ids = fs.identity()
    assert list(ids.columns) == ["site_index", "label", "plate",
                                 "well_row", "well_col"]
    # column() returns the raw (float32) values in shard order
    raw = astore.read_features("nuclei")
    np.testing.assert_array_equal(
        fs.column("Morphology_area"),
        raw["Morphology_area"].to_numpy(np.float32))
    # centroids come from the renamed Morphology columns
    cents = fs.centroids()
    assert cents.shape == (80, 2)
    np.testing.assert_array_equal(
        cents[:, 0], raw["Morphology_centroid_y"].to_numpy(np.float32))
    # a second ensure() reuses the build (same built_at, same digest)
    fs2 = FeatureStore.ensure(astore, "nuclei")
    assert fs2.digest == fs.digest
    assert fs2.meta["built_at"] == fs.meta["built_at"]


def test_store_unknown_feature_contracts(astore):
    fs = FeatureStore.ensure(astore, "nuclei")
    with pytest.raises(RegistryError):
        fs.column("Intensity_nope")
    with pytest.raises(RegistryError, match="features not found"):
        fs.select(["Morphology_area", "Intensity_nope"])


def test_store_staleness_rebuild_on_new_shard(astore, rng):
    fs = FeatureStore.ensure(astore, "nuclei")
    astore.append_features(
        "nuclei", _feature_table(rng, sites=[4], labels=range(1, 6)),
        shard="batch_001")
    fs2 = FeatureStore.ensure(astore, "nuclei")
    assert fs2.n_objects == 85
    assert fs2.digest != fs.digest


def test_standardized_zero_mean_unit_var_and_nan_imputation(tmp_path, rng):
    exp = grid_experiment(name="nan", well_rows=1, well_cols=1,
                          sites_per_well=(1, 1), site_shape=(8, 8))
    store = ExperimentStore.create(tmp_path / "exp", exp)
    table = _feature_table(rng, sites=[0])
    table.loc[3, "Morphology_area"] = np.nan
    table.loc[5, "Intensity_mean_DAPI"] = np.inf
    store.append_features("nuclei", table, shard="s0")
    fs = FeatureStore.ensure(store, "nuclei")
    ids, x, cols = fs.standardized(["Morphology_area",
                                    "Intensity_mean_DAPI"])
    assert cols == ["Morphology_area", "Intensity_mean_DAPI"]
    assert np.isfinite(x).all()
    np.testing.assert_allclose(x.mean(axis=0), 0.0, atol=1e-5)
    np.testing.assert_allclose(x.std(axis=0), 1.0, atol=1e-4)
    # an imputed cell sits at the finite mean -> exactly 0 after z-score
    assert abs(x[3, 0]) < 1e-5


# ===================================================================== ops
def test_knn_matches_bruteforce_and_tile_invariant(rng):
    x = rng.normal(size=(60, 5)).astype(np.float32)
    idx, dist = ops.knn(x, 5)
    # numpy reference: exact pairwise distances, self excluded
    d2 = ((x[:, None, :] - x[None, :, :]) ** 2).sum(-1)
    np.fill_diagonal(d2, np.inf)
    ref = np.argsort(d2, axis=1, kind="stable")[:, :5]
    assert (idx == ref).mean() > 0.99  # ties may legitimately swap
    np.testing.assert_allclose(
        dist, np.sqrt(np.take_along_axis(d2, idx, axis=1)),
        rtol=1e-4, atol=1e-4)
    # tiling partitions the query axis only: same answers at any tile
    idx7, dist7 = ops.knn(x, 5, tile=7)
    np.testing.assert_array_equal(idx7, idx)
    # distances are sqrt(|q|^2 + |x|^2 - 2 q.x) in float32: a different
    # tile gives the matmul another blocking and summation order, so
    # each squared distance (three terms of magnitude <= ~30 here)
    # carries a few float32 ulps of rounding, ~30 * 1.2e-7 * 3 = 1e-5
    # absolute; measured 3.6e-7 on jax 0.9.0 / XLA:CPU
    np.testing.assert_allclose(dist7, dist, rtol=0, atol=1e-5)
    # explicit queries keep their own rows (no self-exclusion)
    qidx, qdist = ops.knn(x, 1, queries=x[:4])
    np.testing.assert_array_equal(qidx[:, 0], np.arange(4))
    np.testing.assert_allclose(qdist[:, 0], 0.0, atol=1e-5)


def test_knn_k_clamped_to_population(rng):
    x = rng.normal(size=(4, 3)).astype(np.float32)
    idx, dist = ops.knn(x, 10)
    assert idx.shape == (4, 3)  # self excluded


def test_pca_recovers_dominant_subspace(rng):
    # rank-2 signal + tiny noise: the two components must explain ~all
    # variance and repeated runs must agree bit for bit
    basis = np.linalg.qr(rng.normal(size=(8, 2)))[0].T  # (2, 8)
    coef = rng.normal(size=(200, 2)) * np.array([5.0, 2.0])
    x = (coef @ basis + rng.normal(size=(200, 8)) * 0.01).astype(np.float32)
    scores, comps, ratio = ops.pca(x, n_components=2)
    assert scores.shape == (200, 2) and comps.shape == (2, 8)
    assert ratio.sum() > 0.99
    np.testing.assert_allclose(comps @ comps.T, np.eye(2), atol=1e-4)
    # recovered components span the planted basis
    overlap = np.abs(comps @ basis.T)
    np.testing.assert_allclose(np.sort(overlap.max(axis=1)),
                               [1.0, 1.0], atol=1e-3)
    scores2, comps2, ratio2 = ops.pca(x, n_components=2)
    np.testing.assert_array_equal(scores, scores2)
    np.testing.assert_array_equal(comps, comps2)


def test_spectral_embedding_deterministic_and_separates_blobs(rng):
    a = rng.normal(size=(30, 4)).astype(np.float32)
    b = (rng.normal(size=(30, 4)) + 40.0).astype(np.float32)
    x = np.concatenate([a, b])
    emb = ops.spectral_embedding(x, n_components=2, k=5)
    assert emb.shape == (60, 2) and np.isfinite(emb).all()
    np.testing.assert_array_equal(
        emb, ops.spectral_embedding(x, n_components=2, k=5))
    # the kNN graph is disconnected between the blobs, so the first
    # non-trivial eigenvector separates them linearly
    gap = abs(emb[:30, 0].mean() - emb[30:, 0].mean())
    spread = max(emb[:30, 0].std(), emb[30:, 0].std())
    assert gap > 5 * spread


# ================================================================= spatial
def test_spatial_window_counts_match_bruteforce(rng):
    n = 400
    site_index = rng.integers(0, 3, size=n)
    cents = rng.uniform(0, 100, size=(n, 2))
    index = spatial.build_index(site_index, cents, grid=16)
    wins = np.array([
        [s, y0, x0, y0 + h, x0 + w]
        for s in range(3)
        for (y0, x0, h, w) in [(0, 0, 16, 16), (2, 3, 5, 7), (10, 0, 6, 16)]
    ])
    counts = index.window_counts(wins)
    for (s, y0, x0, y1, x1), got in zip(wins, counts):
        inside = ((index.site_row == s)
                  & (index.bins[:, 0] >= y0) & (index.bins[:, 0] < y1)
                  & (index.bins[:, 1] >= x0) & (index.bins[:, 1] < x1))
        assert got == inside.sum()


def test_spatial_density_and_enrichment(rng):
    # one dense blob + sparse background in a single site
    blob = rng.uniform(40, 50, size=(120, 2))
    bg = rng.uniform(0, 100, size=(40, 2))
    cents = np.concatenate([blob, bg])
    site_index = np.zeros(len(cents), np.int64)
    index = spatial.build_index(site_index, cents, grid=20)
    dens = spatial.density(index, radius_bins=2)
    assert dens[:120].mean() > 3 * dens[120:].mean()
    # mark the blob: its neighborhoods are enriched, the background not
    mark = np.concatenate([np.ones(120), np.zeros(40)]).astype(np.float32)
    mindex = spatial.build_index(site_index, cents, mark=mark, grid=20)
    enr = spatial.enrichment(mindex, radius_bins=2)
    assert np.median(enr[:120]) > 1.1
    assert np.median(enr[:120]) > np.median(enr[120:])
    with pytest.raises(ValueError, match="marked"):
        spatial.enrichment(index)


def test_spatial_rejects_empty_centroids():
    with pytest.raises(ValueError, match="non-empty"):
        spatial.build_index(np.array([], np.int64),
                            np.zeros((0, 2), np.float32))


# ============================================================ query + cache
def test_query_cache_hit_is_bit_identical(astore):
    payload = {"tool": "knn", "objects_name": "nuclei", "k": 3}
    s1 = run_query(astore, payload)
    assert s1["cache"] == "miss"
    assert s1["key"] == query_key(s1["store_digest"], payload)
    s2 = run_query(astore, payload)
    assert s2["cache"] == "hit" and s2["key"] == s1["key"]
    r1 = ToolResult.load(s1["result_dir"])
    r2 = ToolResult.load(s2["result_dir"])
    pd.testing.assert_frame_equal(r1.values, r2.values, check_exact=True)
    assert s2["attributes"] == s1["attributes"]
    reg = telemetry.get_registry()
    assert reg.counter("tmx_analytics_queries_total",
                       tool="knn", cache="miss").value == 1
    assert reg.counter("tmx_analytics_queries_total",
                       tool="knn", cache="hit").value == 1
    assert reg.counter("tmx_analytics_cache_hits_total",
                       tool="knn").value == 1
    # provenance sidecar pins the digest the result was computed from
    prov = json.loads((astore.tools_dir / "queries" / s1["key"]
                       / "query.json").read_text())
    assert prov["store_digest"] == s1["store_digest"]
    assert prov["tool"] == "knn"


def test_query_key_changes_when_features_change(astore, rng):
    payload = {"tool": "clustering", "objects_name": "nuclei", "k": 2}
    s1 = run_query(astore, payload)
    astore.append_features(
        "nuclei", _feature_table(rng, sites=[4], labels=range(1, 4)),
        shard="batch_001")
    s2 = run_query(astore, payload)
    # new shard -> new store digest -> new key -> a fresh miss
    assert s2["store_digest"] != s1["store_digest"]
    assert s2["key"] != s1["key"]
    assert s2["cache"] == "miss"
    assert s2["n_objects"] == 83


def test_query_payload_validation(astore):
    with pytest.raises(NotSupportedError, match="tool"):
        run_query(astore, {"objects_name": "nuclei"})
    with pytest.raises(NotSupportedError, match="objects_name"):
        run_query(astore, {"tool": "knn"})
    with pytest.raises(RegistryError):
        run_query(astore, {"tool": "nope", "objects_name": "nuclei"})


def test_query_all_analytics_tools_end_to_end(astore):
    for payload in (
        {"tool": "pca", "objects_name": "nuclei", "n_components": 2,
         "features": ["Morphology_area", "Intensity_mean_DAPI"]},
        {"tool": "embedding", "objects_name": "nuclei", "k": 5,
         "features": ["Morphology_area", "Intensity_mean_DAPI"]},
        {"tool": "spatial", "objects_name": "nuclei", "grid": 8,
         "windows": [[0, 0, 0, 8, 8]]},
        {"tool": "spatial", "objects_name": "nuclei", "grid": 8,
         "statistic": "enrichment",
         "mark_feature": "Intensity_mean_DAPI"},
    ):
        s = run_query(astore, payload)
        assert s["cache"] == "miss" and s["n_objects"] == 80
    # pca on the two separating features explains nearly everything
    s = run_query(astore, {"tool": "pca", "objects_name": "nuclei",
                           "n_components": 2,
                           "features": ["Morphology_area",
                                        "Intensity_mean_DAPI"]})
    assert s["cache"] == "hit"
    assert sum(s["attributes"]["explained_variance_ratio"]) > 0.9
    # the full-grid spatial window answers the whole site's population
    s = run_query(astore, {"tool": "spatial", "objects_name": "nuclei",
                           "grid": 8, "windows": [[0, 0, 0, 8, 8]]})
    assert s["attributes"]["windows"][0]["count"] == 20.0
    # enrichment: bright objects cluster on the right half, so their
    # neighborhoods are enriched above the global fraction
    s = run_query(astore, {"tool": "spatial", "objects_name": "nuclei",
                           "grid": 8, "statistic": "enrichment",
                           "mark_feature": "Intensity_mean_DAPI"})
    assert s["attributes"]["marked_fraction"] == pytest.approx(0.5)


def test_spatial_tool_rejects_unknowns(astore):
    with pytest.raises(NotSupportedError, match="statistic"):
        run_query(astore, {"tool": "spatial", "objects_name": "nuclei",
                           "statistic": "ripley"})
    with pytest.raises(NotSupportedError, match="not found"):
        run_query(astore, {"tool": "spatial", "objects_name": "nuclei",
                           "statistic": "enrichment",
                           "mark_feature": "Intensity_nope"})
    with pytest.raises(NotSupportedError, match="window sites"):
        run_query(astore, {"tool": "spatial", "objects_name": "nuclei",
                           "windows": [[99, 0, 0, 4, 4]]})


# ============================================= ToolResult.load (satellite 2)
def test_toolresult_save_load_roundtrip(tmp_path):
    values = pd.DataFrame({
        "site_index": [0, 0, 1], "label": [1, 2, 1],
        "plate": ["p", "p", "p"], "well_row": [0, 0, 0],
        "well_col": [0, 0, 0], "value": [0.5, 1.5, -2.0],
        "nn0": np.array([2, 0, 0], np.int32),
    })
    orig = ToolResult(
        tool="knn", objects_name="nuclei", layer_type="continuous",
        values=values,
        attributes={"k": 1, "store_digest": "abc", "nested": {"a": [1, 2]}},
        plots=[Plot(type="plate_heatmap", figure={"wells": []})],
    )
    orig.save(tmp_path / "res")
    back = ToolResult.load(tmp_path / "res")
    assert back.tool == "knn" and back.layer_type == "continuous"
    assert back.attributes == orig.attributes
    assert [(p.type, p.figure) for p in back.plots] == [
        (p.type, p.figure) for p in orig.plots]
    pd.testing.assert_frame_equal(back.values, orig.values,
                                  check_exact=True)


# ========================================== k-means seeding (satellite 1)
def test_kmeans_seeding_deterministic_and_covers_blobs():
    # four exact integer-valued blobs: greedy farthest-point seeding
    # must land one centroid in each, and repeated runs must agree bit
    # for bit (the fori_loop rewrite pins the old loop's semantics)
    rng = np.random.default_rng(3)
    blobs = np.array([[0, 0], [100, 0], [0, 100], [100, 100]], np.float32)
    x = np.repeat(blobs, 25, axis=0)
    x = x + rng.integers(-2, 3, size=x.shape).astype(np.float32)
    truth = np.repeat(np.arange(4), 25)
    a1, c1 = kmeans(x, 4, seed=0)
    a2, c2 = kmeans(x, 4, seed=0)
    np.testing.assert_array_equal(np.asarray(a1), np.asarray(a2))
    np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2))
    a1 = np.asarray(a1)
    # each true blob maps to exactly one distinct cluster id
    ids = {tuple(sorted(set(a1[truth == t]))) for t in range(4)}
    assert all(len(i) == 1 for i in ids) and len(ids) == 4


# ==================== classification/heatmap through the store (satellite 3)
def test_heatmap_reads_through_store_with_percentiles(astore):
    mgr = ToolRequestManager(astore)
    result = mgr.submit("heatmap", {"objects_name": "nuclei",
                                    "feature": "Intensity_mean_DAPI"})
    # the store build happened as a side effect, and the raw float32
    # column is exactly what the percentiles were computed from
    adir = analytics_dir(astore, "nuclei")
    assert (adir / "matrix.npy").exists()
    fs = FeatureStore.ensure(astore, "nuclei")
    col = fs.column("Intensity_mean_DAPI").astype(np.float64)
    assert result.attributes["p01"] == pytest.approx(
        np.percentile(col, 1))
    assert result.attributes["p99"] == pytest.approx(
        np.percentile(col, 99))
    np.testing.assert_array_equal(result.values["value"].to_numpy(), col)


def test_heatmap_unknown_feature_through_store(astore):
    mgr = ToolRequestManager(astore)
    with pytest.raises(NotSupportedError, match="not found"):
        mgr.submit("heatmap", {"objects_name": "nuclei",
                               "feature": "Intensity_missing"})


def test_classification_reads_through_store(astore):
    mgr = ToolRequestManager(astore)
    examples = [
        {"site_index": 0, "label": 1, "class": "dim"},
        {"site_index": 0, "label": 2, "class": "dim"},
        {"site_index": 0, "label": 11, "class": "bright"},
        {"site_index": 0, "label": 12, "class": "bright"},
    ]
    result = mgr.submit("classification", {
        "objects_name": "nuclei", "method": "logreg",
        "training_examples": examples,
        "features": ["Morphology_area", "Intensity_mean_DAPI"],
    })
    classes = result.attributes["classes"]
    v = result.values
    pred_b = [classes[i] for i in v[v["label"] > 10]["value"]]
    assert np.mean([p == "bright" for p in pred_b]) > 0.9
    # a second store-backed tool reuses the same build (no rebuild)
    built = json.loads((analytics_dir(astore, "nuclei")
                        / "meta.json").read_text())["built_at"]
    mgr.submit("clustering", {"objects_name": "nuclei", "k": 2})
    assert json.loads((analytics_dir(astore, "nuclei")
                       / "meta.json").read_text())["built_at"] == built


# ======================================================= serving + the CLI
def test_serve_runs_query_jobs_with_replay_parity(tmp_path, astore):
    sroot = tmp_path / "srv"
    payload = {"tool": "clustering", "objects_name": "nuclei", "k": 2}
    for job_id in ("q-1", "q-2"):  # identical payloads: second is a hit
        serve.enqueue_job(sroot, JobSpec(
            job_id=job_id, root=str(astore.root), tenant="query",
            submitted_at=1000.0, kind="query", payload=payload))
        rc = serve.run_serve(sroot, poll_s=0.01, max_jobs=1,
                             install_handlers=False)
        assert rc == 0
    done = {p.stem: json.loads(p.read_text())
            for p in serve.spool_dir(sroot, "done").glob("*.json")}
    assert done["q-1"]["summary"]["cache"] == "miss"
    assert done["q-2"]["summary"]["cache"] == "hit"
    assert done["q-1"]["summary"]["key"] == done["q-2"]["summary"]["key"]
    assert done["q-1"]["job"]["kind"] == "query"

    events = RunLedger(serve.ledger_path(sroot)).events()
    done_evs = [e for e in events if e.get("event") == "job_done"]
    assert [(e["kind"], e["tool"], e["cache"]) for e in done_evs] == [
        ("query", "clustering", "miss"), ("query", "clustering", "hit")]
    # the query phases nest as spans on the serve ledger
    spans = {e.get("span") for e in events if e.get("event") == "span"}
    assert {"feature_store", "query_tool", "job"} <= spans

    # registry_from_ledger replays the analytics series exactly as the
    # daemon observed them live (single-host ledger: no host label)
    reg = telemetry.registry_from_ledger(events)
    assert reg.counter("tmx_analytics_queries_total", tool="clustering",
                       cache="hit").value == 1
    assert reg.counter("tmx_analytics_cache_hits_total",
                       tool="clustering").value == 1
    assert reg.counter("tmx_analytics_jobs_total", tenant="query",
                       tool="clustering").value == 2
    h = reg.histogram("tmx_analytics_query_seconds", tool="clustering")
    live_sum = sum(e["query_elapsed_s"] for e in done_evs)
    assert h.count == 2 and h.sum == pytest.approx(live_sum)


def test_query_cli_and_enqueue_kind_query(tmp_path, astore, capsys):
    from tmlibrary_tpu.cli import main

    assert main(["query", "--root", str(astore.root), "--tool",
                 "clustering", "--objects", "nuclei",
                 "--payload", '{"k": 2}']) == 0
    s1 = json.loads(capsys.readouterr().out)
    assert s1["cache"] == "miss" and s1["tool"] == "clustering"
    assert main(["query", "--root", str(astore.root), "--tool",
                 "clustering", "--objects", "nuclei",
                 "--payload", '{"k": 2}']) == 0
    s2 = json.loads(capsys.readouterr().out)
    assert s2["cache"] == "hit" and s2["key"] == s1["key"]
    # --no-cache forces a recompute but lands on the same key
    assert main(["query", "--root", str(astore.root), "--tool",
                 "clustering", "--objects", "nuclei",
                 "--payload", '{"k": 2}', "--no-cache"]) == 0
    assert json.loads(capsys.readouterr().out)["cache"] == "miss"

    sroot = tmp_path / "srv"
    assert main(["enqueue", "--root", str(sroot),
                 "--experiment", str(astore.root),
                 "--tenant", "query", "--job-id", "eq-1",
                 "--kind", "query", "--tool", "knn",
                 "--objects", "nuclei", "--payload", '{"k": 3}']) == 0
    capsys.readouterr()
    spec = json.loads(
        (serve.spool_dir(sroot, "incoming") / "eq-1.json").read_text())
    assert spec["kind"] == "query"
    assert spec["payload"] == {"tool": "knn", "objects_name": "nuclei",
                               "k": 3}
    rc = serve.run_serve(sroot, poll_s=0.01, max_jobs=1,
                         install_handlers=False)
    assert rc == 0
    env = json.loads(
        (serve.spool_dir(sroot, "done") / "eq-1.json").read_text())
    # the enqueue leg reuses the digest-keyed artifacts: knn had not
    # run yet, so this one is the miss that seeds the cache
    assert env["summary"]["tool"] == "knn"
    assert env["summary"]["cache"] == "miss"


def test_query_cli_validation(astore, tmp_path):
    from tmlibrary_tpu.cli import main

    with pytest.raises(SystemExit, match="objects_name"):
        main(["query", "--root", str(astore.root), "--tool", "knn"])
    pfile = tmp_path / "p.json"
    pfile.write_text('{"k": 2}')
    with pytest.raises(SystemExit, match="mutually"):
        main(["query", "--root", str(astore.root), "--tool", "knn",
              "--objects", "nuclei", "--payload", "{}",
              "--payload-file", str(pfile)])
