"""Sublinear analytics: incremental shard ingest, the IVF kNN index,
and multi-query fusion.

Covers ISSUE 17 end to end: append==rebuild equivalence (digests AND
results, with work proportional to the new shard), the TPU-native IVF
index (recall across probe budgets, persistence, append invalidation,
the mode-resolution precedence chain), fused multi-query serving (one
batched sweep, zero new compiles for followers, bit-identical to the
sequential path, per-job cache entries), the deterministic empty-cluster
reseed, the in-place .npy row append, the admission queue's
``take_matching``, and ledger replay parity for the index counters.
"""

import json

import numpy as np
import pandas as pd
import pytest

from tmlibrary_tpu import telemetry
from tmlibrary_tpu.analytics import index as aidx
from tmlibrary_tpu.analytics import ops
from tmlibrary_tpu.analytics import store as astore_mod
from tmlibrary_tpu.analytics.query import (
    fusion_signature, query_key, run_query, run_query_batch,
)
from tmlibrary_tpu.analytics.store import FeatureStore, _append_npy_rows
from tmlibrary_tpu.errors import NotSupportedError
from tmlibrary_tpu.models.experiment import grid_experiment
from tmlibrary_tpu.models.store import ExperimentStore
from tmlibrary_tpu.workflow.admission import (
    AdmissionConfig, AdmissionQueue, JobSpec,
)


@pytest.fixture(autouse=True)
def _fresh_registry():
    telemetry.reset_registry(enabled=True)
    yield
    telemetry.reset_registry()


def _blobs(rng, n, f=8, n_blobs=24, spread=0.15):
    """Clustered synthetic features — the microscopy regime (objects
    concentrate around phenotype modes), which is what cell probing
    exploits; iid noise has no cells and is NOT the relevant case."""
    centers = rng.normal(size=(n_blobs, f))
    labels = rng.integers(0, n_blobs, size=n)
    return (centers[labels] + spread * rng.normal(size=(n, f))
            ).astype(np.float32)


def _table(rng, sites=range(4), labels=range(1, 21)):
    rows = []
    for site in sites:
        for label in labels:
            pop_b = label > (max(labels) // 2)
            rows.append({
                "site_index": site,
                "plate": "plate00",
                "well_row": 0,
                "well_col": 0,
                "site_y": site // 2,
                "site_x": site % 2,
                "label": label,
                "Morphology_area": float(
                    rng.normal(150.0 if pop_b else 80.0, 6.0)),
                "Intensity_mean_DAPI": float(
                    rng.normal(20.0 if pop_b else 8.0, 1.5)),
                "Morphology_centroid_y": float(rng.uniform(0, 16)),
                "Morphology_centroid_x": float(rng.uniform(0, 16)),
            })
    return pd.DataFrame(rows)


def _experiment(tmp_path, name="exp"):
    exp = grid_experiment(name="analytics", well_rows=1, well_cols=1,
                          sites_per_well=(2, 2), site_shape=(16, 16))
    return ExperimentStore.create(tmp_path / name, exp)


# ------------------------------------------------------------------ kmeans
def test_reseed_empty_takes_farthest_points_deterministically():
    from tmlibrary_tpu.tools.clustering import _reseed_empty

    x = np.array([[0.0, 0.0], [1.0, 0.0], [10.0, 0.0], [11.0, 0.0]],
                 np.float32)
    updated = np.array([[0.5, 0.0], [99.0, 99.0]], np.float32)
    counts = np.array([4.0, 0.0], np.float32)
    d_assign = np.array([0.5, 0.5, 9.5, 10.5], np.float32)
    out = np.asarray(_reseed_empty(updated, counts, x, d_assign))
    # live slot keeps the Lloyd update; the dead slot adopts the
    # farthest point (row 3, largest distance to its centroid)
    np.testing.assert_array_equal(out[0], updated[0])
    np.testing.assert_array_equal(out[1], x[3])
    out2 = np.asarray(_reseed_empty(updated, counts, x, d_assign))
    np.testing.assert_array_equal(out, out2)

    # all-live counts: reseed is the identity
    live = np.asarray(_reseed_empty(
        updated, np.array([2.0, 2.0], np.float32), x, d_assign))
    np.testing.assert_array_equal(live, updated)


def test_kmeans_never_reports_empty_clusters(rng):
    from tmlibrary_tpu.tools.clustering import kmeans

    # adversarial: k=8 over 3 tight, far-apart blobs — frozen-centroid
    # k-means would leave dead slots; the reseed keeps every cell live
    centers = np.array([[0, 0], [100, 0], [0, 100]], np.float32)
    x = (centers[rng.integers(0, 3, 120)]
         + rng.normal(size=(120, 2)).astype(np.float32) * 0.1)
    assign, cent = kmeans(x, 8, n_iter=25)
    counts = np.bincount(np.asarray(assign), minlength=8)
    assert (counts > 0).all()
    assign2, cent2 = kmeans(x, 8, n_iter=25)
    np.testing.assert_array_equal(np.asarray(assign), np.asarray(assign2))
    np.testing.assert_array_equal(np.asarray(cent), np.asarray(cent2))


def test_kmeans_stride_init_deterministic(rng):
    from tmlibrary_tpu.tools.clustering import kmeans

    x = _blobs(rng, 400, f=4)
    a1, c1 = kmeans(x, 20, n_iter=10, init="stride")
    a2, c2 = kmeans(x, 20, n_iter=10, init="stride")
    np.testing.assert_array_equal(np.asarray(a1), np.asarray(a2))
    np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2))


# ------------------------------------------------------------------- recall
def test_ivf_recall_across_top_p(rng):
    x = _blobs(rng, 2500, f=8)
    cent, mem, assign = aidx.ivf_build_arrays(x)
    c = cent.shape[0]
    k = 10

    exact_idx, _ = ops.knn(x, k)

    def self_recall(top_p):
        ivf_idx, _ = aidx.ivf_search_arrays(x, cent, mem, k, top_p=top_p)
        hits = sum(len(set(a) & set(b)) for a, b in
                   zip(ivf_idx.tolist(), exact_idx.tolist()))
        return hits / exact_idx.size

    # the acceptance bar: >= 0.95 at the default probe budget, on the
    # realistic (clustered) data regime — for both probe shapes
    assert self_recall(aidx.DEFAULT_TOP_P) >= 0.95
    assert aidx.measure_recall(x, cent, mem, k=k) >= 0.95
    # wider probes never hurt
    assert self_recall(16) >= self_recall(4) - 1e-9
    # top_p == n_cells probes every cell, so the candidate set is the
    # whole population.  The two paths still round |q|^2 + |x|^2 - 2 q.x
    # in different orders (per-cell gather vs one dense matmul), so two
    # candidates whose true distances differ by less than one float32
    # ulp (1.2e-7 relative) may swap at the k-th boundary.  Tolerance:
    # every miss must be such a tie, judged on float64 distances.
    n = x.shape[0]
    rows = np.linspace(0, n - 1, min(aidx.RECALL_SAMPLE, n)).astype(np.int64)
    q = x[rows]
    exact_q, _ = ops.knn(x, k, queries=q)
    ivf_q, _ = aidx.ivf_search_arrays(x, cent, mem, k, queries=q, top_p=c)
    d = np.sqrt(((q[:, None, :].astype(np.float64)
                  - x[None, :, :].astype(np.float64)) ** 2).sum(-1))
    kth = np.sort(d, axis=1)[:, k - 1]
    for r, (a, b) in enumerate(zip(ivf_q.tolist(), exact_q.tolist())):
        for j in set(a) ^ set(b):
            assert abs(d[r, j] - kth[r]) <= 1.2e-7 * kth[r], (r, j)
    assert self_recall(c) == 1.0
    # one swapped tie in the 128 x 10 sample reads 0.99922
    assert aidx.measure_recall(x, cent, mem, k=k, top_p=c) >= 1 - 2 / 1280


def test_ivf_search_contract(rng):
    x = _blobs(rng, 600, f=6)
    cent, mem, _ = aidx.ivf_build_arrays(x)
    idx, dist = aidx.ivf_search_arrays(x, cent, mem, 5)
    assert idx.shape == (600, 5) and dist.shape == (600, 5)
    rows = np.arange(600)[:, None]
    assert not (idx == rows).any()          # self excluded
    assert (np.diff(dist, axis=1) >= 0).all()  # sorted nearest-first

    # explicit queries: query-major path, self NOT excluded
    q = x[:7]
    qidx, qdist = aidx.ivf_search_arrays(x, cent, mem, 1, queries=q)
    np.testing.assert_array_equal(qidx[:, 0], np.arange(7))


def test_ivf_prefix_property_fused_slicing(rng):
    """The fusion correctness root: a larger-k sweep's k-prefix IS the
    smaller-k answer, bit for bit, on both index modes."""
    x = _blobs(rng, 500, f=6)
    cent, mem, _ = aidx.ivf_build_arrays(x)
    for search in (
        lambda k: aidx.ivf_search_arrays(x, cent, mem, k),
        lambda k: ops.knn(x, k),
    ):
        idx_big, dist_big = search(9)
        for k in (3, 5):
            idx_k, dist_k = search(k)
            np.testing.assert_array_equal(idx_k, idx_big[:, :k])
            np.testing.assert_array_equal(dist_k, dist_big[:, :k])


# ------------------------------------------------------- append == rebuild
def test_append_equals_rebuild_bit_identical(tmp_path, rng):
    t0 = _table(rng, sites=range(4), labels=range(1, 21))
    t1 = _table(rng, sites=range(4), labels=range(21, 31))

    inc = _experiment(tmp_path, "incremental")
    inc.append_features("nuclei", t0, shard="batch_000")
    fs_first = FeatureStore.ensure(inc, "nuclei")
    assert fs_first.meta["build_kind"] == "full"
    inc.append_features("nuclei", t1, shard="batch_001")
    fs_inc = FeatureStore.ensure(inc, "nuclei")
    assert fs_inc.meta["build_kind"] == "append"
    assert fs_inc.meta["appended_shards"] == ["batch_001.parquet"]

    scratch = _experiment(tmp_path, "scratch")
    scratch.append_features("nuclei", t0, shard="batch_000")
    scratch.append_features("nuclei", t1, shard="batch_001")
    fs_full = FeatureStore.ensure(scratch, "nuclei")
    assert fs_full.meta["build_kind"] == "full"

    # both digest chains land on exactly the rebuild values
    assert fs_inc.digest == fs_full.digest
    assert fs_inc.meta["source_digest"] == fs_full.meta["source_digest"]
    # ... so the query cache key is identical too
    payload = {"tool": "knn", "objects_name": "nuclei", "k": 3}
    assert (query_key(fs_inc.digest, payload)
            == query_key(fs_full.digest, payload))
    # matrix bytes and identity frame are bit-identical
    assert ((fs_inc.root / "matrix.npy").read_bytes()
            == (fs_full.root / "matrix.npy").read_bytes())
    pd.testing.assert_frame_equal(
        pd.read_parquet(fs_inc.root / "index.parquet"),
        pd.read_parquet(fs_full.root / "index.parquet"))
    # and query RESULTS agree exactly
    r_inc = run_query(inc, payload)
    r_full = run_query(scratch, payload)
    assert r_inc["key"] == r_full["key"]
    assert r_inc["attributes"] == r_full["attributes"]


def test_append_work_proportional_to_new_shard(tmp_path, rng,
                                               monkeypatch):
    """An append must read ONLY the new shards — never re-read ingested
    Parquet, never silently degrade to a full rebuild."""
    exp = _experiment(tmp_path)
    exp.append_features("nuclei", _table(rng), shard="batch_000")
    FeatureStore.ensure(exp, "nuclei")

    read = []
    real = pd.read_parquet

    def tracked(path, *a, **kw):
        read.append(str(path))
        return real(path, *a, **kw)

    monkeypatch.setattr(astore_mod.pd, "read_parquet", tracked)

    # unchanged store: reuse, zero shard reads
    fs = FeatureStore.ensure(exp, "nuclei")
    assert [p for p in read if p.endswith(".parquet")
            and "batch" in p] == []

    # grown store: exactly the new shard is read
    exp.append_features("nuclei", _table(rng, labels=range(21, 31)),
                        shard="batch_001")
    read.clear()
    fs = FeatureStore.ensure(exp, "nuclei")
    shard_reads = [p for p in read if "batch" in p]
    assert len(shard_reads) == 1 and shard_reads[0].endswith(
        "batch_001.parquet")
    assert fs.meta["build_kind"] == "append"
    assert fs.meta["appended_rows"] == 40


def test_append_npy_rows_in_place(tmp_path):
    path = tmp_path / "m.npy"
    a = np.arange(12, dtype=np.float32).reshape(3, 4)
    np.save(path, a)
    b = np.arange(100, 120, dtype=np.float32).reshape(5, 4)
    _append_npy_rows(path, b)
    np.testing.assert_array_equal(np.load(path), np.vstack([a, b]))
    # repeated growth (header shape string gets longer) stays loadable
    for _ in range(3):
        _append_npy_rows(path, b)
    out = np.load(path)
    assert out.shape == (23, 4)
    np.testing.assert_array_equal(out[-5:], b)


# ------------------------------------------------- index persistence/append
def test_index_persist_reuse_and_append_invalidation(tmp_path, rng):
    exp = _experiment(tmp_path)
    exp.append_features("nuclei", _table(rng), shard="batch_000")
    fs = FeatureStore.ensure(exp, "nuclei")

    idx1 = aidx.IvfIndex.ensure(fs)
    assert idx1.cache_state == "build"
    assert idx1.meta["store_digest"] == fs.digest
    assert (idx1.root / "index_meta.json").exists()
    idx2 = aidx.IvfIndex.ensure(fs)
    assert idx2.cache_state == "hit"
    assert idx2.digest == idx1.digest

    reg = telemetry.get_registry()
    assert reg.counter("tmx_analytics_index_builds_total").value == 1
    assert reg.counter("tmx_analytics_index_hits_total").value == 1

    # append rolls the store digest -> the persisted index is stale and
    # MUST rebuild, never serve
    exp.append_features("nuclei", _table(rng, labels=range(21, 31)),
                        shard="batch_001")
    fs2 = FeatureStore.ensure(exp, "nuclei")
    assert fs2.digest != fs.digest
    idx3 = aidx.IvfIndex.ensure(fs2)
    assert idx3.cache_state == "build"
    assert idx3.meta["store_digest"] == fs2.digest
    assert idx3.digest != idx1.digest
    assert idx3.meta["n_objects"] == 120


def test_knn_search_dispatch_and_fallback(tmp_path, rng, monkeypatch):
    exp = _experiment(tmp_path)
    exp.append_features("nuclei", _table(rng), shard="batch_000")
    fs = FeatureStore.ensure(exp, "nuclei")
    _, x, _ = fs.standardized(None)

    idx_b, dist_b, info_b = aidx.knn_search(fs, x, 4, mode="brute")
    assert info_b == {"index": "brute", "index_source": "payload"}
    idx_i, dist_i, info_i = aidx.knn_search(fs, x, 4, mode="ivf")
    assert info_i["index"] == "ivf" and info_i["index_cache"] == "build"
    assert info_i["recall_at_k"] is not None
    assert idx_i.shape == idx_b.shape

    # any index failure degrades to brute force + a fallback counter
    monkeypatch.setattr(aidx.IvfIndex, "ensure",
                        classmethod(lambda *a, **k: (_ for _ in ()).throw(
                            RuntimeError("boom"))))
    idx_f, _, info_f = aidx.knn_search(fs, x, 4, mode="ivf")
    assert info_f["index"] == "brute" and "boom" in info_f["index_fallback"]
    np.testing.assert_array_equal(idx_f, idx_b)
    assert telemetry.get_registry().counter(
        "tmx_analytics_index_fallbacks_total").value == 1


# ------------------------------------------------------- mode precedence
def test_resolve_index_mode_precedence(monkeypatch, tmp_path):
    for var in ("TMX_ANALYTICS_INDEX", "TM_ANALYTICS_INDEX",
                "TMX_TUNING_JSON", "TMX_ANALYTICS_INDEX_MIN"):
        monkeypatch.delenv(var, raising=False)

    # auto: size cutover (env-overridable)
    assert aidx.resolve_index_mode(None, n_objects=10) == ("brute", "auto")
    assert aidx.resolve_index_mode(
        None, n_objects=aidx.DEFAULT_AUTO_MIN_OBJECTS) == ("ivf", "auto")
    monkeypatch.setenv("TMX_ANALYTICS_INDEX_MIN", "5")
    assert aidx.resolve_index_mode(None, n_objects=10) == ("ivf", "auto")
    monkeypatch.delenv("TMX_ANALYTICS_INDEX_MIN")

    # tuned verdict beats auto, scoped to this backend (the provenance
    # gate needs written_by — see tuning.load_tuning)
    import jax
    tuning = tmp_path / "TUNING.json"

    def write_tuning(doc):
        tuning.write_text(json.dumps({"written_by": "bench.py --sweep",
                                      **doc}))

    write_tuning({"analytics_index": {jax.default_backend(): "ivf"}})
    monkeypatch.setenv("TMX_TUNING_JSON", str(tuning))
    assert aidx.resolve_index_mode(None, n_objects=10) == ("ivf", "tuned")
    # a verdict for ANOTHER backend never applies here
    write_tuning({"analytics_index": {"tpu-v9": "ivf"}})
    assert aidx.resolve_index_mode(None, n_objects=10) == ("brute", "auto")
    # malformed verdicts degrade silently to auto
    write_tuning({"analytics_index": "warp-drive"})
    assert aidx.resolve_index_mode(None, n_objects=10) == ("brute", "auto")

    # config beats tuned
    write_tuning({"analytics_index": {jax.default_backend(): "ivf"}})
    monkeypatch.setenv("TM_ANALYTICS_INDEX", "brute")
    assert aidx.resolve_index_mode(None) == ("brute", "config")

    # env beats config
    monkeypatch.setenv("TMX_ANALYTICS_INDEX", "ivf")
    assert aidx.resolve_index_mode(None) == ("ivf", "env")
    # a bad env value fails LOUD (operator knob, not stale data)
    monkeypatch.setenv("TMX_ANALYTICS_INDEX", "flat")
    with pytest.raises(NotSupportedError, match="flat"):
        aidx.resolve_index_mode(None)
    monkeypatch.setenv("TMX_ANALYTICS_INDEX", "ivf")

    # explicit payload beats everything, and validates loud
    assert aidx.resolve_index_mode("brute") == ("brute", "payload")
    with pytest.raises(NotSupportedError, match="hnsw"):
        aidx.resolve_index_mode("hnsw")
    # "auto" at any link falls through to the next
    assert aidx.resolve_index_mode("auto") == ("ivf", "env")


# ------------------------------------------------------------------ fusion
def test_fusion_signature_family():
    base = {"tool": "knn", "objects_name": "nuclei", "k": 3}
    assert fusion_signature(base) == fusion_signature({**base, "k": 9})
    assert fusion_signature(base) != fusion_signature(
        {**base, "features": ["Morphology_area"]})
    assert fusion_signature({"tool": "pca", "objects_name": "n"}) is None
    assert fusion_signature({"tool": "clustering"}) is None


def test_run_query_batch_fuses_one_sweep(tmp_path, rng):
    exp = _experiment(tmp_path)
    exp.append_features("nuclei", _table(rng, labels=range(1, 41)),
                        shard="batch_000")
    ks = [3, 4, 5]
    payloads = [{"tool": "knn", "objects_name": "nuclei", "k": k,
                 "index": "brute"} for k in ks]

    before = ops._knn_tile._cache_size()
    summaries = run_query_batch(exp, payloads)
    # ONE batched sweep: at most one new compiled program for the whole
    # window (zero when the k_max tile shape is already warm) — jobs
    # 2..N never add a compile
    assert ops._knn_tile._cache_size() - before <= 1

    assert [s["cache"] for s in summaries] == ["miss", "fused", "fused"]
    keys = [s["key"] for s in summaries]
    assert len(set(keys)) == 3
    for s in summaries:
        assert s["fusion_window"] == 3
        assert (exp.tools_dir / "queries" / s["key"]
                / "result.json").exists()
    assert summaries[1]["fused_with"] == keys[0]
    assert summaries[2]["fused_with"] == keys[0]

    reg = telemetry.get_registry()
    assert reg.counter("tmx_analytics_queries_total", tool="knn",
                       cache="miss").value == 1
    assert reg.counter("tmx_analytics_queries_total", tool="knn",
                       cache="fused").value == 2

    # bit-identity: each fused result equals the sequential computation
    from tmlibrary_tpu.tools.base import ToolResult
    for s, payload in zip(summaries, payloads):
        seq = run_query(exp, payload, use_cache=False)
        fused = ToolResult.load(exp.tools_dir / "queries" / s["key"])
        assert seq["attributes"] == dict(fused.attributes)
        # re-running sequentially rewrote the same cache dir with an
        # identical frame — load both sides and compare exactly
        seq_res = ToolResult.load(exp.tools_dir / "queries" / seq["key"])
        pd.testing.assert_frame_equal(fused.values, seq_res.values)

    # a repeat batch is all cache hits — no new sweep
    again = run_query_batch(exp, payloads)
    assert [s["cache"] for s in again] == ["hit", "hit", "hit"]


def test_serve_daemon_fuses_concurrent_query_jobs(tmp_path, rng):
    from tmlibrary_tpu import serve
    from tmlibrary_tpu.workflow.engine import RunLedger

    exp = _experiment(tmp_path)
    exp.append_features("nuclei", _table(rng, labels=range(1, 41)),
                        shard="batch_000")
    sroot = tmp_path / "serve"
    for i, k in enumerate((3, 4, 5)):
        serve.enqueue_job(sroot, JobSpec(
            job_id=f"f-{k}", root=str(exp.root), tenant=f"tenant{i}",
            submitted_at=1000.0, kind="query",
            payload={"tool": "knn", "objects_name": "nuclei", "k": k,
                     "index": "brute"}))
    rc = serve.run_serve(sroot, poll_s=0.01, max_jobs=3,
                         install_handlers=False)
    assert rc == 0

    done = {p.stem: json.loads(p.read_text())
            for p in serve.spool_dir(sroot, "done").glob("*.json")}
    assert len(done) == 3
    assert sorted(d["summary"]["cache"] for d in done.values()) == [
        "fused", "fused", "miss"]
    # every job cached under its OWN query key
    assert len({d["summary"]["key"] for d in done.values()}) == 3
    for d in done.values():
        assert d["summary"]["fusion_window"] == 3

    events = RunLedger(serve.ledger_path(sroot)).events()
    fused_evs = [e for e in events if e.get("event") == "query_fused"]
    assert len(fused_evs) == 1 and fused_evs[0]["window"] == 3
    # followers keep their full lifecycle: 3 started, 3 done, and the
    # per-tenant attribution is intact
    assert len([e for e in events
                if e.get("event") == "job_started"]) == 3
    done_evs = [e for e in events if e.get("event") == "job_done"]
    assert sorted(e["tenant"] for e in done_evs) == [
        "tenant0", "tenant1", "tenant2"]

    # ledger replay reconstructs the fusion series exactly as the live
    # registry observed it
    live = telemetry.get_registry()
    reg = telemetry.registry_from_ledger(events)
    for r in (live, reg):
        assert r.counter("tmx_serve_query_fused_total").value == 3.0
        h = r.histogram("tmx_serve_fusion_window")
        assert h.count == 1 and h.sum == 3.0
        assert r.counter("tmx_analytics_queries_total", tool="knn",
                         cache="fused").value == 2

    # and the QUERY row view aggregates the same picture from disk
    view = serve.serve_status_view(sroot)
    q = view["queries"]
    assert q["total"] == 3
    assert q["cache"] == {"miss": 1, "fused": 2}
    assert q["fusion_events"] == 1 and q["fusion_jobs"] == 3
    assert q["index"] == {"brute": 3}


def test_run_query_batch_rejects_mixed_signatures(tmp_path, rng):
    exp = _experiment(tmp_path)
    exp.append_features("nuclei", _table(rng), shard="batch_000")
    with pytest.raises(NotSupportedError, match="fusion signature"):
        run_query_batch(exp, [
            {"tool": "knn", "objects_name": "nuclei", "k": 3},
            {"tool": "knn", "objects_name": "nuclei", "k": 4,
             "features": ["Morphology_area"]},
        ])


def test_take_matching_order_limit_and_removal():
    q = AdmissionQueue(AdmissionConfig(max_queue=32), clock=lambda: 1000.0)
    specs = []
    for tenant, jid, kind in [("beta", "b1", "query"),
                              ("alpha", "a1", "query"),
                              ("alpha", "a2", "workflow"),
                              ("gamma", "g1", "query")]:
        spec = JobSpec(job_id=jid, tenant=tenant, root="/r",
                       submitted_at=999.0, kind=kind)
        assert q.offer(spec).admitted
        specs.append(spec)

    got = q.take_matching(lambda j: j.kind == "query", limit=2)
    # deterministic (tenant-name, priority) order: alpha before beta
    assert [j.job_id for j in got] == ["a1", "b1"]
    # taken jobs left the queue; the rest (workflow a2, query g1) remain
    assert q.depth() == 2
    # duplicate-id admission is allowed again once taken
    assert {j.job_id for j in q.drain()} == {"a2", "g1"}

    assert q.take_matching(lambda j: True, limit=0) == []


# ----------------------------------------------------------- replay parity
def test_registry_from_ledger_replays_index_and_fusion_counters():
    events = [
        {"event": "job_admitted", "tenant": "t1", "queue_wait_s": 0.1},
        {"event": "query_fused", "job": "q1", "tenant": "t1",
         "window": 3, "jobs": ["q1", "q2", "q3"]},
        {"event": "job_done", "tenant": "t1", "kind": "query",
         "tool": "knn", "cache": "miss", "query_elapsed_s": 0.5,
         "index": "ivf", "index_cache": "build"},
        {"event": "job_done", "tenant": "t2", "kind": "query",
         "tool": "knn", "cache": "fused", "query_elapsed_s": 0.5,
         "index": "ivf"},
        {"event": "job_done", "tenant": "t3", "kind": "query",
         "tool": "knn", "cache": "fused", "query_elapsed_s": 0.5,
         "index": "ivf"},
        {"event": "job_done", "tenant": "t1", "kind": "query",
         "tool": "knn", "cache": "miss", "query_elapsed_s": 0.2,
         "index": "ivf", "index_cache": "hit"},
        {"event": "job_done", "tenant": "t1", "kind": "query",
         "tool": "knn", "cache": "miss", "query_elapsed_s": 0.9,
         "index": "brute", "index_fallback": True},
    ]
    reg = telemetry.registry_from_ledger(events)
    assert reg.counter("tmx_analytics_index_builds_total").value == 1
    assert reg.counter("tmx_analytics_index_hits_total").value == 1
    assert reg.counter("tmx_analytics_index_fallbacks_total").value == 1
    assert reg.counter("tmx_serve_query_fused_total").value == 3
    h = reg.histogram("tmx_serve_fusion_window")
    assert h.count == 1 and h.sum == 3.0
    assert reg.counter("tmx_analytics_queries_total", tool="knn",
                       cache="fused").value == 2
