#!/usr/bin/env python
"""CI analytics smoke: feature store + ``tmx query`` + query serving.

    python scripts/ci_analytics_smoke.py [ARTIFACT_DIR] [--keep DIR]

``tests/test_analytics.py`` proves the op/store/cache contracts inside
one pytest process; this harness crosses the real boundaries the
analytics tier promises (DESIGN.md §24): a real ``tmx workflow submit``
subprocess produces the feature shards, one-shot ``tmx query`` commands
answer kNN / clustering / spatial queries over them (first a cache
miss, then — byte-identical payload, unchanged store digest — a cache
HIT on the same key), and a real ``tmx serve run`` daemon answers a
``kind: query`` job for the SAME clustering payload, which must arrive
as a cache hit seeded by the one-shot path: the digest-keyed artifact
cache is shared across serving paths.

Two further legs exercise the sublinear path (DESIGN.md §26): ``tmx
index build`` persists an IVF index whose manifest must carry a recall
measurement, an indexed one-shot kNN must route through it
(``index_cache: hit``, never a rebuild) and — probed exhaustively via
a ``top_p`` above the cell count, which clamps — EQUAL brute; and
a fresh daemon admits THREE concurrent ``kind: query`` kNN jobs with
different k which must coalesce into ONE batched sweep — cache states
``miss`` + 2×``fused``, three distinct per-job cache keys on disk, a
single ``query_fused`` ledger event with ``window: 3``, and every
follower's ``query.json`` naming the leader key.  The daemon legs' SLO
view for the ``query`` tenant, the index manifest, and a schema-valid
Chrome trace (whose job span nests the ``feature_store``/``query_tool``
phases) upload as CI artifacts.  Exit 0 and ``ANALYTICS PASS`` on
success; 1 otherwise.
"""

import argparse
import json
import os
import shutil
import subprocess
import sys
import tempfile
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))
sys.path.insert(0, str(REPO / "scripts"))

# a CI smoke is a CPU run, whatever is attached
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from chaos_run import make_source, make_store  # noqa: E402


def _env() -> dict:
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": str(REPO)}
    env.pop("TMX_FAULT_PLAN", None)
    return env


def _tmx(args: list, timeout=600) -> subprocess.CompletedProcess:
    return subprocess.run(
        [sys.executable, "-m", "tmlibrary_tpu.cli", *args],
        env=_env(), stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True, timeout=timeout,
    )


def _query(root: Path, payload_args: list) -> dict:
    rc = _tmx(["query", "--root", str(root), *payload_args])
    if rc.returncode != 0:
        raise SystemExit(
            f"ANALYTICS FAIL: tmx query exited {rc.returncode}\n{rc.stdout}")
    # the summary is the last JSON line (module imports may warn above)
    for line in reversed(rc.stdout.splitlines()):
        if line.startswith("{"):
            return json.loads(line)
    raise SystemExit(f"ANALYTICS FAIL: no JSON from tmx query\n{rc.stdout}")


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("artifacts", nargs="?", default=None,
                        help="copy the query-tenant slo/trace views here "
                             "for CI artifact upload")
    parser.add_argument("--keep", metavar="DIR", default=None,
                        help="run inside DIR and keep everything "
                             "(default: a temp dir, removed afterwards)")
    args = parser.parse_args(argv)

    with tempfile.TemporaryDirectory() as tmp:
        root = Path(args.keep) if args.keep else Path(tmp)
        root.mkdir(parents=True, exist_ok=True)
        source = make_source(root)

        print("[1/6] real `tmx workflow submit` producing feature shards")
        store, desc = make_store(root, "exp", source)
        desc.save(store.workflow_dir / "workflow.yaml")
        rc = _tmx(["workflow", "submit", "--root", str(store.root),
                   "--retry-delay", "0"])
        if rc.returncode != 0:
            print(f"ANALYTICS FAIL: workflow submit exited "
                  f"{rc.returncode}\n{rc.stdout[-3000:]}")
            return 1
        shards = list((store.root / "features" / "nuclei").glob("*.parquet"))
        if not shards:
            print("ANALYTICS FAIL: submit left no feature shards")
            return 1
        print(f"      {len(shards)} feature shard(s) written")

        print("[2/6] one-shot queries: knn miss -> hit, clustering, "
              "spatial")
        knn1 = _query(store.root, ["--tool", "knn", "--objects", "nuclei",
                                   "--payload", '{"k": 5}'])
        if knn1["cache"] != "miss":
            print(f"ANALYTICS FAIL: first knn query was {knn1['cache']}, "
                  "expected miss")
            return 1
        knn2 = _query(store.root, ["--tool", "knn", "--objects", "nuclei",
                                   "--payload", '{"k": 5}'])
        # the digest-keyed cache contract: unchanged store + identical
        # payload => the SAME key answered as a hit with identical attrs
        if (knn2["cache"] != "hit" or knn2["key"] != knn1["key"]
                or knn2["store_digest"] != knn1["store_digest"]
                or knn2["attributes"] != knn1["attributes"]):
            print(f"ANALYTICS FAIL: knn re-query not a clean cache hit "
                  f"(cache={knn2['cache']}, keys {knn1['key']} vs "
                  f"{knn2['key']})")
            return 1
        print(f"      knn: miss then HIT on key {knn1['key']} "
              f"({knn1['n_objects']} objects, "
              f"mean distance {knn1['attributes']['mean_distance']:.3f})")

        clustering_payload = ["--tool", "clustering", "--objects", "nuclei",
                              "--payload", '{"k": 2}']
        clus = _query(store.root, clustering_payload)
        sizes = clus["attributes"]["cluster_sizes"]
        if clus["cache"] != "miss" or sum(map(int, sizes.values())) \
                != clus["n_objects"]:
            print(f"ANALYTICS FAIL: clustering malformed: {clus}")
            return 1
        print(f"      clustering: k=2 sizes {sizes}")

        spat = _query(store.root, ["--tool", "spatial", "--objects",
                                   "nuclei", "--payload", '{"grid": 8}'])
        if spat["cache"] != "miss" or spat["attributes"]["n_sites"] < 1:
            print(f"ANALYTICS FAIL: spatial malformed: {spat}")
            return 1
        print(f"      spatial: density over {spat['attributes']['n_sites']} "
              "site(s)")

        print("[3/6] serve daemon answers the same clustering query as a "
              "kind=query job (cross-path cache hit)")
        sroot = root / "serve_root"
        rc = _tmx(["enqueue", "--root", str(sroot),
                   "--experiment", str(store.root),
                   "--tenant", "query", "--job-id", "q-clustering",
                   "--kind", "query", "--tool", "clustering",
                   "--objects", "nuclei", "--payload", '{"k": 2}'])
        if rc.returncode != 0:
            print(f"ANALYTICS FAIL: enqueue exited {rc.returncode}\n"
                  f"{rc.stdout}")
            return 1
        rc = _tmx(["enqueue", "--root", str(sroot),
                   "--experiment", str(store.root),
                   "--tenant", "query", "--job-id", "q-spatial-enr",
                   "--kind", "query", "--tool", "spatial",
                   "--objects", "nuclei",
                   "--payload",
                   '{"grid": 8, "statistic": "enrichment", '
                   '"mark_feature": "Intensity_mean_DAPI"}'])
        if rc.returncode != 0:
            print(f"ANALYTICS FAIL: enqueue exited {rc.returncode}\n"
                  f"{rc.stdout}")
            return 1
        rc = _tmx(["serve", "run", "--root", str(sroot), "--poll", "0.1",
                   "--max-jobs", "2"])
        if rc.returncode != 0:
            print(f"ANALYTICS FAIL: serve run exited {rc.returncode}\n"
                  f"{rc.stdout[-3000:]}")
            return 1
        done_dir = sroot / "spool" / "done"
        envelopes = {p.stem: json.loads(p.read_text())
                     for p in done_dir.glob("*.json")}
        if sorted(envelopes) != ["q-clustering", "q-spatial-enr"]:
            print(f"ANALYTICS FAIL: expected both query jobs done, got "
                  f"{sorted(envelopes)}")
            return 1
        cl = envelopes["q-clustering"]["summary"]
        # seeded by the one-shot CLI leg: same digest, same key, a HIT
        if cl["cache"] != "hit" or cl["key"] != clus["key"]:
            print(f"ANALYTICS FAIL: daemon clustering query was "
                  f"{cl['cache']} on key {cl['key']} (one-shot key "
                  f"{clus['key']}) — the digest-keyed cache is not "
                  "shared across paths")
            return 1
        enr = envelopes["q-spatial-enr"]["summary"]
        if enr["cache"] != "miss" or \
                "marked_fraction" not in enr["attributes"]:
            print(f"ANALYTICS FAIL: enrichment job malformed: {enr}")
            return 1
        ledger_events = [
            json.loads(line) for line in
            (sroot / "serve" / "ledger.jsonl").read_text().splitlines()
        ]
        done_evs = [e for e in ledger_events
                    if e.get("event") == "job_done"]
        if not all(e.get("kind") == "query" and e.get("tool")
                   and e.get("cache") for e in done_evs):
            print(f"ANALYTICS FAIL: job_done events missing query "
                  f"provenance: {done_evs}")
            return 1
        spans = {e.get("span") for e in ledger_events
                 if e.get("event") == "span"}
        if not {"feature_store", "query_tool", "job"} <= spans:
            print(f"ANALYTICS FAIL: query phases missing from the serve "
                  f"ledger spans: {sorted(s for s in spans if s)}")
            return 1
        print(f"      daemon: clustering HIT on key {cl['key']}, "
              f"enrichment miss (marked fraction "
              f"{enr['attributes']['marked_fraction']})")

        print("[4/6] tmx index build -> manifest, indexed query agrees "
              "with brute")
        rc = _tmx(["index", "build", "--root", str(store.root),
                   "--objects", "nuclei"])
        if rc.returncode != 0:
            print(f"ANALYTICS FAIL: tmx index build exited "
                  f"{rc.returncode}\n{rc.stdout}")
            return 1
        manifest = None
        for line in reversed(rc.stdout.splitlines()):
            if line.startswith("{"):
                manifest = json.loads(line)
                break
        if not manifest or int(manifest.get("n_cells") or 0) < 1 \
                or float(manifest.get("recall_at_k") or 0.0) < 0.9:
            print(f"ANALYTICS FAIL: index manifest malformed or recall "
                  f"below 0.9 at the default probe width: {manifest}")
            return 1
        lst = _tmx(["index", "list", "--root", str(store.root),
                    "--objects", "nuclei"])
        listing = json.loads(lst.stdout.splitlines()[-1])
        states = [r.get("state") for r in listing.get("indexes", [])]
        if lst.returncode != 0 or states != ["fresh"]:
            print(f"ANALYTICS FAIL: index list should show one fresh "
                  f"index, got {listing}")
            return 1
        # top_p far above the cell count clamps to an exhaustive probe,
        # so the indexed answer must EQUAL brute — and the pre-built
        # index must serve it as a cache hit, not a rebuild
        knn_ivf = _query(store.root, ["--tool", "knn", "--objects",
                                      "nuclei", "--payload",
                                      '{"k": 5, "top_p": 4096}',
                                      "--index", "ivf"])
        attrs = knn_ivf["attributes"]
        if knn_ivf["cache"] != "miss" or attrs.get("index") != "ivf" \
                or attrs.get("index_cache") != "hit":
            print(f"ANALYTICS FAIL: indexed knn did not route through "
                  f"the persisted index: {knn_ivf}")
            return 1
        drift = abs(float(attrs["mean_distance"])
                    - float(knn1["attributes"]["mean_distance"]))
        if drift > 1e-5:
            print(f"ANALYTICS FAIL: indexed knn disagrees with brute at "
                  f"exhaustive probe width (mean distance drift {drift})")
            return 1
        print(f"      index: {manifest['n_objects']} objects in "
              f"{manifest['n_cells']} cells, recall "
              f"{manifest['recall_at_k']}, exhaustive-probe answer "
              "== brute")

        print("[5/6] daemon fuses 3 concurrent kNN jobs into one sweep")
        froot = root / "fusion_root"
        for i, k in enumerate((3, 4, 5)):
            rc = _tmx(["enqueue", "--root", str(froot),
                       "--experiment", str(store.root),
                       "--tenant", "query", "--job-id", f"q-knn-{k}",
                       "--kind", "query", "--tool", "knn",
                       "--objects", "nuclei",
                       "--payload", json.dumps({"k": k}),
                       "--index", "ivf"])
            if rc.returncode != 0:
                print(f"ANALYTICS FAIL: enqueue k={k} exited "
                      f"{rc.returncode}\n{rc.stdout}")
                return 1
        rc = _tmx(["serve", "run", "--root", str(froot), "--poll", "0.1",
                   "--max-jobs", "3"])
        if rc.returncode != 0:
            print(f"ANALYTICS FAIL: fusion serve run exited "
                  f"{rc.returncode}\n{rc.stdout[-3000:]}")
            return 1
        fdone = {p.stem: json.loads(p.read_text())["summary"]
                 for p in (froot / "spool" / "done").glob("*.json")}
        if sorted(fdone) != ["q-knn-3", "q-knn-4", "q-knn-5"]:
            print(f"ANALYTICS FAIL: expected all 3 fused jobs done, got "
                  f"{sorted(fdone)}")
            return 1
        caches = sorted(s["cache"] for s in fdone.values())
        fkeys = {s["key"] for s in fdone.values()}
        if caches != ["fused", "fused", "miss"] or len(fkeys) != 3 \
                or any(s.get("fusion_window") != 3 for s in fdone.values()):
            print(f"ANALYTICS FAIL: fusion window malformed (caches "
                  f"{caches}, {len(fkeys)} keys): {fdone}")
            return 1
        # per-job cache entries on disk, every follower naming the leader
        leader_key = next(s["key"] for s in fdone.values()
                          if s["cache"] == "miss")
        for s in fdone.values():
            cache_dir = Path(s["result_dir"])
            if not (cache_dir / "result.json").exists():
                print(f"ANALYTICS FAIL: fused job left no cache entry "
                      f"at {cache_dir}")
                return 1
            prov = json.loads((cache_dir / "query.json").read_text())
            if prov.get("fusion_window") != 3 \
                    or prov.get("fused_with") != leader_key:
                print(f"ANALYTICS FAIL: cache provenance malformed: "
                      f"{prov}")
                return 1
        fused_evs = [
            json.loads(line) for line in
            (froot / "serve" / "ledger.jsonl").read_text().splitlines()
            if '"query_fused"' in line
        ]
        fused_evs = [e for e in fused_evs
                     if e.get("event") == "query_fused"]
        if len(fused_evs) != 1 or fused_evs[0].get("window") != 3:
            print(f"ANALYTICS FAIL: expected one query_fused event with "
                  f"window 3, got {fused_evs}")
            return 1
        print(f"      fusion: 1 sweep answered 3 jobs (leader "
              f"{leader_key}, caches miss+2 fused)")

        print("[6/6] SLO + trace views for the query tenant")
        slo = _tmx(["slo", "--root", str(sroot), "--json"])
        if slo.returncode != 0:
            print(f"ANALYTICS FAIL: tmx slo exited {slo.returncode}\n"
                  f"{slo.stdout}")
            return 1
        slo_view = json.loads(slo.stdout)
        tenant = (slo_view.get("tenants") or {}).get("query")
        if not tenant or tenant.get("latency_p95_s") is None \
                or tenant.get("breach"):
            print(f"ANALYTICS FAIL: query tenant slo malformed: {tenant}")
            return 1
        print(f"      slo tenant query: p95 {tenant['latency_p95_s']:.3f}s "
              f"availability {tenant['availability']:.2%}")

        trace_out = root / "analytics_trace.json"
        tr = _tmx(["trace", "--root", str(sroot), "--export", "chrome",
                   str(trace_out)])
        if tr.returncode != 0:
            print(f"ANALYTICS FAIL: chrome trace export exited "
                  f"{tr.returncode}\n{tr.stdout}")
            return 1
        doc = json.loads(trace_out.read_text())
        slices = [e for e in doc.get("traceEvents") or []
                  if e.get("ph") == "X"]
        names = {e.get("name", "").split(":")[0] for e in slices}
        if "query_tool" not in names and "feature_store" not in names:
            print(f"ANALYTICS FAIL: trace carries no query phases "
                  f"(slice names: {sorted(names)})")
            return 1
        print(f"      chrome trace: {len(slices)} slices incl. query "
              "phases")

        if args.artifacts:
            art = Path(args.artifacts)
            art.mkdir(parents=True, exist_ok=True)
            (art / "analytics_slo.json").write_text(slo.stdout or "")
            shutil.copy(trace_out, art / "analytics_trace.json")
            (art / "analytics_queries.json").write_text(json.dumps({
                "knn_miss": knn1, "knn_hit": knn2,
                "knn_indexed": knn_ivf,
                "clustering_oneshot": clus,
                "clustering_served": cl, "enrichment_served": enr,
                "fused_served": fdone,
            }, indent=2, default=str))
            (art / "analytics_index_manifest.json").write_text(
                json.dumps({"build": manifest, "list": listing},
                           indent=2, default=str))
            shutil.copy(sroot / "serve" / "ledger.jsonl",
                        art / "analytics_serve_ledger.jsonl")
            shutil.copy(froot / "serve" / "ledger.jsonl",
                        art / "analytics_fusion_ledger.jsonl")

        print("ANALYTICS PASS: digest-keyed query cache shared across "
              "one-shot and served paths")
        return 0


if __name__ == "__main__":
    sys.exit(main())
