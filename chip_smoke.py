#!/usr/bin/env python3
"""Does the plate -> features path start, run and tell the truth on the
attached TPU?  The quickest proof, kept in the repo for every later PR.

    python chip_smoke.py            # one chip: workflow, kernel, serve phases
    python chip_smoke.py --chips 4  # four chips: the sharded paths only

One process holds the chip: every phase calls ``tmlibrary_tpu.cli.main``
(the ``tmx`` console script) in THIS process, the serve daemon included
(``--max-jobs``), and nothing is started beside it.

Default run, on a ``tpu`` platform:

* **workflow** — a seeded synthetic Cell Painting plate at acquisition
  geometry (2160x2160 uint16, DAPI + Actin, 2 wells x 4 fields, hundreds
  of nuclei per field) written as 16-bit TIFFs, then ``tmx create`` and
  ``tmx workflow submit`` over metaconfig -> imextract -> corilla ->
  illuminati -> jterator with the config-3 description.  Batch size,
  in-flight depth and reduction strategy are the engine's own resolution
  and are printed.  Checked: per-site object counts equal the scipy
  reference chain on the same pixels, intensity features within the
  parity tests' tolerance, no ``backend_degraded`` / ``batch_failed`` /
  ``depth_clamped`` event, every array jterator returned on a tpu device.
* **kernels** — each Pallas kernel of ``ops/pallas_kernels.py`` compiled
  NON-interpreted and run once, bit-identical to its XLA twin.  A kernel
  the compiler refuses is listed as refused and fails the phase unless
  ``method="auto"`` keeps it out of dispatch.
* **serve** — ``tmx enqueue`` a ``kind: workflow`` job on a second,
  one-well plate and a ``kind: query`` kNN over the features the workflow
  phase wrote, then ``tmx serve run --max-jobs 2``; both reach ``done/``
  and the kNN equals a brute-force numpy kNN.

On any other platform the same phases run as a rehearsal at a tiny size
(64x64 fields, 1 well x 4 fields, interpret-mode kernels) and the run
still ends ``"ok": false`` with a non-zero exit: a number or a pass from
a CPU is not a chip result.

Output: one JSON object per phase, then as the LAST line exactly
``{"ok": ..., "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

SEED = 21


# --------------------------------------------------------------- reporting
def emit(record: dict) -> None:
    print(json.dumps(record, sort_keys=True, default=str), flush=True)


class CompileMeter:
    """Counts what JAX's own monitoring events report: persistent-cache
    hits and misses, and seconds spent in backend compiles (a cache hit is
    a short one).  ``since(mark)`` gives a phase its share."""

    def __init__(self):
        import jax.monitoring as monitoring

        self.hits = self.misses = 0
        self.compile_s = 0.0
        monitoring.register_event_listener(self._on_event)
        monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += duration

    def mark(self):
        return (self.hits, self.misses, self.compile_s)

    def since(self, mark) -> dict:
        return {"cache_hits": self.hits - mark[0],
                "cache_misses": self.misses - mark[1],
                "compile_s": round(self.compile_s - mark[2], 3)}


def peak_hbm_bytes():
    import jax

    stats = jax.devices()[0].memory_stats()
    return stats.get("peak_bytes_in_use") if stats else None


class Phase:
    """Times one phase and prints its record, pass or fail.  An exception
    inside the phase is recorded AND re-raised: no phase failure lets the
    run end 0."""

    def __init__(self, name: str, meter: CompileMeter, records: list):
        self.name, self.meter, self.records = name, meter, records
        self.fields: dict = {}

    def __enter__(self):
        self.t0 = time.perf_counter()
        self.mark = self.meter.mark()
        return self.fields

    def __exit__(self, exc_type, exc, tb):
        total = time.perf_counter() - self.t0
        compile_part = self.meter.since(self.mark)
        record = {
            "phase": self.name,
            "passed": exc is None and all(
                self.fields.get("checks", {"ran": True}).values()),
            "seconds": {"total": round(total, 3),
                        "compile": compile_part["compile_s"],
                        "run": round(total - compile_part["compile_s"], 3)},
            "compile_cache": {"hits": compile_part["cache_hits"],
                              "misses": compile_part["cache_misses"]},
            "peak_hbm_bytes": peak_hbm_bytes(),
            **self.fields,
        }
        if exc is not None:
            record["error"] = f"{exc_type.__name__}: {exc}"
        self.records.append(record)
        emit(record)
        return False


# ------------------------------------------------------------------- plate
def synth_field(rng, size: int, n_cells: int):
    """One seeded DAPI + Actin field, uint16.  Same recipe as
    ``benchmarks.synthetic_cell_painting_batch`` (noise floor, Gaussian
    nuclei, wider Gaussian cell bodies) but each cell is stamped into a
    local window, so a 2160x2160 field with hundreds of cells takes
    milliseconds instead of minutes."""
    import numpy as np

    dapi = rng.normal(300.0, 25.0, (size, size)).astype(np.float32)
    actin = rng.normal(300.0, 25.0, (size, size)).astype(np.float32)
    margin = max(4, size // 20)
    ys = rng.integers(margin, size - margin, n_cells)
    xs = rng.integers(margin, size - margin, n_cells)
    for y, x in zip(ys, xs):
        r_n = rng.uniform(3.5, 5.5)
        r_c = r_n * rng.uniform(2.0, 3.0)
        half = int(4 * r_c) + 1
        y0, y1 = max(0, y - half), min(size, y + half + 1)
        x0, x1 = max(0, x - half), min(size, x + half + 1)
        yy, xx = np.mgrid[y0:y1, x0:x1].astype(np.float32)
        d2 = (yy - y) ** 2 + (xx - x) ** 2
        dapi[y0:y1, x0:x1] += 4000.0 * np.exp(-d2 / (2 * r_n ** 2))
        actin[y0:y1, x0:x1] += 1500.0 * np.exp(-d2 / (2 * r_c ** 2))
    return {"DAPI": np.clip(dapi, 0, 65535).astype(np.uint16),
            "Actin": np.clip(actin, 0, 65535).astype(np.uint16)}


def write_plate(src: str, wells, fields: int, size: int, cells, seed: int):
    """``<well>_s<field>_<channel>.tif`` files, 16-bit, so metaconfig's
    default handler parses them and imextract's native TIFF decoder runs."""
    import cv2
    import numpy as np

    rng = np.random.default_rng(seed)
    os.makedirs(src)
    for well in wells:
        for field in range(fields):
            planes = synth_field(rng, size, int(rng.integers(*cells)))
            for chan, img in planes.items():
                path = os.path.join(src, f"{well}_s{field}_{chan}.tif")
                if not cv2.imwrite(path, img):
                    raise RuntimeError(f"could not write {path}")
    return len(wells) * fields


PIPE = "cell_painting.pipe.yaml"


def canonical_steps(capacity: int, n_devices: int, illuminati: bool = True,
                    **jterator) -> dict:
    """corilla -> illuminati -> jterator(config 3) args; batch size, depth
    and strategy stay the engine's to resolve unless ``jterator`` says."""
    steps = {
        "corilla": {"n_devices": n_devices},
        "illuminati": {},
        "jterator": {"pipe": PIPE, "max_objects": capacity,
                     "n_devices": n_devices, **jterator},
    }
    if not illuminati:
        del steps["illuminati"]
    return steps


def write_description(root: str, src: str, steps: dict) -> str:
    """``workflow.yaml`` (the serialized form ``tmx workflow submit``
    reads): metaconfig and imextract over ``src``, then ``steps``."""
    import yaml

    from tmlibrary_tpu.benchmarks import CELL_PAINTING_PIPE
    from tmlibrary_tpu.workflow.engine import WorkflowDescription

    with open(os.path.join(root, PIPE), "w") as f:
        yaml.safe_dump(CELL_PAINTING_PIPE, f)
    path = os.path.join(root, "workflow.yaml")
    WorkflowDescription.canonical({
        "metaconfig": {"source_dir": src, "sites_per_well_x": 2},
        "imextract": {},
        **steps,
    }).save(path)
    return path


def tmx(argv: list) -> None:
    """The ``tmx`` console script, in this process.  What it prints goes
    to stderr, so stdout carries the phase records alone."""
    import contextlib

    from tmlibrary_tpu import cli

    with contextlib.redirect_stdout(sys.stderr):
        rc = cli.main([str(a) for a in argv])
    if rc != 0:
        raise RuntimeError(f"tmx {' '.join(map(str, argv[:2]))} exited {rc}")


def ledger_events(root: str) -> list:
    from pathlib import Path

    from tmlibrary_tpu.workflow.engine import RunLedger

    return RunLedger(Path(root) / "workflow" / "ledger.jsonl").events()


# --------------------------------------------------- what jterator returned
class ReturnedArrays:
    """Watches ``ImageAnalysisRunner.block_batch`` — the one place every
    launched batch's device arrays pass through — and records where each
    array lived: platform, and per device the shard shapes it held."""

    def __init__(self):
        from tmlibrary_tpu.workflow.steps.jterator import ImageAnalysisRunner

        self.cls = ImageAnalysisRunner
        self.original = ImageAnalysisRunner.block_batch
        self.platforms: set = set()
        self.images: dict = {}   # device id -> shapes of (.., H, W) shards
        self.n_arrays = 0

    def __enter__(self):
        import jax

        watcher = self

        def block_batch(step, ctx):
            kind, payload = ctx
            tree = payload[0] if kind == "sites" else \
                [payload["labels_dev"], payload["count_dev"]]
            for leaf in jax.tree_util.tree_leaves(tree):
                if not isinstance(leaf, jax.Array):
                    continue
                watcher.n_arrays += 1
                for shard in leaf.addressable_shards:
                    watcher.platforms.add(shard.device.platform)
                    shape = tuple(shard.data.shape)
                    if len(shape) >= 2 and shape[-1] > 8:
                        watcher.images.setdefault(
                            shard.device.id, set()).add(shape)
            return watcher.original(step, ctx)

        self.cls.block_batch = block_batch
        return self

    def __exit__(self, *exc):
        self.cls.block_batch = self.original
        return False

    def held_a_proper_shard(self, n_devices: int, whole: int) -> bool:
        """Every device holds a label-image shard, and it is a part of
        the ``whole`` leading extent, not a replica of it."""
        return len(self.images) == n_devices and all(
            any(shape[0] < whole for shape in shapes)
            for shapes in self.images.values())

    def summary(self) -> dict:
        return {"arrays": self.n_arrays,
                "platforms": sorted(self.platforms),
                "image_shards_per_device": {
                    str(d): sorted(map(list, s))
                    for d, s in sorted(self.images.items())}}


# --------------------------------------------------------- workflow checks
def check_counts_and_features(store) -> dict:
    """Per-site object counts against the repository's scipy reference
    chain (``benchmarks.cpu_reference_site``) on the pixels the store
    holds, and the nuclei intensity features against numpy on the stored
    label stack (tolerances of tests/test_measure.py: mean and sum 1e-5
    relative, min and max exact)."""
    import numpy as np

    from tmlibrary_tpu.benchmarks import cpu_reference_site

    n_sites = store.n_sites
    exp = store.experiment
    dapi = store.read_sites(
        None, channel=exp.channel_index("DAPI")).astype(np.float32)
    actin = store.read_sites(
        None, channel=exp.channel_index("Actin")).astype(np.float32)
    want = [cpu_reference_site(dapi[s], actin[s]) for s in range(n_sites)]
    got = {}
    for name in ("nuclei", "cells"):
        table = store.read_features(name)
        per_site = table.groupby("site_index").size()
        got[name] = [int(per_site.get(s, 0)) for s in range(n_sites)]
    counts_equal = (got["nuclei"] == [w[0] for w in want]
                    and got["cells"] == [w[1] for w in want])

    labels = store.read_labels(None, "nuclei")
    table = store.read_features("nuclei").set_index(["site_index", "label"])
    worst = 0.0
    minmax_exact = True
    for s in range(n_sites):
        lab, img = labels[s].ravel(), dapi[s].ravel().astype(np.float64)
        n = int(lab.max())
        if n == 0:
            continue
        area = np.bincount(lab, minlength=n + 1)[1:]
        total = np.bincount(lab, weights=img, minlength=n + 1)[1:]
        order = np.argsort(lab, kind="stable")
        starts = np.searchsorted(lab[order], np.arange(1, n + 1))
        mins = np.minimum.reduceat(img[order], starts)
        maxs = np.maximum.reduceat(img[order], starts)
        rows = table.loc[s].sort_index()
        ids = rows.index.to_numpy()
        mean = rows["Intensity_mean_DAPI"].to_numpy()
        ssum = rows["Intensity_sum_DAPI"].to_numpy()
        worst = max(
            worst,
            float(np.max(np.abs(mean - (total / area)[ids - 1])
                         / (total / area)[ids - 1])),
            float(np.max(np.abs(ssum - total[ids - 1]) / total[ids - 1])),
        )
        minmax_exact &= bool(
            np.array_equal(rows["Intensity_min_DAPI"].to_numpy(),
                           mins[ids - 1])
            and np.array_equal(rows["Intensity_max_DAPI"].to_numpy(),
                               maxs[ids - 1]))
    return {
        "object_counts": got,
        "reference_counts": {"nuclei": [w[0] for w in want],
                             "cells": [w[1] for w in want]},
        "counts_equal_scipy_chain": counts_equal,
        "intensity_worst_rel_err": worst,
        "intensity_within_tolerance": worst <= 1e-5 and minmax_exact,
    }


def resolved_by_the_engine(events: list) -> dict:
    """Batch size, in-flight depth and reduction strategy as the engine
    resolved them, read back from the run ledger."""
    from tmlibrary_tpu import tuning
    from tmlibrary_tpu.ops.reduction import resolve_reduction_strategy

    done = [e for e in events if e.get("event") == "batch_done"
            and e.get("step") == "jterator"]
    step_done = [e for e in events if e.get("event") in
                 ("step_done", "step_partial") and e.get("step") == "jterator"]
    stats = (step_done[-1].get("pipeline_stats") or {}) if step_done else {}
    return {
        "batches": len(done),
        "batch_size": max((int((e.get("result") or {}).get("n_sites", 0))
                           for e in done), default=0),
        "pipeline_depth": stats.get("depth"),
        "pipeline_depth_source": stats.get("source"),
        "reduction_strategy": resolve_reduction_strategy(),
        "routed_capacities": sorted({
            int((e.get("result") or {}).get("bucket_capacity"))
            for e in done if (e.get("result") or {}).get("bucket_capacity")}),
        "bucket_escalations": sum(
            int((e.get("result") or {}).get("bucket_escalations", 0))
            for e in done),
        "tuning_json": {k: (tuning.load_tuning() or {}).get(k) for k in (
            "best_batch", "best_pipeline", "kernels_ms", "pallas_wins",
            "reduction_strategy")},
    }


FORBIDDEN_EVENTS = ("backend_degraded", "batch_failed", "depth_clamped")


def run_workflow(root: str, src: str, steps: dict) -> list:
    tmx(["create", "--name", os.path.basename(root), "--root", root])
    wf = write_description(root, src, steps)
    tmx(["workflow", "submit", "--description", wf, "--root", root])
    return ledger_events(root)


# ------------------------------------------------------------------ phases
def phase_workflow(fields, work, shape) -> str:
    from pathlib import Path

    from tmlibrary_tpu import aotstore, native
    from tmlibrary_tpu.models.store import ExperimentStore

    src, root = os.path.join(work, "plate_a_src"), os.path.join(work, "plate_a")
    n = write_plate(src, shape["wells"], 4, shape["size"], shape["cells"],
                    SEED)
    fields["sites"] = n
    fields["field"] = [shape["size"], shape["size"]]
    fields["native_library"] = (native.status()["source_digest"]
                                if native.available() else "absent")
    with ReturnedArrays() as returned:
        events = run_workflow(
            root, src, canonical_steps(shape["capacity"], 1))
    store = ExperimentStore.open(Path(root))
    fields.update(resolved_by_the_engine(events))
    fields["returned"] = returned.summary()
    fields["forbidden_events"] = sorted(
        {e["event"] for e in events if e.get("event") in FORBIDDEN_EVENTS})
    stats = aotstore.store_stats()
    fields["executable_store"] = {
        "dir": stats["dir"], "entries": stats["entries"],
        "total_bytes": stats["total_bytes"],
        "cap_bytes": aotstore.max_store_bytes(),
        **aotstore.counts_snapshot()}
    fields.update(check_counts_and_features(store))
    fields["checks"] = {
        "counts_equal_scipy_chain": fields["counts_equal_scipy_chain"],
        "intensity_within_tolerance": fields["intensity_within_tolerance"],
        "no_forbidden_event": not fields["forbidden_events"],
        # the window the pipelined executor needs; the CPU's static
        # batch of 32 swallows the rehearsal plate whole
        "at_least_two_batches": fields["batches"] >= 2 or shape["interpret"],
        "arrays_on_one_platform": (
            returned.n_arrays > 0
            and returned.platforms == {shape["platform"]}),
    }
    return root


def kernel_cases():
    """(name, kernel call, XLA twin call) for every Pallas kernel; 2-D at
    256x256, the 3-D twins at 16x128x128."""
    import jax.numpy as jnp
    import numpy as np

    from tmlibrary_tpu.benchmarks import (
        synthetic_cell_painting_batch, synthetic_volume_batch)
    from tmlibrary_tpu.ops import label, volume
    from tmlibrary_tpu.ops import pallas_kernels as pk
    from tmlibrary_tpu.ops import threshold as thr
    from tmlibrary_tpu.ops.segment_primary import distance_transform_approx
    from tmlibrary_tpu.ops.segment_secondary import watershed_from_seeds
    from tmlibrary_tpu.ops.smooth import gaussian_smooth

    # whole-number pixels, as a camera gives them
    site = synthetic_cell_painting_batch(1, size=256, seed=SEED)
    dapi = jnp.asarray(np.floor(site["DAPI"][0]))
    actin = jnp.asarray(np.floor(site["Actin"][0]))
    mask = thr.threshold_otsu(gaussian_smooth(dapi, 1.5))
    nuclei = label.connected_components(mask, 8, method="xla")[0]
    cell_mask = thr.threshold_otsu(actin, correction_factor=0.8)
    vol = jnp.asarray(
        synthetic_volume_batch(1, size=128, seed=SEED)["DAPI"][0])
    if vol.shape != (16, 128, 128):
        raise RuntimeError(f"volume fixture is {vol.shape}")
    vmask = vol > jnp.median(vol) + 0.5 * vol.std()
    seeds3 = volume.connected_components_3d(vmask, 26, method="xla")[0]

    def cc(conn):
        return (lambda: label.connected_components(
                    mask, conn, method="pallas")[0],
                lambda: label.connected_components(mask, conn, method="xla")[0])

    cases = [
        ("cc4", *cc(4)), ("cc8", *cc(8)),
        ("watershed",
         lambda: watershed_from_seeds(actin, nuclei, cell_mask, n_levels=16,
                                      method="pallas"),
         lambda: watershed_from_seeds(actin, nuclei, cell_mask, n_levels=16,
                                      method="xla")),
        ("fill", lambda: label.fill_holes(mask, method="pallas"),
         lambda: label.fill_holes(mask, method="xla")),
        ("distance",
         lambda: distance_transform_approx(mask, method="pallas"),
         lambda: distance_transform_approx(mask, method="xla")),
        ("cc3d",
         lambda: volume.connected_components_3d(vmask, 26, method="pallas")[0],
         lambda: volume.connected_components_3d(vmask, 26, method="xla")[0]),
        ("watershed3d",
         lambda: volume.watershed_from_seeds_3d(vol, seeds3, vmask, 8,
                                                method="pallas"),
         lambda: volume.watershed_from_seeds_3d(vol, seeds3, vmask, 8,
                                                method="xla")),
    ]
    return cases


def phase_kernels(fields, shape) -> None:
    import jax
    import numpy as np

    from jax._src.pallas.mosaic.lowering import LoweringException

    # what a refusal looks like: the Pallas lowering's own errors, and
    # Mosaic's or XLA's from the compile.  Anything else is a fault in
    # the kernel's wrapper and stops the run.
    refusals = (LoweringException, NotImplementedError, ValueError,
                jax.errors.JaxRuntimeError)
    compiled, refused, mismatched = [], {}, []
    for name, kernel, twin in kernel_cases():
        want = jax.tree_util.tree_leaves(twin())
        try:
            got = jax.block_until_ready(kernel())
        except refusals as e:
            refused[name] = f"{type(e).__name__}: {e}".splitlines()[0][:300]
            continue
        compiled.append(name)
        got = jax.tree_util.tree_leaves(got)
        same = len(got) == len(want) and all(
            np.array_equal(np.asarray(g), np.asarray(w))
            for g, w in zip(got, want))
        if not same:
            mismatched.append(name)
    fields["kernels_compiled"] = compiled
    fields["kernels_refused"] = refused
    fields["kernels_mismatched"] = mismatched
    fields["interpret_mode"] = shape["interpret"]
    fields["checks"] = {"none_refused": not refused,
                        "all_match_their_twin": not mismatched}


def phase_serve(fields, work, plate_a: str, shape) -> None:
    import numpy as np

    from pathlib import Path

    from tmlibrary_tpu.analytics.store import FeatureStore
    from tmlibrary_tpu.models.store import ExperimentStore
    from tmlibrary_tpu.tools.base import ToolResult

    src, root = os.path.join(work, "plate_b_src"), os.path.join(work, "plate_b")
    sroot = os.path.join(work, "serve_root")
    write_plate(src, ("B01",), 4, shape["size"], shape["cells"], SEED + 1)
    tmx(["create", "--name", "plate_b", "--root", root])
    wf = write_description(root, src, canonical_steps(shape["capacity"], 1))
    k = 5
    tmx(["enqueue", "--root", sroot, "--experiment", root, "--job-id",
         "plate-b", "--kind", "workflow", "--description", wf])
    tmx(["enqueue", "--root", sroot, "--experiment", plate_a, "--job-id",
         "knn-a", "--kind", "query", "--tool", "knn", "--objects", "nuclei",
         "--payload", json.dumps({"k": k, "index": "brute"})])
    tmx(["serve", "run", "--root", sroot, "--poll", "0.1", "--max-jobs", "2"])

    done = {p.stem: json.loads(p.read_text())
            for p in (Path(sroot) / "spool" / "done").glob("*.json")}
    fields["jobs_done"] = sorted(done)
    events = ledger_events(root)
    fields["forbidden_events"] = sorted(
        {e["event"] for e in events if e.get("event") in FORBIDDEN_EVENTS})
    store_b = ExperimentStore.open(Path(root))
    fields["sites"] = store_b.n_sites
    counts = check_counts_and_features(store_b)
    fields["object_counts"] = counts["object_counts"]

    # the query's answer against brute force in numpy on the same matrix
    summary = done.get("knn-a", {}).get("summary", {})
    result = ToolResult.load(Path(summary["result_dir"]))
    _, x, _ = FeatureStore.ensure(
        ExperimentStore.open(Path(plate_a)), "nuclei").standardized(None)
    x = np.asarray(x, np.float64)
    sq = (x * x).sum(1)
    d2 = sq[:, None] - 2.0 * x @ x.T + sq[None, :]
    np.fill_diagonal(d2, np.inf)
    ref_idx = np.argsort(d2, axis=1, kind="stable")[:, :k]
    ref_dist = np.sqrt(np.maximum(np.take_along_axis(d2, ref_idx, 1), 0.0))
    got_idx = np.stack([result.values[f"nn{j}"].to_numpy() for j in range(k)], 1)
    got_dist = np.stack([result.values[f"nnd{j}"].to_numpy() for j in range(k)], 1)
    # equal means equal, slot by slot.  The one thing float32 cannot
    # decide is the order of two neighbours whose float64 distances lie
    # within one float32 ulp (1.2e-7 relative) of each other, so a slot
    # may differ only by such a tie (tests/test_analytics_index.py).
    rows, slots = np.nonzero(got_idx != ref_idx)
    d_got = np.sqrt(np.maximum(d2[rows, got_idx[rows, slots]], 0.0))
    d_ref = ref_dist[rows, slots]
    not_ties = int((np.abs(d_got - d_ref) > 1.2e-7 * d_ref).sum())
    fields["knn"] = {
        "objects": int(x.shape[0]), "k": k,
        "slots_differing": int(rows.size),
        "slots_differing_not_ties": not_ties,
        "max_abs_distance_error": float(np.abs(got_dist - ref_dist).max()),
    }
    fields["checks"] = {
        "both_jobs_done": sorted(done) == ["knn-a", "plate-b"],
        "no_forbidden_event": not fields["forbidden_events"],
        "counts_equal_scipy_chain": counts["counts_equal_scipy_chain"],
        # distances to tests/test_analytics.py's 1e-4
        "knn_equals_bruteforce": (
            not_ties == 0
            and fields["knn"]["max_abs_distance_error"] <= 1e-4),
    }


# ------------------------------------------------- four chips: sharded paths
def phase_sharded(fields, work, shape, n_devices: int) -> None:
    """jterator + corilla with the site batch sharded over every device,
    against the same steps on one device of this process.  The batch is
    the engine's own resolution in both runs (one field per device of
    the mesh).  Both pin the capacity (``object_buckets`` off): this
    phase compares shardings, and the bucket ladder's cold-start cost is
    the one-chip workflow phase's to show — here it would be paid twice
    on four chips for nothing this phase checks."""
    import numpy as np

    from pathlib import Path

    from tmlibrary_tpu.models.store import ExperimentStore

    src = os.path.join(work, "plate_src")
    write_plate(src, ("A01", "A02"), 4, shape["size"], shape["cells"], SEED)
    stores, shards = {}, {}
    for n in (n_devices, 1):
        root = os.path.join(work, f"sharded_{n}dev")
        with ReturnedArrays() as returned:
            events = run_workflow(
                root, src,
                canonical_steps(shape["capacity"], n, illuminati=False,
                                object_buckets="off"))
        stores[n] = ExperimentStore.open(Path(root))
        shards[n] = returned
        fields[f"batch_size_{n}dev"] = resolved_by_the_engine(
            events)["batch_size"]
        fields[f"forbidden_events_{n}dev"] = sorted(
            {e["event"] for e in events if e.get("event") in FORBIDDEN_EVENTS})
    many, one = stores[n_devices], stores[1]
    fields["field"] = [shape["size"], shape["size"]]
    fields["sites"] = many.n_sites
    fields["jterator_shards"] = shards[n_devices].summary()
    labels_equal = all(
        np.array_equal(many.read_labels(None, name),
                       one.read_labels(None, name))
        for name in ("nuclei", "cells"))
    feats_equal = True
    for name in ("nuclei", "cells"):
        key = ["site_index", "label"]
        a = many.read_features(name).sort_values(key).reset_index(drop=True)
        b = one.read_features(name).sort_values(key).reset_index(drop=True)
        feats_equal &= bool(a.shape == b.shape and (a[key] == b[key]).all().all())
    fields["object_counts"] = {
        name: many.read_features(name).groupby("site_index").size().tolist()
        for name in ("nuclei", "cells")}
    # sharded vs sequential Welford: tests/test_stats.py's tolerance
    welford_ok = True
    for channel in range(many.experiment.n_channels):
        a, b = many.read_illumstats(channel=channel), \
            one.read_illumstats(channel=channel)
        welford_ok &= bool(
            np.allclose(a["mean_log"], b["mean_log"], rtol=1e-5)
            and np.allclose(a["std_log"], b["std_log"], rtol=5e-3, atol=1e-5))
    fields["checks"] = {
        "labels_bit_identical": labels_equal,
        "counts_and_ids_identical": feats_equal,
        "welford_within_tolerance": welford_ok,
        "every_device_held_a_shard":
            shards[n_devices].held_a_proper_shard(n_devices, n_devices),
        # no device recomputes a padded copy of the batch's first site
        "batch_fills_the_mesh":
            fields[f"batch_size_{n_devices}dev"] % n_devices == 0
            or shape["interpret"],
        "no_forbidden_event": not (fields[f"forbidden_events_{n_devices}dev"]
                                   or fields["forbidden_events_1dev"]),
    }


def phase_spatial(fields, work, shape, n_devices: int) -> None:
    """One well's mosaic (2x2 full fields: 4320x4320 on the chip) through
    ``--layout spatial`` — halo exchange + seam merge, the path with real
    collectives — on every device, against the same mosaic on one."""
    import numpy as np

    from pathlib import Path

    from tmlibrary_tpu.models.store import ExperimentStore

    src = os.path.join(work, "well_src")
    size = shape["size"]
    write_plate(src, ("A01",), 4, size, shape["cells"], SEED + 2)
    fields["field"] = [size, size]
    stores, shards = {}, {}
    for n in (n_devices, 1):
        root = os.path.join(work, f"spatial_{n}dev")
        with ReturnedArrays() as returned:
            run_workflow(root, src, {"jterator": {
                "layout": "spatial", "n_devices": n,
                "spatial_channel": "DAPI",
                "max_objects": 4 * shape["capacity"]}})
        stores[n] = ExperimentStore.open(Path(root))
        shards[n] = returned
    many, one = stores[n_devices], stores[1]
    a = many.read_labels(None, "mosaic_cells")
    b = one.read_labels(None, "mosaic_cells")
    fields["mosaic"] = [2 * size, 2 * size]
    fields["objects"] = int(a.max())
    fields["spatial_shards"] = shards[n_devices].summary()
    fields["checks"] = {
        "labels_bit_identical": bool(np.array_equal(a, b)),
        "counts_identical": int(a.max()) == int(b.max()) and int(a.max()) > 0,
        "every_device_held_a_shard": shards[n_devices].held_a_proper_shard(
            n_devices, 2 * size),
    }


# -------------------------------------------------------------------- main
def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        description="run the plate -> features path on the attached TPU")
    parser.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="4: run ONLY the sharded jterator/corilla and spatial-layout "
             "paths over four devices, each against one device")
    args = parser.parse_args(argv)

    import jax

    from tmlibrary_tpu.utils import enable_compilation_cache

    cache_dir = enable_compilation_cache()
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    on_chip = device["platform"] == "tpu"
    # a chip run is the real size; anything else is the tiny rehearsal
    shape = ({"size": 2160, "cells": (350, 650), "capacity": 1024,
              "wells": ("A01", "A02")}
             if on_chip else
             {"size": 64, "cells": (3, 7), "capacity": 16, "wells": ("A01",)})
    shape.update(platform=device["platform"], interpret=not on_chip)
    meter = CompileMeter()
    records: list = []
    emit({"phase": "start", "device": device, "chips_asked": args.chips,
          "rehearsal": not on_chip, "field": [shape["size"]] * 2,
          "compile_cache_dir": cache_dir,
          "compile_cache_from_env":
              bool(os.environ.get("JAX_COMPILATION_CACHE_DIR"))})
    if len(devices) < args.chips:
        emit({"ok": False, "device": device})
        return 1

    work = tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        if args.chips == 4:
            # the path with collectives first: a four-chip second costs
            # four, so what is likelier to fail fails early
            with Phase("spatial", meter, records) as fields:
                phase_spatial(fields, work, shape, 4)
            with Phase("sharded", meter, records) as fields:
                phase_sharded(fields, work, shape, 4)
        else:
            with Phase("workflow", meter, records) as fields:
                plate_a = phase_workflow(fields, work, shape)
            with Phase("kernels", meter, records) as fields:
                phase_kernels(fields, shape)
            with Phase("serve", meter, records) as fields:
                phase_serve(fields, work, plate_a, shape)
    finally:
        shutil.rmtree(work, ignore_errors=True)

    ok = (on_chip and len(devices) == args.chips
          and all(r["passed"] for r in records))
    emit({"ok": ok, "device": device})
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
