#!/usr/bin/env python
"""Per-stage timing of the cell-painting bench pipeline on the current device.

Each timed fn reduces its output to ONE scalar inside jit so the fetch that
fences a timed run transfers a few bytes, not megapixels.
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

if os.environ.get("BENCH_FORCE_CPU"):
    # rehearsal: the CPU backend, whatever is attached
    jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp
import numpy as np

from tmlibrary_tpu.benchmarks import synthetic_cell_painting_batch
from tmlibrary_tpu.ops import label as lab
from tmlibrary_tpu.ops import threshold as thr
from tmlibrary_tpu.ops.segment_primary import segment_primary
from tmlibrary_tpu.ops.segment_secondary import watershed_from_seeds
from tmlibrary_tpu.ops.measure import intensity_features
from tmlibrary_tpu.ops.smooth import gaussian_smooth

BATCH = int(os.environ.get("BENCH_BATCH", "64"))
SIZE = int(os.environ.get("BENCH_SITE_SIZE", "256"))
MAXOBJ = int(os.environ.get("BENCH_MAX_OBJECTS", "64"))


PIPELINE = int(os.environ.get("PROFILE_PIPELINE", "8"))


#: stage name -> best ms, in measurement order (dict preserves insertion)
STAGES: "dict[str, float]" = {}
#: stage name -> (flops, bytes accessed) from XLA's cost model — the
#: bytes side of the roofline (round-4 VERDICT next-step #3: MFU alone
#: is the wrong lens for this memory/latency-shaped workload)
STAGE_COST: "dict[str, tuple]" = {}


def timeit(name, fn, *args):
    """Pipelined timing: PIPELINE executions per ONE fenced fetch, so the
    per-fence host cost is amortized out of every stage number."""
    try:
        an = fn.lower(*args).compile().cost_analysis()
        STAGE_COST[name] = (
            float(an.get("flops", 0.0)), float(an.get("bytes accessed", 0.0))
        )
    except Exception:
        STAGE_COST[name] = (0.0, 0.0)
    np.asarray(fn(*args))  # compile + warm
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        np.asarray(jnp.stack([fn(*args) for _ in range(PIPELINE)]))
        best = min(best, (time.perf_counter() - t0) / PIPELINE)
    STAGES[name] = best * 1e3
    gbps = STAGE_COST[name][1] / best / 1e9
    print(f"{name:35s} {best*1e3:9.2f} ms  ({BATCH/best:8.1f} sites/s, "
          f"{gbps:6.1f} GB/s)")


def scalar(fn):
    """Wrap fn so jit returns a single float32 checksum."""
    def wrapped(*args):
        out = fn(*args)
        leaves = jax.tree_util.tree_leaves(out)
        return sum(jnp.sum(jnp.asarray(l, jnp.float32)) for l in leaves)
    return jax.jit(wrapped)


def main():
    from tmlibrary_tpu.utils import enable_compilation_cache

    enable_compilation_cache()
    data = synthetic_cell_painting_batch(BATCH, size=SIZE)
    dapi = jax.device_put(jnp.asarray(data["DAPI"]))
    actin = jax.device_put(jnp.asarray(data["Actin"]))

    v = jax.vmap

    timeit("noop (fetch floor)", scalar(lambda a: a[:, 0, 0]), dapi)
    timeit("smooth(gauss 1.5)", scalar(v(lambda im: gaussian_smooth(im, 1.5))), dapi)

    sp = lambda im: segment_primary(
        im, threshold_method="otsu", smooth_sigma=0.0, min_area=20, max_objects=MAXOBJ
    )[0]
    timeit("segment_primary (full)", scalar(v(sp)), dapi)

    # stage internals of segment_primary
    smoothed = jax.jit(v(lambda im: gaussian_smooth(im, 1.5)))(dapi)
    otsu_mask = lambda im: thr.threshold_otsu(im)
    timeit("  otsu threshold", scalar(v(otsu_mask)), smoothed)
    masks = jax.jit(v(otsu_mask))(smoothed)
    timeit("  fill_holes", scalar(v(lab.fill_holes)), masks)
    filled = jax.jit(v(lab.fill_holes))(masks)
    timeit("  connected_components(xla)",
           scalar(v(lambda m: lab.connected_components(m, method="xla")[0])), filled)
    timeit("  connected_components(pallas)",
           scalar(v(lambda m: lab.connected_components(m, method="pallas")[0])), filled)
    nuclei = jax.jit(v(sp))(dapi)

    def sec_method(method):
        return lambda lbl, im: watershed_from_seeds(
            im, lbl, thr.threshold_otsu(im, correction_factor=0.8),
            n_levels=16, method=method,
        )

    timeit("segment_secondary (xla)", scalar(v(sec_method("xla"))), nuclei, actin)
    timeit("segment_secondary (pallas)", scalar(v(sec_method("pallas"))), nuclei, actin)
    cells = jax.jit(v(sec_method("xla")))(nuclei, actin)

    mi = lambda lbl, im: intensity_features(lbl, im, MAXOBJ)
    timeit("measure_intensity(nuclei)", scalar(v(mi)), nuclei, dapi)
    timeit("measure_intensity(cells)", scalar(v(mi)), cells, actin)

    from tmlibrary_tpu.ops.measure import (
        haralick_features,
        intensity_quantiles,
        morphology_features,
        zernike_features,
    )

    timeit("measure_morphology", scalar(v(lambda l: morphology_features(l, MAXOBJ))),
           nuclei)
    timeit("intensity_quantiles", scalar(v(lambda l, im: intensity_quantiles(
        l, im, MAXOBJ))), nuclei, dapi)
    for method in ("matmul", "scatter"):
        timeit(f"haralick L=16 ({method})", scalar(v(lambda l, im: haralick_features(
            l, im, MAXOBJ, levels=16, glcm_method=method))), nuclei, actin)
    timeit("zernike deg=6", scalar(v(lambda l: zernike_features(l, MAXOBJ, degree=6))),
           nuclei)

    out_path = os.environ.get("PROFILE_OUT")
    if out_path:
        # machine-readable capture of the per-stage table
        import json

        payload = {
            "stages_ms": {k: round(v, 3) for k, v in STAGES.items()},
            "stages_flops": {
                k: round(v[0]) for k, v in STAGE_COST.items()
            },
            "stages_bytes": {
                k: round(v[1]) for k, v in STAGE_COST.items()
            },
            "batch": BATCH,
            "site_size": SIZE,
            "max_objects": MAXOBJ,
            "pipeline": PIPELINE,
            "backend": jax.default_backend(),
            "device": str(jax.devices()[0]),
            "written_at": time.strftime(
                "%Y-%m-%dT%H:%M:%S+00:00", time.gmtime()
            ),
            "written_by": "scripts/profile_bench.py",
        }
        tmp = out_path + ".tmp"
        with open(tmp, "w") as f:
            # no sort_keys: stages_ms insertion order IS the pipeline
            # order and the renderer preserves it
            json.dump(payload, f, indent=2)
        os.replace(tmp, out_path)
        print(f"wrote {out_path}")


if __name__ == "__main__":
    main()
