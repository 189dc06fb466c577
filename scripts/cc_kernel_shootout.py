#!/usr/bin/env python
"""Interleaved A/B of CC kernel variants on the current device.

Run-to-run host variance swamps single measurements (the same
kernel measured 30 ms and 67 ms in adjacent processes); this interleaves
best-of-N timings of the shipped pallas kernel, CHUNK-granularity
variants of it, and the XLA twin on the SAME batch in ONE process so
they share whatever the link and host are doing.  Historical verdicts
this harness produced (recorded in ops/pallas_kernels.py): the
log-doubling segmented run-scan kernel measured ~2.2x SLOWER than plain
stepping, and the separable 3x3 window-min decomposition ~2x slower.
"""
import functools
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tmlibrary_tpu.benchmarks import synthetic_cell_painting_batch
from tmlibrary_tpu.ops.pallas_kernels import _cc_kernel
from tmlibrary_tpu.ops import label as lab
from tmlibrary_tpu.ops import threshold as thr
from tmlibrary_tpu.ops.smooth import gaussian_smooth

BATCH = int(os.environ.get("BENCH_BATCH", "64"))
SIZE = int(os.environ.get("BENCH_SITE_SIZE", "256"))
REPS = int(os.environ.get("BENCH_REPS", "5"))


def make(kernel):
    @jax.jit
    def run(masks):
        def one(m):
            return pl.pallas_call(
                functools.partial(kernel, connectivity=8),
                out_shape=jax.ShapeDtypeStruct((SIZE, SIZE), jnp.int32),
                in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
                out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
            )(m.astype(jnp.int32))
        return jnp.sum(jax.vmap(one)(masks))
    return run


@jax.jit
def run_xla(masks):
    def one(m):
        labels, _ = lab.connected_components(m, method="xla")
        return jnp.sum(labels)
    return jnp.sum(jax.vmap(one)(masks))


def main():
    data = synthetic_cell_painting_batch(BATCH, size=SIZE)
    dapi = jnp.asarray(data["DAPI"])
    smoothed = jax.jit(jax.vmap(lambda im: gaussian_smooth(im, 1.5)))(dapi)
    masks = jax.jit(jax.vmap(thr.threshold_otsu))(smoothed)
    masks = jax.device_put(np.asarray(masks))

    import tmlibrary_tpu.ops.pallas_kernels as pk

    def make_chunk(c):
        def kern(mask_ref, out_ref, *, connectivity):
            old = pk.CHUNK
            pk.CHUNK = c
            try:
                return _cc_kernel(mask_ref, out_ref, connectivity=connectivity)
            finally:
                pk.CHUNK = old
        return make(kern)

    variants = {
        "shipped": make(_cc_kernel),  # CHUNK as committed
        "chunk16": make_chunk(16),
        "chunk4": make_chunk(4),
        "xla": run_xla,
    }
    for name, fn in variants.items():
        np.asarray(fn(masks))  # compile + warm
    best = {name: float("inf") for name in variants}
    for _ in range(REPS):
        for name, fn in variants.items():  # interleaved
            t0 = time.perf_counter()
            np.asarray(fn(masks))
            best[name] = min(best[name], time.perf_counter() - t0)
    for name, t in best.items():
        print(f"{name:8s} {t * 1e3:9.2f} ms   ({BATCH / t:8.1f} sites/s)")


if __name__ == "__main__":
    main()
