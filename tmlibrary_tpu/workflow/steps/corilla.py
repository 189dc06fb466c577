"""corilla: online illumination statistics per channel.

Reference parity: ``tmlib/workflow/corilla/api.py``
``IlluminationStatisticsCalculator`` — one run job per channel folding every
site through ``OnlineStatistics`` and writing an ``IllumstatsFile``
(SURVEY.md §4.4).

TPU execution: sites stream through ``lax.scan`` in device-resident chunks
(bounded HBM) with the Welford carry living on device across chunks; on a
multi-chip mesh the site axis shards and shard states merge with the
parallel-variance fold (``tmlibrary_tpu.parallel.stats``).  The metric is
channels/sec (BASELINE.json).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from tmlibrary_tpu.ops.stats import (
    welford_finalize,
    welford_init,
    welford_merge,
    welford_scan,
)
from tmlibrary_tpu.parallel.mesh import shard_batch, site_mesh
from tmlibrary_tpu.parallel.stats import sharded_welford
from tmlibrary_tpu.utils import create_partitions
from tmlibrary_tpu.workflow.api import Step
from tmlibrary_tpu.workflow.pipelined import prefetch_iter
from tmlibrary_tpu.workflow.args import Argument, ArgumentCollection
from tmlibrary_tpu.workflow.registry import register_step

import functools


@functools.lru_cache(maxsize=1)
def _welford_scan_jit():
    """Shared jit wrapper: a per-run ``jax.jit(welford_scan)`` would
    re-trace every chunk shape on every step instance (re-run overhead
    measured by the workflow bench)."""
    return jax.jit(welford_scan)


@functools.lru_cache(maxsize=1)
def _welford_merge_jit():
    return jax.jit(welford_merge)


@register_step("corilla")
class IlluminationStatisticsCalculator(Step):
    batch_args = ArgumentCollection(
        Argument("chunk_size", int, default=32,
                 help="sites per device-resident chunk"),
        Argument("n_devices", int, default=0,
                 help="mesh size (0 = all visible devices)"),
        Argument("smooth_sigma", float, default=0.0,
                 help="pre-smooth stat fields before storing (0 = off)"),
        Argument("prefetch_chunks", int, default=2,
                 help="site chunks read ahead on worker threads while the "
                      "device scans the current chunk (1 = sequential)"),
    )

    def create_batches(self, args):
        # one batch per (cycle, channel), exactly the reference's job split
        exp = self.store.experiment
        return [
            {"cycle": cycle, "channel": ch.index}
            for cycle in range(exp.n_cycles)
            for ch in exp.channels
            if self.store.has_plane(cycle=cycle, channel=ch.index)
        ]

    def run_batch(self, batch: dict) -> dict:
        import time

        from tmlibrary_tpu import telemetry

        bt0 = time.perf_counter()
        args = batch["args"]
        cycle, channel = batch["cycle"], batch["channel"]
        exp = self.store.experiment
        n_sites = self.store.n_sites
        n_dev = args["n_devices"] or len(jax.devices())
        n_dev = min(n_dev, len(jax.devices()))
        chunk = max(args["chunk_size"], 1)

        site_indices = list(range(n_sites))
        state = None

        if n_dev > 1:
            mesh = site_mesh(n_dev)
            # largest site prefix divisible by the mesh; remainder scans below
            even = n_sites - n_sites % n_dev
            if even:
                stack = self.store.read_sites(site_indices[:even], cycle=cycle,
                                              channel=channel)
                state = jax.tree.map(
                    np.asarray, sharded_welford(shard_batch(jnp.asarray(stack), mesh), mesh)
                )
                site_indices = site_indices[even:]

        scan_jit = _welford_scan_jit()
        merge_jit = _welford_merge_jit()
        dev_state = None
        # store reads for chunk N+1 run on prefetch workers while the
        # device scans chunk N; prefetch_iter preserves chunk order, so
        # the Welford merge chain (order-sensitive in floating point) is
        # bit-identical to the sequential loop
        chunks = create_partitions(site_indices, chunk)
        loaded = prefetch_iter(
            chunks,
            lambda part: self.store.read_sites(part, cycle=cycle,
                                               channel=channel),
            depth=max(args.get("prefetch_chunks", 2), 1),
        )
        while True:
            with telemetry.span("read_wait"):
                stack = next(loaded, None)
            if stack is None:
                break
            # upload + dispatch: the device's work is waited for below
            with telemetry.span("scan", bytes=stack.nbytes):
                if dev_state is None:
                    dev_state = scan_jit(jnp.asarray(stack))
                else:
                    dev_state = merge_jit(dev_state,
                                          scan_jit(jnp.asarray(stack)))
        with telemetry.span("finalize"):
            if dev_state is not None:
                state = (
                    jax.tree.map(np.asarray, dev_state)
                    if state is None
                    else jax.tree.map(
                        np.asarray,
                        merge_jit(
                            jax.tree.map(jnp.asarray, state),
                            jax.tree.map(jnp.asarray, dev_state),
                        ),
                    )
                )
            if state is None:
                state = jax.tree.map(np.asarray, welford_init((exp.site_height, exp.site_width)))

            out = jax.tree.map(np.asarray, welford_finalize(jax.tree.map(jnp.asarray, state)))
            if args["smooth_sigma"] > 0:
                from tmlibrary_tpu.ops.smooth import gaussian_smooth

                out["mean_log"] = np.asarray(
                    gaussian_smooth(out["mean_log"], args["smooth_sigma"])
                )
                out["std_log"] = np.asarray(
                    gaussian_smooth(out["std_log"], args["smooth_sigma"])
                )
        # the finalize already inverted exact raw-intensity percentiles
        # from the Welford histogram — hand them to the QC session (one
        # no-op call when QC is off) so the run profile records each
        # channel's acquisition dynamic range for free
        from tmlibrary_tpu import qc as qc_mod

        ch_name = next(
            (c.name for c in exp.channels if c.index == channel),
            str(channel),
        )
        qc_mod.get_session().observe_illumination(
            ch_name, out["percentile_keys"], out["percentile_values"]
        )
        out.pop("hist", None)
        with telemetry.span("write"):
            self.store.write_illumstats(out, cycle=cycle, channel=channel)
        # one batch == one channel; same perf_counter wall-time math as
        # bench.py's channels/sec metric (BASELINE.json)
        telemetry.get_registry().throughput(
            "tmx_corilla_channels_per_sec"
        ).add(1, time.perf_counter() - bt0)
        return {"cycle": cycle, "channel": channel, "n_sites": int(out["n"])}

    def delete_previous_output(self) -> None:
        for p in (self.store.root / "illumstats").glob("*.npz"):
            p.unlink()
