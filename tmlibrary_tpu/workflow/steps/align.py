"""align: register acquisition cycles per site.

Reference parity: ``tmlib/workflow/align/`` ``ImageRegistrator`` — computes
per-site shifts of every cycle against a reference cycle (one reference
channel), stores ``SiteShift`` rows and, in collect, the ``SiteIntersection``
overlap window (SURVEY.md §2 align row).

TPU execution: FFT phase correlation batched over the site axis with vmap;
shifts exceeding ``max_shift`` are zeroed (registration failure fallback,
as in the reference).  One batch a cycle: its sites go to the device in
launches of as many pairs as the device's free memory holds
(:func:`tmlibrary_tpu.ops.registration.pairs_in_flight`), as uint16, and the
cycle's shift table is written once, whole, tmp + rename.  What collect
stores is the window every consumer crops to
(:func:`tmlibrary_tpu.ops.registration.stored_window`), with the exact
intersection beside it.
"""

from __future__ import annotations

import numpy as np

from tmlibrary_tpu import telemetry
from tmlibrary_tpu.ops.registration import (
    batch_phase_correlation_quality,
    intersection_window,
    pairs_in_flight,
    stored_window,
)
from tmlibrary_tpu.utils import create_partitions
from tmlibrary_tpu.workflow.api import Step
from tmlibrary_tpu.workflow.args import Argument, ArgumentCollection
from tmlibrary_tpu.workflow.registry import register_step


@register_step("align")
class ImageRegistrator(Step):
    batch_args = ArgumentCollection(
        Argument("ref_cycle", int, default=0, help="reference cycle"),
        Argument("ref_channel", int, default=0, help="channel used to register"),
        Argument("batch_size", int, default=0,
                 help="site pairs per device launch (0 = as many as the "
                      "device's free memory holds)"),
        Argument("max_shift", int, default=50,
                 help="shifts larger than this are treated as failures (zeroed)"),
        Argument("min_quality", float, default=0.0,
                 help="zero shifts whose correlation peak falls below this "
                      "(0 = off); peak is 1.0 for identical shifted content"),
    )

    def create_batches(self, args):
        exp = self.store.experiment
        if exp.n_cycles < 2:
            return []
        sites = list(range(self.store.n_sites))
        return [
            {"cycle": cycle, "sites": sites}
            for cycle in range(exp.n_cycles)
            if cycle != args["ref_cycle"]
        ]

    def _launch_size(self, args: dict) -> int:
        """Pairs a launch: the argument, or what the memory free now holds."""
        if args["batch_size"] > 0:
            return args["batch_size"]
        from tmlibrary_tpu.workflow.steps.illuminati import free_memory

        exp = self.store.experiment
        return pairs_in_flight(4 * exp.site_height * exp.site_width,
                               *free_memory())

    def run_batch(self, batch: dict) -> dict:
        import jax.numpy as jnp

        args = batch["args"]
        cycle, sites = batch["cycle"], batch["sites"]
        table = np.zeros((self.store.n_sites, 2), np.int32)
        n_failed = 0
        launch = self._launch_size(args)
        for part in create_partitions(sites, launch):
            with telemetry.span("read") as read:
                ref = self.store.read_sites(part, cycle=args["ref_cycle"],
                                            channel=args["ref_channel"])
                tgt = self.store.read_sites(part, cycle=cycle,
                                            channel=args["ref_channel"])
                read["bytes"] = int(ref.nbytes + tgt.nbytes)
            # upload (uint16), the one program, and the fetch of its few
            # numbers: the dispatch and the wait together
            with telemetry.span("register", pairs=len(part)):
                dev_shifts, dev_quality = batch_phase_correlation_quality(
                    jnp.asarray(ref), jnp.asarray(tgt)
                )
                # np.array (copy): np.asarray of a jax.Array is read-only
                shifts = np.array(dev_shifts)
                quality = np.asarray(dev_quality)
            bad = np.abs(shifts).max(axis=1) > args["max_shift"]
            if args["min_quality"] > 0.0:
                bad |= quality < args["min_quality"]
            shifts[bad] = 0
            n_failed += int(bad.sum())
            table[np.asarray(part)] = shifts
        with telemetry.span("write_shifts"):
            self.store.write_shifts(table, cycle)
        metrics = telemetry.get_registry()
        metrics.counter("tmx_align_sites_total").inc(len(sites))
        metrics.counter("tmx_align_failed_sites_total").inc(n_failed)
        return {"cycle": cycle, "n_sites": len(sites), "n_failed": n_failed,
                "pairs_per_launch": min(launch, len(sites)),
                "max_abs_shift": int(np.abs(table).max(initial=0))}

    def collect(self, results: list[dict] | None = None) -> dict:
        exp = self.store.experiment
        args = self.batch_args.resolve(
            self.load_batch(0)["args"] if self.list_batches() else None
        )
        all_shifts = [
            self.store.read_shifts(c)
            for c in range(exp.n_cycles)
            if c != args["ref_cycle"] and self.store.has_shifts(c)
        ]
        stacked = (np.concatenate(all_shifts) if all_shifts
                   else np.zeros((0, 2), np.int32))
        intersection = intersection_window(stacked)
        window = stored_window(intersection)
        with telemetry.span("write_shifts"):
            self.store.write_intersection(
                {**window, "intersection": intersection})
        results = results or []
        return {
            "window": window,
            "intersection": intersection,
            "sites": sum(r["n_sites"] for r in results),
            "failed_sites": sum(r["n_failed"] for r in results),
            "max_abs_shift": int(np.abs(stacked).max(initial=0)),
        }

    def delete_previous_output(self) -> None:
        for p in (self.store.root / "alignment").glob("*"):
            p.unlink()
