"""illuminati: multi-resolution pyramid tiles for the viewer.

Reference parity: ``tmlib/workflow/illuminati/api.py`` ``PyramidBuilder`` —
level 0 stitches corrected/aligned/rescaled site images into the plate
mosaic and cuts 256-px tiles; level L+1 jobs consume level L (inter-level
dependency waves); tiles land in the DB (SURVEY.md §4.5).

TPU execution: one batch per (plate, channel); correction + rescale run
batched on device, the mosaic assembles host-side (it can exceed HBM for
large plates), the downsample chain runs on device per level, PNG tiles go
to ``pyramids/<channel>/<level>/<row>_<col>.png`` — a zoomify-style layout
any slippy-map viewer can serve statically.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from tmlibrary_tpu.errors import WorkflowError
from tmlibrary_tpu.models.experiment import SiteRef
from tmlibrary_tpu.models.image import IllumstatsContainer
from tmlibrary_tpu.models.metadata import ChannelLayer
from tmlibrary_tpu.ops import image_ops
from tmlibrary_tpu.ops.pyramid import cut_tiles, pyramid_levels, to_uint8
from tmlibrary_tpu.utils import create_partitions
from tmlibrary_tpu.workflow.api import Step
from tmlibrary_tpu.workflow.args import Argument, ArgumentCollection
from tmlibrary_tpu.workflow.registry import register_step


@register_step("illuminati")
class PyramidBuilder(Step):
    batch_args = ArgumentCollection(
        Argument("correct", bool, default=True, help="apply illumination stats"),
        Argument("align", bool, default=False, help="apply cycle-0 alignment"),
        Argument("clip_percent", float, default=99.9,
                 help="upper clip percentile for display rescale"),
        Argument("batch_size", int, default=32, help="sites per device batch"),
        Argument("cycle", int, default=0, help="cycle to tile"),
        Argument("n_devices", int, default=1,
                 help="row-shard the mosaic pyramid over this many devices "
                      "(mosaics larger than one chip's HBM)"),
    )

    def create_batches(self, args):
        exp = self.store.experiment
        return [
            {"plate": p.name, "channel": ch.index}
            for p in exp.plates
            for ch in exp.channels
            if self.store.has_plane(cycle=args["cycle"], channel=ch.index)
        ]

    # ------------------------------------------------------------------ run
    def run_batch(self, batch: dict) -> dict:
        import time

        from tmlibrary_tpu import telemetry

        bt0 = time.perf_counter()
        args = batch["args"]
        exp = self.store.experiment
        channel = batch["channel"]
        cycle = args["cycle"]
        plate = next(p for p in exp.plates if p.name == batch["plate"])

        stats = None
        if args["correct"] and self.store.has_illumstats(cycle=cycle, channel=channel):
            with telemetry.span("stats_read"):
                stats = IllumstatsContainer.from_store(
                    self.store.read_illumstats(cycle=cycle, channel=channel)
                )

        # display range from corilla's exact raw-intensity percentiles
        # (reference: scale step); both bounds or neither
        lower = upper = None
        if stats is not None:
            lower = stats.closest_percentile(0.1)
            upper = stats.closest_percentile(args["clip_percent"])
        from_corilla = lower is not None and upper is not None

        prep = image_ops.make_batch_prep(stats, apply_shift=args["align"])

        # site grid geometry (shared helper — same layout as the static
        # outlines and the pyramid-depth computation)
        from tmlibrary_tpu.models.mapobject import plate_grid, plate_mosaic_shape

        rows, cols, spw_y, spw_x = plate_grid(exp, plate.name)
        H, W = exp.site_height, exp.site_width
        mosaic = np.zeros(plate_mosaic_shape(exp, plate.name), np.float32)

        refs = [
            (SiteRef(plate.name, w.row, w.column, s.y, s.x), w, s)
            for w in plate.wells
            for s in w.sites
        ]
        shifts_table = (
            self.store.read_shifts(cycle)
            if args["align"] and self.store.has_shifts(cycle)
            else np.zeros((self.store.n_sites, 2), np.int32)
        )
        for part in create_partitions(refs, args["batch_size"]):
            idx = [self.store.site_linear_index(r) for r, _, _ in part]
            with telemetry.span("read"):
                stack = self.store.read_sites(idx, cycle=cycle, channel=channel)
            # upload, the one `prep` program, and the fetch of its result
            with telemetry.span("prep", bytes=stack.nbytes):
                prepped = np.asarray(
                    prep(jnp.asarray(stack), jnp.asarray(shifts_table[idx]))
                )
            with telemetry.span("mosaic"):
                for (ref, w, s), img in zip(part, prepped):
                    y0 = (w.row * spw_y + s.y) * H
                    x0 = (w.column * spw_x + s.x) * W
                    mosaic[y0 : y0 + H, x0 : x0 + W] = img

        if not from_corilla:
            # corilla stored no such percentile (a `clip_percent` outside
            # its five) or no statistics were read (`correct` false): select
            # from this submit's mosaic.  One call partitions both quantiles
            # in a single pass (two np.percentile calls measured ~2x the cost)
            with telemetry.span("percentile"):
                lo_up = np.percentile(mosaic, [0.1, args["clip_percent"]])
            lower, upper = float(lo_up[0]), float(lo_up[1])

        n_dev = min(args["n_devices"], len(jax.devices()))
        # the mosaic's upload and the downsample chain's dispatch
        with telemetry.span("pyramid", bytes=mosaic.nbytes):
            if n_dev > 1:
                from jax.sharding import Mesh

                from tmlibrary_tpu.parallel.halo import sharded_pyramid_levels

                mesh = Mesh(np.asarray(jax.devices()[:n_dev]), ("rows",))
                levels = sharded_pyramid_levels(jnp.asarray(mosaic), mesh)
            else:
                levels = pyramid_levels(jnp.asarray(mosaic))
        out_dir = self.store.root / "pyramids" / f"channel{channel:02d}"
        # PNG encode is host-side and embarrassingly parallel; cv2 releases
        # the GIL during imencode, so a thread pool overlaps tile encodes
        # (the reference fanned per-level tile jobs out to the cluster)
        import concurrent.futures as cf
        import os as _os

        import cv2

        workers = min(8, _os.cpu_count() or 1)
        n_tiles = 0
        with cf.ThreadPoolExecutor(max_workers=workers) as pool:
            # submit per level so only one level8 array is held at a time
            # (cut_tiles returns views into it) — encodes overlap the next
            # level's cut; futures are drained per level before the array
            # is dropped
            for li, level in enumerate(levels):
                with telemetry.span("level_fetch"):
                    level8 = np.asarray(
                        to_uint8(level, float(lower), float(upper)))
                ldir = out_dir / f"{len(levels) - 1 - li}"
                ldir.mkdir(parents=True, exist_ok=True)
                with telemetry.span("encode", bytes=level8.nbytes) as enc:
                    futures = {
                        pool.submit(cv2.imwrite, str(ldir / f"{ty}_{tx}.png"), tile):
                        f"{ty}_{tx}.png"
                        for (ty, tx), tile in cut_tiles(level8).items()
                    }
                    bad = [name for fut, name in futures.items() if not fut.result()]
                    enc["tiles"] = len(futures)
                if bad:
                    raise WorkflowError(
                        f"PNG tile encode failed for {len(bad)} tiles of "
                        f"level {len(levels) - 1 - li}, e.g. {bad[0]}"
                    )
                n_tiles += len(futures)
        layer = ChannelLayer(
            channel=f"channel{channel:02d}",
            height=mosaic.shape[0],
            width=mosaic.shape[1],
            max_zoom=len(levels) - 1,
        )
        import json

        (out_dir / "layer.json").write_text(json.dumps(layer.to_dict()))
        telemetry.get_registry().throughput(
            "tmx_illuminati_tiles_per_sec"
        ).add(n_tiles, time.perf_counter() - bt0)
        return {
            "channel": channel,
            "mosaic_shape": list(mosaic.shape),
            "n_levels": len(levels),
            "n_tiles": n_tiles,
            "display_range": "corilla" if from_corilla else "mosaic",
            "display_lower": float(lower),
            "display_upper": float(upper),
        }

    def collect(self) -> dict:
        """Register the static Plates/Wells/Sites mapobject types with their
        grid outlines (reference: the static ``MapobjectType`` rows created
        alongside the pyramid so the viewer can overlay plate geometry)."""
        import pandas as pd

        from tmlibrary_tpu.models.mapobject import (
            STATIC_REF_TYPES,
            MapobjectType,
            MapobjectTypeRegistry,
            static_mapobjects,
        )

        registry = MapobjectTypeRegistry(self.store.root)
        out_dir = self.store.root / "segmentations"
        out_dir.mkdir(exist_ok=True)
        counts: dict[str, int] = {}
        for plate in self.store.experiment.plates:
            geo = static_mapobjects(self.store.experiment, plate.name)
            for type_name, outlines in geo.items():
                rows = [
                    {
                        "plate": plate.name,
                        "name": label,
                        "centroid_y": float(rect[:-1, 0].mean()),
                        "centroid_x": float(rect[:-1, 1].mean()),
                        "contour_y": rect[:, 0].tolist(),
                        "contour_x": rect[:, 1].tolist(),
                    }
                    for label, rect in outlines
                ]
                df = pd.DataFrame(rows)
                df.to_parquet(
                    out_dir / f"{type_name}_polygons_{plate.name}.parquet",
                    index=False,
                )
                counts[type_name] = counts.get(type_name, 0) + len(rows)
        for type_name in counts:
            registry.register(
                MapobjectType(
                    name=type_name,
                    ref_type=STATIC_REF_TYPES[type_name],
                    min_poly_zoom=0,
                )
            )
        return {"static_mapobjects": counts}

    def delete_previous_output(self) -> None:
        import shutil

        root = self.store.root / "pyramids"
        if root.exists():
            shutil.rmtree(root)
        root.mkdir()
