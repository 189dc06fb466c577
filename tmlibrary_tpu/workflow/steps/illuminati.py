"""illuminati: multi-resolution pyramid tiles for the viewer.

Reference parity: ``tmlib/workflow/illuminati/api.py`` ``PyramidBuilder`` —
level 0 stitches corrected/aligned/rescaled site images into the plate
mosaic and cuts 256-px tiles; level L+1 jobs consume level L (inter-level
dependency waves); tiles land in the DB (SURVEY.md §4.5).

TPU execution: one batch per (plate, cycle, channel) that holds planes — a
multiplexed plate's stains live in one acquisition cycle each, and every
one gets its layer, shifted by its cycle's stored shifts so that the layers
register; correction + rescale run batched on device, the mosaic assembles
host-side (it can exceed HBM for large plates), the downsample chain runs on
device per level, PNG tiles go to ``pyramids/<layer>/<level>/<row>_<col>.png``
(:func:`layer_name`) — a zoomify-style layout any slippy-map viewer can
serve statically.  "Channel" below is such a channel-cycle.

The channels are in flight together: the step exposes the pipelined
executor's split (``workflow/pipelined.py``), so one channel's levels are
fetched and encoded on a persist worker while the next channel's planes
are read (prefetch pool), corrected and laid out (engine thread).  How many
channels are between launch and the end of persist at once is bounded by
what a channel holds — its float32 mosaic on the host, its pyramid on the
device — against the memory the step finds free (:func:`channels_in_flight`);
a plate whose pyramid fits once runs one channel at a time, as the
sequential path (:meth:`PyramidBuilder.run_batch`) always does.  One PNG
pool serves every channel that is persisting.
"""

from __future__ import annotations

import concurrent.futures as cf
import contextlib
import json
import os
import threading
import time
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from tmlibrary_tpu import telemetry
from tmlibrary_tpu.errors import WorkflowError
from tmlibrary_tpu.models.experiment import SiteRef
from tmlibrary_tpu.models.image import IllumstatsContainer
from tmlibrary_tpu.models.mapobject import plate_grid, plate_mosaic_shape
from tmlibrary_tpu.models.metadata import ChannelLayer
from tmlibrary_tpu.ops import image_ops
from tmlibrary_tpu.ops.pyramid import cut_tiles, pyramid_levels, to_uint8
from tmlibrary_tpu.utils import create_partitions
from tmlibrary_tpu.workflow.api import Step
from tmlibrary_tpu.workflow.args import Argument, ArgumentCollection
from tmlibrary_tpu.workflow.registry import register_step

#: threads of the step's PNG pool
ENCODE_THREAD_PREFIX = "tmx-illuminati-png"


def free_memory() -> tuple[int | None, int | None]:
    """``(device, host)`` bytes the step may plan with, None where the
    platform does not say: the first device's ``bytes_limit`` less
    ``bytes_in_use`` (``memory_stats()``; the CPU backend has none) and the
    kernel's ``MemAvailable``."""
    stats = jax.devices()[0].memory_stats() or {}
    device = None
    if stats.get("bytes_limit"):
        device = int(stats["bytes_limit"]) - int(stats.get("bytes_in_use", 0))
    host = None
    try:
        with open("/proc/meminfo") as f:
            for line in f:
                if line.startswith("MemAvailable:"):
                    host = int(line.split()[1]) * 1024
                    break
    except OSError:
        pass
    return device, host


def channels_in_flight(mosaic_bytes: int, device_free: int | None,
                       host_free: int | None) -> int:
    """How many channels may be between launch and the end of persist at
    once.  A channel in flight holds, of its float32 mosaic's bytes: on the
    device the level chain (4/3) and the uint8 level being fetched (1/4);
    on the host its raw planes (1/2, read ahead), the mosaic until it is
    uploaded (1) and the uint8 level being cut (1/4).  Half of what is free
    is planned with — ``prep``'s batches, XLA's temporaries and everything
    else the process allocates take the rest.  At least one."""
    bounds = []
    if device_free is not None:
        bounds.append(device_free // 2 // max(1, mosaic_bytes * 19 // 12))
    if host_free is not None:
        bounds.append(host_free // 2 // max(1, mosaic_bytes * 7 // 4))
    return max(1, min(bounds)) if bounds else 1


def layer_name(cycle: int, channel: int) -> str:
    """A channel-cycle's directory under ``pyramids/``: ``channelNN`` in
    the first cycle, as a one-cycle experiment always had it, and
    ``cycleCC_channelNN`` in a later one."""
    name = f"channel{channel:02d}"
    return name if cycle == 0 else f"cycle{cycle:02d}_{name}"


class _Flight(NamedTuple):
    """The step's in-flight plan (:meth:`PyramidBuilder._flight`)."""

    #: one slot a channel between launch and the end of persist
    slots: threading.Semaphore
    #: whether the prefetch pool reads the planes ahead of the launch
    reads_ahead: bool


@register_step("illuminati")
class PyramidBuilder(Step):
    batch_args = ArgumentCollection(
        Argument("correct", bool, default=True, help="apply illumination stats"),
        Argument("align", bool, default=True,
                 help="shift each cycle's sites by the shifts the align "
                      "step stored for it (a cycle with none is tiled as "
                      "it is)"),
        Argument("clip_percent", float, default=99.9,
                 help="upper clip percentile for display rescale"),
        Argument("batch_size", int, default=32, help="sites per device batch"),
        Argument("cycle", int, default=-1,
                 help="cycle to tile (-1: every cycle that holds planes)"),
        Argument("n_devices", int, default=1,
                 help="row-shard the mosaic pyramid over this many devices "
                      "(mosaics larger than one chip's HBM)"),
    )

    def __init__(self, store):
        super().__init__(store)
        self._lock = threading.Lock()
        #: planned at the first batch (:meth:`_flight`)
        self._flight_plan: _Flight | None = None
        self._pool: cf.ThreadPoolExecutor | None = None
        self._pool_users = 0

    def create_batches(self, args):
        exp = self.store.experiment
        cycles = (range(exp.n_cycles) if args["cycle"] < 0
                  else [args["cycle"]])
        return [
            {"plate": p.name, "cycle": cycle, "channel": ch.index}
            for p in exp.plates
            for cycle in cycles
            for ch in exp.channels
            if self.store.has_plane(cycle=cycle, channel=ch.index)
        ]

    # ------------------------------------------------------ channels in flight
    def _flight(self) -> _Flight:
        """The step's in-flight plan, made once from the largest plate's
        mosaic and the memory free at the first batch: a semaphore of
        :func:`channels_in_flight` slots, and whether the prefetch pool
        reads planes ahead — only when every planned channel fits in flight
        at once, so that the planes read ahead are never more than the
        plan counted."""
        with self._lock:
            if self._flight_plan is None:
                exp = self.store.experiment
                mosaic_bytes = 4 * max(
                    int(np.prod(plate_mosaic_shape(exp, p.name)))
                    for p in exp.plates)
                slots = channels_in_flight(mosaic_bytes, *free_memory())
                self._flight_plan = _Flight(
                    threading.Semaphore(slots),
                    slots >= len(self.list_batches()),
                )
            return self._flight_plan

    @contextlib.contextmanager
    def _encode_pool(self):
        """The step's one PNG pool, for as long as any channel persists:
        the first to arrive opens it, the last to leave (done or failed)
        closes it.  cv2 releases the GIL during imencode, so the threads
        encode side by side (the reference fanned per-level tile jobs out
        to the cluster)."""
        with self._lock:
            if self._pool_users == 0:
                self._pool = cf.ThreadPoolExecutor(
                    max_workers=min(8, os.cpu_count() or 1),
                    thread_name_prefix=ENCODE_THREAD_PREFIX)
            self._pool_users += 1
            pool = self._pool
        try:
            yield pool
        finally:
            with self._lock:
                self._pool_users -= 1
                if self._pool_users == 0:
                    self._pool = None
                    pool.shutdown(wait=True)

    # ------------------------------------------------- launch/persist split
    # (the pipelined executor's step protocol — workflow/pipelined.py)
    def prefetch_batch(self, batch: dict) -> dict:
        """Host-side input loading only (illumination statistics, the shift
        table, the site grid and — where the plan allows — the channel's
        planes): safe on a prefetch worker thread."""
        t0 = time.perf_counter()
        args = batch["args"]
        exp = self.store.experiment
        channel, cycle = batch["channel"], batch["cycle"]
        plate = next(p for p in exp.plates if p.name == batch["plate"])

        stats = None
        if args["correct"] and self.store.has_illumstats(cycle=cycle, channel=channel):
            with telemetry.span("stats_read"):
                stats = IllumstatsContainer.from_store(
                    self.store.read_illumstats(cycle=cycle, channel=channel)
                )
        refs = [
            (SiteRef(plate.name, w.row, w.column, s.y, s.x), w, s)
            for w in plate.wells
            for s in w.sites
        ]
        parts = [
            (part, [self.store.site_linear_index(r) for r, _, _ in part])
            for part in create_partitions(refs, args["batch_size"])
        ]
        # a cycle with no stored shifts (the reference cycle, every cycle
        # of an experiment that was never aligned) runs the program that
        # shifts nothing
        aligned = args["align"] and self.store.has_shifts(cycle)
        shifts_table = (
            self.store.read_shifts(cycle) if aligned
            else np.zeros((self.store.n_sites, 2), np.int32)
        )
        stacks = None
        if self._flight().reads_ahead:
            stacks = [self._read(idx, cycle, channel) for _, idx in parts]
        return {"plate": plate, "stats": stats, "parts": parts,
                "shifts": shifts_table, "aligned": aligned, "stacks": stacks,
                "seconds": time.perf_counter() - t0}

    def _read(self, idx: list[int], cycle: int, channel: int) -> np.ndarray:
        with telemetry.span("read"):
            return self.store.read_sites(idx, cycle=cycle, channel=channel)

    def launch_batch(self, batch: dict, prefetched: dict | None = None):
        """Correct, lay out and upload one channel and dispatch its level
        chain; returns ``(batch, ctx)`` with the un-fetched device levels
        in ``ctx``.  Waits for a free slot of the in-flight plan first."""
        if prefetched is None:
            prefetched = self.prefetch_batch(batch)
        slots = self._flight().slots
        slots.acquire()
        try:
            return batch, self._launch(batch, prefetched)
        except BaseException:
            slots.release()
            raise

    def persist_batch(self, batch: dict, ctx: dict) -> dict:
        """Fetch and encode every level of one launched channel.  Entered
        by several persist workers at once, each with another channel
        (``pyramids/<layer>/`` is the channel-cycle's own)."""
        try:
            return self._persist(batch, ctx)
        finally:
            self._flight().slots.release()

    def run_batch(self, batch: dict) -> dict:
        """One channel from its reads to its tiles, on the calling thread:
        the engine's sequential path and ``tmx illuminati run``."""
        return self._persist(batch, self._launch(batch, self.prefetch_batch(batch)))

    def _launch(self, batch: dict, pre: dict) -> dict:
        t0 = time.perf_counter()
        args = batch["args"]
        exp = self.store.experiment
        channel, cycle = batch["channel"], batch["cycle"]
        plate, stats = pre["plate"], pre["stats"]

        # display range from corilla's exact raw-intensity percentiles
        # (reference: scale step); both bounds or neither
        lower = upper = None
        if stats is not None:
            lower = stats.closest_percentile(0.1)
            upper = stats.closest_percentile(args["clip_percent"])
        from_corilla = lower is not None and upper is not None

        prep = image_ops.make_batch_prep(stats, apply_shift=pre["aligned"])

        # site grid geometry (shared helper — same layout as the static
        # outlines and the pyramid-depth computation)
        _, _, spw_y, spw_x = plate_grid(exp, plate.name)
        H, W = exp.site_height, exp.site_width
        mosaic = np.zeros(plate_mosaic_shape(exp, plate.name), np.float32)

        for k, (part, idx) in enumerate(pre["parts"]):
            stack = (self._read(idx, cycle, channel) if pre["stacks"] is None
                     else pre["stacks"][k])
            # upload, the one `prep` program, and the fetch of its result
            with telemetry.span("prep", bytes=stack.nbytes):
                prepped = np.asarray(
                    prep(jnp.asarray(stack), jnp.asarray(pre["shifts"][idx]))
                )
            with telemetry.span("mosaic"):
                for (_ref, w, s), img in zip(part, prepped):
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
        return {
            "levels": levels,
            "mosaic_shape": list(mosaic.shape),
            "lower": float(lower),
            "upper": float(upper),
            "from_corilla": from_corilla,
            "out_dir": self.store.root / "pyramids" / layer_name(cycle, channel),
            # the channel's own seconds so far, waits between phases apart
            "seconds": pre["seconds"] + time.perf_counter() - t0,
        }

    def _persist(self, batch: dict, ctx: dict) -> dict:
        import cv2

        t0 = time.perf_counter()
        channel = batch["channel"]
        levels, out_dir = ctx["levels"], ctx["out_dir"]
        n_levels = len(levels)
        n_tiles = 0
        with self._encode_pool() as pool:
            # submit per level so only one level8 array is held at a time
            # (cut_tiles returns views into it) — encodes overlap the next
            # level's cut; futures are drained per level before the array
            # is dropped, and a level leaves the device once it is fetched
            for li in range(n_levels):
                with telemetry.span("level_fetch"):
                    level8 = np.asarray(
                        to_uint8(levels[li], ctx["lower"], ctx["upper"]))
                levels[li] = None
                ldir = out_dir / f"{n_levels - 1 - li}"
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
                        f"level {n_levels - 1 - li}, e.g. {bad[0]}"
                    )
                n_tiles += len(futures)
        layer = ChannelLayer(
            channel=out_dir.name,
            height=ctx["mosaic_shape"][0],
            width=ctx["mosaic_shape"][1],
            max_zoom=n_levels - 1,
        )
        (out_dir / "layer.json").write_text(json.dumps(layer.to_dict()))
        telemetry.get_registry().throughput(
            "tmx_illuminati_tiles_per_sec"
        ).add(n_tiles, ctx["seconds"] + time.perf_counter() - t0)
        return {
            "channel": channel,
            "cycle": batch["cycle"],
            "mosaic_shape": ctx["mosaic_shape"],
            "n_levels": n_levels,
            "n_tiles": n_tiles,
            "display_range": "corilla" if ctx["from_corilla"] else "mosaic",
            "display_lower": ctx["lower"],
            "display_upper": ctx["upper"],
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
