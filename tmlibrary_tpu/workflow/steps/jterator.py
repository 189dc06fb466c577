"""jterator: run the image-analysis pipeline over all sites.

Reference parity: ``tmlib/workflow/jterator/api.py`` ``ImageAnalysisPipeline``
— ``create_run_batches`` groups sites by ``batch_size``; ``run_job`` loads
channel images (correct + align), runs the module chain per site, registers
segmented objects (label images → PostGIS polygons) and persists feature
values (SURVEY.md §4.3 — THE hot path).

TPU execution: one compiled program per experiment geometry
(jit(vmap(chain))); a batch of sites is one device dispatch, sharded over
the mesh when more than one chip is visible.  Outputs: label stacks in the
segmentation store, feature Parquet shards (idempotent per batch), optional
host-traced polygons.  Metric: sites/sec/chip (BASELINE.json).
"""

from __future__ import annotations

import threading
import time

import numpy as np

import logging

from tmlibrary_tpu import telemetry
from tmlibrary_tpu.errors import PipelineError, StoreError
from tmlibrary_tpu.models.image import IllumstatsContainer
from tmlibrary_tpu.utils import create_partitions
from tmlibrary_tpu.workflow.api import Step
from tmlibrary_tpu.workflow.args import Argument, ArgumentCollection
from tmlibrary_tpu.workflow.registry import register_step

logger = logging.getLogger(__name__)


def _mosaic_intensity_stats(labels, vals_mosaic, count):
    """Ragged per-object intensity accumulators over a mosaic:
    (sum, sq_sum, min, max), each ``(count + 1,)`` with index 0 =
    background.  ONE native C pass (``tm_mosaic_intensity``) with a
    chunked-vectorized numpy fallback — no O(H) interpreter loop on a
    plate-scale mosaic (round-3 VERDICT weak #4)."""
    from tmlibrary_tpu import native as native_mod

    return native_mod.mosaic_intensity_host(labels, vals_mosaic, count)


_CORRECT_JIT = None


def _well_shard(batch: dict) -> str:
    """The ONE home of the per-well shard token used by feature-table
    shards, polygon filenames and figure filenames alike."""
    plate, well_row, well_col = batch["well"]
    return f"well_{plate}_{well_row:02d}_{well_col:02d}"


def _best_spatial_grid(requested: int, hm: int, wm: int,
                       squarest: bool = False) -> tuple[int, int]:
    """Largest ``nr * nc <= requested`` with ``nr`` dividing the mosaic
    rows and ``nc`` the columns; equal products prefer more rows (the
    1-D-like shape, fewer seam axes) or, with ``squarest``, the shape
    nearest a square (a four-chip host's own 2 x 2), more rows among
    equally square ones."""
    best = (1, 1)
    for nr in range(requested, 0, -1):
        if hm % nr:
            continue
        cap = requested // nr
        nc = next(k for k in range(cap, 0, -1) if wm % k == 0)
        more = nr * nc - best[0] * best[1]
        if more > 0 or (squarest and more == 0
                        and abs(nr - nc) < abs(best[0] - best[1])):
            best = (nr, nc)
    return best


def _spatial_mesh_shape(kind: str, requested: int, hm: int,
                       wm: int) -> tuple[int, int]:
    """``(rows, cols)`` of the mesh ``--layout spatial`` lays over an
    ``hm x wm`` mosaic when ``requested`` devices are there, as
    ``spatial_grid`` (``kind``) resolves it; ``cols`` is 1 for row shards.

    The mesh must divide the mosaic EXACTLY — padding would corrupt the
    global Otsu histogram and edge smoothing, breaking bit-identity with
    the unsharded chain; shrink to divisors instead.  Candidates: 1-D row
    shards vs a 2-D rows x cols tile grid — a 2-D factorization often
    keeps MORE devices busy (e.g. 100 rows on 8 devices: rows-only
    shrinks to 5, a 4x2 grid uses all 8), and the outputs are
    layout-invariant either way.  An explicit ``grid`` takes the squarest
    of the factorizations that use equally many devices; ``auto`` takes
    the grid only where it uses more devices than row shards."""
    if kind == "grid":
        return _best_spatial_grid(requested, hm, wm, squarest=True)
    n_rows1d = next(k for k in range(requested, 0, -1) if hm % k == 0)
    if kind == "auto":
        nr, nc = _best_spatial_grid(requested, hm, wm)
        if nr * nc > n_rows1d:
            return nr, nc
    return n_rows1d, 1


def _correct_batch(imgs, mean_log, std_log) -> "np.ndarray":
    """Batched illumination correction, jitted ONCE (per shape) — a
    per-well closure would recompile the same elementwise program for
    every well of the plate."""
    import jax
    import jax.numpy as jnp

    from tmlibrary_tpu.ops import image_ops

    global _CORRECT_JIT
    if _CORRECT_JIT is None:
        _CORRECT_JIT = jax.jit(
            jax.vmap(image_ops.correct_illumination, in_axes=(0, None, None))
        )
    return np.asarray(
        _CORRECT_JIT(
            jnp.asarray(imgs, jnp.float32),
            jnp.asarray(mean_log),
            jnp.asarray(std_log),
        )
    )


def _host_shift(img: np.ndarray, dy: int, dx: int) -> np.ndarray:
    """Integer translate with zero fill — host twin of ops.image_ops.shift_image."""
    out = np.roll(img, (int(dy), int(dx)), axis=(0, 1))
    h, w = out.shape
    if dy > 0:
        out[:dy, :] = 0
    elif dy < 0:
        out[h + dy:, :] = 0
    if dx > 0:
        out[:, :dx] = 0
    elif dx < 0:
        out[:, w + dx:] = 0
    return out


@register_step("jterator")
class ImageAnalysisRunner(Step):
    batch_args = ArgumentCollection(
        Argument("pipe", str, default="",
                 help="path to the .pipe.yaml pipeline description "
                      "(required for --layout sites)"),
        Argument("layout", str, default="sites", choices=("sites", "spatial"),
                 help="'sites': vmap the module chain over per-site batches; "
                      "'spatial': stitch each well into one mosaic, row-shard "
                      "it over the device mesh and segment it with halo "
                      "exchange + distributed connected components — objects "
                      "crossing site borders get ONE id (the reference splits "
                      "them, SURVEY.md §6 long-context row)"),
        Argument("spatial_channel", str, default="",
                 help="channel segmented in spatial layout "
                      "(default: first experiment channel)"),
        Argument("spatial_sigma", float, default=1.5,
                 help="gaussian sigma for spatial-layout smoothing"),
        Argument("spatial_grid", str, default="auto",
                 choices=("auto", "rows", "grid"),
                 help="spatial-layout mesh shape: 'rows' shards the mosaic "
                      "row axis 1-D; 'grid' tiles it rows x cols (2-D halo "
                      "exchange, corner-exact seams); 'auto' picks whichever "
                      "uses more devices — results are identical either way"),
        Argument("spatial_objects", str, default="mosaic_cells",
                 help="objects name for spatial-layout segmentation output"),
        Argument("spatial_zernike_degree", int, default=9,
                 help="Zernike moment degree for spatial-layout features "
                      "(matches measure_zernike's default; 0 disables)"),
        Argument("spatial_secondary_channel", str, default="",
                 help="grow secondary objects (cells) from the primary "
                      "mosaic objects through THIS channel via distributed "
                      "watershed — ids stay the primary's global ids "
                      "(empty: disabled)"),
        Argument("spatial_secondary_objects", str, default="mosaic_secondary",
                 help="objects name for the spatial secondary segmentation"),
        Argument("spatial_secondary_factor", float, default=1.0,
                 help="otsu correction factor for the secondary mask "
                      "(segment_secondary's correction_factor)"),
        Argument("spatial_secondary_levels", int, default=32,
                 help="watershed flooding levels for the secondary mask "
                      "(segment_secondary's n_levels)"),
        Argument("spatial_align", bool, default=True,
                 help="apply align-step shifts when stitching (the sites "
                      "layout gates this per pipe channel; disable if the "
                      "stored registration is untrusted)"),
        Argument("batch_size", int, default=0,
                 help="sites per device batch (0 = auto: the tuning "
                      "sweep's best_batch on device backends, else 32)"),
        Argument("max_objects", int, default=256,
                 help="static per-site object capacity; under layout "
                      "'spatial' it bounds the connected components one "
                      "shard of the mosaic may hold, and never under "
                      "4096"),
        Argument("object_buckets", str, default="auto",
                 help="object-capacity bucket ladder (capacity.py): "
                      "'auto' compiles power-of-two buckets up to "
                      "max_objects and routes each batch by observed "
                      "object counts; 'off' pins every batch at "
                      "max_objects; or an explicit comma list of "
                      "capacities, e.g. '8,32'. Results are bit-identical "
                      "across bucket choices — routing is purely a "
                      "performance decision"),
        Argument("schedule", str, default="auto",
                 choices=("auto", "pack", "off"),
                 help="work-aware site scheduling (workflow/schedule.py): "
                      "'pack' plans cost-model batches (rung-homogeneous "
                      "packing + straggler-balanced shard order) from the "
                      "per-site count history; 'off' keeps directory-order "
                      "batching; 'auto' follows TMX_SCHEDULE / config / "
                      "the tuned verdict, then packs. Results are "
                      "bit-identical per site either way — scheduling is "
                      "purely a performance decision"),
        Argument("donate_buffers", bool, default=True,
                 help="donate each batch's raw-image/stats/shift device "
                      "buffers to the compiled program so XLA reuses "
                      "their memory for outputs (safe: the engine "
                      "transfers fresh arrays per batch)"),
        Argument("auto_resegment", bool, default=True,
                 help="collect re-runs saturated batches at doubled "
                      "max_objects (bounded at 4096) until counts fit; "
                      "disable to keep the manual warn-and-rerun flow"),
        Argument("n_devices", int, default=0, help="mesh size (0 = all)"),
        Argument("cycle", int, default=0),
        Argument("tpoint", int, default=0),
        Argument("zplane", int, default=0),
        Argument("as_polygons", bool, default=False,
                 help="also trace object outlines host-side"),
        Argument("figures", bool, default=False,
                 help="write segmentation-overlay PNGs: per site in the "
                      "sites layout, one downsampled whole-well mosaic per "
                      "object family in the spatial layout (reference: "
                      "jterator module plot/Figure artifacts)"),
    )

    def __init__(self, store):
        super().__init__(store)
        # (capacity, qc gate) -> compiled batch fn: the bucket router
        # compiles one program per object-capacity bucket it actually
        # routes to (each is also process-cached in
        # jterator.pipeline.cached_batch_fn)
        self._compiled: dict[tuple, object] = {}
        self._desc = None
        self._window: tuple[int, int, int, int] | None = None
        self._window_resolved = False
        # prefetch workers read the pipeline description (and the figures
        # path re-resolves the compiled program) concurrently with the
        # main thread's launch; the lock keeps the compile cache coherent
        # when two threads race on different capacities
        self._compile_lock = threading.Lock()
        # bucket routing reads/writes the process-level per-program
        # peak-count history (capacity.note_observed_peak) — scoped by
        # compiled-program key so a long-lived serve process interleaving
        # tenants with different object densities never thrashes another
        # experiment's capacity-rung choices.  The lock only guards this
        # instance's memoized routing-key table (persist runs on the
        # pipelined executor's worker thread while launch runs on the
        # engine's).
        self._bucket_lock = threading.Lock()
        self._routing_keys: dict[tuple, str] = {}

    def create_batches(self, args):
        if args["layout"] == "spatial":
            # one batch per well: the well mosaic is the sharding unit
            wells: dict[tuple, list[int]] = {}
            for i, r in enumerate(self.store.experiment.sites()):
                key = (r.plate, r.well_row, r.well_column)
                wells.setdefault(key, []).append(i)
            return [
                {"sites": idxs, "well": list(key)}
                for key, idxs in sorted(wells.items())
            ]
        if not args["pipe"]:
            raise ValueError("--pipe is required for --layout sites")
        sites = list(range(self.store.n_sites))
        batch_size = args["batch_size"] or self._auto_batch_size(
            args["n_devices"])
        plan = self._schedule_plan(args, sites, batch_size)
        if plan is not None:
            from tmlibrary_tpu.workflow import schedule as schedule_mod

            schedule_mod.write_plan(self._schedule_plan_path, plan)
            return [
                {
                    "sites": b["sites"],
                    "schedule": {
                        "rung": b["rung"],
                        "predicted": b["predicted"],
                        "shard_work": b["shard_work"],
                        "shard_work_naive": b["shard_work_naive"],
                        "plan_digest": plan["digest"],
                    },
                }
                for b in plan["batches"]
            ]
        return [
            {"sites": part} for part in create_partitions(sites, batch_size)
        ]

    def init(self, args=None):
        """Harvest the PREVIOUS run's persisted per-site object counts
        into the scheduler's cost model before ``delete_previous_output``
        wipes the feature shards they live in — the predictor's seed for
        a fresh process planning over a previously-analyzed experiment."""
        resolved = self.batch_args.resolve(args)
        if resolved.get("layout", "sites") == "sites" and resolved.get("pipe"):
            self._seed_schedule_history(resolved)
        return super().init(args)

    def _seed_schedule_history(self, args) -> None:
        from tmlibrary_tpu.workflow import schedule as schedule_mod

        try:
            mode, _ = schedule_mod.resolve_schedule(args.get("schedule"))
            if not schedule_mod.schedule_enabled(mode):
                return
            counts = schedule_mod.harvest_store_counts(self.store)
            if not counts:
                return
            from tmlibrary_tpu.capacity import (
                resolve_bucket_ladder,
                seed_site_counts,
            )

            ceiling = int(args["max_objects"])
            ladder = resolve_bucket_ladder(
                ceiling, args.get("object_buckets", "auto")
            )
            seeded = seed_site_counts(
                self._routing_key(args, ceiling, ladder), counts
            )
            if seeded:
                logger.info(
                    "schedule: seeded %d site cost(s) from persisted "
                    "feature shards", seeded,
                )
        except Exception:
            # the cost model is a performance input, never a planning
            # dependency — a broken harvest degrades to the prior
            logger.debug("schedule history harvest failed", exc_info=True)

    def _schedule_plan(self, args, sites: list, batch_size: int):
        """The work-model packing plan for a sites-layout run, or None
        when scheduling is off (or the run is too small to pack)."""
        from tmlibrary_tpu.workflow import schedule as schedule_mod

        mode, source = schedule_mod.resolve_schedule(args.get("schedule"))
        if not schedule_mod.schedule_enabled(mode) or len(sites) <= 1:
            schedule_mod.write_plan(self._schedule_plan_path, None)
            return None
        import jax

        from tmlibrary_tpu.capacity import (
            observed_peak,
            resolve_bucket_ladder,
        )
        from tmlibrary_tpu.jterator.pipeline import description_digest

        ceiling = int(args["max_objects"])
        ladder = resolve_bucket_ladder(
            ceiling, args.get("object_buckets", "auto")
        )
        key = self._routing_key(args, ceiling, ladder)
        from tmlibrary_tpu.capacity import site_count_snapshot

        table = site_count_snapshot(key)
        peak = observed_peak(key)
        if not table and peak is None:
            # true cold start: no per-site history AND no program-family
            # peak.  A uniform prediction cannot beat directory order,
            # and pinning a guessed rung would mint compiles the
            # unpacked run never pays — degenerate to no plan (classic
            # ladder[0]-and-escalate routing) until history exists.
            schedule_mod.write_plan(self._schedule_plan_path, None)
            return None
        # prior for sites with no history: the routing-key peak when one
        # exists, else the densest harvested site (conservative)
        prior = float(peak) if peak is not None else float(max(table.values()))
        predicted = schedule_mod.predict_site_counts(key, sites, prior)
        n_dev = args["n_devices"] or len(jax.devices())
        n_dev = min(int(n_dev), len(jax.devices()))
        return schedule_mod.pack_plan(
            sites, predicted, batch_size, ladder, n_dev,
            seed=description_digest(self._description(args)),
            mode=mode, source=source,
        )

    def _auto_batch_size(self, n_devices: int = 0) -> int:
        """``batch_size=0``.  On device backends the default is a pixel
        budget, not a site count: the hardware-swept ``best_batch`` (else
        the static 32) was measured on 256x256 sites, so it is scaled by
        this experiment's site pixels — 128 sites of 256x256 become one
        2160x2160 field per batch, which is what fits HBM next to the
        in-flight window.  The budget is then rounded up to a whole
        number of sites per device of the mesh (``n_devices``, 0 = all):
        a batch smaller than the mesh is padded with copies of its first
        site, and those devices recompute it for nothing.  It never
        exceeds the batch the sweep ran — smaller sites than the swept
        one were not measured.  The sweep measured the device, so a CPU
        run keeps the static default."""
        import jax

        if jax.default_backend() == "cpu":
            return 32
        from tmlibrary_tpu.tuning import TUNED_SITE_PIXELS, tuned_batch_size

        tuned = tuned_batch_size()
        swept = tuned or 32
        exp = self.store.experiment
        site_pixels = max(1, int(exp.site_height) * int(exp.site_width))
        n_dev = min(int(n_devices) or len(jax.devices()), len(jax.devices()))
        budget = max(1, swept * TUNED_SITE_PIXELS // site_pixels)
        per_device = min(-(-budget // n_dev), max(1, swept // n_dev))
        batch = per_device * n_dev
        logger.info(
            "batch_size auto: %d sites/batch (%s %d sites of 256x256, "
            "scaled to %dx%d sites, %d per device on %d)", batch,
            "tuning best_batch" if tuned else "default", swept,
            exp.site_height, exp.site_width, per_device, n_dev,
        )
        return batch

    # ---------------------------------------------------------------- compile
    def _description(self, args):
        """The parsed pipeline description alone — prefetch workers need
        the channel/object lists to plan store reads without forcing a
        compile on their thread."""
        from pathlib import Path

        from tmlibrary_tpu.jterator.description import PipelineDescription

        with self._compile_lock:
            if self._desc is None:
                pipe_path = Path(args["pipe"])
                if not pipe_path.is_absolute():
                    pipe_path = self.store.root / pipe_path
                desc = PipelineDescription.load(pipe_path)
                self._check_channel_planes(desc, args)
                self._desc = desc
            return self._desc

    def _channel_cycle(self, args, name: str) -> int:
        """The acquisition cycle a channel's planes, illumination
        statistics and shifts are read from: its description entry's
        ``cycle``, else the step's ``cycle`` argument (every channel of a
        one-cycle experiment; the spatial layout without a ``pipe``)."""
        if args["pipe"]:
            for ch in self._description(args).channels:
                if ch.name == name and ch.cycle is not None:
                    return ch.cycle
        return args["cycle"]

    def _check_channel_planes(self, desc, args) -> None:
        """Every channel's plane exists in the cycle it is asked from —
        found out when the description is first read, before anything is
        read for a launch: a multiplexed plate's stains live in one cycle
        each, and a typo in ``cycle`` would otherwise surface as a missing
        ``.npy`` from a prefetch worker, batches into the step."""
        exp = self.store.experiment
        for ch in desc.channels:
            cycle = args["cycle"] if ch.cycle is None else ch.cycle
            zplanes = range(exp.n_zplanes) if ch.zstack else [args["zplane"]]
            if not all(
                self.store.has_plane(
                    cycle=cycle, channel=exp.channel_index(ch.name),
                    tpoint=args["tpoint"], zplane=zp)
                for zp in zplanes
            ):
                held = sorted(
                    c for c in range(exp.n_cycles)
                    if self.store.has_plane(
                        cycle=c, channel=exp.channel_index(ch.name),
                        tpoint=args["tpoint"], zplane=args["zplane"]))
                raise PipelineError(
                    f"channel '{ch.name}' is asked from cycle {cycle}, "
                    f"which holds no plane of it (cycles that do: {held})"
                )

    def _pipeline(self, args, capacity: int | None = None):
        """The compiled batch program for ``capacity`` (default: the
        ``max_objects`` ceiling).  One entry per object-capacity bucket —
        the router picks the capacity at launch time, and collect's
        auto-resegmentation re-runs a batch at a doubled ceiling, so the
        cache is keyed by the cap a program was actually built for."""
        self._description(args)
        cap = int(capacity if capacity is not None else args["max_objects"])
        from tmlibrary_tpu import qc as qc_mod

        # the QC gate joins the instance cache key: a QC-on program
        # returns (SiteResult, qc_stats) instead of a bare SiteResult,
        # so a mid-process gate flip (tests, tools) must never reuse a
        # program built for the other shape
        qc_on = qc_mod.enabled()
        cache_key = (cap, qc_on)
        with self._compile_lock:
            if cache_key not in self._compiled:
                # aligned multiplexing experiments crop every channel to the
                # inter-cycle intersection (reference SiteIntersection); the
                # window is experiment-static, so it compiles into the program
                if not self._window_resolved:
                    if any(ch.align for ch in self._desc.channels):
                        try:
                            w = self.store.read_intersection()
                            self._window = (w["top"], w["bottom"],
                                            w["left"], w["right"])
                        except StoreError:
                            self._window = None  # align step didn't run: no crop
                        if self._window == (0, 0, 0, 0):
                            self._window = None
                    self._window_resolved = True
                # process-level cache: a re-built Step (fresh Workflow, engine
                # re-run, tool request) running the same description reuses
                # the traced+compiled program instead of re-paying trace+load
                from tmlibrary_tpu.jterator.pipeline import (
                    cached_batch_fn,
                    weight_digests,
                )

                # checkpoint provenance, once per step: the resolved
                # weight content digests this run's programs compiled
                # against (the same digests keying the program cache)
                digests = weight_digests(self._desc)
                if digests and not getattr(self, "_weights_logged", False):
                    self._weights_logged = True
                    logger.info(
                        "model weights resolved: %s",
                        "; ".join(f"{m} {s} @{d}" for m, s, d in digests),
                    )

                self._compiled[cache_key] = cached_batch_fn(
                    self._desc, cap, self._window,
                    # arg True defers to the config default (so
                    # TM_DONATE_BUFFERS=0 still disables it); arg False
                    # forces donation off for this run
                    donate=None if args.get("donate_buffers", True) else False,
                    qc=qc_on,
                )
            return self._desc, self._compiled[cache_key]

    # -------------------------------------------------------------------- run
    def _effective_batch(self, batch: dict) -> dict:
        """Fold in collect's auto-resegmentation cap escalation.  The
        override lives in a SIDE file rather than a rewritten
        batch_*.json: the engine's resume staleness check compares
        planned batch args against the description's, and a rewritten
        cap would read as "args changed" and trigger a from-scratch
        re-plan that wipes every output."""
        override = self._cap_overrides().get(str(batch["index"]))
        if override and override > batch["args"].get("max_objects", 0):
            return {**batch, "args": {**batch["args"],
                                      "max_objects": int(override)}}
        return batch

    def _route_capacity(self, batch: dict) -> int:
        """Pick the object-capacity bucket for a batch at launch time.

        Ordering matters for the pipelined executor: routing happens on
        the engine thread at launch, reading the peak per-site count the
        persist worker has recorded so far — the first batch has no
        history, so it starts from the hardware-swept capacity verdict
        (``TUNING.json``) when one is on the ladder, else the ladder's
        smallest bucket.  A mis-route only costs a re-launch at the rung
        the launch's own demand selects (:meth:`_persist` re-launches
        before persisting), never a wrong result."""
        args = batch["args"]
        ceiling = int(args["max_objects"])
        from tmlibrary_tpu.capacity import resolve_bucket_ladder, select_capacity

        ladder = resolve_bucket_ladder(
            ceiling, args.get("object_buckets", "auto")
        )
        if len(ladder) == 1:
            return ceiling
        # a packed batch routes to its PLANNED rung: the whole point of
        # rung-homogeneous packing is that a sparse batch stops paying
        # for the global peak.  Under-prediction only costs the
        # re-launch at the demanded rung (_persist), never a wrong result.
        planned = (batch.get("schedule") or {}).get("rung")
        if planned and int(planned) in ladder:
            return int(planned)
        from tmlibrary_tpu.capacity import observed_peak

        observed = observed_peak(self._routing_key(args, ceiling, ladder))
        if observed is None:
            from tmlibrary_tpu.tuning import tuned_object_capacity

            hint = tuned_object_capacity()
            if hint and hint in ladder:
                return int(hint)
            return ladder[0]
        return select_capacity(observed, ladder)

    def _routing_key(self, args, ceiling: int,
                     ladder: tuple[int, ...]) -> str:
        """The compiled-program-family key scoping this step's bucket
        history (memoized per (ceiling, ladder) — the description digest
        is instance-stable)."""
        from tmlibrary_tpu.capacity import routing_key
        from tmlibrary_tpu.jterator.pipeline import description_digest

        desc = self._description(args)
        cache_key = (int(ceiling), tuple(ladder))
        with self._bucket_lock:
            key = self._routing_keys.get(cache_key)
            if key is None:
                key = routing_key(description_digest(desc), ceiling, ladder)
                self._routing_keys[cache_key] = key
            return key

    def _note_peak(self, args, peak: int) -> None:
        """Feed one batch's peak per-site object count into the
        per-program routing history (persist-worker side)."""
        from tmlibrary_tpu.capacity import (
            note_observed_peak,
            resolve_bucket_ladder,
        )

        ceiling = int(args["max_objects"])
        ladder = resolve_bucket_ladder(
            ceiling, args.get("object_buckets", "auto")
        )
        note_observed_peak(self._routing_key(args, ceiling, ladder), peak)

    def _note_site_costs(self, args, sites, site_counts) -> None:
        """Feed one batch's per-site peak object counts into the work
        model's EWMA history (persist-worker side, same stream as
        :meth:`_note_peak`).  Fed unconditionally — a schedule-off run
        still builds the history a later packed run predicts from."""
        try:
            from tmlibrary_tpu.capacity import (
                note_site_counts,
                resolve_bucket_ladder,
            )

            ceiling = int(args["max_objects"])
            ladder = resolve_bucket_ladder(
                ceiling, args.get("object_buckets", "auto")
            )
            note_site_counts(
                self._routing_key(args, ceiling, ladder),
                {int(s): float(c) for s, c in zip(sites, site_counts)},
            )
        except Exception:
            logger.debug("site-cost history update failed", exc_info=True)

    def _shard_objects(self, args, site_counts) -> "list[int] | None":
        """Actual per-shard object totals under the leading-axis slicing
        :meth:`_load_inputs` applies (ceil-width chunks; padding lanes
        are appended at the END and their recomputed objects are dropped
        on export, so they count zero here).  None on a 1-device mesh —
        there is no skew to report."""
        try:
            import jax

            n_dev = int(args["n_devices"] or len(jax.devices()))
            n_dev = min(n_dev, len(jax.devices()))
        except Exception:
            return None
        n = len(site_counts)
        if n_dev <= 1 or n == 0:
            return None
        chunk = -(-n // n_dev)
        arr = np.asarray(site_counts)
        return [
            int(arr[s * chunk:(s + 1) * chunk].sum()) for s in range(n_dev)
        ]

    def _note_schedule(self, escalations: int) -> None:
        """Plan-accounting counters: batches dispatched under a schedule
        plan, and plan hits (the planned rung held without an escalation
        re-launch) — the prediction-quality signal ``tmx top``'s PACK
        row and ``tmx perf`` read."""
        if not telemetry.enabled():
            return
        reg = telemetry.get_registry()
        reg.counter("tmx_schedule_batches_total").inc()
        if not escalations:
            reg.counter("tmx_schedule_plan_hit_total").inc()

    def run_batch(self, batch: dict) -> dict:
        self._mark_work_start()
        batch = self._effective_batch(batch)
        # .get: batch JSONs persisted by a pre-layout init lack the key
        if batch["args"].get("layout", "sites") == "spatial":
            return self._run_spatial(batch)
        cap = self._route_capacity(batch)
        tally: dict = {}
        result = self._launch(batch, capacity=cap, tally=tally)
        return self._persist(batch, result, capacity=cap, tally=tally)

    # -------------------------------------------------- throughput gauge
    # sites/sec over cumulative wall time since the first batch — the same
    # total-units / perf_counter-wall math bench.py's
    # jterator_*_sites_per_sec metrics use, so the live gauge converges to
    # the bench figure for the same workload (pipelined overlap included)
    def _mark_work_start(self) -> None:
        if telemetry.enabled() and getattr(self, "_sites_t0", None) is None:
            self._sites_lock = threading.Lock()
            self._sites_t0 = time.perf_counter()
            self._sites_done = 0

    def _note_sites(self, n: int) -> None:
        if not telemetry.enabled() or getattr(self, "_sites_t0", None) is None:
            return
        with self._sites_lock:
            self._sites_done += int(n)
            elapsed = time.perf_counter() - self._sites_t0
            done = self._sites_done
        reg = telemetry.get_registry()
        reg.counter("tmx_jterator_sites_total").inc(n)
        if elapsed > 0:
            reg.gauge("tmx_jterator_sites_per_sec").set(done / elapsed)

    def _note_bucket(
        self, cap: int, ceiling: int, objects: int, slots: int,
        escalations: int, rungs_skipped: int,
    ) -> None:
        """Bucket-router telemetry: routed/saturated/skipped counters plus the
        run-cumulative slot-occupancy and padded-FLOPs-avoided gauges
        (the per-object measure FLOPs scale with the capacity, so the
        slot ratio routed/ceiling IS the padded-work fraction saved)."""
        if not telemetry.enabled():
            return
        reg = telemetry.get_registry()
        reg.counter(
            "tmx_jterator_bucket_routed_total", capacity=str(cap)
        ).inc()
        if escalations:
            reg.counter("tmx_jterator_bucket_saturated_total").inc(escalations)
        if rungs_skipped:
            reg.counter(
                "tmx_jterator_bucket_rungs_skipped_total"
            ).inc(rungs_skipped)
        from tmlibrary_tpu.capacity import ceiling_slots

        with self._bucket_lock:
            self._occ_objects = getattr(self, "_occ_objects", 0) + objects
            self._occ_slots = getattr(self, "_occ_slots", 0) + slots
            self._occ_ceiling_slots = (
                getattr(self, "_occ_ceiling_slots", 0)
                + ceiling_slots(slots, cap, ceiling)
            )
            occ_o, occ_s, occ_c = (
                self._occ_objects, self._occ_slots, self._occ_ceiling_slots
            )
        if occ_s:
            reg.gauge("tmx_jterator_slot_occupancy").set(occ_o / occ_s)
        if occ_c:
            reg.gauge("tmx_jterator_padded_flops_avoided_frac").set(
                1.0 - occ_s / occ_c
            )

    # ------------------------------------------------- launch/persist split
    # (the pipelined executor's step protocol — workflow/pipelined.py)
    def prefetch_batch(self, batch: dict):
        """Host-side input loading only (store reads, illumstats, shift
        tables, mosaic stitching) — safe on a prefetch worker thread."""
        batch = self._effective_batch(batch)
        if batch["args"].get("layout", "sites") == "spatial":
            return self._prefetch_spatial(batch)
        with telemetry.span("load"):
            return self._load_inputs(batch)

    def launch_batch(self, batch: dict, prefetched=None):
        """Async device dispatch; returns ``(effective_batch, ctx)`` with
        un-fetched device arrays inside ``ctx``."""
        self._mark_work_start()
        batch = self._effective_batch(batch)
        if batch["args"].get("layout", "sites") == "spatial":
            return batch, ("spatial", self._launch_spatial(batch, prefetched))
        cap = self._route_capacity(batch)
        # meta travels alongside the device arrays so block_batch can stamp
        # per-device completion times against the true dispatch instant
        meta = {"t0": time.perf_counter(), "index": batch.get("index")}
        plan = batch.get("schedule") or {}
        if plan.get("shard_work"):
            # predicted per-shard work rides to the telemetry/ledger
            # surfaces so the anomaly plane can tell data skew (predicted
            # AND actual both skewed) from a slow device (actual only)
            meta["predicted_shard_work"] = [
                float(w) for w in plan["shard_work"]
            ]
        # meta doubles as the batch's tally of bytes sent to the device
        return batch, (
            "sites",
            (self._launch(batch, prefetched, capacity=cap, tally=meta),
             cap, meta),
        )

    def block_batch(self, ctx) -> None:
        """Wait for the launched device arrays (distinct pipeline-stats
        phase from the persist writes that follow)."""
        import jax

        kind, payload = ctx
        if kind == "sites":
            meta = payload[2] if len(payload) > 2 else None
            if meta is not None and telemetry.enabled():
                times = telemetry.device_wall_times(payload[0], meta["t0"])
                if len(times) > 1:
                    meta["device_times"] = times
                    meta["skew"] = telemetry.record_device_times(
                        times, step=self.name, batch=meta.get("index"),
                        predicted=meta.get("predicted_shard_work"),
                    )
            # SiteResult is a registered pytree: block on all leaves
            jax.block_until_ready(payload[0])
            return
        with telemetry.span("device_wait"):
            jax.block_until_ready(payload["labels_dev"])
            jax.block_until_ready(payload["count_dev"])
            if payload["sec"] is not None:
                jax.block_until_ready(payload["sec"][2])

    @property
    def persist_serial(self) -> bool:
        """True when :meth:`persist_batch` must see batches one at a time,
        in submission order: the executor then gives persist one worker.
        Only the QC session asks for it — it folds running statistics
        (z-scores against the sites seen so far, P² sketches) in the
        order batches are observed, and that order is in the ledger's
        ``qc_batch`` events.  Everything else a persist touches is per
        batch (one Parquet shard, disjoint rows of a label stack, one
        well's mosaic) or merges commutatively under a lock (the routing
        history's max, ``saturation.json`` keyed by batch)."""
        from tmlibrary_tpu import qc as qc_mod

        return qc_mod.enabled()

    def persist_batch(self, batch: dict, ctx) -> dict:
        """Fetch + write one launched batch (the effective batch from
        :meth:`launch_batch`).  Entered by several persist workers at
        once, each with another batch (:attr:`persist_serial`)."""
        kind, payload = ctx
        if kind == "spatial":
            return self._persist_spatial(batch, payload)
        result, cap = payload[0], payload[1]
        meta = payload[2] if len(payload) > 2 else None
        out = self._persist(batch, result, capacity=cap, tally=meta)
        if meta and meta.get("device_times"):
            # ride the batch summary so the ledger's batch_done record (and
            # registry_from_ledger) carry device provenance; the ledger
            # append itself stays on the engine thread
            out["device_wall_times"] = {
                d: round(float(t), 6) for d, t in meta["device_times"]
            }
            out["straggler_skew_s"] = round(float(meta.get("skew", 0.0)), 6)
        if meta and meta.get("predicted_shard_work"):
            pred = [round(float(w), 3) for w in meta["predicted_shard_work"]]
            out["predicted_shard_work"] = pred
            out["predicted_skew"] = round(max(pred) - min(pred), 3)
        return out

    # ------------------------------------------------------------ spatial run
    def _stitched_channel(
        self, sites, srefs, ch_index, args, n_sy, n_sx, h, w
    ) -> "np.ndarray":
        """One channel's well mosaic, illumination-corrected when corilla
        statistics exist and cycle-aligned when the align step stored
        shifts for this cycle (the same correct+align prep the sites
        layout applies — the two layouts must see the same pixels).
        Alignment is shift-only: the per-site intersection crop cannot
        apply at mosaic scale (it would shrink tiles out of the grid), so
        shifted-in edges are zero-filled exactly like the sites path's
        ``shift_image``."""
        cycle = self._channel_cycle(args, next(
            c.name for c in self.store.experiment.channels
            if c.index == ch_index))
        with telemetry.span("stitch", bytes=4 * n_sy * h * n_sx * w):
            imgs = self.store.read_sites(
                sites, cycle=cycle, channel=ch_index,
                tpoint=args["tpoint"], zplane=args["zplane"],
            )
            if self.store.has_illumstats(cycle=cycle, channel=ch_index):
                cont = IllumstatsContainer.from_store(
                    self.store.read_illumstats(cycle=cycle, channel=ch_index)
                )
                imgs = _correct_batch(imgs, cont.mean_log, cont.std_log)
            shifts = None
            if args.get("spatial_align", True) and self.store.has_shifts(cycle):
                shifts = self.store.read_shifts(cycle)
            mosaic = np.zeros((n_sy * h, n_sx * w), np.float32)
            for img, r, site_idx in zip(imgs, srefs, sites):
                if shifts is not None:
                    dy, dx = int(shifts[site_idx][0]), int(shifts[site_idx][1])
                    if dy or dx:
                        img = _host_shift(img, dy, dx)
                mosaic[r.site_y * h:(r.site_y + 1) * h,
                       r.site_x * w:(r.site_x + 1) * w] = img
            return mosaic

    def _stitch_validity(
        self, sites, srefs, args, n_sy, n_sx, h, w, cycle
    ) -> "np.ndarray | None":
        """Boolean mosaic of pixels that carry real data after the
        per-site alignment shift (zero-filled shifted-in edges are
        False).  None when no shift moved anything — every pixel is
        valid and callers can skip the masked-threshold path."""
        if not (args.get("spatial_align", True)
                and self.store.has_shifts(cycle)):
            return None
        shifts = self.store.read_shifts(cycle)
        if not any(
            int(shifts[s][0]) or int(shifts[s][1]) for s in sites
        ):
            return None
        valid = np.zeros((n_sy * h, n_sx * w), bool)
        for r, site_idx in zip(srefs, sites):
            v = _host_shift(
                np.ones((h, w), np.float32),
                int(shifts[site_idx][0]), int(shifts[site_idx][1]),
            ) > 0
            valid[r.site_y * h:(r.site_y + 1) * h,
                  r.site_x * w:(r.site_x + 1) * w] = v
        return valid

    def _run_spatial(self, batch: dict) -> dict:
        return self._persist_spatial(batch, self._launch_spatial(batch))

    def _prefetch_spatial(self, batch: dict) -> dict:
        """Host half of the spatial launch: resolve the well geometry and
        stitch the segmentation channel's mosaic (store reads + host
        assembly) ahead of device dispatch."""
        args = batch["args"]
        sites = batch["sites"]
        exp = self.store.experiment
        ch_name = args["spatial_channel"] or exp.channels[0].name
        idx = exp.channel_index(ch_name)
        refs = list(exp.sites())
        srefs = [refs[i] for i in sites]
        h, w = exp.site_height, exp.site_width
        n_sy = max(r.site_y for r in srefs) + 1
        n_sx = max(r.site_x for r in srefs) + 1
        mosaic = self._stitched_channel(sites, srefs, idx, args, n_sy, n_sx, h, w)
        valid = self._stitch_validity(sites, srefs, args, n_sy, n_sx, h, w,
                                      self._channel_cycle(args, ch_name))
        return {
            "idx": idx, "channel": ch_name, "srefs": srefs, "h": h, "w": w,
            "n_sy": n_sy, "n_sx": n_sx, "mosaic": mosaic, "valid": valid,
        }

    def _launch_spatial(self, batch: dict, prefetched: dict | None = None) -> dict:
        """Whole-mosaic segmentation of one well (``--layout spatial``) —
        the LAUNCH half: host stitch + async device dispatch (primary
        segmentation and, when configured, the chained secondary
        watershed).  Returns a context of un-fetched device arrays for
        :meth:`_persist_spatial`.

        Stitch the well's sites into one mosaic (illumination-corrected
        when corilla statistics exist — same op as the sites layout's
        preprocess), row-shard it over the device mesh, segment with
        halo-exact smoothing + a global Otsu cut +
        :func:`~tmlibrary_tpu.parallel.label.distributed_connected_components`
        (scipy scan order across the WHOLE mosaic), then export: per-site
        label stacks carrying the global ids, a mosaic-level polygon table
        when ``as_polygons`` is set, and a host-side ragged feature table
        (area/centroid) for the well.  This is the rebuild's
        context-parallelism path: objects crossing site borders keep one
        identity, which per-site fan-out (reference or 'sites' layout)
        cannot do.  Cycle-alignment shifts stored by the align step are
        applied per site during stitching (shift-only — see
        :meth:`_stitched_channel`), so multiplexing cycles segment in
        the aligned frame; ``--figures`` writes one downsampled
        whole-well overlay PNG per object family."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh, NamedSharding, PartitionSpec

        from tmlibrary_tpu.parallel.label import (
            segment_mosaic,
            sharded_otsu_mask,
            watershed_mosaic,
        )

        args = batch["args"]
        sites = batch["sites"]
        exp = self.store.experiment
        tpoint, zplane = args["tpoint"], args["zplane"]

        if prefetched is None:
            prefetched = self._prefetch_spatial(batch)
        idx = prefetched["idx"]
        srefs = prefetched["srefs"]
        h, w = prefetched["h"], prefetched["w"]
        n_sy, n_sx = prefetched["n_sy"], prefetched["n_sx"]
        mosaic = prefetched["mosaic"]

        # alignment zero-fills shifted-in edges INSIDE the mosaic; those
        # stripes would feed the global Otsu histogram as an artificial
        # zero mode (the sites layout crops them away via the
        # intersection window), so when any exist the threshold is
        # computed over the VALID pixels only and passed in explicitly
        # (stitch + validity come prefetched; the device-side smoothing
        # and Otsu stay on the dispatching thread)
        valid = prefetched["valid"]
        threshold = None
        if valid is not None:
            from tmlibrary_tpu.ops.smooth import gaussian_smooth
            from tmlibrary_tpu.ops.threshold import otsu_value

            sm = np.asarray(jax.jit(
                lambda x: gaussian_smooth(x, args["spatial_sigma"])
            )(jnp.asarray(mosaic)))
            threshold = float(otsu_value(jnp.asarray(sm[valid])))

        requested = args["n_devices"] or len(jax.devices())
        requested = min(requested, len(jax.devices()))
        hm, wm = mosaic.shape
        kind = args.get("spatial_grid", "auto")
        nr, nc = _spatial_mesh_shape(kind, requested, hm, wm)
        n_dev = nr * nc
        if n_dev < requested:
            logger.info(
                "spatial layout: %dx%d mesh uses %d of %d devices — "
                "mosaic %dx%d must divide the mesh evenly",
                nr, nc, n_dev, requested, hm, wm,
            )
        # an explicit grid runs the 2-D programs also where the columns
        # cannot be split (a column axis of one)
        if kind == "grid" or nc > 1:
            mesh = Mesh(
                np.asarray(jax.devices()[:n_dev]).reshape(nr, nc),
                ("rows", "cols"),
            )
        else:
            mesh = Mesh(np.asarray(jax.devices()[:n_dev]), ("rows",))
        # host memory to the shards directly: no device ever holds a
        # whole plane, and nothing is re-sharded on the device
        sharding = NamedSharding(mesh, PartitionSpec(*mesh.axis_names))

        # with a secondary channel every stitched mosaic is used at least
        # twice (watershed input + both families' intensity loops), so
        # memoize — accepting a peak of one mosaic per channel.  Without
        # one, each channel is read exactly once: caching would only
        # regress peak memory (plate-scale mosaics are GBs each), so
        # stitch on demand and let each mosaic go out of scope.
        sec_ch = args.get("spatial_secondary_channel", "")
        stitched = {idx: mosaic}

        def get_channel(i: int) -> np.ndarray:
            if i in stitched:
                return stitched[i]
            m = self._stitched_channel(sites, srefs, i, args, n_sy, n_sx, h, w)
            if sec_ch:
                stitched[i] = m
            return m

        sec_np = None
        if sec_ch:
            sec_np = np.asarray(
                get_channel(exp.channel_index(sec_ch)), np.float32)
        h2d_bytes = int(mosaic.nbytes) + (
            int(sec_np.nbytes) if sec_np is not None else 0)
        with telemetry.span("upload", bytes=h2d_bytes):
            img = jax.device_put(mosaic, sharding)
            sec_img = (jax.device_put(sec_np, sharding)
                       if sec_np is not None else None)

        # secondary objects over the whole mosaic: primary labels seed a
        # distributed watershed through a second channel (the sites
        # layout's segment_secondary chain — otsu mask, level flooding,
        # seed ids preserved), so cells keep their nucleus' GLOBAL id.
        # Chained DEVICE-side on the un-fetched primary labels; the one
        # wait of the chain is the root table's overflow check.
        sec = steps_dev = None
        with telemetry.span("segment"):
            labels, count, info = segment_mosaic(
                img, mesh, sigma=args["spatial_sigma"], threshold=threshold,
                # max_objects is a capacity a SITE (256 by default);
                # a shard holds several sites' objects, so its root
                # table never gets under 4096
                max_roots_per_shard=max(args["max_objects"], 4096),
            )
            # Otsu's cut a stain, as used (before the secondary factor),
            # and un-fetched what each was taken from
            otsu = {prefetched["channel"]: info.pop("otsu")}
            if sec_img is not None:
                if valid is not None:
                    from tmlibrary_tpu.ops import threshold as threshold_ops

                    # same zero-stripe exclusion as the primary threshold
                    t_sec = threshold_ops.otsu_value(
                        jnp.asarray(sec_np[valid]))
                    otsu[sec_ch] = {"cut": t_sec}
                    mask = sec_img > (
                        float(t_sec) * args["spatial_secondary_factor"])
                else:
                    mask, otsu[sec_ch] = sharded_otsu_mask(
                        sec_img, mesh,
                        correction_factor=args["spatial_secondary_factor"],
                    )
                sec_labels, steps_dev = watershed_mosaic(
                    sec_img, labels, mask, mesh,
                    n_levels=args["spatial_secondary_levels"],
                )
                sec = (args["spatial_secondary_objects"], sec_np, sec_labels)

        return {
            "batch": batch, "labels_dev": labels, "count_dev": count,
            "sec": sec, "mosaic": mosaic, "get_channel": get_channel,
            "sites": sites, "srefs": srefs, "mesh_shape": [nr, nc],
            "tpoint": tpoint, "zplane": zplane, "h2d_bytes": h2d_bytes,
            "info": info, "adopt_steps_dev": steps_dev, "otsu_dev": otsu,
        }

    def _persist_spatial(self, batch: dict, ctx: dict) -> dict:
        """Fetch one launched well's device results and write them out —
        the host half of the stitch → device → write overlap
        (``run_batches_pipelined`` launches well N+1's stitch while this
        blocks on well N's arrays).  Peak memory holds two wells'
        mosaics while the pipeline is full."""
        args = batch["args"]
        sites = ctx["sites"]
        srefs = ctx["srefs"]
        tpoint, zplane = ctx["tpoint"], ctx["zplane"]
        get_channel = ctx["get_channel"]
        with telemetry.span("fetch") as fetched:
            labels = np.asarray(ctx["labels_dev"])
            count = int(ctx["count_dev"])
            sec_labels = (None if ctx["sec"] is None
                          else np.asarray(ctx["sec"][2]))
            fetched["bytes"] = int(labels.nbytes) + (
                0 if sec_labels is None else int(sec_labels.nbytes))
            # Otsu's cut a stain, as the programs used it: two scalars
            # that come with the labels, so the launch waits for neither
            otsu_cut = {stain: float(reading["cut"])
                        for stain, reading in ctx["otsu_dev"].items()}
            fetched["otsu_cut"] = otsu_cut
        shard = _well_shard(batch)

        def emit_figure(fam_name, fam_mosaic, fam_labels):
            if not args.get("figures"):
                return
            from tmlibrary_tpu.jterator.figures import write_mosaic_figure

            write_mosaic_figure(
                self.store.root / "figures", fam_name, fam_mosaic,
                fam_labels, shard,
            )

        name = args["spatial_objects"]
        self._persist_mosaic_objects(
            name, labels, count, batch, args, sites, srefs, get_channel,
            tpoint, zplane, shard,
        )
        objects = {name: count}
        emit_figure(name, ctx["mosaic"], labels)

        if sec_labels is not None:
            sec_name, sec_np, _ = ctx["sec"]
            # watershed preserves seed ids: the id space (and count) is
            # the primary's, so features join across the two families
            self._persist_mosaic_objects(
                sec_name, sec_labels, count, batch, args, sites, srefs,
                get_channel, tpoint, zplane, shard,
            )
            objects[sec_name] = count
            emit_figure(sec_name, sec_np, sec_labels)

        self._note_sites(len(sites))
        summary = {
            "n_sites": len(sites),
            "objects": objects,
            "mosaic_shape": [int(labels.shape[0]), int(labels.shape[1])],
            "layout": "spatial",
            "mesh_shape": ctx["mesh_shape"],
            # bytes handed to the device: every plane, once, to its shards
            "h2d_bytes": ctx["h2d_bytes"],
            "otsu_cut": otsu_cut,
        }
        # what the sharded programs counted (a 1-device CPU mesh runs the
        # native union-find and frontier flood, which count nothing): the
        # fullest shard's roots against max_objects, the seam loop's
        # rounds, the watershed's adopt steps
        counted = dict(ctx["info"])
        if ctx["adopt_steps_dev"] is not None:
            counted["adopt_steps"] = int(ctx["adopt_steps_dev"])
        summary.update(counted)
        if counted and telemetry.enabled():
            reg = telemetry.get_registry()
            for key in ("seam_rounds", "adopt_steps"):
                if key in counted:
                    reg.counter(
                        f"tmx_jterator_mosaic_{key}_total").inc(counted[key])
            reg.gauge("tmx_jterator_mosaic_roots_max_per_shard").set(
                counted["roots_max_per_shard"])
        return summary

    def _persist_mosaic_objects(
        self, name, labels, count, batch, args, sites, srefs,
        get_channel, tpoint, zplane, shard,
    ) -> None:
        """Persist one mosaic-scale object family: per-site label stacks
        carrying the global ids, the ragged host-side feature table
        (morphology + per-channel intensity + Zernike), and optional
        mosaic-frame polygons.  ``get_channel(i)`` returns the stitched
        (corrected) mosaic of channel ``i`` — memoized by the caller so
        families share one stitch per channel."""
        import pandas as pd

        exp = self.store.experiment
        h, w = exp.site_height, exp.site_width
        per_site = np.stack([
            labels[r.site_y * h:(r.site_y + 1) * h,
                   r.site_x * w:(r.site_x + 1) * w]
            for r in srefs
        ])
        with telemetry.span("write_labels"):
            self.store.write_labels(per_site, sites, name,
                                    tpoint=tpoint, zplane=zplane)

        # ragged global features, host-side (object count is dynamic here —
        # nothing is padded to max_objects in the mosaic path).  ONE
        # native C pass over the mosaic (area + centroid sums + bounding
        # boxes), chunked-vectorized numpy fallback — no O(H)
        # interpreter loop on a plate-scale mosaic.
        from tmlibrary_tpu import native as native_mod

        with telemetry.span("morph"):
            area_i, cy_sum, cx_sum, ymin, ymax, xmin, xmax = (
                native_mod.mosaic_morph_host(labels, count)
            )
        area = area_i[1:].astype(np.float64)
        denom = np.maximum(area, 1)
        cy = cy_sum[1:] / denom
        cx = cx_sum[1:] / denom
        bbox_h = (ymax[1:] - ymin[1:] + 1).astype(np.float64)
        bbox_w = (xmax[1:] - xmin[1:] + 1).astype(np.float64)

        # hull solidity uses the native helper when the library built; its
        # pure-python fallback is O(count * H * W) — at mosaic scale that
        # is effectively a hang, so degrade to NaN instead
        from tmlibrary_tpu import native as native_mod

        if count and native_mod.available():
            with telemetry.span("solidity"):
                solidity = native_mod.solidity_host(
                    labels, count, areas=area
                ).astype(np.float64)
        else:
            if count:
                logger.info(
                    "native library unavailable: mosaic solidity emitted "
                    "as NaN (the python hull fallback is quadratic at "
                    "mosaic scale)"
                )
            solidity = np.full(count, np.nan)
        plate, well_row, well_col = batch["well"]
        cols = {
            "site_index": -1,  # mosaic objects may span several sites
            "plate": plate,
            "well_row": well_row,
            "well_col": well_col,
            "site_y": -1,
            "site_x": -1,
            "label": np.arange(1, count + 1, dtype=np.int64),
            "Morphology_area": area,
            "Morphology_centroid_y": cy,
            "Morphology_centroid_x": cx,
            "Morphology_bbox_height": bbox_h,
            "Morphology_bbox_width": bbox_w,
            "Morphology_solidity": solidity,
        }
        # intensity over EVERY channel (sites-layout parity:
        # measure_intensity per channel), one stitched mosaic at a time;
        # the segmentation channel reuses the already-corrected stitch.
        # Zero-object wells still emit the (empty) columns so every
        # well's parquet shard carries the same schema.
        for ch in exp.channels:
            if count == 0:
                empty = np.zeros(0)
                for stat in ("mean", "sum", "std", "min", "max"):
                    cols[f"Intensity_{stat}_{ch.name}"] = empty
                continue
            vals_mosaic = get_channel(ch.index)
            with telemetry.span("intensity", channel=ch.name):
                s2, q2, mn2, mx2 = _mosaic_intensity_stats(
                    labels, vals_mosaic, count)
            mean2 = s2[1:] / denom
            var2 = np.maximum(q2[1:] / denom - mean2 * mean2, 0.0)
            cols[f"Intensity_mean_{ch.name}"] = mean2
            cols[f"Intensity_sum_{ch.name}"] = s2[1:]
            cols[f"Intensity_std_{ch.name}"] = np.sqrt(var2)
            cols[f"Intensity_min_{ch.name}"] = np.where(area > 0, mn2[1:], 0.0)
            cols[f"Intensity_max_{ch.name}"] = np.where(area > 0, mx2[1:], 0.0)
        # shape moments: the public ragged host Zernike handles a dynamic
        # object count in row blocks (mahotas semantics; default degree 9
        # matches the sites layout's measure_zernike default, 0 disables)
        z_degree = args["spatial_zernike_degree"]
        if z_degree > 0:
            from tmlibrary_tpu.ops.measure import (
                _zernike_coeffs,
                zernike_host_features,
            )

            zern = zernike_host_features(labels, count, z_degree)
            for z_idx, (n_z, m_z, _) in enumerate(_zernike_coeffs(z_degree)):
                cols[f"Zernike_{n_z}_{m_z}"] = zern[:, z_idx].astype(np.float64)
        with telemetry.span("write_features") as written:
            table = pd.DataFrame(cols)
            self.store.append_features(name, table, shard=shard)
            # object rows and feature columns (the seven site and label
            # keys left out), as the sites path writes them
            written["rows"] = len(table)
            written["columns"] = len(cols) - 7

        if args.get("as_polygons"):
            # mosaic-frame polygons: one ring per GLOBAL object, traced on
            # the stitched label image (site_index -1 marks the frame)
            from tmlibrary_tpu.ops.polygons import (
                labels_to_polygons,
                polygons_to_table,
            )

            polys = labels_to_polygons(labels)
            if polys:
                df = polygons_to_table(polys, site_index=-1)
                out = (self.store.root / "segmentations"
                       / f"{name}_polygons_{shard}.parquet")
                df.to_parquet(out, index=False)

    def run_batches_pipelined(self, batches, depth: int | None = None):
        """Generator over ``(batch, result_summary)`` with host work
        overlapped against device compute.

        XLA dispatch is asynchronous: device calls return futures
        immediately and only the host fetch blocks, so keeping a bounded
        window of launched batches in flight puts the host IO — store
        reads, Parquet writes, polygon tracing — in the shadow of device
        execution.  This recovers the reference's overlap of cluster
        jobs with DB writes (SURVEY.md §4.3 crossing points) without
        process fan-out.  Delegates to the shared
        :class:`~tmlibrary_tpu.workflow.pipelined.PipelinedExecutor`
        (``depth=None`` resolves config > tuning > per-backend default);
        yields stay in batch order and bit-identical to sequential runs.
        """
        from tmlibrary_tpu.workflow.pipelined import PipelinedExecutor

        yield from PipelinedExecutor(self, depth=depth).run(batches)

    def _load_inputs(self, batch: dict) -> dict:
        """Host-side input loading for a sites-layout batch: store reads,
        illumination statistics and shift tables, all as numpy — no
        device transfers, so a prefetch worker can run it while the
        device chews on earlier batches."""
        import jax

        args = batch["args"]
        sites = batch["sites"]
        desc = self._description(args)
        exp = self.store.experiment
        tpoint, zplane = args["tpoint"], args["zplane"]
        cycle_of = {ch.name: self._channel_cycle(args, ch.name)
                    for ch in desc.channels}

        n_dev = args["n_devices"] or len(jax.devices())
        n_dev = min(n_dev, len(jax.devices()))
        # pad the batch so the site axis shards evenly (padded lanes are
        # recomputed copies of site 0 and dropped on export)
        n_valid = len(sites)
        padded_sites = list(sites)
        if n_valid % n_dev:
            padded_sites += [sites[0]] * (n_dev - n_valid % n_dev)

        raw = {}
        for ch in desc.channels:
            idx = exp.channel_index(ch.name)
            cycle = cycle_of[ch.name]
            if ch.zstack:
                planes = [
                    self.store.read_sites(padded_sites, cycle=cycle, channel=idx,
                                          tpoint=tpoint, zplane=zp)
                    for zp in range(exp.n_zplanes)
                ]
                stack = np.stack(planes, axis=1)  # (B, Z, H, W)
            else:
                stack = self.store.read_sites(padded_sites, cycle=cycle, channel=idx,
                                              tpoint=tpoint, zplane=zplane)
            raw[ch.name] = stack
        for obj in desc.objects_in:
            raw[obj.name] = self.store.read_labels(padded_sites, obj.name,
                                                   tpoint=tpoint, zplane=zplane)

        stats = {}
        for ch in desc.channels:
            # volumes skip correction (see build_preprocess_fn) — don't
            # demand stats they will never use
            if ch.correct and not ch.zstack:
                idx = exp.channel_index(ch.name)
                cycle = cycle_of[ch.name]
                if not self.store.has_illumstats(cycle=cycle, channel=idx):
                    raise PipelineError(
                        f"channel '{ch.name}' wants illumination correction but "
                        f"corilla statistics are missing — run corilla first"
                    )
                cont = IllumstatsContainer.from_store(
                    self.store.read_illumstats(cycle=cycle, channel=idx)
                )
                stats[ch.name] = (cont.mean_log, cont.std_log)

        # a row of (dy, dx) for each aligned channel, from its own cycle's
        # table; a cycle with no table (the reference cycle, or no align
        # step) shifts nothing
        from tmlibrary_tpu.jterator.pipeline import aligned_channels

        shifts_np = None
        aligned = aligned_channels(desc)
        if aligned:
            tables = {
                cycle: self.store.read_shifts(cycle)[np.asarray(padded_sites)]
                for cycle in {cycle_of[name] for name in aligned}
                if self.store.has_shifts(cycle)
            }
            zeros = np.zeros((len(padded_sites), 2), np.int32)
            shifts_np = np.stack(
                [tables.get(cycle_of[name], zeros) for name in aligned],
                axis=1).astype(np.int32)

        return {"padded_sites": padded_sites, "n_dev": n_dev,
                "raw": raw, "stats": stats, "shifts_np": shifts_np,
                "cycles_read": sorted(set(cycle_of.values())),
                "aligned_channels": len(aligned)}

    def _launch(
        self, batch: dict, inputs: dict | None = None,
        capacity: int | None = None, tally: dict | None = None,
    ):
        """Transfer the (possibly prefetched) inputs and dispatch the
        device computation; returns without waiting for completion.
        ``tally["h2d_bytes"]`` grows by the host arrays handed to the
        device (planes, shifts, illumination statistics)."""
        import jax
        import jax.numpy as jnp

        from tmlibrary_tpu.parallel.mesh import batch_sharding, site_mesh

        _, fn = self._pipeline(batch["args"], capacity)
        if inputs is None:
            with telemetry.span("load"):
                inputs = self._load_inputs(batch)
        padded_sites = inputs["padded_sites"]
        n_dev = inputs["n_dev"]

        sharding = None
        if n_dev > 1:
            sharding = batch_sharding(site_mesh(n_dev))

        sent = [*inputs["raw"].values(),
                *(a for pair in inputs["stats"].values() for a in pair)]
        if inputs["shifts_np"] is not None:
            sent.append(inputs["shifts_np"])
        nbytes = sum(int(np.asarray(a).nbytes) for a in sent)
        if tally is not None:
            tally["h2d_bytes"] = tally.get("h2d_bytes", 0) + nbytes
            tally["cycles_read"] = inputs["cycles_read"]
            tally["aligned_channels"] = inputs["aligned_channels"]
        with telemetry.span("upload", bytes=nbytes):
            raw = {}
            for name, stack in inputs["raw"].items():
                arr = jnp.asarray(stack)
                raw[name] = jax.device_put(arr, sharding) if sharding else arr

            if inputs["shifts_np"] is not None:
                shifts = jnp.asarray(inputs["shifts_np"])   # (B, C, 2)
            else:
                # no channel is aligned: the program never reads the
                # argument, and the placeholder keeps the shape it always
                # had (the signature is part of a store entry's key)
                shifts = jnp.zeros((len(padded_sites), 2), jnp.int32)
            if sharding is not None:
                shifts = jax.device_put(shifts, sharding)

        self._note_speculation_ctx(
            batch["args"], capacity, (raw, inputs["stats"], shifts)
        )
        return fn(raw, inputs["stats"], shifts)

    # ------------------------------------------- compile-ahead speculation
    def _note_speculation_ctx(self, args, capacity, call_args) -> None:
        """Remember the shape/dtype skeleton of the latest dispatch so
        the compile-ahead warm thread (:meth:`speculate_ahead`) can
        precompile the next capacity rung against the exact same input
        signature.  No buffers are retained — the skeleton is
        ``ShapeDtypeStruct`` leaves only (the real arrays may be
        donated)."""
        try:
            from tmlibrary_tpu import aotstore

            if not aotstore.speculation_enabled():
                return
            from tmlibrary_tpu import perf

            cap = int(capacity if capacity is not None
                      else args["max_objects"])
            self._spec_ctx = (args, cap, perf.abstract_args(call_args, {}))
        except Exception:
            pass

    def speculate_ahead(self, upcoming=None) -> None:
        """Compile-ahead speculation (DESIGN.md §28): precompile the
        likely next capacity rungs on a background daemon thread while
        the device chews on dispatched batches, so bucket escalation
        (and the TUNING.json-hinted rung) never pays compile on the
        critical path.  Wired as the pipelined executor's warm hook;
        no-op when disabled, before the first dispatch, or while a
        previous warm thread is still running.

        ``upcoming`` (optional) is the not-yet-launched tail of the
        batch list: when batches carry a schedule plan, their planned
        rungs are certainties, not guesses, so the worker warms those
        first and falls back to the ladder heuristics after."""
        try:
            from tmlibrary_tpu import aotstore

            if not aotstore.speculation_enabled():
                return
        except Exception:
            return
        if getattr(self, "_spec_ctx", None) is None:
            return
        prev = getattr(self, "_spec_thread", None)
        if prev is not None and prev.is_alive():
            return
        self._spec_upcoming = list(upcoming) if upcoming else []
        # NOT a daemon thread: the interpreter tearing down while XLA
        # is mid-compile aborts the whole process (C++ terminate), so
        # exit must join an in-flight speculative compile.  The worker
        # checks main-thread liveness between rungs to keep that join
        # bounded to at most one rung.
        t = threading.Thread(
            target=self._speculate_worker, name="tmx-warm", daemon=False
        )
        self._spec_thread = t
        t.start()

    def _speculate_worker(self) -> None:
        try:
            args, cap, (abs_args, abs_kwargs) = self._spec_ctx
            ceiling = int(args["max_objects"])
            from tmlibrary_tpu.capacity import (
                likely_next_rungs,
                observed_peak,
                resolve_bucket_ladder,
            )

            ladder = resolve_bucket_ladder(
                ceiling, args.get("object_buckets", "auto")
            )
            observed = None
            if len(ladder) > 1:
                observed = observed_peak(
                    self._routing_key(args, ceiling, ladder)
                )
            targets = list(likely_next_rungs(cap, ladder, observed=observed))
            from tmlibrary_tpu.tuning import tuned_object_capacity

            hint = tuned_object_capacity()
            if hint and hint in ladder and hint > cap \
                    and hint not in targets:
                targets.append(int(hint))
            # planned rungs from the schedule plan's upcoming batches are
            # certainties, not heuristics: warm them FIRST, in dispatch
            # order, then fall through to the ladder guesses
            planned: list[int] = []
            for b in getattr(self, "_spec_upcoming", []) or []:
                rung = (b.get("schedule") or {}).get("rung")
                if rung and int(rung) in ladder and int(rung) != cap \
                        and int(rung) not in planned:
                    planned.append(int(rung))
            targets = planned + [t for t in targets if t not in planned]
            if not targets:
                return
            from tmlibrary_tpu import perf
            from tmlibrary_tpu import qc as qc_mod
            from tmlibrary_tpu.jterator.pipeline import cached_batch_fn

            desc = self._description(args)
            for rung in targets:
                if not threading.main_thread().is_alive():
                    return  # shutting down: don't start another compile
                # the process-level cache, NOT self._pipeline: tracing a
                # new rung takes seconds and must not hold the instance
                # compile lock a concurrent escalation launch needs
                fn = cached_batch_fn(
                    desc, int(rung), self._window,
                    donate=None if args.get("donate_buffers", True)
                    else False,
                    qc=qc_mod.enabled(),
                )
                outcome = perf.speculate_compile(fn, abs_args, abs_kwargs)
                if outcome in ("compiled", "imported"):
                    logger.info(
                        "compile-ahead: capacity rung %d %s in the "
                        "background", rung, outcome,
                    )
        except Exception:
            logger.debug("compile-ahead speculation failed", exc_info=True)

    @staticmethod
    def _batch_demand(result, n_valid: int) -> int:
        """Peak :attr:`SiteResult.demand` over a batch's valid sites."""
        return int(np.asarray(result.demand)[:n_valid].max(initial=0))

    def _persist(self, batch: dict, result, capacity: int | None = None,
                 tally: dict | None = None) -> dict:
        """Fetch one launched batch's device results and write them out.
        ``tally`` carries the bytes the launches sent to the device; the
        re-launches of the escalation loop add theirs."""
        import jax

        tally = {} if tally is None else tally
        # QC-on programs return (SiteResult, fused per-site image stats);
        # split the pair here so the persist path below is shape-agnostic
        qc_dev = None
        if isinstance(result, tuple):
            result, qc_dev = result
        args = batch["args"]
        sites = batch["sites"]
        tpoint, zplane = args["tpoint"], args["zplane"]
        n_valid = len(sites)
        ceiling = int(args["max_objects"])
        cap = int(capacity) if capacity is not None else ceiling
        escalations = rungs_skipped = 0
        demand = self._batch_demand(result, n_valid)
        if cap < ceiling:
            # Re-launch until the routed capacity holds the batch's
            # demand.  A demand AT the cap may have been clipped there
            # (before or after a filter), so nothing below the ceiling is
            # ever persisted from such a launch — this is the
            # bit-identity contract (capacity.py): below the ceiling,
            # routing can cost a re-launch, never a different result.
            # The re-launch goes to the rung the demand selects, not the
            # next one; a pipeline whose modules report no demand reads
            # "at least cap" from its clipped counts and so climbs one
            # rung at a time.  Ceiling saturation keeps its existing
            # warn/auto-resegment flow below, which reads the counts.
            from tmlibrary_tpu.capacity import (
                resolve_bucket_ladder, select_capacity,
            )

            ladder = resolve_bucket_ladder(
                ceiling, args.get("object_buckets", "auto")
            )
            while demand >= cap and cap < ceiling:
                new_cap = select_capacity(demand, ladder)
                logger.info(
                    "batch %s saturated its routed object-capacity bucket "
                    "(demand %d at capacity %d) — re-running at capacity %d",
                    batch.get("index"), demand, cap, new_cap,
                )
                escalations += 1
                rungs_skipped += sum(1 for c in ladder if cap < c < new_cap)
                from_cap, cap = cap, new_cap
                # planes re-read, re-sent, the program re-launched and
                # waited for, all on this persist worker
                with telemetry.span("escalate", capacity=cap,
                                    from_capacity=from_cap, demand=demand):
                    result = self._launch(batch, capacity=cap, tally=tally)
                    w0 = time.perf_counter()
                    with telemetry.span("device_wait"):
                        jax.block_until_ready(result)
                    tally["device_wait_s"] = (
                        tally.get("device_wait_s", 0.0)
                        + time.perf_counter() - w0)
                if isinstance(result, tuple):
                    result, qc_dev = result
                demand = self._batch_demand(result, n_valid)
        with telemetry.span("fetch") as fetched:
            counts = {k: np.asarray(v)[:n_valid]
                      for k, v in result.counts.items()}
            objects = {k: np.asarray(v)[:n_valid]
                       for k, v in result.objects.items()}
            measurements = {
                obj: {f: np.asarray(v)[:n_valid] for f, v in feats.items()}
                for obj, feats in result.measurements.items()
            }
            fetched["bytes"] = sum(
                int(a.nbytes) for a in jax.tree_util.tree_leaves(
                    (counts, objects, measurements)))

        if self._window is not None:
            # cropped intersection frame → site frame: pad labels back with
            # the window offsets and shift positional features, so stored
            # stacks, polygons and figures all live in site coordinates
            top, bottom, left, right = self._window
            # labels (2-D (B,H,W) or volume (B,Z,H,W)) were computed in the
            # cropped frame; pad the spatial dims back to the site frame
            objects = {
                name: np.pad(
                    lab,
                    [(0, 0)] * (lab.ndim - 2) + [(top, bottom), (left, right)],
                )
                for name, lab in objects.items()
            }
            for feats in measurements.values():
                if "Morphology_centroid_y" in feats:
                    feats["Morphology_centroid_y"] = feats["Morphology_centroid_y"] + top
                    feats["Morphology_centroid_x"] = feats["Morphology_centroid_x"] + left

        # solidity is hull-based and ragged, so it is measured host-side on
        # the exported label images and joined into the morphology features
        # (reference: jtlib/features/morphology solidity via regionprops)
        from tmlibrary_tpu.native import solidity_host

        max_obj = args["max_objects"]
        for name, feats in measurements.items():
            if "Morphology_area" in feats and objects.get(name) is not None \
                    and objects[name].ndim == 3:
                with telemetry.span("solidity"):
                    feats["Morphology_solidity"] = np.stack(
                        [solidity_host(objects[name][b], max_obj)
                         for b in range(n_valid)]
                    )

        # ------------------------------------------------------------ persist
        with telemetry.span("write_labels"):
            for name, labels in objects.items():
                if labels.ndim == 4:  # (B, Z, H, W) volumes: one stack per z
                    for zp in range(labels.shape[1]):
                        self.store.write_labels(labels[:, zp], sites, name,
                                                tpoint=tpoint, zplane=zp)
                else:
                    self.store.write_labels(labels, sites, name,
                                            tpoint=tpoint, zplane=zplane)

        shard = f"batch_{batch['index']:03d}"
        site_meta = self._site_metadata(sites)
        for name in objects:
            with telemetry.span("write_features") as written:
                table = self._feature_table(
                    name, counts[name], measurements.get(name, {}), site_meta,
                    args["max_objects"],
                )
                self.store.append_features(name, table, shard=shard)
                # object rows and feature columns (the seven site and
                # label keys left out): what this shard holds
                written["rows"] = len(table)
                written["columns"] = len(measurements.get(name, {}))
            # polygon tracing is 2-D only; volume objects skip it
            if args["as_polygons"] and objects[name].ndim == 3:
                with telemetry.span("write_polygons"):
                    self._write_polygons(name, objects[name], sites, shard)

        if args.get("figures"):
            # segmentation-overlay artifacts (reference module Figure
            # outputs) — rendered host-side from the persisted labels on
            # the first input channel
            from tmlibrary_tpu.jterator.figures import write_figures

            desc = self._description(args)
            first_ch = next((c for c in desc.channels if not c.zstack), None)
            if first_ch is not None:
                idx = self.store.experiment.channel_index(first_ch.name)
                cycle = self._channel_cycle(args, first_ch.name)
                base = self.store.read_sites(
                    sites, cycle=cycle, channel=idx,
                    tpoint=tpoint, zplane=zplane,
                )
                if first_ch.align and self.store.has_shifts(cycle):
                    # labels live in the aligned frame; shift the raw base
                    # the same way or boundaries draw offset from the cells
                    table = self.store.read_shifts(cycle)
                    base = np.stack([
                        _host_shift(base[b], *table[s])
                        for b, s in enumerate(sites)
                    ])
                for name, labels in objects.items():
                    if labels.ndim == 3:
                        write_figures(
                            self.store.root / "figures", name, base,
                            labels, sites,
                        )

        summary = {
            "n_sites": n_valid,
            "objects": {k: int(v.sum()) for k, v in counts.items()},
        }
        # bucket bookkeeping: feed the router's count history, and carry
        # capacity + slot occupancy in the batch summary so the ledger
        # (tmx workflow status, registry_from_ledger) sees padding waste
        from tmlibrary_tpu.capacity import slot_occupancy

        peak = max(
            (int(v.max(initial=0)) for v in counts.values()), default=0
        )
        self._note_peak(args, peak)
        # per-site costs feed the work-model scheduler's EWMA through the
        # same persist-side stream the peak rides; the densest object
        # family is what sets a site's capacity rung
        site_counts = None
        if counts:
            site_counts = np.maximum.reduce(
                [np.asarray(v) for v in counts.values()]
            )
            self._note_site_costs(args, sites, site_counts)
            shard_objects = self._shard_objects(args, site_counts)
            if shard_objects is not None:
                # actual per-shard work under the applied site order —
                # the straggler-balance evidence a ledger alone can
                # compare against predicted_shard_work (and against an
                # unbalanced run of the same experiment)
                summary["shard_objects"] = shard_objects
        plan = batch.get("schedule") or {}
        if plan.get("rung"):
            summary["schedule_rung"] = int(plan["rung"])
            self._note_schedule(escalations)
        total_objects = sum(summary["objects"].values())
        slots = len(counts) * n_valid * cap
        summary["bucket_capacity"] = cap
        # the ladder ceiling travels with every batch summary so a ledger
        # alone can reconstruct padded-FLOPs-avoided post hoc
        # (telemetry.registry_from_ledger) — additive, PR-5 readers ignore it
        summary["bucket_ceiling"] = ceiling
        summary["slot_occupancy"] = round(slot_occupancy(total_objects, slots), 4)
        # peak demand of the batch's sites: over bucket_capacity never
        # below the ceiling; over the ceiling where the raw count clipped
        summary["bucket_demand"] = demand
        if escalations:
            summary["bucket_escalations"] = escalations
            summary["device_wait_s"] = round(tally["device_wait_s"], 6)
        if rungs_skipped:
            summary["bucket_rungs_skipped"] = rungs_skipped
        # bytes handed to the device: the first launch and every re-launch
        summary["h2d_bytes"] = int(tally.get("h2d_bytes", 0))
        # where the planes came from: the cycles read, and how many
        # channels went through the program under a shift row of their own
        summary["cycles_read"] = tally.get("cycles_read", [args["cycle"]])
        summary["aligned_channels"] = int(tally.get("aligned_channels", 0))
        self._note_bucket(cap, ceiling, total_objects, slots, escalations,
                          rungs_skipped)
        # object-capacity saturation must be LOUD: clip_label_count silently
        # zeroes labels past max_objects, so a site whose count sits AT the
        # cap may have lost objects — surface it per batch in the ledger,
        # accumulate for the collect-phase warning, and leave the re-run
        # recipe in the log (round-2 VERDICT weak-spot #4)
        saturated = {
            k: int((v >= max_obj).sum()) for k, v in counts.items()
        }
        saturated = {k: n for k, n in saturated.items() if n}
        # record unconditionally: a clean re-run of a previously saturated
        # batch must CLEAR its stale entry
        self._record_saturation(batch["index"], saturated)
        if saturated:
            summary["saturated"] = saturated
            logger.warning(
                "object capacity saturated (count == max_objects == %d) for "
                "%s — objects beyond the cap were dropped; re-run the step "
                "with a higher cap: `tmx jterator cleanup && tmx jterator "
                "init --max-objects N && tmx jterator run` (max_objects is "
                "an init-time argument)",
                max_obj,
                ", ".join(f"{n} site(s) of '{k}'" for k, n in saturated.items()),
            )
        if qc_dev is not None:
            # QC rides the already-fetched arrays: fused image stats from
            # the device, numerics guards + feature sketches on the numpy
            # the persist path produced anyway.  The summary travels with
            # the batch result so the ENGINE thread appends the
            # qc_batch/qc_site ledger events (same thread discipline as
            # straggler records) — flags never fail the batch.
            from tmlibrary_tpu import qc as qc_mod
            from tmlibrary_tpu.jterator.pipeline import MODEL_QC_KEY

            image_stats = {
                ch: {m: np.asarray(v)[:n_valid] for m, v in metrics.items()}
                for ch, metrics in qc_dev.items()
            }
            # model diagnostic streams (DL segmenters' flow-magnitude /
            # probability samples) ride the qc pytree under a reserved
            # pseudo-channel; they are value STREAMS, not per-site image
            # scalars, so they route into the feature sketches (every
            # sample valid — no counts mask) under the "__model__"
            # pseudo-objects the model drift profile keys on
            model_stats = image_stats.pop(MODEL_QC_KEY, None)
            meas_for_qc = measurements
            if model_stats:
                meas_for_qc = {
                    **measurements, qc_mod.MODEL_OBJECTS: model_stats,
                }
            qc_summary = qc_mod.get_session().observe_batch(
                self.name, sites, image_stats=image_stats, counts=counts,
                measurements=meas_for_qc, saturated=bool(saturated),
            )
            if qc_summary:
                summary["qc"] = qc_summary
        self._note_sites(n_valid)
        return summary

    # ---------------------------------------------------------------- helpers
    def _site_metadata(self, sites: list[int]) -> list[dict]:
        refs = list(self.store.experiment.sites())
        out = []
        for s in sites:
            r = refs[s]
            out.append(
                {
                    "site_index": s,
                    "plate": r.plate,
                    "well_row": r.well_row,
                    "well_col": r.well_column,
                    "site_y": r.site_y,
                    "site_x": r.site_x,
                }
            )
        return out

    @staticmethod
    def _feature_table(name, counts, feats, site_meta, max_objects):
        import pandas as pd

        rows: dict[str, list] = {k: [] for k in
                                 ("site_index", "plate", "well_row", "well_col",
                                  "site_y", "site_x", "label")}
        for fname in feats:
            rows[fname] = []
        for b, meta in enumerate(site_meta):
            n = int(counts[b])
            for lab in range(1, min(n, max_objects) + 1):
                for k in ("site_index", "plate", "well_row", "well_col",
                          "site_y", "site_x"):
                    rows[k].append(meta[k])
                rows["label"].append(lab)
                for fname, arr in feats.items():
                    rows[fname].append(float(arr[b, lab - 1]))
        return pd.DataFrame(rows)

    def _write_polygons(self, name, labels, sites, shard):
        import pandas as pd

        from tmlibrary_tpu.ops.polygons import labels_to_polygons, polygons_to_table

        tables = []
        for b, site in enumerate(sites):
            polys = labels_to_polygons(labels[b])
            if polys:
                tables.append(polygons_to_table(polys, site))
        if tables:
            df = pd.concat(tables, ignore_index=True)
            out = self.store.root / "segmentations" / f"{name}_polygons_{shard}.parquet"
            df.to_parquet(out, index=False)

    def collect(self) -> dict:
        """Register mapobject types and summarize counts per object type
        (reference's collect phase creates ``MapobjectType`` rows and
        computes their polygon-zoom threshold)."""
        from tmlibrary_tpu.models.mapobject import (
            MapobjectType,
            MapobjectTypeRegistry,
            min_poly_zoom,
            plate_mosaic_shape,
        )
        from tmlibrary_tpu.ops.pyramid import n_pyramid_levels

        # resegment FIRST: the registry pass below derives min_poly_zoom
        # from mean object area, which the capped feature shards would
        # misstate for exactly the object types that saturated
        resegmented = self._resegment_saturated()

        registry = MapobjectTypeRegistry(self.store.root)
        # zoom levels are defined over the viewer pyramid, which illuminati
        # builds from the full plate mosaic — use the largest plate's
        # mosaic dimensions, not a single site's
        exp = self.store.experiment
        n_levels = 1
        for plate in exp.plates:
            n_levels = max(
                n_levels, n_pyramid_levels(*plate_mosaic_shape(exp, plate.name))
            )
        summary = {}
        for name in self.store.list_objects():
            try:
                feats = self.store.read_features(name)
                summary[name] = int(len(feats))
            except Exception:
                continue
            mean_px = 0.0
            cols = getattr(feats, "columns", [])
            # measure_morphology emits 'Morphology_area'; accept a bare
            # 'area' too for externally-written feature tables
            area_col = next(
                (c for c in ("Morphology_area", "area") if c in cols), None
            )
            if area_col is not None:
                mean_px = float(feats[area_col].mean())
            registry.register(
                MapobjectType(
                    name=name,
                    ref_type="segmented",
                    min_poly_zoom=min_poly_zoom(n_levels, mean_px),
                )
            )
        out = {"objects_total": summary}
        if resegmented:
            out["resegmented"] = resegmented
        totals = self._saturation_totals()
        if totals:
            # repeat the saturation warning at collect so it is the LAST
            # thing in the step log, not buried between batches
            out["saturated_sites"] = totals
            logger.warning(
                "object capacity was saturated during this run: %s — those "
                "sites' feature tables and label stacks are missing the "
                "objects beyond the cap; re-run with a higher "
                "--max-objects to recover them",
                ", ".join(f"'{k}': {n} site(s)" for k, n in totals.items()),
            )
        return out

    # ------------------------------------------------- saturation bookkeeping
    #: bounded escalation: up to 4 doublings of the init-time cap, never
    #: past the absolute ceiling (a runaway segmentation must not compile
    #: ever-larger programs forever)
    _RESEGMENT_DOUBLINGS = 4
    _RESEGMENT_CEILING = 4096

    def _resegment_saturated(self) -> dict:
        """Close the saturation loop without a manual step (round-3
        VERDICT next-step #7): re-run JUST the saturated batches at a
        doubled ``max_objects`` until their counts fit, the doubling
        budget runs out, or the ceiling is hit.  The raised cap lives in
        ``cap_overrides.json`` (NOT the batch file — the engine's resume
        staleness check would read a rewritten cap as a changed plan and
        wipe all outputs), is applied by :meth:`run_batch`, and survives
        for resume; each re-run goes through :meth:`run` (per-batch log
        captured) and the escalations land in the collect summary — and
        therefore the run ledger — as ``resegmented``."""
        from tmlibrary_tpu.errors import JobDescriptionError

        done: dict[str, int] = {}
        for _ in range(self._RESEGMENT_DOUBLINGS):
            state = self._saturation_state()
            if not state:
                break
            progressed = False
            for bidx_str in sorted(state):
                try:
                    batch = self.load_batch(int(bidx_str))
                except JobDescriptionError:
                    continue  # batches re-planned since; stale entry
                args = batch.get("args", {})
                if not args.get("auto_resegment", True):
                    return done  # manual mode: leave the warning flow
                if args.get("layout", "sites") == "spatial":
                    continue  # ragged mosaic path has no object cap
                cap = max(
                    int(args.get("max_objects", 256)),
                    self._cap_overrides().get(bidx_str, 0),
                )
                new_cap = min(cap * 2, self._RESEGMENT_CEILING)
                if new_cap <= cap:
                    continue  # ceiling reached; the collect warning fires
                self._write_cap_override(bidx_str, new_cap)
                logger.warning(
                    "auto-resegmenting batch %d at max_objects=%d "
                    "(saturated: %s)",
                    batch["index"], new_cap, state[bidx_str],
                )
                self.run(batch["index"])  # re-records/clears saturation
                done[bidx_str] = new_cap
                progressed = True
            if not progressed:
                break
        return done

    @property
    def _schedule_plan_path(self):
        return self.step_dir / "schedule_plan.json"

    def schedule_plan_info(self) -> dict | None:
        """The recorded packing plan's compact summary (the engine's
        ``schedule_plan`` ledger event) — re-read from the side file so
        a resume appends the SAME digest it recorded at init time, which
        is the bit-identical-boundaries proof."""
        from tmlibrary_tpu.workflow import schedule as schedule_mod

        plan = schedule_mod.load_plan(self._schedule_plan_path)
        return schedule_mod.plan_event(plan) if plan else None

    @property
    def _cap_override_path(self):
        return self.step_dir / "cap_overrides.json"

    def _cap_overrides(self) -> dict:
        import json

        try:
            return json.loads(self._cap_override_path.read_text())
        except (OSError, ValueError):
            return {}

    def _write_cap_override(self, bidx_str: str, cap: int) -> None:
        import json
        import os

        state = self._cap_overrides()
        state[bidx_str] = int(cap)
        tmp = self._cap_override_path.with_suffix(f".tmp{os.getpid()}")
        tmp.write_text(json.dumps(state, sort_keys=True))
        os.replace(tmp, self._cap_override_path)

    @property
    def _saturation_path(self):
        return self.step_dir / "saturation.json"

    def _record_saturation(self, batch_index: int, saturated: dict) -> None:
        """Persist per-batch saturation keyed by batch index, so collect
        sees it from a fresh process (per-verb CLI runs) and a batch
        re-run overwrites — or, when clean, clears — its own entry instead
        of double-counting.  ``run --job N`` batches may execute as
        concurrent processes (cluster-style fan-out), so the
        read-modify-write is flock-serialized and the write is atomic
        (tmp + rename): no lost entries, no torn JSON."""
        import fcntl
        import json
        import os

        path = self._saturation_path
        if not saturated and not path.exists():
            return
        with open(path.with_suffix(".lock"), "w") as lockf:
            fcntl.flock(lockf, fcntl.LOCK_EX)
            try:
                state = json.loads(path.read_text()) if path.exists() else {}
            except ValueError:
                state = {}  # torn by a crashed writer; rebuilt from here on
            if saturated:
                state[str(batch_index)] = saturated
            else:
                state.pop(str(batch_index), None)
            tmp = path.with_suffix(f".tmp{os.getpid()}")
            tmp.write_text(json.dumps(state, sort_keys=True))
            os.replace(tmp, path)

    def _saturation_state(self) -> dict:
        """Raw per-batch saturation map: {batch_index_str: {objects: n}}."""
        import json

        path = self._saturation_path
        if not path.exists():
            return {}
        try:
            return json.loads(path.read_text())
        except ValueError:
            logger.warning(
                "saturation.json is unreadable (crashed writer?) — "
                "per-batch saturation truth remains in the run ledger"
            )
            return {}

    def _saturation_totals(self) -> dict:
        totals: dict[str, int] = {}
        for per_batch in self._saturation_state().values():
            for k, n in per_batch.items():
                totals[k] = totals.get(k, 0) + n
        return totals

    def delete_previous_output(self) -> None:
        import shutil

        for sub in ("segmentations", "features", "figures"):
            d = self.store.root / sub
            if d.exists():
                shutil.rmtree(d)
            d.mkdir()
        # stale saturation signal, cap escalations and the packing plan
        # belong to the deleted outputs (a fresh plan restarts from the
        # init-time cap; create_batches re-derives the schedule from the
        # just-harvested history)
        self._saturation_path.unlink(missing_ok=True)
        self._saturation_path.with_suffix(".lock").unlink(missing_ok=True)
        self._cap_override_path.unlink(missing_ok=True)
        self._schedule_plan_path.unlink(missing_ok=True)
