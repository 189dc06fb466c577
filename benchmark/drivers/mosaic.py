"""The ``mosaic`` driver: ``cp3-plate``'s loop — one client, closed loop,
a unit is one whole ``tmx create`` + ``tmx workflow submit`` of the same
seeded well into a fresh experiment root — for a well that is analysed as
ONE mosaic (``layout: spatial``) on a mesh of chips.  The run's kind
stays ``"plate"``: the readers of the steps, the spans and the device's
idle share read it as they read a plate cell's."""

import os
import sys
import time
from pathlib import Path

import numpy as np

from benchmark import ledger, mosaic, plate
from benchmark.drivers.plate import PlateRun, Unit, join_speculation
from benchmark.harness import (HERE, TraceWindow, at_size, emit, load_module,
                               real_compiles, tmx)


class ReturnedShards:
    """Watches ``ImageAnalysisRunner.block_batch`` — the one place every
    launched batch's device arrays pass through — and records where each
    array lived: platform, and per device the shapes of the image shards
    it held.  Copied from ``chip_smoke.py``'s ``ReturnedArrays`` (proven
    on the chip, PR 21).  Of the FIRST batch (the warm unit's, before the
    window) it also fetches what the program took its Otsu cuts from: 256
    numbers a stain."""

    def __init__(self):
        from tmlibrary_tpu.workflow.steps.jterator import ImageAnalysisRunner

        self.cls = ImageAnalysisRunner
        self.original = ImageAnalysisRunner.block_batch
        self.platforms: set = set()
        self.images: dict = {}   # device id -> shapes of (H, W) shards
        self.n_arrays = 0
        self.otsu = None         # stain -> the program's between, lo, hi

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
            if watcher.otsu is None and kind == "spatial":
                watcher.otsu = {
                    stain: program_criterion(reading)
                    for stain, reading in payload["otsu_dev"].items()
                    if "hist" in reading}
            return watcher.original(step, ctx)

        self.cls.block_batch = block_batch
        return self

    def __exit__(self, *exc):
        self.cls.block_batch = self.original
        return False

    def faults(self, device: dict, n_devices: int, part: tuple) -> int:
        """Arrays off the platform the run reports (1 if any, or if none
        was seen) and devices, of ``n_devices``, that did not hold
        label-image shards of the shape ``part`` and of no other."""
        good = sum(1 for shapes in self.images.values() if shapes == {part})
        return int(self.n_arrays == 0
                   or self.platforms != {device["platform"]}) \
            + abs(n_devices - good) + abs(len(self.images) - good)

    def summary(self) -> dict:
        return {"arrays": self.n_arrays,
                "platforms": sorted(self.platforms),
                "image_shards_per_device": {
                    str(d): sorted(map(list, s))
                    for d, s in sorted(self.images.items())}}


def program_criterion(reading: dict) -> dict:
    """The between-class criterion as the program's arithmetic gives it
    from the histogram its cut was taken from — ``ops/threshold.py``'s
    ``_otsu_argmax`` written out, in float32 on the run's device — with
    the range the histogram lies over."""
    import jax.numpy as jnp

    hist, lo, hi = reading["hist"], reading["lo"], reading["hi"]
    bins = hist.shape[0]
    span = jnp.maximum(hi - lo, 1e-6)
    centers = lo + (jnp.arange(bins, dtype=jnp.float32) + 0.5) / bins * span
    w0 = jnp.cumsum(hist)
    w1 = w0[-1] - w0
    sum0 = jnp.cumsum(hist * centers)
    mu0 = sum0 / jnp.maximum(w0, 1e-12)
    mu1 = (sum0[-1] - sum0) / jnp.maximum(w1, 1e-12)
    between = jnp.where((w0 > 0) & (w1 > 0), w0 * w1 * (mu0 - mu1) ** 2,
                        -1.0)
    return {"between": np.asarray(between, np.float64).tolist(),
            "lo": float(lo), "hi": float(hi)}


def said(events: list) -> dict:
    """What a unit's one jterator batch said of itself."""
    return ledger.batch_results(events, "jterator")[0]


#: the driver's own checks -> the numbers of ``compared`` that decide
#: them (the reference's ``DECIDES`` gives its own)
DECIDES = {
    "every_site_on_disk": ("run_faults",),
    "resubmissions_identical": ("run_faults",),
    "no_forbidden_event": ("run_faults",),
    "no_compile_in_window": ("run_faults",),
    "arrays_on_reported_platform": ("layout_faults",),
    "mesh_is_the_configurations": ("layout_faults",),
    "every_device_held_a_part_and_none_the_whole": ("layout_faults",),
}


def submit(work: str, index: int, src: str, sites: int, config: dict,
           capacity: int) -> Unit:
    from tmlibrary_tpu import capacity as router

    # as the plate driver: a new well comes to a new process, so nothing
    # a unit learnt routes the next (the spatial layout routes nothing,
    # corilla and illuminati are the plate cells' own)
    router.reset_routing_history()
    unit = Unit(os.path.join(work, f"exp{index:03d}"), sites)
    unit.t0 = time.time()
    tmx(["create", "--name", os.path.basename(unit.root),
         "--root", unit.root])
    wf = mosaic.write_description(unit.root, src, config, capacity)
    tmx(["workflow", "submit", "--description", wf, "--root", unit.root])
    unit.t1 = time.time()
    return unit


def on_disk(root: str, object_names: list) -> tuple:
    """``(sites done, object counts)``: a unit's sites are done when, for
    every object type, every stack is readable and the ids its stacks
    hold are exactly the labels of the well's feature rows."""
    from tmlibrary_tpu.models.store import ExperimentStore

    store = ExperimentStore.open(Path(root))
    ok, counts = True, {}
    for name in object_names:
        rows = np.sort(store.read_features(name)["label"].to_numpy())
        ids = np.unique(store.read_labels(None, name))
        counts[name] = int(len(rows))
        ok &= bool(len(rows) > 0 and np.array_equal(ids[ids > 0], rows))
    return (store.n_sites if ok else 0), counts


def control(seed: int, config: dict, traffic: dict, device: dict,
            work: str) -> dict:
    """``benchmark/control.py``'s reading of this cell: the seed's well
    through one unit as the program stands (``stated``) and through one
    with the reference's correction formula in bfloat16 in the program's
    place (``control``: ``jterator._correct_batch`` replaced by the
    reference's ``control_corrected``), each held by ``reference.check``.
    On fewer devices than the configuration's the step shrinks its mesh,
    which changes no label and no feature: what is read here are the
    float limits."""
    from tmlibrary_tpu.models.store import ExperimentStore
    from tmlibrary_tpu.workflow.steps import jterator

    on_chip = device["platform"] == "tpu"
    sized, mix = at_size(config, on_chip), at_size(traffic, on_chip)
    size, fields_x = sized["field_size"], config["sites_per_well_x"]
    planes, n_cells = mosaic.draw_well(
        seed, size, fields_x, config["fields_per_well"],
        plate.parse_range(mix["cells_per_field"]), config["channels"])
    src = os.path.join(work, "src")
    sites = mosaic.write_well(src, plate.well_names(1)[0], planes, size,
                              fields_x)
    del planes
    reference = load_module(os.path.join(HERE, "configs",
                                         config["reference"]))
    program = jterator._correct_batch
    reading = {"seed": seed, "field": [size, size], "cells_drawn": n_cells}
    for index, (name, correct) in enumerate(
            (("stated", program),
             ("control", reference.control_corrected))):
        jterator._correct_batch = correct
        try:
            with ReturnedShards() as returned:
                unit = submit(work, index, src, sites, config,
                              sized["max_objects"])
        finally:
            jterator._correct_batch = program
        verdict = reference.check(
            ExperimentStore.open(Path(unit.root)), list(range(sites)),
            config, {**said(ledger.run_ledger(unit.root)),
                     "otsu_reading": returned.otsu})
        if name == "stated":
            # the stored tables' control: the reference's statistics of
            # the first stain in bfloat16 against its own in float64
            raw = ExperimentStore.open(Path(unit.root)).read_sites(
                None, channel=0)
            reading["statistics_in_bfloat16"] = {
                f"stored_{key}_abs": float(np.abs(low - own).max())
                for key, own, low in zip(
                    ("mean_log", "std_log"), reference.statistics(raw),
                    reference.control_statistics(raw))}
            del raw
        reading[name] = {
            "checks_failed": sorted(k for k, ok in verdict["checks"].items()
                                    if not ok),
            "compared": verdict["compared"],
            **{k: verdict["info"][k]
               for k in ("otsu", "mask_pixels_outside_band",
                         "mask_pixels_differing", "nuclei_minus_chain",
                         "stored_tables", "cell_faults", "flood")},
            "objects": verdict["info"]["object_counts"]}
    reading["answers_failed"] = len(reading["control"]["checks_failed"])
    return reading


def run(args, config, traffic, device, meter, work, t_process) -> dict:
    from tmlibrary_tpu.models.store import ExperimentStore

    on_chip = device["platform"] == "tpu"
    sized, mix = at_size(config, on_chip), at_size(traffic, on_chip)
    size, capacity = sized["field_size"], sized["max_objects"]
    fields_x = config["sites_per_well_x"]
    side = fields_x * size
    from tmlibrary_tpu.parallel import label

    if not hasattr(label, "segment_mosaic"):
        # the check tries a new cell on the parent commit with these
        # files laid over it, and a parent that cannot run the
        # configuration has to say so at once: before `segment_mosaic`
        # the step sharded this well 4 x 1 whatever it was told
        print(f"this checkout's program cannot lay a {config['mesh']} "
              f"mesh over the well: it cannot run {config['name']}",
              file=sys.stderr)
        sys.exit(2)

    planes, n_cells = mosaic.draw_well(
        args.seed, size, fields_x, config["fields_per_well"],
        plate.parse_range(mix["cells_per_field"]), config["channels"])
    src = os.path.join(work, "src")
    sites = mosaic.write_well(
        src, plate.well_names(mix["wells_per_submit"])[0], planes, size,
        fields_x)
    del planes
    run_ = PlateRun(config, device, size, capacity)

    # ---- set-up: one whole unit compiles every program the window runs
    mark = meter.mark()
    with ReturnedShards() as returned:
        warm = submit(work, 0, src, sites, config, capacity)
        warm.events = ledger.run_ledger(warm.root)
        join_speculation()
        run_.compile["setup"] = meter.since(mark)
        setup_s = time.time() - t_process
        results = ledger.batch_results(warm.events, "jterator")
        emit({"line": "setup", "setup_s": setup_s, "field": [size, size],
              "mosaic": [side, side], "cells_drawn": n_cells,
              "sites_per_unit": sites, "warm_unit_s": warm.seconds,
              "compile": run_.compile["setup"],
              "jterator_batches": results})

        # ---- the window: admit no new submit after --seconds
        mark = meter.mark()
        if args.trace:
            run_.tracer = TraceWindow(os.path.join(work, "trace"))
        t0 = time.time()
        index = 1
        while time.time() - t0 < args.seconds:
            traced = run_.tracer is not None and index == 1
            if traced:
                run_.tracer.start()
            unit = submit(work, index, src, sites, config, capacity)
            if traced:
                run_.tracer.stop()
                run_.traced_units.append(unit)
            run_.units.append(unit)
            index += 1
        window_s = time.time() - t0
    run_.compile["window"] = meter.since(mark)

    # ---- after the window: what is on disk, and is it right
    reference = load_module(os.path.join(HERE, "configs",
                                         config["reference"]))
    names = [config["jterator"]["spatial_objects"],
             config["jterator"]["spatial_secondary_objects"]]
    _, want_counts = on_disk(warm.root, names)
    done = failed = unlike = 0
    forbidden = set(ledger.forbidden(warm.events))
    meshes, errors = {tuple(r.get("mesh_shape") or ()) for r in results}, []
    for unit in run_.units:
        unit.events = ledger.run_ledger(unit.root)
        forbidden |= set(ledger.forbidden(unit.events))
        meshes |= {tuple(r.get("mesh_shape") or ())
                   for r in ledger.batch_results(unit.events, "jterator")}
        try:
            good, counts = on_disk(unit.root, names)
        except Exception as exc:  # an unreadable store is a failed unit
            good, counts = 0, None
            errors.append(f"{os.path.basename(unit.root)}: "
                          f"{type(exc).__name__}: {exc}")
        done += good
        failed += unit.sites - good
        unlike += int(counts != want_counts)
    verdict = reference.check(
        ExperimentStore.open(Path(warm.root)), list(range(sites)), config,
        {**said(warm.events), "otsu_reading": returned.otsu})
    part = (side // config["mesh"][0], side // config["mesh"][1])
    window_compiles = real_compiles(run_.compile["window"])
    # the numbers that decide, each beside its limit (a number is within
    # its limit when it is not above it): the reference's thirteen and
    # these two, each a sum of counts that have to be 0; their parts and
    # what only informs go on the `checks` line
    compared = dict(verdict["compared"])
    compared.update({
        "run_faults": (failed + int(done == 0) + unlike + len(forbidden)
                       + window_compiles, 0),
        "layout_faults": (
            returned.faults(device, config["chips"], part)
            + int(meshes != {tuple(config["mesh"])}), 0),
    })
    checks = dict(verdict["checks"])
    checks.update(reference.verdicts(compared, DECIDES))
    emit({"line": "checks", "checks": checks, **verdict["info"],
          "sites_not_on_disk": failed, "units_unlike_the_first": unlike,
          "window_compiles": window_compiles,
          "forbidden_events": sorted(forbidden), "errors": errors,
          "mesh_shapes": sorted(map(list, meshes)),
          "label_shards": returned.summary(),
          "units": [round(u.seconds, 3) for u in run_.units],
          "window_s": window_s, "window_compile": run_.compile["window"]})

    metrics = {"sites_per_s": {"value": done / window_s, "unit": "sites/s"},
               "setup_s": {"value": setup_s, "unit": "s"}}
    return {"run": run_, "metrics": metrics, "correct": all(checks.values()),
            "attempted": sum(u.sites for u in run_.units),
            "failed": failed, "compared": compared}
