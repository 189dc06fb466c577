"""The ``multiplex`` driver: ``cp3-plate``'s loop — one client, closed
loop, a unit is one whole ``tmx create`` + ``tmx workflow submit`` of the
same seeded wells into a fresh experiment root — for wells imaged over
several cycles (``benchmark/multiplex.py``) and analysed by the
``multiplexing`` workflow: ``align`` registers every later cycle on the
first, jterator reads every stain from its own cycle under that cycle's
shifts.  The run's kind stays ``"plate"``: the readers of the steps, the
spans and the device's idle share read it as they read a plate cell's.
The ``checks`` line says, for every unit of the window, where its seconds
went (``unit_steps``: each step's ``step_done.elapsed``)."""

import os
import sys
import time
from pathlib import Path

import numpy as np

from benchmark import ledger, multiplex, plate
from benchmark.drivers.plate import (PlateRun, Unit, join_speculation,
                                     on_disk)
from benchmark.harness import (HERE, ReturnedArrays, TraceWindow, at_size,
                               emit, load_module, real_compiles, tmx)

#: the driver's own checks -> the numbers of ``compared`` that decide
#: them (the reference's ``DECIDES`` gives its own)
DECIDES = {
    "every_site_on_disk": ("run_faults",),
    "resubmissions_identical": ("run_faults",),
    "no_forbidden_event": ("run_faults",),
    "no_compile_in_window": ("run_faults",),
    "arrays_on_reported_platform": ("platform_faults",),
}


def program_reads_channels_by_cycle() -> bool:
    """Whether this checkout's pipeline description lets a channel name
    the cycle it is read from."""
    import dataclasses

    from tmlibrary_tpu.jterator.description import ChannelInput

    return "cycle" in {f.name for f in dataclasses.fields(ChannelInput)}


def sized(config: dict, traffic: dict, on_chip: bool) -> tuple:
    config, traffic = at_size(config, on_chip), at_size(traffic, on_chip)
    return config, (config["field_size"], config["max_objects"],
                    plate.parse_range(traffic["cells_per_field"]),
                    traffic["drift_px"])


def submit(work: str, index: int, src: str, sites: int, config: dict,
           capacity: int) -> Unit:
    from tmlibrary_tpu import capacity as router

    # as the plate driver: a new well comes to a new process, so nothing a
    # unit learnt routes the next
    router.reset_routing_history()
    unit = Unit(os.path.join(work, f"exp{index:03d}"), sites)
    unit.t0 = time.time()
    tmx(["create", "--name", os.path.basename(unit.root),
         "--root", unit.root])
    wf = multiplex.write_description(unit.root, src, config, capacity)
    tmx(["workflow", "submit", "--description", wf, "--root", unit.root])
    unit.t1 = time.time()
    return unit


def align_collected(events: list) -> dict:
    """What the align step said of itself (``step_done.collected``)."""
    for e in events:
        if e.get("event") == "step_done" and e.get("step") == "align":
            return e.get("collected") or {}
    return {}


def unit_steps(units: list, steps: list) -> list:
    """For every unit, each step's ``step_done.elapsed`` to a millisecond:
    which step carried a slow unit, at no cost to an untraced run."""
    out = []
    for unit in units:
        seconds = ledger.step_seconds(unit.events)
        out.append({step: round(seconds[step], 3)
                    for step in steps if step in seconds})
    return out


def sampled_sites(seed: int, sites: int, config: dict) -> list:
    """The seed's sample of sites the reference's chain and intensities
    are held on."""
    rng = np.random.default_rng(seed)
    return sorted(int(s) for s in rng.choice(
        sites, size=min(config["reference_sample_sites"], sites),
        replace=False))


def held(reference, unit: Unit, sample: list, config: dict,
         planted: dict) -> dict:
    """``reference.check`` of one unit's store."""
    from tmlibrary_tpu.models.store import ExperimentStore

    return reference.check(
        ExperimentStore.open(Path(unit.root)), sample, config,
        {"planted": planted, "quantum": config["window_quantum"],
         "align": align_collected(ledger.run_ledger(unit.root))})


def control(seed: int, config: dict, traffic: dict, device: dict,
            work: str) -> dict:
    """``benchmark/control.py``'s reading of this cell: the seed's wells
    through one unit as the program stands (``stated``) and through one in
    which the second cycle's stored shifts are one pixel off in x from the
    moment the align step has written them (``control``: illuminati and
    jterator read the table that is off), each held by
    ``reference.check``."""
    from tmlibrary_tpu.workflow.steps.align import ImageRegistrator

    on_chip = device["platform"] == "tpu"
    config, (size, capacity, cells, drift) = sized(config, traffic, on_chip)
    src = os.path.join(work, "src")
    sites, planted = multiplex.write_wells(
        src, plate.well_names(traffic["wells_per_submit"]), config, size,
        cells, drift, seed)
    reference = load_module(os.path.join(HERE, "configs",
                                         config["reference"]))
    sample = sampled_sites(seed, sites, config)
    program = ImageRegistrator.run_batch

    def off_by_one(step, batch):
        result = program(step, batch)
        if batch["cycle"] == 1:
            table = step.store.read_shifts(1)
            table[:, 1] += 1
            step.store.write_shifts(table, 1)
        return result

    reading = {"seed": seed, "field": [size, size], "sampled_sites": sample}
    for index, (name, run_batch) in enumerate(
            (("stated", program), ("control", off_by_one))):
        ImageRegistrator.run_batch = run_batch
        try:
            unit = submit(work, index, src, sites, config, capacity)
        finally:
            ImageRegistrator.run_batch = program
        verdict = held(reference, unit, sample, config, planted)
        reading[name] = {
            "checks_failed": sorted(k for k, ok in verdict["checks"].items()
                                    if not ok),
            "compared": verdict["compared"],
            "stored_window": verdict["info"]["stored_window"],
            "objects": verdict["info"]["object_counts"]}
    reading["answers_failed"] = len(reading["control"]["checks_failed"])
    return reading


def run(args, config, traffic, device, meter, work, t_process) -> dict:
    if not program_reads_channels_by_cycle():
        # the check tries a new cell on the parent commit with these files
        # laid over it, and a parent that cannot run the configuration has
        # to say so at once: before a channel could name its cycle, every
        # stain was read from the step's one cycle
        print("this checkout's pipeline description has no per-channel "
              f"cycle: it cannot run {config['name']}", file=sys.stderr)
        sys.exit(2)
    from tmlibrary_tpu import aotstore

    on_chip = device["platform"] == "tpu"
    config, (size, capacity, cells, drift) = sized(config, traffic, on_chip)
    src = os.path.join(work, "src")
    sites, planted = multiplex.write_wells(
        src, plate.well_names(traffic["wells_per_submit"]), config, size,
        cells, drift, args.seed)
    run_ = PlateRun(config, device, size, capacity)

    # ---- set-up: one whole unit compiles every program the window runs,
    # the batch program's rungs at this well's window among them
    mark = meter.mark()
    with ReturnedArrays() as returned:
        warm = submit(work, 0, src, sites, config, capacity)
        warm.events = ledger.run_ledger(warm.root)
        join_speculation()
        store_counts = dict(aotstore.counts_snapshot())
        run_.compile["setup"] = meter.since(mark)
        setup_s = time.time() - t_process
        emit({"line": "setup", "setup_s": setup_s, "field": [size, size],
              "sites_per_unit": sites, "warm_unit_s": warm.seconds,
              "planted_max_abs": int(max(np.abs(t).max()
                                         for t in planted.values())),
              "align": align_collected(warm.events),
              "compile": run_.compile["setup"],
              "executable_store": store_counts,
              "engine": ledger.resolved_by_the_engine(warm.events)})

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
    names = [o["name"] for o in config["pipeline"]["output"]["objects"]]
    _, want_counts = on_disk(warm.root, names)
    done = failed = unlike = 0
    forbidden = set(ledger.forbidden(warm.events))
    errors = []
    for unit in run_.units:
        unit.events = ledger.run_ledger(unit.root)
        forbidden |= set(ledger.forbidden(unit.events))
        try:
            good, counts = on_disk(unit.root, names)
        except Exception as exc:  # an unreadable store is a failed unit
            good, counts = 0, None
            errors.append(f"{os.path.basename(unit.root)}: "
                          f"{type(exc).__name__}: {exc}")
        done += good
        failed += unit.sites - good
        unlike += int(counts != want_counts)
    verdict = held(reference, warm,
                   sampled_sites(args.seed, sites, config), config, planted)
    run_.reference_info = verdict["info"]
    window_compiles = real_compiles(run_.compile["window"])
    # the numbers that decide, each beside its limit: the reference's ten
    # and these two, each a sum of counts that have to be 0; their parts
    # and what only informs go on the `checks` line
    compared = dict(verdict["compared"])
    compared.update({
        "run_faults": (failed + int(done == 0) + unlike + len(forbidden)
                       + window_compiles, 0),
        "platform_faults": (int(returned.n_arrays == 0 or
                                returned.platforms != {device["platform"]}),
                            0),
    })
    checks = dict(verdict["checks"])
    checks.update(reference.verdicts(compared, DECIDES))
    emit({"line": "checks", "checks": checks, **verdict["info"],
          "sites_not_on_disk": failed, "units_unlike_the_first": unlike,
          "window_compiles": window_compiles,
          "forbidden_events": sorted(forbidden), "errors": errors,
          "units": [round(u.seconds, 3) for u in run_.units],
          "unit_steps": unit_steps(run_.units, config["steps"]),
          "window_s": window_s, "window_compile": run_.compile["window"]})

    metrics = {"sites_per_s": {"value": done / window_s, "unit": "sites/s"},
               "setup_s": {"value": setup_s, "unit": "s"}}
    return {"run": run_, "metrics": metrics, "correct": all(checks.values()),
            "attempted": sum(u.sites for u in run_.units),
            "failed": failed, "compared": compared}
