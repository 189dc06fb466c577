"""The ``plate`` driver: one client, closed loop, a unit is one whole
``tmx create`` + ``tmx workflow submit`` of the same seeded well files
into a fresh experiment root."""

import os
import threading
import time
from pathlib import Path

import numpy as np

from benchmark import ledger, plate
from benchmark.harness import (HERE, ReturnedArrays, Run, TraceWindow,
                               at_size, emit, load_module, real_compiles,
                               tmx)


class Unit:
    """One submit: where it wrote, when it ran, what its ledger says."""

    def __init__(self, root: str, sites: int):
        self.root, self.sites = root, sites
        self.t0 = self.t1 = 0.0
        self.events: list = []

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


class PlateRun(Run):
    """A plate cell's run: the window's units and the traced ones."""

    def __init__(self, config: dict, device: dict, field_size: int,
                 capacity: int):
        super().__init__("plate", config, device)
        self.field_size, self.capacity = field_size, capacity
        self.units: list = []         # the window's units
        self.traced_units: list = []  # those inside the profiler window

    @property
    def sites(self) -> int:
        return sum(u.sites for u in self.units)

    def events(self) -> list:
        return [e for u in self.units for e in u.events]


def sized(config: dict, traffic: dict, on_chip: bool) -> tuple:
    """``(field size, capacity, cells range)``: the configuration's on
    the chip, its rehearsal's anywhere else."""
    config, traffic = at_size(config, on_chip), at_size(traffic, on_chip)
    return (config["field_size"], config["max_objects"],
            plate.parse_range(traffic["cells_per_field"]))


def write_well(work: str, config: dict, traffic: dict, on_chip: bool,
               seed: int) -> tuple:
    size, capacity, cells = sized(config, traffic, on_chip)
    src = os.path.join(work, "src")
    wells = plate.well_names(traffic["wells_per_submit"])
    sites = plate.write_plate(src, wells, config["fields_per_well"], size,
                              cells, config["channels"], seed)
    return src, sites, size, capacity


def submit(work: str, index: int, src: str, sites: int, config: dict,
           capacity: int) -> Unit:
    from tmlibrary_tpu import capacity as router

    # The router's history is process-global, keyed by the pipeline's
    # content and by site index, so a second submit in this process would
    # be routed by the first one's counts.  A new plate comes to a new
    # process: drop the history (the program's own hook for "fresh
    # benchmarking runs"), so every unit walks the ladder the warm-up did.
    router.reset_routing_history()
    unit = Unit(os.path.join(work, f"exp{index:03d}"), sites)
    unit.t0 = time.time()
    tmx(["create", "--name", os.path.basename(unit.root),
         "--root", unit.root])
    wf = plate.write_description(unit.root, src, config, capacity)
    tmx(["workflow", "submit", "--description", wf, "--root", unit.root])
    unit.t1 = time.time()
    return unit


def join_speculation() -> None:
    """Compile-ahead speculation runs on background ``tmx-warm`` threads
    that can outlive the submit that started them: after a set-up submit,
    wait for them, so that their compiles are set-up and not window."""
    for thread in threading.enumerate():
        if thread.name == "tmx-warm":
            thread.join()


def on_disk(root: str, object_names: list) -> tuple:
    """``(sites done, object counts)``: a site is done when, for every
    object type, its label stack is readable and its feature rows are as
    many as the objects the stack holds."""
    from tmlibrary_tpu.models.store import ExperimentStore

    store = ExperimentStore.open(Path(root))
    ok = np.ones(store.n_sites, bool)
    counts = {}
    for name in object_names:
        rows = store.read_features(name).groupby("site_index").size()
        labels = store.read_labels(None, name)
        counts[name] = [int(rows.get(s, 0)) for s in range(store.n_sites)]
        for s in range(store.n_sites):
            in_stack = np.count_nonzero(np.bincount(labels[s].ravel())[1:])
            ok[s] &= in_stack == counts[name][s]
    return int(ok.sum()), counts


def run(args, config, traffic, device, meter, work, t_process) -> dict:
    from tmlibrary_tpu import aotstore
    from tmlibrary_tpu.models.store import ExperimentStore

    on_chip = device["platform"] == "tpu"
    rng = np.random.default_rng(args.seed)
    src, sites, size, capacity = write_well(work, config, traffic, on_chip,
                                            args.seed)
    run_ = PlateRun(config, device, size, capacity)

    # ---- set-up: one whole unit walks exactly the rungs the window will
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
    done = failed = 0
    repeat_equal, forbidden = True, set(ledger.forbidden(warm.events))
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
        repeat_equal &= counts == want_counts
    warm_store = ExperimentStore.open(Path(warm.root))
    sample = sorted(int(s) for s in rng.choice(
        sites, size=min(config["reference_sample_sites"], sites),
        replace=False))
    verdict = reference.check(warm_store, sample, config)
    checks = dict(verdict["checks"])
    checks.update({
        "every_site_on_disk": failed == 0 and done > 0,
        "resubmissions_identical": repeat_equal,
        "no_forbidden_event": not forbidden,
        "no_compile_in_window": real_compiles(run_.compile["window"]) == 0,
        "arrays_on_reported_platform": (
            returned.n_arrays > 0
            and returned.platforms == {device["platform"]}),
    })
    emit({"line": "checks", "checks": checks, **verdict["info"],
          "forbidden_events": sorted(forbidden), "errors": errors,
          "units": [round(u.seconds, 3) for u in run_.units],
          "window_s": window_s, "window_compile": run_.compile["window"]})

    metrics = {"sites_per_s": {"value": done / window_s, "unit": "sites/s"},
               "setup_s": {"value": setup_s, "unit": "s"}}
    return {"run": run_, "metrics": metrics, "correct": all(checks.values()),
            "attempted": sum(u.sites for u in run_.units),
            "failed": failed}
