"""The ``cp3-multiplex.drift`` cell: what ``BENCHMARK.json`` says of it,
stated by name and never by place; the new readers and
``roofline_align``'s arithmetic on made-up ledgers and traces; and the
cell itself at its rehearsal size — a sound unit the plain reference
holds, and the faults it has to fail: one cycle's stored shifts a pixel
off (the cell's control), a wrong window, a cycle dropped."""

import json
import math
import re
import shutil
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from benchmark import harness, ledger, roofline_align  # noqa: E402
from benchmark.drivers import multiplex as driver  # noqa: E402
from benchmark.drivers.plate import PlateRun, Unit  # noqa: E402

CELL = "cp3-multiplex.drift"
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
CONFIG = json.loads(
    (REPO / "benchmark" / "configs" / "cp3-multiplex.json").read_text())
TRAFFIC = json.loads(
    (REPO / "benchmark" / "traffic" / "drift.json").read_text())
NEW = ["align_ms_per_site", "align_read_ms_per_site",
       "align_device_ms_per_site", "align_register_roofline",
       "align_failed_sites", "intersection_lost_share"]
#: the plate cells' metrics whose readers read this cell's run unchanged
JOINS = ["sites_per_s", "engine_other_ms_per_site", "ingest_ms_per_site",
         "illum_pyramid_ms_per_site", "jterator_ms_per_site",
         "device_block_ms_per_site", "persist_ms_per_site",
         "escalations_per_site", "program_ms_per_site",
         "device_idle_share.plate", "peak_hbm_gb.plate", "warm_compile_s",
         "window_compiles.plate", "stage_smooth_ms_per_site",
         "stage_threshold_ms_per_site", "stage_fill_ms_per_site",
         "stage_label_ms_per_site", "stage_watershed_ms_per_site",
         "stage_measure_ms_per_site", "stage_other_ms_per_site",
         "plate_steps_device_ms_per_site", "corilla_ms_per_site",
         "illuminati_prep_ms_per_site", "illuminati_pyramid_ms_per_site",
         "illuminati_encode_ms_per_site", "jit_in_window_ms_per_site",
         "persist_escalate_ms_per_site", "persist_fetch_ms_per_site",
         "persist_labels_ms_per_site", "persist_features_ms_per_site",
         "h2d_mb_per_site", "decode_mpix_per_s",
         "measure_intensity_ms_per_site", "feature_values_per_site"]
#: those whose arithmetic does not hold here: the batch program's
#: compulsory bytes count label planes of the whole field (the window's
#: frame is written), the other measure families and the hulls are not
#: in the pipeline, the spatial layout is not run
MAY_NOT_JOIN = ["batch_program_roofline", "measure_morphology_ms_per_site",
                "measure_texture_ms_per_site", "measure_zernike_ms_per_site",
                "measure_texture_roofline", "persist_solidity_ms_per_site"]


def metric(name: str) -> dict:
    return next(m for group in ("end_to_end", "per_layer")
                for m in BENCH[group] if m["name"] == name)


def reader(name: str):
    return harness.load_module(
        str(REPO / "benchmark" / "metrics" / (name + ".py")))


# ------------------------------------------------------------- the entries
def test_the_cell_is_one_chip_of_the_multiplex_configuration():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("cp3-multiplex", "drift", 1)
    assert len(cell["why"]) <= 200
    assert len(BENCH["workloads"]) == 5
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 1


def test_the_configuration_entry_is_its_files():
    entry = next(c for c in BENCH["configs"] if c["name"] == "cp3-multiplex")
    assert entry["file"] == "benchmark/configs/cp3-multiplex.json"
    assert entry["source"] == CONFIG["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == sorted(CONFIG["reduced"]) == \
        ["cycles", "wells_per_submit"]
    assert CONFIG["driver"] == "multiplex"
    assert (REPO / "benchmark" / "configs" / CONFIG["reference"]).exists()
    for key in ("assumed", "guarantees", "rehearsal"):
        assert CONFIG[key]


def one_line(text) -> bool:
    """The driver's rule for a ``why``, a ``layer``, a ``source`` and a
    word of ``command``: 1 to 200 printable characters, on one line and
    with no tab (the check refused this PR's first configuration ``why``
    at 206, as it had PR 33's at 204)."""
    return (isinstance(text, str) and 1 <= len(text) <= 200
            and all(32 <= ord(c) < 127 for c in text))


NAME = r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}"
UNIT = r"[A-Za-z0-9_/%.-]{1,16}"


def form_faults(bench: dict) -> list:
    """Every fault of form in a ``BENCHMARK.json``, by the rules the
    driver holds the file to before a single run (it names only the
    first; this names them all)."""
    faults = []

    def hold(ok, what):
        if not ok:
            faults.append(what)

    for word in bench["command"]:
        hold(one_line(word), "command word %r" % word)
    names = {}
    for group, keys in (("configs", {"name", "source", "file", "reduced",
                                     "why"}),
                        ("workloads", {"name", "config", "traffic", "chips",
                                       "why"})):
        for entry in bench[group]:
            at = "%s %s" % (group, entry.get("name"))
            hold(set(entry) == keys, at + ": keys %s" % sorted(entry))
            hold(re.fullmatch(NAME, entry["name"]), at + ": name")
            hold(one_line(entry["why"]),
                 at + ": why has %d characters" % len(entry["why"]))
            hold(names.setdefault((group, entry["name"]), entry) is entry,
                 at + ": name given twice")
    files = [c["file"] for c in bench["configs"]]
    hold(len(set(files)) == len(files), "two configurations share a file")
    for config in bench["configs"]:
        at = "configs " + config["name"]
        hold(one_line(config["source"]), at + ": source")
        hold(any(config["file"].startswith(p + "/") for p in bench["paths"]),
             at + ": file outside paths")
        hold(re.fullmatch(r"[A-Za-z0-9_./-]+", config["file"]), at + ": file")
        hold((REPO / config["file"]).is_file(), at + ": file missing")
        hold(len(config["reduced"]) <= 16, at + ": reduced too long")
        for key in config["reduced"]:
            hold(re.fullmatch(NAME, key), at + ": reduced key %r" % key)
        hold(any(w["config"] == config["name"] for w in bench["workloads"]),
             at + ": no cell runs it")
    cells = [w["name"] for w in bench["workloads"]]
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    hold(len(set(pairs)) == len(pairs), "a configuration and traffic twice")
    for cell in bench["workloads"]:
        at = "workloads " + cell["name"]
        hold(("configs", cell["config"]) in names, at + ": unknown config")
        hold(re.fullmatch(NAME, cell["traffic"]), at + ": traffic")
        hold(cell["chips"] in (1, 4), at + ": chips")
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    hold(four <= max(1, len(cells) // 2), "too many four-chip cells")
    hold(1 <= len(bench["configs"]) <= 24 and 1 <= len(cells) <= 24,
         "counts of configurations or cells")
    hold(1 <= len(bench["per_layer"]) <= 128, "count of per-layer metrics")
    end_to_end = {m["name"]: m for m in bench["end_to_end"]}
    seen = set()
    for group, keys, sources in (
            ("end_to_end", {"name", "unit", "better", "bound", "source"},
             {"host_clock", "device_trace"}),
            ("per_layer", {"name", "unit", "better", "source", "layer",
                           "moves"},
             {"host_clock", "device_trace", "program_span",
              "program_counter"})):
        for m in bench[group]:
            at = "%s %s" % (group, m.get("name"))
            hold(set(m) - {"workloads"} == keys, at + ": keys %s" % sorted(m))
            hold(re.fullmatch(NAME, m["name"]), at + ": name")
            hold(m["name"] not in seen, at + ": name given twice")
            seen.add(m["name"])
            hold(re.fullmatch(UNIT, m["unit"]), at + ": unit")
            hold(m["better"] in ("lower", "higher"), at + ": better")
            hold(m["source"] in sources, at + ": source")
            listed = m.get("workloads", cells)
            hold(set(listed) <= set(cells) and len(set(listed)) ==
                 len(listed), at + ": workloads")
            if group == "per_layer":
                hold(one_line(m["layer"]), at + ": layer")
                moved = end_to_end.get(m["moves"])
                hold(moved is not None, at + ": moves")
                if moved is not None:
                    hold(set(listed) <= set(moved.get("workloads", cells)),
                         at + ": a cell without the metric it moves")
    for cell in cells:
        reports = [m["name"] for m in bench["end_to_end"]
                   if cell in m.get("workloads", cells)]
        hold("setup_s" in reports and len(reports) >= 2,
             "workloads %s: end-to-end metrics %s" % (cell, reports))
        hold(any(cell in m.get("workloads", cells)
                 for m in bench["per_layer"]),
             "workloads %s: no per-layer metric" % cell)
    runs = (2 + 14 * len(cells)) * (bench["run_seconds"] + 60)
    hold(runs + 2 * 90 * len(cells) + 1200 <= 43200, "the time rule")
    return faults


def test_benchmark_json_keeps_the_drivers_form():
    assert form_faults(BENCH) == []
    assert (REPO / "BENCHMARK.json").stat().st_size <= 64 * 1024


@pytest.mark.parametrize("spoil, fault", [
    (lambda b: b["configs"][-1].update(why="x" * 201), "why has 201"),
    (lambda b: b["workloads"][-1].update(why="a\tb"), "why has 3"),
    (lambda b: b["per_layer"][-1].update(why="no such key"), "keys"),
    (lambda b: b["per_layer"][-1].update(unit="ms per site"), "unit"),
    (lambda b: [w.update(chips=4) for w in b["workloads"][-3:]], "four-chip"),
    (lambda b: b["workloads"][-1].update(chips=2), ": chips"),
], ids=["why-long", "why-tab", "metric-key", "unit-space", "four-chip-share",
        "chips"])
def test_the_form_check_names_a_planted_fault(spoil, fault):
    bench = json.loads(json.dumps(BENCH))
    spoil(bench)
    assert any(fault in f for f in form_faults(bench)), form_faults(bench)


@pytest.mark.parametrize("name", JOINS)
def test_plate_metric_lists_the_cell(name):
    assert CELL in metric(name)["workloads"]
    assert metric(name)["workloads"][-1] == CELL    # appended, nothing moved


@pytest.mark.parametrize("name", MAY_NOT_JOIN)
def test_metric_whose_arithmetic_does_not_hold_leaves_the_cell_out(name):
    assert CELL not in metric(name)["workloads"]


def test_no_mosaic_metric_lists_the_cell():
    assert not [m["name"] for m in BENCH["per_layer"]
                if m["name"].startswith("mosaic_") and CELL in m["workloads"]]


@pytest.mark.parametrize("name", NEW)
def test_new_metric_has_its_reader_its_unit_and_lists_only_the_cell(name):
    entry = metric(name)
    assert entry["workloads"] == [CELL] and entry["moves"] == "sites_per_s"
    assert entry["layer"] == ("kernels" if name.endswith("_roofline")
                              else "align step")
    assert sorted(entry) == ["better", "layer", "moves", "name", "source",
                             "unit", "workloads"]
    module = reader(name)
    assert module.UNIT == entry["unit"] and callable(module.read)


def test_the_pipeline_reads_every_stain_from_its_cycle():
    channels = CONFIG["pipeline"]["input"]["channels"]
    assert [(c["name"], c["cycle"]) for c in channels] == [
        ("DAPI", 0), ("Actin", 0), ("Tubulin", 0), ("ER", 1), ("Mito", 1),
        ("Golgi", 2), ("Nucleolin", 2)]
    assert all(c["align"] and not c["correct"] for c in channels)
    modules = [m["handles"]["module"] for m in CONFIG["pipeline"]["pipeline"]]
    assert modules[:3] == ["smooth", "segment_primary", "segment_secondary"]
    assert modules[3:] == ["measure_intensity"] * 12
    cp3 = json.loads((REPO / "benchmark" / "configs" / "cp3-plate.json")
                     .read_text())["pipeline"]["pipeline"][:3]
    assert CONFIG["pipeline"]["pipeline"][:3] == cp3
    assert CONFIG["features_per_object"] == 5 * len(CONFIG["stains_measured"])
    assert CONFIG["steps"] == ["metaconfig", "imextract", "corilla", "align",
                               "illuminati", "jterator"]
    assert (CONFIG["field_size"], CONFIG["fields_per_well"],
            CONFIG["max_objects"], CONFIG["max_shift"]) == (2160, 9, 1024, 50)
    assert TRAFFIC["drift_px"] == 24 and TRAFFIC["cells_per_field"] == "350-650"
    assert (TRAFFIC["wells_per_submit"], TRAFFIC["clients"],
            TRAFFIC["loop"]) == (2, 1, "plate_closed")


def test_the_program_reads_channels_by_cycle_and_the_probe_says_so():
    assert driver.program_reads_channels_by_cycle()


# ------------------------------------------ roofline_align, by hand
def test_pair_bytes_and_flops_by_hand():
    assert roofline_align.pair_bytes(2160, 2160) == 2 * 2160 * 2160 * 2 + 12
    assert roofline_align.pair_bytes(2160, 2160) == 18_662_412
    n = 2160 * 2160
    assert roofline_align.pair_flops(2160, 2160) == pytest.approx(
        3 * 2.5 * n * math.log2(n))
    assert roofline_align.pair_flops(64, 64) == 3 * 2.5 * 4096 * 12


class FakeTrace:
    def __init__(self, modules: dict, anchor_s: float):
        self.modules, self.anchor_s = modules, anchor_s


class FakeTracer:
    anchor_wall = 1000.0


def traced_run(register_pairs=(9, 9)) -> PlateRun:
    """One traced unit whose align step span is wall 1001..1003: two
    executions of the registration program inside it (30 and 36 ms), one
    after it, and another program's inside it."""
    run = PlateRun(CONFIG, {"platform": "tpu", "kind": "TPU v5 lite",
                            "count": 1}, 2160, 1024)
    unit = Unit("made-up", 9)
    unit.t0, unit.t1 = 1000.5, 1010.0
    unit.events = [
        {"event": "span", "span": "step", "step": "align", "t0": 1001.0,
         "elapsed": 2.0},
        *({"event": "span", "span": "register", "step": "align",
           "parent": "step", "t0": 1001.1 + k, "elapsed": 0.05, "pairs": p}
          for k, p in enumerate(register_pairs)),
        {"event": "span", "span": "read", "step": "align", "parent": "step",
         "t0": 1001.0, "elapsed": 0.09},
        {"event": "span", "span": "read", "step": "align", "parent": "step",
         "t0": 1002.0, "elapsed": 0.09},
        {"event": "step_done", "step": "align", "elapsed": 2.25,
         "collected": {"failed_sites": 0, "sites": 18,
                       "window": dict.fromkeys(
                           ("top", "bottom", "left", "right"), 32)}},
    ]
    run.units.append(unit)
    run.traced_units.append(unit)
    run.tracer = FakeTracer()
    module = CONFIG["align_program_module"] + "(123)"
    # trace clock = wall - 1000 + 5
    run.trace = FakeTrace({"/device:TPU:0": [
        (6.12, 6.15, module), (7.12, 7.156, module), (9.0, 9.03, module),
        (6.5, 6.9, "jit_prep(7)")]}, anchor_s=5.0)
    return run


def test_executions_are_the_programs_inside_the_step_span():
    runs = roofline_align.executions(traced_run())
    assert [(round(a, 3), round(b, 3)) for a, b in runs] == [
        (6.12, 6.15), (7.12, 7.156)]
    assert reader("align_device_ms_per_site").read(traced_run()) == \
        pytest.approx(1e3 * 0.066 / 9)


def test_register_roofline_is_the_larger_bound_over_a_pairs_median():
    run = traced_run()
    seconds = roofline_align.pair_seconds(run)
    assert seconds == pytest.approx((0.030 / 9 + 0.036 / 9) / 2)
    share, bound = roofline_align.register_share(run)
    by_bytes = 18_662_412 / 819e9
    by_flops = roofline_align.pair_flops(2160, 2160) / 197e12
    assert bound == "memory" and by_bytes > by_flops
    assert share == pytest.approx(100 * by_bytes / seconds)
    assert 0 < reader("align_register_roofline").read(run) < 100


def test_register_roofline_reads_nothing_where_spans_and_runs_do_not_pair():
    assert roofline_align.pair_seconds(traced_run((9,))) is None
    assert reader("align_register_roofline").read(traced_run((9,))) is None


def test_ledger_readers_by_hand():
    run = traced_run()
    assert reader("align_ms_per_site").read(run) == pytest.approx(250.0)
    assert reader("align_read_ms_per_site").read(run) == pytest.approx(20.0)
    assert reader("align_failed_sites").read(run) == 0
    assert reader("intersection_lost_share").read(run) == pytest.approx(
        100 * (1 - 2096 ** 2 / 2160 ** 2))


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_align_step_reads_as_nothing(name):
    """The parent's ledger and trace (no align step, no such program):
    every new reader returns nothing and none raises."""
    run = traced_run()
    run.units[0].events = [
        {"event": "span", "span": "step", "step": "corilla", "t0": 1001.0,
         "elapsed": 2.0, "parent": "run"},
        {"event": "step_done", "step": "corilla", "elapsed": 2.0}]
    run.trace = FakeTrace({"/device:TPU:0": [(6.5, 6.9, "jit_prep(7)")]}, 5.0)
    assert reader(name).read(run) is None
    bare = PlateRun(CONFIG, run.device, 2160, 1024)
    assert reader(name).read(bare) is None


# --------------------------------------------- the cell at its rehearsal size
@pytest.fixture(scope="module")
def unit(tmp_path_factory):
    """One sound unit at the rehearsal size, through the driver's own
    ``submit`` (``tmx create`` + ``tmx workflow submit``)."""
    from benchmark import multiplex, plate

    config, (size, capacity, cells, drift) = driver.sized(
        CONFIG, TRAFFIC, False)
    work = tmp_path_factory.mktemp("multiplex_cell")
    src = str(work / "src")
    sites, planted = multiplex.write_wells(
        src, plate.well_names(TRAFFIC["wells_per_submit"]), config, size,
        cells, drift, seed=5)
    made = driver.submit(str(work), 0, src, sites, config, capacity)
    reference = harness.load_module(
        str(REPO / "benchmark" / "configs" / config["reference"]))
    return {"unit": made, "config": config, "planted": planted,
            "sites": sites, "reference": reference, "work": work,
            "window": {k: 16 for k in ("top", "bottom", "left", "right")}}


def held(unit, root) -> dict:
    copy = Unit(str(root), unit["sites"])
    return driver.held(unit["reference"], copy, [0, 8, 13], unit["config"],
                       unit["planted"])


def tampered(unit, name: str) -> Path:
    root = unit["work"] / name
    shutil.copytree(unit["unit"].root, root)
    return root


def over(verdict: dict) -> set:
    return {n for n, (value, limit) in verdict["compared"].items()
            if value > limit}


def test_the_reference_holds_a_sound_unit(unit):
    verdict = held(unit, unit["unit"].root)
    assert not over(verdict) and all(verdict["checks"].values())
    assert verdict["info"]["stored_window"] == unit["window"]
    assert verdict["info"]["max_abs_shift"] == 3
    assert len(verdict["compared"]) + 2 <= 15
    assert all(len(n) <= 32 for n in verdict["compared"])
    assert set(verdict["compared"]) == set(unit["reference"].LIMITS)


def test_a_planted_off_by_one_shift_fails_through_the_intensities(unit):
    """What ``benchmark/control.py`` runs on the chip: the second cycle's
    stored shifts one pixel off in x before illuminati and jterator."""
    from tmlibrary_tpu.models.store import ExperimentStore

    root = tampered(unit, "off_by_one")
    store = ExperimentStore.open(root)
    table = store.read_shifts(1)
    table[:, 1] += 1
    store.write_shifts(table, 1)
    # the labels and features on disk were measured under the right
    # shifts: against the table that is off they are a pixel out
    failed = over(held(unit, root))
    assert failed == {"shift_entries_unlike_reference",
                      "shift_entries_unlike_planted"}
    # ... and a unit that RAN under the table that is off
    reading = driver.control(5, CONFIG, TRAFFIC,
                             {"platform": "cpu", "kind": "cpu", "count": 1},
                             str(unit["work"] / "control"))
    assert reading["stated"]["checks_failed"] == []
    assert "intensity_within_tolerance" in reading["control"]["checks_failed"]
    rel, limit = reading["control"]["compared"]["intensity_mean_sum_rel"]
    assert rel > 100 * limit
    assert reading["control"]["compared"]["intensity_minmax_unlike"][0] > 0
    assert reading["answers_failed"] >= 2


def test_a_wrong_window_fails(unit):
    from tmlibrary_tpu.models.store import ExperimentStore

    # the exact intersection stored in the widened window's place
    root = tampered(unit, "exact_window")
    ExperimentStore.open(root).write_intersection(
        {"top": 3, "bottom": 3, "left": 3, "right": 3})
    assert "window_margins_unlike_reference" in over(held(unit, root))
    # a stack that holds an object outside the window
    root = tampered(unit, "label_outside")
    store = ExperimentStore.open(root)
    labels = store.read_labels([0], "cells").copy()
    labels[0, :4, :4] = 1
    store.write_labels(labels, [0], "cells")
    verdict = held(unit, root)
    assert verdict["compared"]["label_pixels_outside_window"][0] == 16
    assert not verdict["checks"]["window_is_the_references"]


def test_a_dropped_cycle_fails(unit):
    # the third cycle never registered, tiled or measured
    root = tampered(unit, "dropped_cycle")
    (root / "alignment" / "shifts_cycle02.npy").unlink()
    for layer in (root / "pyramids").glob("cycle02_*"):
        shutil.rmtree(layer)
    import pandas as pd

    for shard in (root / "features").glob("*/*.parquet"):
        table = pd.read_parquet(shard)
        table[[c for c in table.columns
               if not c.endswith(("_Golgi", "_Nucleolin"))]].to_parquet(
                   shard, index=False)
    verdict = held(unit, root)
    assert {"shift_entries_unlike_reference", "pyramid_layers_missing",
            "feature_columns_unlike_30"} <= over(verdict)
    assert verdict["compared"]["pyramid_layers_missing"][0] == 3
    assert verdict["compared"]["feature_columns_unlike_30"][0] == 2


def test_the_unit_says_what_the_readers_read(unit):
    events = ledger.run_ledger(unit["unit"].root)
    said = roofline_align.collected(events)
    assert len(said) == 1 and said[0]["window"] == unit["window"]
    assert said[0]["sites"] == 36 and said[0]["failed_sites"] == 0
    spans = [e for e in events if e.get("event") == "span"
             and e.get("step") == "align"]
    step = next(e for e in spans if e["span"] == "step")
    inner = sum(e["elapsed"] for e in spans
                if e["span"] in ("read", "register", "write_shifts"))
    assert inner <= step["elapsed"]
    results = ledger.batch_results(events, "jterator")
    assert all(r["cycles_read"] == [0, 1, 2] and r["aligned_channels"] == 7
               for r in results)
    assert sorted(r["cycle"] for r in ledger.batch_results(
        events, "illuminati")) == [0, 0, 0, 1, 1, 1, 2, 2, 2]


def test_unit_steps_says_where_every_units_seconds_went(unit):
    """What the ``checks`` line carries beside ``units``: for every unit
    of the window, each of the six steps' ``step_done.elapsed``."""
    first = unit["unit"]
    first.events = ledger.run_ledger(first.root)
    second = Unit("made-up", 18)
    second.events = [{"event": "step_done", "step": "align",
                      "elapsed": 1.23456},
                     {"event": "step_done", "step": "jterator",
                      "elapsed": 7.0004}]
    said = driver.unit_steps([first, second], CONFIG["steps"])
    assert len(said) == 2
    assert list(said[0]) == CONFIG["steps"]
    assert all(isinstance(v, float) and v >= 0 and round(v, 3) == v
               for v in said[0].values())
    assert said[1] == {"align": 1.235, "jterator": 7.0}
    assert sum(said[0].values()) <= first.seconds
