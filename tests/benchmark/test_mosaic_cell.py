"""The ``cp3-mosaic.x4`` cell's own arithmetic, and the cell itself at its
rehearsal size: the new readers on a recorded run ledger and on a recorded
four-plane trace (``scripts/record_mosaic_trace.py``), the scopes and the
collectives on made-up operation names, the roofline's bytes by hand, and
whole ``run.main`` rehearsals in processes of their own — sound, with the
seam join of the sharded connected components made a no-op where the
program calls it, and with corilla's sharded fold merging nothing: the
plain reference has to fail both."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from benchmark import harness, ledger, roofline_mosaic, stages  # noqa: E402
from benchmark.drivers.plate import PlateRun, Unit  # noqa: E402

CELL = "cp3-mosaic.x4"
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
CONFIG = json.loads(
    (REPO / "benchmark" / "configs" / "cp3-mosaic.json").read_text())
TRACE = DATA / "tiny_mosaic_tpu_v5e_x4.xplane.pb"
NEW = [m["name"] for m in BENCH["per_layer"] if m["name"].startswith("mosaic_")]


def reader(name: str):
    return harness.load_module(
        str(REPO / "benchmark" / "metrics" / (name + ".py")))


@pytest.fixture(scope="module")
def recorded():
    """A run of one unit, as the driver would hold it: the recorded
    ledger's events, nine sites."""
    unit_json = json.loads((DATA / "mosaic_unit.json").read_text())
    run = PlateRun(CONFIG, unit_json["device"], unit_json["field_size"], 64)
    unit = Unit("recorded", unit_json["sites"])
    unit.t0, unit.t1 = unit_json["t0"], unit_json["t1"]
    unit.events = ledger.read_events(DATA / "mosaic_run_ledger.jsonl")
    run.units.append(unit)
    return run, unit_json


def elapsed(events: list, names, parent=None) -> float:
    return sum(float(e["elapsed"]) for e in events
               if e.get("event") == "span" and e.get("step") == "jterator"
               and e.get("span") in names
               and (parent is None or e.get("parent") == parent))


# -------------------------------------------------- readers, recorded ledger
@pytest.mark.parametrize("name, spans, parent", [
    ("mosaic_stitch_ms_per_site", ("stitch",), None),
    ("mosaic_upload_ms_per_site", ("upload",), None),
    ("mosaic_device_wait_ms_per_site", ("device_wait",), None),
    ("mosaic_measure_ms_per_site", ("morph", "intensity", "solidity"), None),
    # the pipelined executor's readers, as a plate cell's run reads them
    ("persist_fetch_ms_per_site", ("fetch",), "persist"),
    ("persist_labels_ms_per_site", ("write_labels",), "persist"),
    ("persist_features_ms_per_site", ("write_features",), "persist"),
    ("persist_solidity_ms_per_site", ("solidity",), "persist"),
])
def test_span_reader_is_the_spans_seconds_over_sites(recorded, name, spans,
                                                     parent):
    run, _ = recorded
    want = 1e3 * elapsed(run.units[0].events, spans, parent) / 9
    assert want > 0
    module = reader(name)
    assert module.UNIT == "ms/site"
    assert module.read(run) == pytest.approx(want, rel=1e-9)


def test_the_executors_counters_and_phases_read_a_mosaic_unit(recorded):
    """``h2d_bytes`` of ``batch_done``, ``rows`` x ``columns`` of the two
    ``write_features`` spans, and the ``device_block`` and ``persist``
    phases of the step's ``pipeline_stats``: the accepted readers, on
    the recorded unit."""
    run, unit_json = recorded
    events = run.units[0].events
    (batch,) = unit_json["jterator_batches"]
    assert reader("h2d_mb_per_site").read(run) == pytest.approx(
        batch["h2d_bytes"] / 1e6 / 9)
    written = [e for e in events if e.get("event") == "span"
               and e.get("span") == "write_features"]
    assert len(written) == 2
    assert reader("feature_values_per_site").read(run) == pytest.approx(
        sum(e["rows"] * e["columns"] for e in written) / 9)
    phases = ledger.phase_seconds(events, "jterator")
    for name, phase in (("persist_ms_per_site", "persist"),
                        ("device_block_ms_per_site", "device_block")):
        assert phases[phase] > 0
        assert reader(name).read(run) == pytest.approx(
            1e3 * phases[phase] / 9)


def test_the_recorded_ledger_holds_what_the_spans_are_counted_from(recorded):
    events = recorded[0].units[0].events
    count = {name: sum(1 for e in events if e.get("event") == "span"
                       and e.get("step") == "jterator"
                       and e.get("span") == name)
             for name in ("stitch", "upload", "segment", "device_wait",
                          "fetch", "morph", "intensity", "solidity",
                          "write_labels", "write_features")}
    assert count == {"stitch": 5, "upload": 1, "segment": 1,
                     "device_wait": 1, "fetch": 1, "morph": 2,
                     "intensity": 10, "solidity": 2, "write_labels": 2,
                     "write_features": 2}


@pytest.mark.parametrize("name, key", [
    ("mosaic_seam_rounds", "seam_rounds"),
    ("mosaic_adopt_steps", "adopt_steps"),
    ("mosaic_roots_max_per_shard", "roots_max_per_shard"),
])
def test_counter_reader_is_batch_dones_number(recorded, name, key):
    run, unit_json = recorded
    (batch,) = unit_json["jterator_batches"]
    assert batch["mesh_shape"] == [2, 2] and batch[key] > 0
    module = reader(name)
    assert module.UNIT == "count" and module.read(run) == batch[key]


def test_counters_are_a_units_and_the_fullest_shards(recorded):
    """Two units of the same well: rounds and steps read a unit's, the
    roots read the most."""
    run, _ = recorded
    twice = PlateRun(CONFIG, run.device, run.field_size, 64)
    twice.units = [run.units[0], run.units[0]]
    for key in ("seam_rounds", "adopt_steps"):
        assert roofline_mosaic.counter_per_unit(twice, key) == \
            roofline_mosaic.counter_per_unit(run, key)
    assert roofline_mosaic.counter_per_unit(
        twice, "roots_max_per_shard", max) == \
        roofline_mosaic.counter_per_unit(run, "roots_max_per_shard", max)


def test_a_ledger_without_the_spatial_layouts_spans_reads_as_nothing():
    """A sites-layout unit (``stages_run_ledger.jsonl``, PR 25): no
    ``morph`` span, no counter."""
    run = PlateRun(CONFIG, {"platform": "tpu"}, 64, 16)
    unit = Unit("sites", 9)
    unit.events = ledger.read_events(DATA / "stages_run_ledger.jsonl")
    run.units.append(unit)
    assert reader("mosaic_measure_ms_per_site").read(run) is None
    for name in ("mosaic_seam_rounds", "mosaic_adopt_steps",
                 "mosaic_roots_max_per_shard"):
        assert reader(name).read(run) is None
    # and an untraced run gives no device metric
    for metric in BENCH["per_layer"]:
        if metric["name"] in NEW and metric["source"] == "device_trace":
            assert reader(metric["name"]).read(run) is None, metric["name"]


# ----------------------------------------- scopes and collectives, made up
class MadeUpPlane:
    """A device plane as ``stages.Plane`` holds it, from ``(start ns,
    duration ns, HLO text, tf_op)`` rows."""

    def __init__(self, rows):
        self.lines = {stages.OPS_LINE: [(t0, d, i)
                                        for i, (t0, d, _, _) in
                                        enumerate(rows)]}
        assert len(rows) == len(self.lines[stages.OPS_LINE])
        self.names = {i: text for i, (_, _, text, _) in enumerate(rows)}
        self.stats = {i: {"tf_op": op} for i, (_, _, _, op) in
                      enumerate(rows)}


def made_up_rows(scale: int = 1):
    """Every start and duration times ``scale``."""
    s = scale
    return [(t0 * s, d, text, op) for t0, d, text, op in [
        (0, 100 * s, "%fusion.1 = f32[8] fusion(%p)",
         "jit(body)/mosaic_smooth/smooth/conv:"),
        (200, 50 * s, "%all-reduce.2 = f32[256] all-reduce(%h)",
         "jit(body)/mosaic_otsu/psum:"),
        # a while holding a permute and a fusion: its self time is 20
        (400, 100 * s, "%while.3 = (s32[8]) while(%t)",
         "jit(body)/mosaic_cc/while:"),
        (400, 30 * s, "%collective-permute-start.4 = (s32[8]) "
         "collective-permute-start(%r)",
         "jit(body)/mosaic_cc/mosaic_seam/ppermute:"),
        (440, 50 * s, "%fusion.5 = s32[8] fusion(%l)",
         "jit(body)/mosaic_cc/while/body/min:"),
        (600, 40 * s, "%all-gather.6 = s32[64] all-gather(%roots)",
         "jit(body)/mosaic_cc/all_gather:"),
        (700, 60 * s, "%fusion.7 = s32[8] fusion(%l)",
         "jit(body)/mosaic_watershed/while/body/adopt:"),
        # no scope: another program's (corilla's) collective and fusion
        (900, 500 * s, "%all-gather.8 = f32[4] all-gather(%w)",
         "jit(_scan_and_merge)/all_gather:"),
        (1500, 70 * s, "%copy.9 = f32[8] copy(%p)", ""),
    ]]


def test_scope_of_is_the_outermost_mosaic_scope():
    assert roofline_mosaic.scope_of(
        "jit(body)/mosaic_cc/mosaic_seam/ppermute:") == "mosaic_cc"
    assert roofline_mosaic.scope_of(
        "jit(body)/mosaic_smooth/smooth/conv:") == "mosaic_smooth"
    assert roofline_mosaic.scope_of(
        "jit(one_site)/vmap(segment_primary)/label/while:") is None
    assert roofline_mosaic.scope_of("") is None


@pytest.mark.parametrize("text, collective", [
    ("%collective-permute.1 = f32[8] collective-permute(%x)", True),
    ("%collective-permute-start.4 = (f32[8]) collective-permute-start(%x)",
     True),
    ("%collective-permute-done.4 = f32[8] collective-permute-done(%s)",
     True),
    ("%all-reduce.2 = f32[] all-reduce(%x)", True),
    ("%all-gather.6 = s32[64] all-gather(%x)", True),
    ("%fusion.all-reduce = f32[8] fusion(%x)", False),
    ("%reduce.3 = f32[] reduce(%x)", False),
    ("%while.3 = (s32[8]) while(%t)", False),
])
def test_collectives_by_made_up_operation_names(text, collective):
    assert roofline_mosaic.is_collective(text) is collective


def test_segment_seconds_of_made_up_planes_by_hand():
    """Two planes, the second twice as slow: the mean is 1.5 times the
    first.  First plane, ns: smooth 100; otsu 50 (a collective); cc = the
    while's self 20 + permute 30 + fusion 50 + all-gather 40 = 140;
    watershed 60; collectives inside scopes 50 + 30 + 40 = 120."""
    table = roofline_mosaic.segment_seconds(
        [MadeUpPlane(made_up_rows(1)), MadeUpPlane(made_up_rows(2))])
    assert table["mosaic_smooth"] == pytest.approx(150e-9)
    assert table["mosaic_otsu"] == pytest.approx(75e-9)
    assert table["mosaic_watershed"] == pytest.approx(90e-9)
    assert table["mosaic_cc"] == pytest.approx(1.5 * 140e-9)
    assert table["collective"] == pytest.approx(1.5 * 120e-9)
    whole = sum(table[s] for s in roofline_mosaic.SCOPES)
    assert 100 * table["collective"] / whole == pytest.approx(
        100 * 120 / (100 + 50 + 140 + 60))


def test_planes_without_a_scope_read_as_nothing():
    rows = [r for r in made_up_rows() if "mosaic_" not in r[3]]
    assert roofline_mosaic.segment_seconds([MadeUpPlane(rows)]) == {}
    assert roofline_mosaic.segment_seconds([]) == {}


# ------------------------------------------------ the recorded 4-plane trace
@pytest.fixture(scope="module")
def traced(recorded):
    """The recorded run with its trace read, as ``run.py`` leaves it."""
    run, _ = recorded

    class Tracer:
        def file(self):
            return str(TRACE)

    traced_run = PlateRun(CONFIG, run.device, run.field_size, 64)
    traced_run.units = traced_run.traced_units = list(run.units)
    traced_run.tracer = Tracer()
    return traced_run


def test_the_recorded_trace_has_four_device_planes_with_every_scope(traced):
    planes = stages.device_planes(str(TRACE))
    assert len(planes) == 4
    table = roofline_mosaic.segment_seconds(planes)
    assert set(table) == set(roofline_mosaic.SCOPES) | {"collective"}
    assert all(table[s] > 0 for s in roofline_mosaic.SCOPES)
    whole = sum(table[s] for s in roofline_mosaic.SCOPES)
    assert 0 < table["collective"] < whole


def test_device_readers_on_the_recorded_trace_sum_to_the_whole(traced):
    whole = reader("mosaic_segment_device_ms_per_site").read(traced)
    parts = [reader(f"mosaic_{s}_ms_per_site").read(traced)
             for s in ("smooth", "otsu", "cc", "watershed")]
    assert all(p > 0 for p in parts)
    assert sum(parts) == pytest.approx(whole, rel=1e-9)
    assert whole == pytest.approx(
        1e3 * roofline_mosaic.segment_device_s(traced) / 9)
    share = reader("mosaic_collective_share").read(traced)
    assert reader("mosaic_collective_share").UNIT == "%"
    assert 0 < share < 100
    # a 192 x 192 mosaic's 589,824 compulsory bytes over four chips' peak
    roofline = reader("mosaic_segment_roofline").read(traced)
    seconds = roofline_mosaic.segment_device_s(traced)
    assert roofline == pytest.approx(
        100 * 589_824 / (4 * 819e9) / seconds, rel=1e-9)
    assert 0 < roofline < 100


# ------------------------------------------------------- roofline, by hand
def test_compulsory_bytes_of_a_units_segmentation_by_hand():
    """6480 x 6480 = 41,990,400 pixels; two float32 planes read
    (335,923,200 B), two int32 label planes written (335,923,200 B)."""
    assert roofline_mosaic.segment_compulsory_bytes(6480, 6480) == \
        671_846_400 == 4 * 167_961_600
    assert roofline_mosaic.segment_compulsory_bytes(192, 192) == 589_824
    assert roofline_mosaic.segment_compulsory_bytes(
        10, 20, planes_read=1, label_planes=1) == 200 * 8


def test_every_new_metric_has_its_reader_its_unit_and_lists_only_the_cell():
    assert len(NEW) == 14
    for metric in BENCH["per_layer"]:
        if metric["name"] in NEW:
            assert metric["workloads"] == [CELL]
            assert metric["moves"] == "sites_per_s"
            assert reader(metric["name"]).UNIT == metric["unit"]


# --------------------------------------------------------------- the control
@pytest.mark.parametrize("seed", [21, 22])
def test_the_control_is_not_correct_and_the_program_is(seed, tmp_path,
                                                       devices):
    """``benchmark/control.py``'s reading at the rehearsal size: the
    reference's correction in bfloat16 in the program's place fails the
    features by every float limit, the program as it stands none; the
    statistics in bfloat16 lie over the stored tables' limits."""
    from benchmark.drivers import mosaic as driver

    reference = harness.load_module(
        str(REPO / "benchmark" / "configs" / CONFIG["reference"]))
    traffic = json.loads(
        (REPO / "benchmark" / "traffic" / "x4.json").read_text())
    reading = driver.control(seed, CONFIG, traffic, {"platform": "cpu"},
                             str(tmp_path))
    assert reading["stated"]["checks_failed"] == []
    assert reading["answers_failed"] > 0
    assert "features_within_limits" in reading["control"]["checks_failed"]
    for key in ("intensity_mean_sum_rel", "intensity_min_max_rel",
                "std_over_mean"):
        limit = reference.LIMITS[key][0]
        assert reading["stated"]["compared"][key][0] < limit / 3
        assert reading["control"]["compared"][key][0] > 3 * limit
    tables = reference.LIMITS["stored_tables_abs"][0]
    assert min(reading["statistics_in_bfloat16"].values()) > 3 * tables
    assert reading["stated"]["compared"]["stored_tables_abs"][0] < tables / 3
    # the threshold: the control's mask is not the plane over its own cut
    assert "mask_is_the_plane_over_the_cut" in \
        reading["control"]["checks_failed"]
    assert reading["control"]["mask_pixels_outside_band"] > 0
    assert reading["stated"]["mask_pixels_outside_band"] == 0


# ------------------------------------------------- whole runs, own processes
def rehearse(fault: str, trace: int, seed: int, tmp_path) -> tuple:
    # four host devices, few threads and a low priority: the run shares
    # the machine with the other workers of the test run
    env = dict(os.environ, JAX_PLATFORMS="cpu", TMPDIR=str(tmp_path),
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               OMP_NUM_THREADS="2", OPENBLAS_NUM_THREADS="2")
    done = subprocess.run(
        ["nice", "-n", "10", sys.executable,
         str(Path(__file__).with_name("drive_mosaic.py")), fault,
         "--workload", CELL, "--seed", str(seed), "--seconds", "2",
         "--trace", str(trace)],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900)
    lines = [json.loads(line) for line in done.stdout.splitlines()
             if line.startswith("{")]
    assert done.returncode == 1, done.stderr[-2000:]   # a rehearsal
    return lines, done.stderr


@pytest.mark.parametrize("fault, trace, correct", [
    ("none", 0, True), ("none", 1, True), ("seam_join_off", 0, False),
    ("fold_keeps_one_shard", 0, False), ("watershed_halo_off", 0, False)])
def test_a_whole_run_is_correct_only_with_the_seam_join(fault, trace,
                                                        correct, tmp_path):
    """``run.main`` from argument parsing to the result line.  With the
    seam join off, an object across a mesh seam has an id a shard: the
    labels are no longer scipy's of their own foreground, and the
    reference says so whatever the threshold's float details.  With the
    fold merging nothing, the stored statistics are three fields' and
    not nine's: the reference, which makes its own, says so.  With the
    watershed's halo left out every cell stops at the mesh seam and is
    still a sound cell: only the reference's own flood says so."""
    lines, stderr = rehearse(fault, trace, 3000000931 + trace, tmp_path)
    result = lines[-1]["would_have_printed"]
    checks = lines[-2]["checks"]
    assert result["correct"] is correct, checks
    assert result["failed"] == 0 and result["attempted"] >= 9
    assert list(result)[-1] == "numbers_compared"
    compared = result["numbers_compared"]
    assert lines[-2]["mesh_shapes"] == [[2, 2]]
    assert compared["layout_faults"] == [0, 0]
    assert compared["run_faults"] == [0, 0]
    assert lines[-2]["window_compiles"] == 0
    # what the driver's ledger keeps of a line decides, and nothing else:
    # at most 15 numbers, no name over 32 characters, every limit a
    # number, every check the verdict of numbers that are there
    from benchmark.drivers import mosaic as driver

    reference = harness.load_module(
        str(REPO / "benchmark" / "configs" / CONFIG["reference"]))
    assert len(compared) <= 15
    decides = {**reference.DECIDES, **driver.DECIDES}
    assert set(decides) == set(checks)
    assert set().union(*decides.values()) == set(compared)
    for name, (number, limit) in compared.items():
        assert len(name) <= 32, name
        for value in (number, limit):
            assert type(value) in (int, float) and value == value, name
    wrong = {name for name, (number, limit) in compared.items()
             if number > limit}
    for check, names in decides.items():
        assert checks[check] == (not wrong & set(names)), check
    across = lines[-2]["objects_across"]
    if correct:
        assert not wrong and all(checks.values())
        assert min(across.values()) > 0
    elif fault == "fold_keeps_one_shard":
        assert "stored_tables_abs" in wrong
        assert not checks["stored_statistics_are_the_nine_fields"]
        assert checks["nuclei_are_scipy_labels_of_their_foreground"]
    elif fault == "watershed_halo_off":
        assert wrong == {"cells_unlike_flood_rel"}
        assert not checks["cells_are_the_flood_of_their_nuclei"]
        assert checks["cells_hold_their_nuclei"]
        assert min(across.values()) > 0
        told = lines[-2]["flood"]
        assert told["their_pixels_unlike"] == told["pixels_unlike"] > 0
    else:
        assert "seam_faults" in wrong
        assert not checks["nuclei_are_scipy_labels_of_their_foreground"]
    # the last lines of standard error say the same numbers
    tail = stderr.splitlines()[-(len(compared) + 1):]
    for name, (number, limit) in compared.items():
        assert f"compared {name}: {number} limit {limit}" in tail
    if trace:
        # what a host can read; the device's metrics need the chip
        listed = {m["name"] for m in BENCH["per_layer"]
                  if CELL in m.get("workloads", [CELL])}
        assert set(result["metrics"]) <= listed
        for name in ("jterator_ms_per_site", "mosaic_stitch_ms_per_site",
                     "mosaic_measure_ms_per_site", "mosaic_seam_rounds",
                     "mosaic_adopt_steps", "mosaic_roots_max_per_shard",
                     "window_compiles.plate", "warm_compile_s",
                     # the pipelined executor's, as a plate cell reports
                     "device_block_ms_per_site", "persist_ms_per_site",
                     "persist_fetch_ms_per_site",
                     "persist_labels_ms_per_site",
                     "persist_features_ms_per_site",
                     "persist_solidity_ms_per_site", "h2d_mb_per_site",
                     "feature_values_per_site"):
            assert name in result["metrics"], name
        assert result["metrics"]["jit_in_window_ms_per_site"]["value"] == 0
    else:
        assert set(result["metrics"]) == {"sites_per_s", "setup_s"}
