"""What PR 27 added to the benchmark, on what it reads: the bytes of one
``measure_texture`` call from shapes, the four per-module readers and the
texture roofline on a trace of the config-4 program recorded on a TPU v5e
(``scripts/record_stage_trace.py chiprun_out/stages cp4-plate``: one 64x64
unit, twelve nuclei a field, rung 8 then 16), the two persist readers on
its run ledger, and their silence on programs that lack the names, the
span or the attributes.  Nothing here needs a chip."""

import json
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from benchmark import harness, ledger, roofline_measure, stages  # noqa: E402
from benchmark.drivers.plate import PlateRun, Unit  # noqa: E402

BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
CONFIG = json.loads(
    (REPO / "benchmark" / "configs" / "cp4-plate.json").read_text())
CP3 = json.loads(
    (REPO / "benchmark" / "configs" / "cp3-plate.json").read_text())
TRACE = DATA / "tiny_cp4-plate_stages_tpu_v5e.xplane.pb"
META = json.loads((DATA / "cp4-plate_stages_unit.json").read_text())
CELL = "cp4-plate.dense"
MODULES = ("measure_intensity", "measure_morphology", "measure_texture",
           "measure_zernike")
NEW = [m + "_ms_per_site" for m in MODULES] + [
    "measure_texture_roofline", "persist_solidity_ms_per_site",
    "feature_values_per_site"]


class _Tracer:
    def __init__(self, path):
        self.path, self.anchor_wall = str(path), 0.0

    def file(self):
        return self.path


def _run(config, events, trace_path=None, sites=9, meta=META):
    run = PlateRun(config, meta["device"], meta["field_size"],
                   meta["capacity"])
    unit = Unit("/nowhere", sites)
    unit.events = events
    run.units = [unit]
    if trace_path is not None:
        run.tracer = _Tracer(trace_path)
        run.traced_units = [unit]
    return run


def _read(name, run):
    return harness.load_module(str(
        REPO / "benchmark" / "metrics" / (name + ".py"))).read(run)


@pytest.fixture(scope="module")
def events():
    return ledger.read_events(DATA / "cp4-plate_stages_run_ledger.jsonl")


@pytest.fixture(scope="module")
def recorded(events):
    return _run(CONFIG, events, TRACE, META["sites"])


@pytest.fixture(scope="module")
def cp3_recorded():
    """PR 25's recording of the config-3 program: stage names, one
    measure module, no ``rows`` on its spans."""
    meta = json.loads((DATA / "stages_unit.json").read_text())
    return _run(CP3, ledger.read_events(DATA / "stages_run_ledger.jsonl"),
                DATA / "tiny_stages_tpu_v5e.xplane.pb", meta["sites"], meta)


@pytest.fixture(scope="module")
def old_program():
    """PR 23's fixtures: no inner spans, HLO without scope names."""
    meta = dict(META, field_size=64, capacity=16)
    return _run(CP3, ledger.read_events(DATA / "run_ledger.jsonl"),
                DATA / "tiny_tpu_v5e.xplane.pb", 4, meta)


# ------------------------------------------------------------- BENCHMARK.json
def test_fixture_is_a_chip_recording_of_the_config_4_program():
    assert META["device"]["platform"] == "tpu"
    assert META["device"]["kind"] == "TPU v5 lite"
    assert TRACE.stat().st_size < 400_000
    assert META["escalations"] >= 1 and META["sites"] == 9


@pytest.mark.parametrize("name", NEW)
def test_new_metric_is_listed_for_the_new_cell_alone(name):
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    assert by_name[name]["workloads"] == [CELL]
    assert by_name[name]["moves"] == "sites_per_s"
    assert [m["name"] for m in BENCH["per_layer"]][-7:] == NEW


def test_new_cell_is_appended_to_every_plate_metric_and_to_warm_compile():
    assert BENCH["workloads"][-1]["name"] == CELL
    assert BENCH["workloads"][-1]["chips"] == 1
    assert BENCH["configs"][-1]["reduced"] == ["wells_per_submit"]
    for metric in BENCH["per_layer"][:-7] + BENCH["end_to_end"][:1]:
        assert metric["workloads"] == ["cp3-plate.dense", "cp3-plate.sparse",
                                       CELL], metric["name"]


def test_configuration_is_config_4_at_acquisition_geometry():
    assert (CONFIG["field_size"], CONFIG["max_objects"]) == (2160, 1024)
    assert CONFIG["channels_read_by_pipeline"] == CONFIG["channels"]
    assert len(CONFIG["channels"]) == 5 and CONFIG["fields_per_well"] == 9
    assert (CONFIG["texture_levels"], CONFIG["zernike_degree"]) == (16, 6)
    assert list(CONFIG["reduced"]) == ["wells_per_submit"]
    modules = [m["handles"]["module"] for m in CONFIG["pipeline"]["pipeline"]]
    assert len(modules) == 17
    assert [modules.count(m) for m in MODULES] == [10, 2, 1, 1]


def test_configurations_pipeline_is_full_feature_description_at_its_defaults(
        monkeypatch):
    from tmlibrary_tpu import benchmarks
    from tmlibrary_tpu.jterator.description import PipelineDescription

    seen = {}
    original = PipelineDescription.from_dict.__func__
    monkeypatch.setattr(
        PipelineDescription, "from_dict",
        classmethod(lambda cls, d, base_dir=None: (
            seen.update(d=d), original(cls, d, base_dir))[1]))
    benchmarks.full_feature_description()
    assert CONFIG["pipeline"] == json.loads(json.dumps(seen["d"]))


# ----------------------------------------------------------- bytes from shapes
def test_texture_bytes_of_a_field_at_capacity_1024():
    # 2160 x 2160 = 4,665,600 pixels: an int32 label plane and a float32
    # intensity plane read once = 37,324,800; 13 x 1,024 floats written
    # = 53,248
    assert roofline_measure.texture_compulsory_bytes(2160, 2160, 1024) \
        == 37_324_800 + 53_248 == 37_378_048


def test_texture_bytes_follow_the_shapes_and_nothing_else():
    small = roofline_measure.texture_compulsory_bytes(64, 64, 16)
    assert small == 64 * 64 * 8 + 13 * 16 * 4
    assert roofline_measure.texture_compulsory_bytes(64, 64, 8) \
        == small - 13 * 8 * 4


# ------------------------------------------------------ the per-module readers
def test_module_calls_sum_to_the_stage_tables_by_module(recorded):
    calls = roofline_measure.module_call_seconds(
        str(TRACE), CONFIG["batch_program_module"])
    table = stages.stage_table(str(TRACE), CONFIG["batch_program_module"])
    for module in MODULES:
        want = sum(s for (m, _), s in table["by_module"].items()
                   if m == module)
        got = sum(sum(c) for c in calls[module].values())
        assert want > 0 and got == pytest.approx(want, rel=1e-9)
        # every execution of every rung is there, a zero where nothing ran
        assert sum(len(c) for c in calls[module].values()) \
            == table["executions"]


def test_each_measure_module_is_a_by_module_key_of_the_stage_table():
    table = stages.stage_table(str(TRACE), CONFIG["batch_program_module"])
    keys = {module for module, _ in table["by_module"]}
    assert set(MODULES) <= keys
    # the scopes this PR put under the families' own
    assert ("measure_texture", "measure") in table["by_module"]
    assert ("measure_zernike", "measure") in table["by_module"]
    assert ("measure_morphology", "measure") in table["by_module"]


@pytest.mark.parametrize("module", MODULES)
def test_module_reader_is_the_modules_seconds_over_sites(recorded, module):
    table = stages.stage_table(str(TRACE), CONFIG["batch_program_module"])
    want = 1e3 * sum(s for (m, _), s in table["by_module"].items()
                     if m == module) / META["sites"]
    assert _read(module + "_ms_per_site", recorded) == pytest.approx(want)


def test_four_modules_and_the_rest_make_the_program(recorded):
    program = 1e3 * stages.stage_table(
        str(TRACE), CONFIG["batch_program_module"])["module_s"] \
        / META["sites"]
    measured = sum(_read(m + "_ms_per_site", recorded) for m in MODULES)
    assert 0 < measured < program


def test_texture_roofline_is_the_bytes_over_the_slowest_rungs_call(recorded):
    calls = roofline_measure.module_call_seconds(
        str(TRACE), CONFIG["batch_program_module"])["measure_texture"]
    assert len(calls) >= 2      # rung 8, then 16
    import statistics

    call_s = max(statistics.median(c) for c in calls.values())
    want = 100.0 * roofline_measure.texture_compulsory_bytes(
        META["field_size"], META["field_size"], META["capacity"]) \
        / 819e9 / call_s
    share = _read("measure_texture_roofline", recorded)
    assert share == pytest.approx(want) and 0 < share < 100


def test_config_3_program_has_one_measure_module(cp3_recorded):
    assert _read("measure_intensity_ms_per_site", cp3_recorded) > 0
    for module in MODULES[1:]:
        assert _read(module + "_ms_per_site", cp3_recorded) is None
    assert _read("measure_texture_roofline", cp3_recorded) is None


# ------------------------------------------------------- the persist readers
def test_solidity_reader_is_the_spans_over_sites(recorded, events):
    spans_ = [e for e in events if e.get("event") == "span"
              and e.get("span") == "solidity"]
    assert spans_ and all(e["parent"] == "persist" for e in spans_)
    want = 1e3 * sum(e["elapsed"] for e in spans_) / META["sites"]
    assert _read("persist_solidity_ms_per_site", recorded) \
        == pytest.approx(want)


def test_feature_values_are_rows_times_columns_over_sites(recorded, events):
    written = [e for e in events if e.get("event") == "span"
               and e.get("span") == "write_features"]
    assert written and all("rows" in e and "columns" in e for e in written)
    # nuclei carry 55 columns, cells 52; one span each a batch
    assert {e["columns"] for e in written} == {55, 52}
    want = sum(e["rows"] * e["columns"] for e in written) / META["sites"]
    assert _read("feature_values_per_site", recorded) == pytest.approx(want)
    assert want > 0


def test_feature_values_of_made_up_spans():
    span = {"event": "span", "step": "jterator", "span": "write_features",
            "parent": "persist", "t0": 0.0, "elapsed": 0.1}
    run = _run(CONFIG, [dict(span, rows=10, columns=55),
                        dict(span, rows=12, columns=52)], sites=2)
    assert _read("feature_values_per_site", run) == (550 + 624) / 2


# ------------------------------------------------ silence, where nothing is
@pytest.mark.parametrize("name", NEW)
def test_reader_is_silent_on_a_program_without_its_source(old_program, name):
    assert _read(name, old_program) is None


@pytest.mark.parametrize("name", ["persist_solidity_ms_per_site",
                                  "feature_values_per_site"])
def test_persist_readers_are_silent_on_the_config_3_program(cp3_recorded,
                                                            name):
    # config 3 measures no morphology, and PR 25's spans carry no rows
    assert _read(name, cp3_recorded) is None


@pytest.mark.parametrize("name", NEW)
def test_reader_is_silent_outside_a_plate_run(name):
    run = harness.Run("serve", CONFIG, META["device"])
    assert _read(name, run) is None
