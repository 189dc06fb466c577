"""The readers PR 25 added, on what they read: a trace and a run ledger
recorded together on a TPU v5e (``scripts/record_stage_trace.py``: one
64x64 unit of the ``cp3-plate`` configuration, twelve nuclei a field, so
the batch escalates from rung 8 to rung 16), and on what they must stay
silent about: a ledger and a trace of a program that has no inner spans
and no stage names (PR 23's fixtures).  Nothing here needs a chip."""

import json
import sys
import warnings
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from benchmark import harness, ledger, spans, stages, xplane  # noqa: E402
from benchmark.drivers.plate import PlateRun, Unit  # noqa: E402

BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
CONFIG = json.loads(
    (REPO / "benchmark" / "configs" / "cp3-plate.json").read_text())
TRACE = DATA / "tiny_stages_tpu_v5e.xplane.pb"
META = json.loads((DATA / "stages_unit.json").read_text())
MODULE = CONFIG["batch_program_module"]

NEW = [m["name"] for m in BENCH["per_layer"]
       if m["name"].startswith(("stage_", "plate_steps_device", "corilla_",
                                "illuminati_", "jit_in_window", "persist_e",
                                "persist_f", "persist_l", "h2d_", "decode_"))]
SPAN_READERS = [n for n in NEW if not n.startswith(
    ("stage_", "plate_steps_device", "corilla_", "h2d_", "decode_"))]


class _Tracer:
    """What a reader needs of ``harness.TraceWindow`` after the run."""

    def __init__(self, path, anchor_wall):
        self.path, self.anchor_wall = str(path), anchor_wall

    def file(self):
        return self.path


def _run(events, trace_path=None, anchor_wall=0.0, t0=0.0, t1=0.0,
         sites=9):
    run = PlateRun(CONFIG, META["device"], META["field_size"],
                   META["capacity"])
    unit = Unit("/nowhere", sites)
    unit.t0, unit.t1, unit.events = t0, t1, events
    run.units = [unit]
    if trace_path is not None:
        run.tracer = _Tracer(trace_path, anchor_wall)
        run.trace = xplane.Trace.from_file(str(trace_path))
        run.traced_units = [unit]
    return run


def _read(name, run):
    return harness.load_module(str(
        REPO / "benchmark" / "metrics" / (name + ".py"))).read(run)


@pytest.fixture(scope="module")
def events():
    return ledger.read_events(DATA / "stages_run_ledger.jsonl")


@pytest.fixture(scope="module")
def recorded(events):
    return _run(events, TRACE, META["anchor_wall"], META["t0"], META["t1"],
                META["sites"])


@pytest.fixture(scope="module")
def old_program():
    """PR 23's fixtures: phase spans only, HLO without stage names."""
    return _run(ledger.read_events(DATA / "run_ledger.jsonl"),
                DATA / "tiny_tpu_v5e.xplane.pb", sites=4)


# ------------------------------------------------------------ the fixtures
def test_fixture_is_a_chip_recording_under_200_kb():
    assert META["device"]["platform"] == "tpu"
    assert META["device"]["kind"] == "TPU v5 lite"
    assert TRACE.stat().st_size < 200_000
    assert META["escalations"] >= 1 and META["sites"] == 9


def test_nineteen_new_metrics_each_listed_in_both_cells():
    assert len(NEW) == 19
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    for name in NEW:
        assert by_name[name]["workloads"] == ["cp3-plate.dense",
                                              "cp3-plate.sparse"]
        assert by_name[name]["moves"] == "sites_per_s"
    # appended: the thirteen PR 23 brought keep their places
    assert [m["name"] for m in BENCH["per_layer"]][13:] == NEW


# ----------------------------------------------------------- the wire walk
def test_wire_walk_agrees_with_profiledata_on_every_event():
    from jax.profiler import ProfileData

    (mine,) = stages.device_planes(str(TRACE))
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        (theirs,) = [p for p in ProfileData.from_file(str(TRACE)).planes
                     if p.name == mine.name]
        lines = {line.name: list(line.events) for line in theirs.lines}
        assert set(mine.lines) == {stages.OPS_LINE, stages.MODULES_LINE}
        for name, walked in mine.lines.items():
            assert len(walked) == len(lines[name]) > 0
            for (start, duration, mid), event in zip(walked, lines[name]):
                assert mine.names[mid] == event.name
                # ProfileData gives whole nanoseconds
                assert abs(duration - event.duration_ns) < 1.0
                assert abs(start - event.start_ns) < 1.0


def test_metadata_carries_tf_op_with_the_scopes():
    (plane,) = stages.device_planes(str(TRACE))
    ops = {mid for _, _, mid in plane.lines[stages.OPS_LINE]}
    tf_ops = [plane.stats[mid]["tf_op"] for mid in ops
              if "tf_op" in plane.stats.get(mid, {})]
    assert len(tf_ops) > len(ops) // 2        # copies and iotas carry none
    joined = " ".join(tf_ops)
    for module in ("smooth", "segment_primary", "segment_secondary",
                   "measure_intensity"):
        assert f"({module})/" in joined or f"/{module}/" in joined, module
    # (the pyramid chain runs op by op: its programs are the primitives'
    # own, jit_reduce_window_sum and the like, and carry no scope)
    for scope in ("otsu", "fill_holes", "label", "filter_area", "watershed",
                  "welford", "prep"):
        assert f"/{scope}/" in joined, scope


# ------------------------------------------------------------ stage table
def test_seven_stages_sum_to_the_modules_time(recorded):
    table = stages.stage_table(str(TRACE), MODULE)
    module_s, calls = xplane.module_seconds(recorded.trace, MODULE)
    assert table["executions"] == calls == 1 + META["escalations"]
    # ProfileData gives whole nanoseconds: up to 1 ns an execution apart
    assert table["module_s"] == pytest.approx(module_s, abs=calls * 2e-9)
    assert sum(table["stages"].values()) == pytest.approx(module_s,
                                                          rel=0.01)
    assert table["named"]
    assert 0.0 <= table["stages"]["other"] < module_s / 5
    assert all(table["stages"][s] > 0 for s in
               ("smooth", "threshold", "fill", "label", "watershed",
                "measure"))
    assert sum(table["by_module"].values()) <= module_s * 1.0001


def test_stage_metrics_sum_to_program_ms_per_site(recorded):
    program = _read("program_ms_per_site", recorded)
    values = {s: _read(f"stage_{s}_ms_per_site", recorded)
              for s in stages.STAGES}
    assert all(v is not None and v >= 0 for v in values.values())
    assert sum(values.values()) == pytest.approx(program, rel=0.01)
    assert values["other"] < program / 5


@pytest.mark.parametrize("tf_op, want", [
    ("jit(one_site)/vmap(segment_primary)/label/while/body/min:",
     ("segment_primary", "label")),
    ("jit(one_site)/vmap(segment_primary)/fill_holes/while/cond/ne:",
     ("segment_primary", "fill")),
    ("jit(one_site)/vmap(segment_secondary)/otsu/otsu/reduce_max:",
     ("segment_secondary", "threshold")),
    ("jit(one_site)/vmap(segment_secondary)/watershed/jit(_where)/select_n:",
     ("segment_secondary", "watershed")),
    ("jit(one_site)/vmap(smooth)/smooth/conv_general_dilated:",
     ("smooth", "smooth")),
    ("jit(one_site)/vmap(measure_intensity)/measure_intensity/dot_general:",
     ("measure_intensity", "measure")),
    ("jit(one_site)/vmap(segment_primary)/filter_area/gather:",
     ("segment_primary", "other")),
    ("jit(one_site)/vmap(preprocess)/convert_element_type:",
     ("preprocess", "other")),
    ("jit(one_site)/reduce_max:", ("", "other")),
    ("", ("", "other")),
])
def test_module_and_stage_of_an_op_name(tf_op, want):
    assert stages.module_and_stage(tf_op) == want


def test_self_time_takes_the_nested_events_out():
    # a while 0-100 holding 10-30 and 40-90, which holds 50-60; then 200-250
    events = [(0.0, 100.0, 1), (10.0, 20.0, 2), (40.0, 50.0, 3),
              (50.0, 10.0, 4), (200.0, 50.0, 5)]
    assert stages.self_times(events) == [[30.0, 1], [20.0, 2], [40.0, 3],
                                         [10.0, 4], [50.0, 5]]


# ------------------------------------------------------------ span readers
def test_every_new_reader_reads_the_recording(recorded):
    for name in NEW:
        value = _read(name, recorded)
        assert value is not None and value >= 0.0, name


@pytest.mark.parametrize("name, step, names", [
    ("illuminati_prep_ms_per_site", "illuminati", ("prep",)),
    ("illuminati_pyramid_ms_per_site", "illuminati",
     ("pyramid", "level_fetch")),
    ("illuminati_encode_ms_per_site", "illuminati", ("encode",)),
    ("persist_escalate_ms_per_site", "jterator", ("escalate",)),
    ("persist_fetch_ms_per_site", "jterator", ("fetch",)),
    ("persist_labels_ms_per_site", "jterator", ("write_labels",)),
    ("persist_features_ms_per_site", "jterator", ("write_features",)),
])
def test_span_reader_is_the_spans_summed_over_sites(recorded, events, name,
                                                    step, names):
    by_hand = sum(e["elapsed"] for e in events
                  if e.get("event") == "span" and e.get("step") == step
                  and e.get("span") in names)
    assert by_hand > 0
    assert _read(name, recorded) == pytest.approx(
        1e3 * by_hand / META["sites"])


def test_persist_parts_stay_inside_persist(recorded):
    parts = sum(_read(f"persist_{p}_ms_per_site", recorded)
                for p in ("escalate", "fetch", "labels", "features"))
    assert 0 < parts <= _read("persist_ms_per_site", recorded) * 1.001


def test_corilla_is_its_step_span(recorded, events):
    (step,) = [e for e in events if e.get("event") == "span"
               and e.get("span") == "step" and e.get("step") == "corilla"]
    assert _read("corilla_ms_per_site", recorded) == pytest.approx(
        1e3 * step["elapsed"] / META["sites"])
    assert _read("corilla_ms_per_site", recorded) \
        <= _read("illum_pyramid_ms_per_site", recorded)


def test_h2d_is_the_planes_times_launches(recorded):
    plane = META["field_size"] ** 2 * 2
    launches = 1 + META["escalations"]
    assert _read("h2d_mb_per_site", recorded) == pytest.approx(
        2 * plane * launches / 1e6)


def test_decode_is_pixels_over_the_wait(recorded, events):
    decodes = [e for e in events if e.get("span") == "decode"]
    assert sum(e["pixels"] for e in decodes) \
        == META["sites"] * 5 * META["field_size"] ** 2
    assert _read("decode_mpix_per_s", recorded) == pytest.approx(
        sum(e["pixels"] for e in decodes) / 1e6
        / sum(e["elapsed"] for e in decodes))


def test_jit_in_window_counts_nested_compile_spans_once(recorded, events):
    kinds = [e for e in events if e.get("span") in spans.COMPILE_SPANS]
    union = spans.union_seconds(events, spans.COMPILE_SPANS)
    assert 0 <= union <= sum(e["elapsed"] for e in kinds) + 1e-9
    assert _read("jit_in_window_ms_per_site", recorded) == pytest.approx(
        1e3 * union / META["sites"])
    nested = [
        {"event": "span", "span": "jit_compile", "t0": 10.0, "elapsed": 2.0},
        {"event": "span", "span": "cache_load", "t0": 10.5, "elapsed": 1.0},
        {"event": "span", "span": "jit_trace", "t0": 20.0, "elapsed": 0.5},
        {"event": "span", "span": "prep", "t0": 0.0, "elapsed": 50.0},
    ]
    assert spans.union_seconds(nested, spans.COMPILE_SPANS) \
        == pytest.approx(2.5)


def test_plate_steps_device_time_is_the_modules_inside_the_two_steps(
        recorded, events):
    value = _read("plate_steps_device_ms_per_site", recorded)
    tr = recorded.trace
    all_modules = sum(t1 - t0 for evs in tr.modules.values()
                      for t0, t1, _ in evs)
    program, _ = xplane.module_seconds(tr, MODULE)
    assert 0 < value * META["sites"] / 1e3 <= all_modules - program + 1e-9
    # and none of it is the batch program's: jterator is another step
    shift = tr.anchor_s - META["anchor_wall"]
    steps = [(t0 + shift, t1 + shift) for name, t0, t1
             in ledger.spans(events) if name in ("corilla", "illuminati")]
    assert len(steps) == 2
    inside = [name for evs in tr.modules.values() for t0, _, name in evs
              if any(a <= t0 < b for a, b in steps)]
    assert inside and not any(n.startswith(MODULE) for n in inside)


def test_idle_gaps_are_split_among_the_innermost_spans(recorded, events):
    """``xplane.attribute`` gives a gap to the ONE span that covers most
    of it, so a gap that crosses several inner spans goes to their step.
    ``stages.idle_gap_table`` reads the spans from the trace itself (each
    is a ``TraceAnnotation`` named ``<step>/<span>``) and splits a gap
    among the innermost ones."""
    table = stages.idle_gap_table(str(TRACE), 5)
    assert len(table) == 5
    assert [g["seconds"] for g in table] == sorted(
        (g["seconds"] for g in table), reverse=True)
    tr = recorded.trace
    longest = xplane.idle_gaps(tr, min(t0 for t0, _, _ in
                                       tr.ops["/device:TPU:0"]),
                               max(t1 for _, t1, _ in
                                   tr.ops["/device:TPU:0"]))[0]
    assert table[0]["seconds"] == pytest.approx(longest[1] - longest[0],
                                                rel=1e-6)
    names = {name for g in table for name, _ in g["inside"]}
    known = {f"{e['step']}/{e['span']}" for e in events
             if e.get("event") == "span" and e.get("step")
             and e["span"] not in ("step", "batch")}
    # the program's own spans are there, under the ledger's names
    assert len(names & known) >= 3, sorted(names)
    for gap in table:
        assert gap["inside"], gap
        # one thread cannot be in two innermost spans at once
        engine = sum(s for n, s in gap["inside"]
                     if n.split("/")[0] in ("illuminati", "corilla",
                                            "imextract", "metaconfig"))
        assert engine <= gap["seconds"] * 1.001


# ------------------------------------------- a program without these spans
@pytest.mark.parametrize("name", [n for n in NEW if n not in (
    "corilla_ms_per_site", "plate_steps_device_ms_per_site")])
def test_reader_is_silent_on_a_program_without_its_source(old_program, name):
    """The parent of PR 25 records no inner span, no ``h2d_bytes``, no
    stage name: each reader returns nothing and does not raise."""
    assert _read(name, old_program) is None


def test_step_span_readers_read_any_program(old_program):
    """Step spans and module executions predate PR 25: the two readers
    that need nothing else give a number on the parent too."""
    assert _read("corilla_ms_per_site", old_program) > 0
    assert _read("plate_steps_device_ms_per_site", old_program) >= 0


@pytest.mark.parametrize("name", NEW)
def test_reader_is_silent_outside_a_plate_run(name):
    run = harness.Run("serve", CONFIG, META["device"])
    assert _read(name, run) is None


def test_inner_spans_recorded_is_the_parent_field(events):
    assert spans.inner_spans_recorded(events)
    stripped = [{k: v for k, v in e.items() if k != "parent"}
                for e in events]
    assert not spans.inner_spans_recorded(stripped)
    assert spans.select(events, "jterator", "upload", parent="escalate")
    assert not spans.select(events, "corilla", "upload")
