"""The benchmark's own arithmetic, checked where tier-1 counts it: the
trace reduction on a trace recorded on a TPU v5e, the ledger readers on
recorded ledgers, the compulsory-bytes function against hand-worked
sizes, the percentile rule, the peaks table, and ``BENCHMARK.json``
against the files it names.  Nothing here needs a chip, and no topology
is described while a module is imported."""

import json
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from benchmark import harness, ledger, roofline, stats, xplane  # noqa: E402

BENCH = json.loads((REPO / "BENCHMARK.json").read_text())


# ------------------------------------------------------------ trace reduction
def synthetic_trace():
    """One device: operations at 1-2, 1.5-3 (overlapping), 5-6, and a
    ``while`` container 5-6.5; a module ``jit_one_site(7)`` 1-3 and
    another program 5-6.5."""
    ops = {"/device:TPU:0": [
        (1.0, 2.0, "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)"),
        (1.5, 3.0, "%fusion.2 = f32[8]{0} fusion(f32[8]{0} %p)"),
        (5.0, 6.5, "%while.3 = (s32[]) while((s32[]) %t)"),
        (5.0, 6.0, "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)"),
    ]}
    modules = {"/device:TPU:0": [(1.0, 3.0, "jit_one_site(7)"),
                                 (5.0, 6.5, "jit_prep(9)")]}
    return xplane.Trace(ops, modules, anchor_s=0.5)


def test_union_merges_overlaps_and_keeps_gaps():
    assert xplane.union([(5, 6), (1, 2), (1.5, 3), (2.5, 2.75)]) == \
        [(1, 3), (5, 6)]
    assert xplane.union([]) == []


def test_busy_union_and_idle_share():
    tr = synthetic_trace()
    # 1-3 and 5-6.5 busy inside 0-10: 3.5 s
    assert xplane.busy_seconds(tr, 0.0, 10.0) == pytest.approx(3.5)
    # clipped to 2-6: 2-3 and 5-6
    assert xplane.busy_seconds(tr, 2.0, 6.0) == pytest.approx(2.0)
    assert xplane.idle_share(3.5, 10.0) == pytest.approx(65.0)


def test_busy_is_averaged_over_devices():
    tr = synthetic_trace()
    tr.ops["/device:TPU:1"] = [(0.0, 1.0, "%copy.1 = f32[] copy(f32[] %p)")]
    assert xplane.busy_seconds(tr, 0.0, 10.0) == pytest.approx((3.5 + 1) / 2)


def test_module_seconds_by_prefix():
    tr = synthetic_trace()
    assert xplane.module_seconds(tr, "jit_one_site") == (2.0, 1)
    assert xplane.module_seconds(tr, "jit_absent") == (0.0, 0)


def test_top_operations_skip_containers_and_sum_by_name():
    top = xplane.top_operations(synthetic_trace(), 10)
    assert top == [["fusion.1", pytest.approx(2.0)],
                   ["fusion.2", pytest.approx(1.5)]]


def test_idle_gaps_longest_first_and_attributed_to_innermost_span():
    tr = synthetic_trace()
    gaps = xplane.idle_gaps(tr, 0.0, 10.0)
    assert gaps == [(6.5, 10.0), (3.0, 5.0), (0.0, 1.0)]
    spans = [("jterator", 2.0, 9.0), ("jterator/persist", 6.0, 9.0),
             ("imextract", 0.0, 1.2), ("illuminati", 3.2, 4.8)]
    named = xplane.gap_breakdown(tr, 0.0, 10.0, spans, 5)
    # 6.5-10: persist and its step both cover 2.5 s; the inner one wins.
    # 3-5: the step covers all 2.0, illuminati only 1.6.
    assert named == [["jterator/persist", pytest.approx(3.5)],
                     ["jterator", pytest.approx(2.0)],
                     ["imextract", pytest.approx(1.0)]]
    assert xplane.attribute((20.0, 21.0), spans) == "outside_spans"


@pytest.fixture(scope="module")
def recorded_trace():
    return xplane.Trace.from_file(str(DATA / "tiny_tpu_v5e.xplane.pb"))


def test_recorded_tpu_trace_planes_lines_and_anchor(recorded_trace):
    """Recorded on a TPU v5e (PR 23): two jitted programs, three calls
    each, sleeps between them."""
    tr = recorded_trace
    assert tr.devices == ["/device:TPU:0"]
    assert len(tr.modules["/device:TPU:0"]) == 6
    assert len(tr.ops["/device:TPU:0"]) == 24
    assert tr.anchor_s == pytest.approx(0.0466, abs=0.002)
    sort_s, calls = xplane.module_seconds(
        tr, "jit__lambda(13145869781090519593)")
    assert calls == 3 and sort_s == pytest.approx(0.838e-3, rel=0.01)


def test_recorded_tpu_trace_busy_is_the_programs_time(recorded_trace):
    tr = recorded_trace
    modules_s = sum(t1 - t0 for t0, t1, _ in tr.modules["/device:TPU:0"])
    busy = xplane.busy_seconds(tr, 0.0, 1.0)
    assert 0.0 < busy <= modules_s * 1.001
    assert busy == pytest.approx(modules_s, rel=0.05)
    # three rounds with 20 and 30 ms sleeps: the device is idle
    first = min(t0 for t0, _, _ in tr.ops["/device:TPU:0"])
    last = max(t1 for _, t1, _ in tr.ops["/device:TPU:0"])
    assert xplane.idle_share(xplane.busy_seconds(tr, first, last),
                             last - first) > 98.0
    assert xplane.top_operations(tr, 1)[0][0] == "sort.6"


# ------------------------------------------------------------ ledger readers
@pytest.fixture(scope="module")
def run_events():
    return ledger.read_events(DATA / "run_ledger.jsonl")


def test_run_ledger_steps_and_phases(run_events):
    steps = ledger.step_seconds(run_events)
    assert list(steps) == ["metaconfig", "imextract", "corilla",
                           "illuminati", "jterator"]
    assert steps["illuminati"] == pytest.approx(2.9396800994873047)
    phases = ledger.phase_seconds(run_events, "jterator")
    assert phases == {"prefetch_wait": 0.0, "dispatch": 2.5687,
                      "device_block": 0.0003, "persist": 0.0319}


def test_run_ledger_engine_resolution_and_events(run_events):
    resolved = ledger.resolved_by_the_engine(run_events)
    assert resolved["batches"] == 1 and resolved["batch_size"] == 4
    assert resolved["routed_capacities"] == [8]
    assert ledger.escalations(run_events) == 0
    assert ledger.forbidden(run_events) == []
    bad = run_events + [{"event": "batch_failed", "step": "jterator"}]
    assert ledger.forbidden(bad) == ["batch_failed"]


def test_run_ledger_spans_are_named_by_step_and_phase(run_events):
    names = [name for name, _, _ in ledger.spans(run_events)]
    assert "imextract" in names and "jterator/persist" in names
    assert "run" not in names and "jterator/batch" not in names
    name, t0, t1 = next(s for s in ledger.spans(run_events)
                        if s[0] == "jterator/dispatch")
    assert t1 - t0 == pytest.approx(2.568676)


def test_escalations_are_summed_over_batches():
    events = [{"event": "batch_done", "step": "jterator",
               "result": {"bucket_escalations": n}} for n in (3, 0, 4)]
    events.append({"event": "batch_done", "step": "corilla",
                   "result": {"bucket_escalations": 9}})
    assert ledger.escalations(events) == 7


def test_serve_ledger_spans():
    events = ledger.read_events(DATA / "serve_ledger.jsonl")
    assert len(ledger.span_durations(events, "job")) == 3
    assert ledger.span_durations(events, "queue_wait")[0] == \
        pytest.approx(0.103133)
    done = [e for e in events if e.get("event") == "job_done"]
    assert [e["cache"] for e in done] == ["miss", "miss", "miss"]


def test_torn_ledger_line_is_skipped(tmp_path):
    path = tmp_path / "ledger.jsonl"
    path.write_text('{"event": "a"}\n{"event": "b", "t\n[1, 2]\n')
    assert ledger.read_events(path) == [{"event": "a"}]
    assert ledger.read_events(tmp_path / "absent.jsonl") == []


# ------------------------------------------------- roofline, peaks, percentile
def test_compulsory_bytes_of_a_cp3_field():
    """By hand: 2160 x 2160 = 4,665,600 pixels; two uint16 channels read
    (18,662,400 B), two int32 label planes written (37,324,800 B), two
    object types x 1024 rows x 5 float32 features (40,960 B)."""
    assert roofline.compulsory_bytes(
        batch=1, height=2160, width=2160, channels_read=2, label_planes=2,
        capacity=1024, features_per_object=5) == 56_028_160
    assert roofline.compulsory_bytes(
        batch=4, height=64, width=64, channels_read=1, label_planes=1,
        capacity=16, features_per_object=5) == 4 * (8192 + 16384 + 320)


def test_roofline_share_is_bound_by_the_larger_floor():
    peak = roofline.peaks("TPU v5 lite")
    # 56 MB at 819 GB/s is 68.4 us; over a 94 ms call that is 0.0728 %
    share, bound = roofline.roofline_share(56_028_160, 0.0, 0.094, peak)
    assert bound == "memory" and share == pytest.approx(0.07278, rel=1e-3)
    share, bound = roofline.roofline_share(1e6, 197e12, 2.0, peak)
    assert bound == "compute" and share == pytest.approx(50.0)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="TPU v9"):
        roofline.peaks("TPU v9")
    assert roofline.peaks("TPU v5 lite")["bytes_per_s"] == 819e9


@pytest.mark.parametrize("n, want", [(10, 50.0), (20, 50.0), (100, 90.0),
                                     (200, 95.0), (1000, 99.0)])
def test_highest_percentile_with_ten_samples_beyond(n, want):
    assert stats.highest_supported_percentile(n) == pytest.approx(want)


def test_percentile_and_tail():
    xs = list(range(1, 101))            # 1..100
    assert stats.percentile(xs, 50.0) == pytest.approx(50.5)
    assert stats.percentile(xs, 95.0) == pytest.approx(95.05)
    assert stats.percentile([3.0], 95.0) == 3.0
    value, supported = stats.tail(xs)
    assert value == pytest.approx(95.05) and supported == pytest.approx(90.0)
    with pytest.raises(ValueError):
        stats.percentile([], 50.0)


def test_spread_is_the_quartile_distance_over_the_median():
    assert stats.iqr_share([10, 10, 10, 10, 10, 10]) == 0.0
    # statistics.quantiles(n=4) of 1..6: q1 1.75, q3 5.25, median 3.5
    assert stats.iqr_share([1, 2, 3, 4, 5, 6]) == pytest.approx(1.0)


# ----------------------------------------------- BENCHMARK.json and its files
def test_every_named_file_exists_and_is_under_paths():
    paths = BENCH["paths"]
    for config in BENCH["configs"]:
        assert any(config["file"].startswith(p + "/") for p in paths)
        body = json.loads((REPO / config["file"]).read_text())
        assert body["name"] == config["name"]
        assert sorted(body["reduced"]) == sorted(config["reduced"])
        assert (REPO / "benchmark" / "drivers"
                / (body["driver"] + ".py")).exists()
        assert (REPO / "benchmark" / "configs" / body["reference"]).exists()
    for cell in BENCH["workloads"]:
        assert (REPO / "benchmark" / "traffic"
                / (cell["traffic"] + ".json")).exists()
        assert cell["name"] == f"{cell['config']}.{cell['traffic']}"


@pytest.mark.parametrize("metric", BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_per_layer_metric_has_a_reader_and_moves_a_reported_metric(metric):
    reader = harness.load_module(str(
        REPO / "benchmark" / "metrics" / (metric["name"] + ".py")))
    assert reader.UNIT == metric["unit"] and callable(reader.read)
    end_to_end = {m["name"]: m for m in BENCH["end_to_end"]}
    moved = end_to_end[metric["moves"]]
    cells = [w["name"] for w in BENCH["workloads"]]
    for cell in metric.get("workloads", cells):
        assert cell in moved.get("workloads", cells)


def test_every_cell_reports_setup_another_metric_and_a_layer():
    for cell in (w["name"] for w in BENCH["workloads"]):
        names = [m["name"] for m in BENCH["end_to_end"]
                 if cell in m.get("workloads", [cell])]
        assert "setup_s" in names and len(names) >= 2
        assert any(cell in m.get("workloads", [cell])
                   for m in BENCH["per_layer"])


# ------------------------------------------------------------- traffic, plate
def test_query_stream_is_the_seeds_alone():
    import numpy as np

    from benchmark.drivers import serve

    traffic = harness.load_json(str(REPO), "benchmark", "traffic",
                                "query.json")
    columns = {o: [f"Intensity_{s}_X" for s in
                   ("max", "mean", "min", "std", "sum")] + ["Other"]
               for o in traffic["objects"]}

    def stream(seed):
        rng = np.random.default_rng(seed)
        enumerated = serve.payloads(traffic, columns, rng, on_chip=True)
        s = serve.Stream(enumerated, traffic, rng)
        return enumerated, [s.next() for _ in range(400)]

    enumerated, jobs = stream(3000000001)
    assert len(enumerated) == 60
    assert len({json.dumps(p, sort_keys=True) for p in enumerated}) == 60
    assert stream(3000000001)[1] == jobs and stream(5)[1] != jobs
    seen, repeats = set(), 0
    for _, payload, repeat in jobs:
        key = json.dumps(payload, sort_keys=True)
        assert (key in seen) == repeat    # a repeat is exact, a fresh job new
        seen.add(key)
        repeats += repeat
        assert len(payload["features"]) == 3 and payload["index"] == "brute"
    assert 0.18 < repeats / len(jobs) < 0.32


def test_synthetic_field_is_seeded_and_sized():
    import numpy as np

    from benchmark import plate

    channels = ["DAPI", "Actin", "Tubulin", "ER", "Mito"]
    a = plate.synth_field(np.random.default_rng(3), 64, 5, channels)
    b = plate.synth_field(np.random.default_rng(3), 64, 5, channels)
    assert list(a) == channels
    for c in channels:
        assert a[c].dtype == np.uint16 and a[c].shape == (64, 64)
        assert np.array_equal(a[c], b[c])
    assert a["DAPI"].max() > 2000           # a nucleus over the noise floor
    assert plate.parse_range("350-650") == (350, 650)
    assert plate.well_names(2) == ["A01", "A02"]
    assert plate.well_names(25)[-1] == "B01"
