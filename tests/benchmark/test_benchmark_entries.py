"""What ``BENCHMARK.json`` has to say, stated by name and never by place:
which cells each metric lists at the least, which it may not list, what
the entries' files are.  A later PR appends a cell, joins it to some of
the lists and brings metrics of its own; every check here is made on the
file as committed, on a copy with the pending serve entries
(``benchmark/pending/cp3-serve.entries.json``: the cell is built and
measured, and stays out until its store's size and its traffic are
settled, PERF.md section 7) laid after it, AND on a copy
with a made-up cell and metric appended after those — so a check that
would stop an append fails here first.  Nothing here needs a chip."""

import copy
import json
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from benchmark import harness, ledger  # noqa: E402
from benchmark.drivers import serve  # noqa: E402

CP3 = ["cp3-plate.dense", "cp3-plate.sparse"]
CP4 = "cp4-plate.dense"
SERVE = "cp3-serve.query"
PLATE = CP3 + [CP4]

#: the thirteen per-layer metrics of PR 23's plate cells
PR23 = ["engine_other_ms_per_site", "ingest_ms_per_site",
        "illum_pyramid_ms_per_site", "jterator_ms_per_site",
        "device_block_ms_per_site", "persist_ms_per_site",
        "escalations_per_site", "program_ms_per_site",
        "batch_program_roofline", "device_idle_share.plate",
        "peak_hbm_gb.plate", "warm_compile_s", "window_compiles.plate"]
#: the nineteen of PR 25: spans, counters and scopes inside the steps
PR25 = ["stage_smooth_ms_per_site", "stage_threshold_ms_per_site",
        "stage_fill_ms_per_site", "stage_label_ms_per_site",
        "stage_watershed_ms_per_site", "stage_measure_ms_per_site",
        "stage_other_ms_per_site", "plate_steps_device_ms_per_site",
        "corilla_ms_per_site", "illuminati_prep_ms_per_site",
        "illuminati_pyramid_ms_per_site", "illuminati_encode_ms_per_site",
        "jit_in_window_ms_per_site", "persist_escalate_ms_per_site",
        "persist_fetch_ms_per_site", "persist_labels_ms_per_site",
        "persist_features_ms_per_site", "h2d_mb_per_site",
        "decode_mpix_per_s"]
#: the seven of PR 27: what only a config-4 program has
PR27 = ["measure_intensity_ms_per_site", "measure_morphology_ms_per_site",
        "measure_texture_ms_per_site", "measure_zernike_ms_per_site",
        "measure_texture_roofline", "persist_solidity_ms_per_site",
        "feature_values_per_site"]
PENDING = "benchmark/pending/cp3-serve.entries.json"
#: the serve cell's (pending): three end to end, six per layer
SERVE_END_TO_END = ["jobs_per_s", "job_p95_s"]      # job_p50_s: held back
SERVE_PER_LAYER = {"pickup_p50_ms": "job_p95_s",
                   "queue_wait_p95_ms": "job_p95_s",
                   "query_exec_p50_ms": "jobs_per_s",
                   "query_cache_hit_share": "jobs_per_s",
                   "device_idle_share.serve": "jobs_per_s",
                   "window_compiles.serve": "jobs_per_s"}

MADE_UP_CELL = "cp3-plate.made-up"
MADE_UP_METRIC = "made_up_ms_per_site"


def committed(tmp_path_factory) -> tuple:
    return json.loads((REPO / "BENCHMARK.json").read_text()), REPO


def with_pending(tmp_path_factory) -> tuple:
    """The file once the serve cell is entered: its entries laid after
    the committed ones, as ``run.py --entries`` lays them."""
    return harness.load_benchmark(PENDING), REPO


def appended(tmp_path_factory) -> tuple:
    """What a later PR may do: a cell at the end of ``workloads``, joined
    to ``sites_per_s`` and to SOME of the plate metrics, and a per-layer
    metric of its own at the end of ``per_layer`` — in a copy of the
    checkout's benchmark, with the two files the entries name."""
    root = tmp_path_factory.mktemp("appended")
    shutil.copytree(REPO / "benchmark", root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = harness.load_benchmark(PENDING)
    bench["workloads"].append({
        "name": MADE_UP_CELL, "config": "cp3-plate", "traffic": "made-up",
        "chips": 4, "why": "a cell a later PR appends"})
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if metric["name"] in ["sites_per_s"] + PR23[:5] + PR25[:3]:
            metric["workloads"].append(MADE_UP_CELL)
    bench["per_layer"].append({
        "name": MADE_UP_METRIC, "unit": "ms/site", "better": "lower",
        "source": "program_span", "layer": "a layer a later PR names",
        "moves": "sites_per_s", "workloads": [MADE_UP_CELL]})
    (root / "benchmark" / "traffic" / "made-up.json").write_text(
        (REPO / "benchmark" / "traffic" / "dense.json").read_text())
    (root / "benchmark" / "metrics" / (MADE_UP_METRIC + ".py")).write_text(
        'UNIT = "ms/site"\n\n\ndef read(run):\n    return None\n')
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return bench, root


@pytest.fixture(scope="module", params=[committed, with_pending, appended],
                ids=["as_committed", "with_the_pending_serve_cell",
                     "with_a_made_up_cell_and_metric_appended"])
def checkout(request, tmp_path_factory):
    """``(bench, root)``: ``BENCHMARK.json`` and the directory that holds
    the files it names."""
    return request.param(tmp_path_factory)


@pytest.fixture(scope="module", params=[with_pending, appended],
                ids=["with_the_pending_serve_cell",
                     "with_a_made_up_cell_and_metric_appended"])
def entered(request, tmp_path_factory):
    """As ``checkout``, the variants that hold the serve cell."""
    return request.param(tmp_path_factory)


def metric_of(bench: dict, name: str) -> dict:
    found = [m for m in bench["end_to_end"] + bench["per_layer"]
             if m["name"] == name]
    assert len(found) == 1, name
    return found[0]


# ------------------------------------------- every entry against its files
def yardstick_on(bench: dict, root: Path):
    """``test_benchmark_yardstick.py``, a copy of the module of its own,
    reading ``bench`` and the files under ``root``."""
    module = harness.load_module(str(
        Path(__file__).resolve().parent / "test_benchmark_yardstick.py"))
    module.BENCH, module.REPO = bench, root
    return module


def check_names_and_bounds(bench: dict) -> None:
    for group in ("configs", "workloads"):
        names = [e["name"] for e in bench[group]]
        assert len(set(names)) == len(names)
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(set(names)) == len(names)
    for metric in bench["end_to_end"]:
        assert 0.01 <= metric["bound"] <= 0.25, metric["name"]
        assert metric["source"] in ("host_clock", "device_trace")
    for metric in bench["per_layer"]:
        assert "bound" not in metric and metric["better"] in ("lower",
                                                              "higher")
    assert "workloads" not in metric_of(bench, "setup_s")
    four = sum(1 for w in bench["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(bench["workloads"]) // 2)


def test_the_yardsticks_checks_of_the_file_pass(checkout):
    """Every test of ``test_benchmark_yardstick.py`` that reads
    ``BENCHMARK.json``, run on this checkout's: on the copy with the
    made-up cell and metric appended they have to pass as they stand."""
    bench, root = checkout
    yardstick = yardstick_on(bench, root)
    yardstick.test_every_named_file_exists_and_is_under_paths()
    yardstick.test_every_cell_reports_setup_another_metric_and_a_layer()
    assert len(bench["per_layer"]) >= len(PR23 + PR25 + PR27)
    for metric in bench["per_layer"]:
        yardstick.\
            test_per_layer_metric_has_a_reader_and_moves_a_reported_metric(
                metric)


def test_a_check_of_the_yardstick_fails_on_an_entry_without_its_file(
        checkout):
    bench, root = checkout
    broken = copy.deepcopy(bench)
    broken["per_layer"].append(dict(broken["per_layer"][-1],
                                    name="no_such_reader"))
    yardstick = yardstick_on(broken, root)
    with pytest.raises(FileNotFoundError):
        yardstick.\
            test_per_layer_metric_has_a_reader_and_moves_a_reported_metric(
                broken["per_layer"][-1])
    broken["workloads"].append(dict(broken["workloads"][0],
                                    name="cp3-plate.absent",
                                    traffic="absent"))
    with pytest.raises(AssertionError):
        yardstick.test_every_named_file_exists_and_is_under_paths()


def test_names_are_unique_and_bounds_inside_the_contract(checkout):
    check_names_and_bounds(checkout[0])


def test_the_append_was_made(checkout, request):
    bench, root = checkout
    appended_here = "appended" in request.node.callspec.id
    assert (bench["workloads"][-1]["name"] == MADE_UP_CELL) == appended_here
    assert (bench["per_layer"][-1]["name"] == MADE_UP_METRIC) == \
        appended_here
    if "pending" not in request.node.callspec.id:
        assert json.loads((root / "BENCHMARK.json").read_text()) == bench


# ------------------------------------------------- which metric lists what
@pytest.mark.parametrize("name", PR23 + PR25 + ["sites_per_s"])
def test_plate_metric_lists_at_least_the_three_plate_cells(checkout, name):
    """At least: a later cell is free to join a list, and to join only
    some of them."""
    metric = metric_of(checkout[0], name)
    assert set(PLATE) <= set(metric["workloads"])
    assert SERVE not in metric["workloads"]
    assert metric.get("moves", "sites_per_s") in ("sites_per_s", "setup_s")


@pytest.mark.parametrize("name", PR25)
def test_inside_span_metric_lists_both_cp3_cells_and_moves_sites_per_s(
        checkout, name):
    metric = metric_of(checkout[0], name)
    assert set(CP3) <= set(metric["workloads"])
    assert metric["moves"] == "sites_per_s"


@pytest.mark.parametrize("name", PR27)
def test_measure_module_metric_lists_cp4_and_no_cp3_cell(checkout, name):
    """A config-3 program has one measure module and no ``solidity``
    span: the reader would find nothing there."""
    metric = metric_of(checkout[0], name)
    assert CP4 in metric["workloads"]
    assert not set(CP3) & set(metric["workloads"])
    assert metric["moves"] == "sites_per_s"


@pytest.mark.parametrize("name", SERVE_END_TO_END + list(SERVE_PER_LAYER))
def test_serve_metric_lists_the_serve_cell_and_no_plate_cell(entered, name):
    bench = entered[0]
    metric = metric_of(bench, name)
    assert SERVE in metric["workloads"]
    drivers = {c["name"]: json.loads((REPO / c["file"]).read_text())["driver"]
               for c in bench["configs"]}
    by_name = {w["name"]: w for w in bench["workloads"]}
    for cell in metric["workloads"]:
        assert drivers[by_name[cell]["config"]] == "serve", cell
    if name in SERVE_PER_LAYER:
        assert metric["moves"] == SERVE_PER_LAYER[name]
    else:
        assert metric in bench["end_to_end"] and metric["bound"] <= 0.25


def test_cells_and_configurations_found_by_name(checkout):
    bench = checkout[0]
    cells = {w["name"]: w for w in bench["workloads"]}
    configs = {c["name"]: c for c in bench["configs"]}
    assert set(PLATE) <= set(cells)
    assert cells[CP4]["chips"] == 1 and cells[CP4]["config"] == "cp4-plate"
    assert configs["cp4-plate"]["reduced"] == ["wells_per_submit"]
    assert configs["cp3-plate"]["reduced"] == ["wells_per_submit"]
    if SERVE in cells:
        assert cells[SERVE] == {
            "name": SERVE, "config": "cp3-serve", "traffic": "query",
            "chips": 1, "why": cells[SERVE]["why"]}
        assert configs["cp3-serve"]["reduced"] == ["store_objects"]
    assert bench["run_seconds"] == 40
    assert bench["command"] == ["python3", "benchmark/run.py"]


def test_run_py_gives_each_cell_the_metrics_that_list_it(entered):
    saved = list(sys.path)      # run.py puts its checkout first on import
    try:
        run_py = harness.load_module(str(REPO / "benchmark" / "run.py"))
    finally:
        sys.path[:] = saved
    bench = entered[0]
    assert run_py.metric_names(bench, "end_to_end", SERVE) == \
        ["setup_s"] + SERVE_END_TO_END
    assert run_py.metric_names(bench, "end_to_end", CP4) == \
        ["sites_per_s", "setup_s"]
    per_layer = run_py.metric_names(bench, "per_layer", SERVE)
    assert per_layer == list(SERVE_PER_LAYER)
    assert set(PR27) <= set(run_py.metric_names(bench, "per_layer", CP4))
    assert not set(PR27) & set(run_py.metric_names(bench, "per_layer",
                                                   CP3[0]))


# ------------------------------------------------ what is pending, and why
def test_the_serve_cell_is_pending_and_not_in_the_committed_file():
    """It stays out until its store's size and its traffic are settled
    (PERF.md section 7); its entries wait where ``run.py --entries``
    finds them."""
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    pending = json.loads((REPO / PENDING).read_text())
    assert SERVE not in [w["name"] for w in bench["workloads"]]
    assert [w["name"] for w in pending["workloads"]] == [SERVE]
    assert [c["name"] for c in pending["configs"]] == ["cp3-serve"]
    names = [m["name"] for m in pending["end_to_end"] + pending["per_layer"]]
    assert set(names) <= set(SERVE_END_TO_END) | set(SERVE_PER_LAYER)
    assert names == SERVE_END_TO_END + list(SERVE_PER_LAYER)
    held = [m["name"] for m in pending["held_back"]["end_to_end"]]
    assert held == ["job_p50_s"]
    assert not set(names) & {m["name"] for m in
                             bench["end_to_end"] + bench["per_layer"]}
    assert not (REPO / "tests" / "benchmark" / "conftest.py").exists()


def test_entries_are_laid_after_the_committed_file_list_by_list():
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    pending = json.loads((REPO / PENDING).read_text())
    laid = harness.load_benchmark(PENDING)
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        assert laid[group] == bench[group] + pending[group]
    assert harness.load_benchmark() == bench


def test_run_py_finds_a_cell_of_an_entries_file_by_name():
    """``run.py --entries``: an unknown name exits 2 before JAX is
    touched, naming every cell it knows — the committed ones and those
    of the entries file."""
    argv = [sys.executable, str(REPO / "benchmark" / "run.py"),
            "--workload", "no-such-cell", "--seed", "1", "--seconds", "1"]
    for extra, knows_serve in (([], False), (["--entries", PENDING], True)):
        done = subprocess.run(argv + extra, cwd=REPO, capture_output=True,
                              text=True, timeout=120)
        assert done.returncode == 2 and done.stdout == ""
        assert CP4 in done.stderr and CP3[0] in done.stderr
        assert (SERVE in done.stderr) == knows_serve


# ------------------------------- the serve cell's end-to-end metrics by hand
def client_records(events: list) -> list:
    """The records the driver's clients would have kept of a recorded
    serve ledger: a job is due when it was enqueued (its ``queue_wait``
    span's start) and ends when ``job_done`` was written."""
    due = {e["job"]: e["t0"] for e in events
           if e.get("event") == "span" and e.get("span") == "queue_wait"}
    return [{"job": e["job"], "due": due[e["job"]], "end": e["ts"],
             "state": "done"} for e in events
            if e.get("event") == "job_done"]


def test_serve_end_to_end_metrics_of_a_recorded_ledger_by_hand():
    """Three jobs, recorded on a TPU v5e (PR 23).  By hand: enqueued at
    ...709.852961, .926316, .939702; done at ...710.0937102, .1215875,
    .138173; so the window is 0.2852120 s, the latencies 0.2407492,
    0.1952715, 0.1984710 s."""
    records = client_records(
        ledger.read_events(DATA / "serve_ledger.jsonl"))
    assert [r["job"] for r in records] == ["knn-0", "knn-1", "knn-2"]
    t0 = min(r["due"] for r in records)
    metrics, window_s = serve.end_to_end(records, t0)
    assert window_s == pytest.approx(0.2852120, abs=1e-6)
    assert metrics["jobs_per_s"] == {
        "value": pytest.approx(3 / 0.2852120, rel=1e-5), "unit": "jobs/s"}
    assert metrics["job_p50_s"]["value"] == pytest.approx(0.1984710,
                                                           abs=1e-6)
    # 95th of three: 0.9 of the way from the second to the third
    assert metrics["job_p95_s"]["value"] == pytest.approx(
        0.1984710 + 0.9 * (0.2407492 - 0.1984710), abs=1e-6)
    assert metrics["job_p95_s"]["unit"] == "s"


def test_a_job_that_did_not_end_in_done_counts_for_no_rate_and_no_latency():
    records = client_records(
        ledger.read_events(DATA / "serve_ledger.jsonl"))
    t0 = min(r["due"] for r in records)
    records[2] = dict(records[2], state="expired")
    metrics, window_s = serve.end_to_end(records, t0)
    # the window now ends with the second job's answer
    assert window_s == pytest.approx(1790621710.1215875 - t0, abs=1e-6)
    assert metrics["jobs_per_s"]["value"] == pytest.approx(2 / window_s)
    assert serve.end_to_end([dict(r, state="open") for r in records],
                            t0)[0] == {}


def test_made_up_entries_are_not_in_the_committed_file():
    text = (REPO / "BENCHMARK.json").read_text()
    assert MADE_UP_CELL not in text and MADE_UP_METRIC not in text
