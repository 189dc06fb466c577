"""``cp3-mosaic`` through the normal path on the CPU at its rehearsal size,
on four of the suite's eight host devices: one well drawn as one mosaic by
``benchmark/mosaic.py``, ``tmx create`` + ``tmx workflow submit`` with the
cell's step arguments (``layout: spatial``, ``spatial_grid: grid``, a 2 x 2
mesh), then the plain reference's ``check`` on the store — and the things
the cell forced in the program: the squarest grid, the root table's bound
from ``max_objects``, programs built once a process, planes that go to
their shards directly, the counters in ``batch_done.result``."""

import io
import json
import os
import sys
import threading
import time
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from benchmark import harness, ledger, mosaic, plate  # noqa: E402

CONFIG = json.loads(
    (REPO / "benchmark" / "configs" / "cp3-mosaic.json").read_text())
SIZED = harness.at_size(CONFIG, on_chip=False)
REFERENCE = REPO / "benchmark" / "configs" / CONFIG["reference"]
SIZE, FIELDS_X = SIZED["field_size"], CONFIG["sites_per_well_x"]
COMPILE_SPANS = ("jit_trace", "jit_lower", "jit_compile", "cache_load")


@pytest.fixture(scope="module")
def reference():
    return harness.load_module(str(REFERENCE))


def submit(work, name: str, planes: dict, capacity: int = None):
    """The well's planes written as its nine fields and submitted as
    ``benchmark/drivers/mosaic.py`` submits them; ``(store, events)``."""
    from tmlibrary_tpu.models.store import ExperimentStore

    src = str(work / f"{name}_src")
    mosaic.write_well(src, plate.well_names(1)[0], planes, SIZE, FIELDS_X)
    root = str(work / name)
    harness.tmx(["create", "--name", name, "--root", root])
    wf = mosaic.write_description(root, src, CONFIG,
                                  capacity or SIZED["max_objects"])
    harness.tmx(["workflow", "submit", "--description", wf, "--root", root])
    return ExperimentStore.open(Path(root)), ledger.run_ledger(root)


@pytest.fixture(scope="module")
def drawn():
    """``(planes, cells drawn)`` of seed 11: 3-7 cells a field, over the
    whole 192 x 192 mosaic."""
    return mosaic.draw_well(11, SIZE, FIELDS_X, CONFIG["fields_per_well"],
                            (3, 7), CONFIG["channels"])


@pytest.fixture(scope="module")
def units(tmp_path_factory, drawn, devices):
    """Two units of the same well in ONE process: ``[(store, events)]``.
    The first starts from empty jit caches, whatever ran in this process
    before (``tests/benchmark/test_mosaic_cell.py`` submits the same unit:
    on one xdist worker its programs would be this first unit's hits)."""
    import jax

    jax.clear_caches()
    work = tmp_path_factory.mktemp("cp3mosaic")
    return [submit(work, f"exp{i}", drawn[0]) for i in range(2)]


def jterator_result(events: list) -> dict:
    results = ledger.batch_results(events, "jterator")
    assert len(results) == 1            # one well, one mosaic, one batch
    return results[0]


# ------------------------------------------------------- against the reference
def test_the_submitted_well_is_correct_by_the_reference(units, reference):
    store, events = units[0]
    verdict = reference.check(store, list(range(store.n_sites)), CONFIG,
                              jterator_result(events))
    assert all(verdict["checks"].values()), verdict["checks"]
    compared, info = verdict["compared"], verdict["info"]
    assert compared["seam_faults"] == (0, 0)
    assert compared["mask_faults"] == (0, 0)
    assert info["mask_pixels_outside_band"] == 0
    # the mechanism was worked: objects across borders and across seams
    assert compared["seam_objects_missing"] == (0, 0)
    assert set(info["objects_across"]) == {
        "nuclei_across_field_borders", "nuclei_across_mesh_seams",
        "cells_across_field_borders", "cells_across_mesh_seams"}
    assert min(info["objects_across"].values()) > 0
    for key, (limit, why) in reference.LIMITS.items():
        assert limit > 0 and len(why) > 40
    for name, (number, limit) in compared.items():
        assert number <= limit, name
    # both cuts are bins of the reference's own histograms
    for stain in ("DAPI", "Actin"):
        held = info["otsu"][stain]
        assert held["cut"] == jterator_result(events)["otsu_cut"][stain]
        assert held["bin"] == held["reference_bin"]
    assert info["object_counts"]["nuclei"] == \
        info["reference_counts"]["nuclei"] > 0


def test_both_units_wrote_the_same_objects(units):
    (a, _), (b, _) = units
    for name in ("nuclei", "cells"):
        assert np.array_equal(a.read_labels(None, name),
                              b.read_labels(None, name))
        assert len(a.read_features(name)) == len(b.read_features(name)) > 0


def test_the_stitch_of_the_nine_files_is_the_drawn_mosaic(units, drawn,
                                                          reference):
    """``benchmark/mosaic.py`` cuts the mosaic row-major under the names
    metaconfig's default handler parses: the store's nine sites, laid
    side by side, are the planes that were drawn."""
    store, _ = units[0]
    assert store.n_sites == 9
    for channel, want in drawn[0].items():
        stack = store.read_sites(
            None, channel=store.experiment.channel_index(channel))
        assert np.array_equal(reference.stitch(stack, FIELDS_X), want)


@pytest.mark.parametrize("tampered, failing", [
    ("one_id_split_at_the_seam",
     "nuclei_are_scipy_labels_of_their_foreground"),
    ("a_cell_without_its_nucleus", "cells_hold_their_nuclei"),
    ("statistics_of_seven_fields", "stored_statistics_are_the_nine_fields"),
])
def test_the_reference_fails_a_store_that_is_wrong(units, reference,
                                                   tampered, failing):
    store, _ = units[0]
    seam = FIELDS_X * SIZE // 2

    class Tampered:
        def __getattr__(self, name):
            return getattr(store, name)

        def read_labels(self, sites, name, **kw):
            stack = store.read_labels(sites, name, **kw).copy()
            whole = reference.stitch(stack, FIELDS_X)
            if tampered == "one_id_split_at_the_seam" and name == "nuclei":
                # what a seam join that does nothing leaves: the part of
                # an object below the seam keeps an id of its own
                whole[seam:][whole[seam:] > 0] += 1
            if tampered == "a_cell_without_its_nucleus" and name == "cells":
                whole[whole == 1] = 0
            for f in range(len(stack)):
                y, x = divmod(f, FIELDS_X)
                stack[f] = whole[y * SIZE:(y + 1) * SIZE,
                                 x * SIZE:(x + 1) * SIZE]
            return stack

        def read_illumstats(self, channel=0, **kw):
            stats = dict(store.read_illumstats(channel=channel, **kw))
            if tampered == "statistics_of_seven_fields":
                # what a fold that drops a shard of two fields stores
                mean_log, std_log = reference.statistics(
                    store.read_sites(None, channel=channel)[:7])
                stats.update(mean_log=mean_log.astype(np.float32),
                             std_log=std_log.astype(np.float32))
            return stats

    verdict = reference.check(Tampered(), list(range(9)), CONFIG,
                              jterator_result(units[0][1]))
    assert not verdict["checks"][failing], verdict["checks"]
    if tampered == "statistics_of_seven_fields":
        # the planes the program corrected are sound: only the tables fail
        assert [k for k, ok in verdict["checks"].items() if not ok] == \
            [failing]


# ------------------------------------------------ what the cell forced
def test_batch_done_says_the_mesh_the_bytes_and_what_was_counted(units):
    for _, events in units:
        result = jterator_result(events)
        assert result["layout"] == "spatial"
        assert result["mesh_shape"] == [2, 2]
        side = FIELDS_X * SIZE
        assert result["mosaic_shape"] == [side, side]
        # two float32 planes (DAPI, Actin), each handed over once
        assert result["h2d_bytes"] == 2 * 4 * side * side
        assert result["objects"]["nuclei"] == result["objects"]["cells"] > 0
        assert 1 <= result["seam_rounds"] <= 8
        assert result["adopt_steps"] >= CONFIG["jterator"][
            "spatial_secondary_levels"] + 1     # a last step a flood
        assert 0 < result["roots_max_per_shard"] <= SIZED["max_objects"]
        # Otsu's cut a stain, as used (before the secondary factor)
        assert set(result["otsu_cut"]) == {"DAPI", "Actin"}
        assert all(300 < cut < 65535 for cut in result["otsu_cut"].values())
    # everything but how many host tasks happened to run at one instant
    first, second = (dict(jterator_result(events)) for _, events in units)
    for result in (first, second):
        assert result.pop("host_tasks") == 5 + 2 * (3 + 5)
        assert 1 <= result.pop("host_peak_concurrency") <= 8
    assert first == second


def test_the_spans_the_readers_read_are_in_the_ledger(units):
    _, events = units[0]
    spans = [e for e in events if e.get("event") == "span"
             and e.get("step") == "jterator"]
    by_name = {}
    for e in spans:
        by_name.setdefault(e["span"], []).append(e)
    side = FIELDS_X * SIZE
    assert len(by_name["stitch"]) == 5           # one a stain
    # all five stitched ahead, on the host pool, from the launch
    assert {e["parent"] for e in by_name["stitch"]} == {"dispatch"}
    assert all(e["bytes"] == 4 * side * side for e in by_name["stitch"])
    assert [e["bytes"] for e in by_name["upload"]] == [2 * 4 * side * side]
    assert len(by_name["segment"]) == len(by_name["device_wait"]) == 1
    # the launch waits for no cut: both come with the labels
    assert "otsu_cut" not in by_name["segment"][0]
    assert by_name["segment"][0]["parent"] == "dispatch"
    assert [e["bytes"] for e in by_name["fetch"]] == [2 * 4 * side * side]
    assert by_name["fetch"][0]["otsu_cut"] == \
        jterator_result(events)["otsu_cut"]
    assert by_name["fetch"][0]["parent"] == "persist"
    assert len(by_name["intensity"]) == 2 * 5    # both types, every stain
    for name in ("morph", "solidity", "write_labels", "write_features"):
        assert len(by_name[name]) == 2, name
    # pool tasks keep the step, batch and enclosing span of the thread
    # that submitted them: the persist readers select on both
    for name in ("morph", "solidity", "write_labels", "write_features",
                 "intensity"):
        assert {e["parent"] for e in by_name[name]} == {"persist"}, name
        assert {e["batch"] for e in by_name[name]} == {0}, name
    pooled = {e["thread"] for name in ("morph", "solidity", "intensity",
                                       "write_labels", "stitch")
              for e in by_name[name]}
    assert all(t.startswith("tmx-mosaic-host") for t in pooled), pooled
    objects = jterator_result(events)["objects"]["nuclei"]
    for e in by_name["write_features"]:
        # area, centroid (2), box (2), solidity, five statistics a stain
        assert (e["rows"], e["columns"]) == (objects, 6 + 5 * 5)


def test_a_second_unit_in_one_process_traces_and_compiles_nothing(units):
    """Every sharded program — the halo smooth, the Otsu, the connected
    components, the watershed, corilla's scan-and-fold — is built and
    jitted once a process."""
    first = [e for e in units[0][1] if e.get("event") == "span"
             and e.get("span") in COMPILE_SPANS]
    assert first                                 # the first unit compiled
    second = [(e.get("step"), e.get("span"), e.get("program"))
              for e in units[1][1] if e.get("event") == "span"
              and e.get("span") in COMPILE_SPANS]
    assert second == []


def test_the_counters_are_in_the_registry(units):
    from tmlibrary_tpu import telemetry

    text = telemetry.get_registry().render_prometheus() \
        if hasattr(telemetry.get_registry(), "render_prometheus") \
        else json.dumps(telemetry.get_registry().snapshot())
    for name in ("tmx_jterator_mosaic_seam_rounds_total",
                 "tmx_jterator_mosaic_adopt_steps_total",
                 "tmx_jterator_mosaic_roots_max_per_shard"):
        assert name in text


# ------------------------------------ the host pool of the spatial step
def serial_stitch(store, channel: str) -> np.ndarray:
    """One stain's mosaic as the serial step stitched it: the nine fields
    corrected by the step's own program, laid side by side."""
    from tmlibrary_tpu.models.image import IllumstatsContainer
    from tmlibrary_tpu.workflow.steps.jterator import _correct_batch

    index = store.experiment.channel_index(channel)
    stats = IllumstatsContainer.from_store(
        store.read_illumstats(channel=index))
    fields = _correct_batch(store.read_sites(list(range(9)), channel=index),
                            stats.mean_log, stats.std_log)
    return reference_stitch(fields)


def reference_stitch(stack: np.ndarray) -> np.ndarray:
    rows = [np.concatenate(list(stack[r * FIELDS_X:(r + 1) * FIELDS_X]),
                           axis=1) for r in range(len(stack) // FIELDS_X)]
    return np.concatenate(rows, axis=0)


def serial_table(labels: np.ndarray, count: int, planes: dict, well):
    """One family's feature table, one native pass after another, in the
    column order of the step before its host pool."""
    import pandas as pd

    from tmlibrary_tpu import native

    area_i, cy_sum, cx_sum, ymin, ymax, xmin, xmax = \
        native.mosaic_morph_host(labels, count)
    area = area_i[1:].astype(np.float64)
    denom = np.maximum(area, 1)
    cols = {"site_index": -1, "plate": well[0], "well_row": well[1],
            "well_col": well[2], "site_y": -1, "site_x": -1,
            "label": np.arange(1, count + 1, dtype=np.int64),
            "Morphology_area": area,
            "Morphology_centroid_y": cy_sum[1:] / denom,
            "Morphology_centroid_x": cx_sum[1:] / denom,
            "Morphology_bbox_height": (ymax[1:] - ymin[1:] + 1).astype(
                np.float64),
            "Morphology_bbox_width": (xmax[1:] - xmin[1:] + 1).astype(
                np.float64),
            "Morphology_solidity": native.solidity_host(
                labels, count, areas=area).astype(np.float64)}
    for stain, plane in planes.items():
        s, q, mn, mx = native.mosaic_intensity_host(labels, plane, count)
        mean = s[1:] / denom
        cols[f"Intensity_mean_{stain}"] = mean
        cols[f"Intensity_sum_{stain}"] = s[1:]
        cols[f"Intensity_std_{stain}"] = np.sqrt(
            np.maximum(q[1:] / denom - mean * mean, 0.0))
        cols[f"Intensity_min_{stain}"] = np.where(area > 0, mn[1:], 0.0)
        cols[f"Intensity_max_{stain}"] = np.where(area > 0, mx[1:], 0.0)
    out = io.BytesIO()
    pd.DataFrame(cols).to_parquet(out, index=False)
    return out.getvalue()


def test_the_pooled_outputs_are_the_serial_passes_byte_for_byte(units):
    """Label stacks and both feature shards of a submit whose host work
    ran side by side equal, byte for byte, what one pass after another
    writes from the same labels and stitched planes."""
    import pandas as pd

    (store, events), (again, _) = units
    # the experiment's channel order is the columns' order
    planes = {c.name: serial_stitch(store, c.name)
              for c in store.experiment.channels}
    count = jterator_result(events)["objects"]["nuclei"]
    for name in ("nuclei", "cells"):
        stack = store.root / "segmentations" / f"{name}_t000_z000.npy"
        assert stack.read_bytes() == (
            again.root / "segmentations" / stack.name).read_bytes()
        labels = reference_stitch(store.read_labels(None, name))
        (shard,) = sorted((store.root / "features" / name).glob("*.parquet"))
        head = pd.read_parquet(shard).iloc[0]
        well = (str(head["plate"]), int(head["well_row"]),
                int(head["well_col"]))
        assert shard.read_bytes() == serial_table(labels, count, planes,
                                                  well), name


#: the cell's per-layer metrics read from the run ledger alone (the others
#: read the device trace, or the harness's clocks and meters)
LEDGER_READERS = (
    "jterator_ms_per_site", "device_block_ms_per_site", "persist_ms_per_site",
    "persist_fetch_ms_per_site", "persist_labels_ms_per_site",
    "persist_features_ms_per_site", "persist_solidity_ms_per_site",
    "h2d_mb_per_site", "feature_values_per_site", "mosaic_stitch_ms_per_site",
    "mosaic_upload_ms_per_site", "mosaic_device_wait_ms_per_site",
    "mosaic_measure_ms_per_site", "mosaic_seam_rounds", "mosaic_adopt_steps",
    "mosaic_roots_max_per_shard",
)


@pytest.mark.parametrize("name", LEDGER_READERS)
def test_the_cells_ledger_readers_read_the_pooled_spans(units, name):
    from benchmark.drivers.plate import PlateRun, Unit

    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    (metric,) = [m for m in bench["per_layer"] if m["name"] == name]
    assert "cp3-mosaic.x4" in metric["workloads"]
    _, events = units[0]
    run = PlateRun(CONFIG, {"platform": "cpu"}, SIZE, SIZED["max_objects"])
    unit = Unit("rehearsal", 9)
    unit.events = events
    run.units.append(unit)
    module = harness.load_module(
        str(REPO / "benchmark" / "metrics" / f"{name}.py"))
    value = module.read(run)
    assert isinstance(value, (int, float)) and value >= 0, value


@pytest.mark.skipif((os.cpu_count() or 1) < 2, reason="one host thread")
def test_two_intensity_passes_run_at_once(monkeypatch, tmp_path, devices):
    """The first two intensity passes wait for each other: one pass after
    another would break the barrier and fail the batch."""
    from tmlibrary_tpu.workflow.steps import jterator

    real = jterator._mosaic_intensity_stats
    meet = threading.Barrier(2, timeout=60)
    calls = []
    lock = threading.Lock()

    def both_at_once(*args):
        with lock:
            calls.append(threading.current_thread().name)
            first_two = len(calls) <= 2
        if first_two:
            meet.wait()
        return real(*args)

    monkeypatch.setattr(jterator, "_mosaic_intensity_stats", both_at_once)
    planes, _ = seam_planes(np.random.default_rng(7))
    _, events = submit(tmp_path, "side", planes)
    assert len(set(calls[:2])) == 2
    assert jterator_result(events)["host_peak_concurrency"] >= 2


def test_a_failed_host_task_fails_the_batch_and_ends_the_pool(
        monkeypatch, tmp_path, devices):
    from tmlibrary_tpu import native
    from tmlibrary_tpu.models.store import ExperimentStore
    from tmlibrary_tpu.workflow.steps.jterator import HOST_THREAD_PREFIX

    def hull_fails(*args, **kw):
        raise RuntimeError("hull fault")

    monkeypatch.setattr(native, "solidity_host", hull_fails)
    planes, _ = seam_planes(np.random.default_rng(8))
    src = str(tmp_path / "src")
    mosaic.write_well(src, plate.well_names(1)[0], planes, SIZE, FIELDS_X)
    root = str(tmp_path / "fault")
    harness.tmx(["create", "--name", "fault", "--root", root])
    wf = mosaic.write_description(root, src, CONFIG, SIZED["max_objects"])
    rc = harness.tmx_exit(["workflow", "submit", "--description", wf,
                           "--root", root])
    assert rc != 0
    events = ledger.run_ledger(root)
    failed = [e for e in events if e.get("event") == "batch_failed"
              and e.get("step") == "jterator"]
    assert failed and "hull fault" in json.dumps(failed)
    assert not ledger.batch_results(events, "jterator")
    assert not list((Path(root) / "features").glob("*/*.parquet"))
    deadline = time.time() + 10
    while time.time() < deadline and any(
            t.name.startswith(HOST_THREAD_PREFIX)
            for t in threading.enumerate()):
        time.sleep(0.05)
    assert not [t.name for t in threading.enumerate()
                if t.name.startswith(HOST_THREAD_PREFIX)]
    assert ExperimentStore.open(Path(root)).n_sites == 9


def test_concurrent_first_calls_build_one_correction_program(monkeypatch):
    import jax

    from tmlibrary_tpu.workflow.steps import jterator

    monkeypatch.setattr(jterator, "_CORRECT_JIT", None)
    built = []
    real = jax.jit

    def slow_jit(fn, *args, **kw):
        if getattr(fn, "__name__", "") == "correct_illumination":
            built.append(fn)
            time.sleep(0.2)     # a racing second caller would build too
        return real(fn, *args, **kw)

    monkeypatch.setattr(jax, "jit", slow_jit)
    imgs = np.arange(2 * 8 * 8, dtype=np.uint16).reshape(2, 8, 8)
    mean_log = np.full((8, 8), 1.5, np.float32)
    std_log = np.full((8, 8), 0.5, np.float32)
    start = threading.Barrier(6, timeout=30)

    def first_call():
        start.wait()
        return jterator._correct_batch(imgs, mean_log, std_log)

    threads_out = []
    threads = [threading.Thread(target=lambda: threads_out.append(
        first_call())) for _ in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    assert len(threads_out) == 6
    assert len(built) == 1
    assert all(np.array_equal(out, threads_out[0]) for out in threads_out)


@pytest.mark.parametrize("kind, requested, shape, want", [
    ("grid", 4, (6480, 6480), (2, 2)),      # the cell: the host's own shape
    ("grid", 4, (192, 192), (2, 2)),        # and its rehearsal
    ("grid", 8, (100, 100), (4, 2)),        # more devices beat a square
    ("grid", 4, (6, 7), (3, 1)),            # columns cannot be split
    ("grid", 1, (64, 64), (1, 1)),
    ("auto", 4, (6480, 6480), (4, 1)),      # as before PR 33
    ("auto", 8, (100, 100), (4, 2)),
    ("rows", 4, (6480, 6480), (4, 1)),
    ("rows", 8, (100, 100), (5, 1)),
])
def test_spatial_grid_resolves_the_mesh(kind, requested, shape, want):
    from tmlibrary_tpu.workflow.steps.jterator import _spatial_mesh_shape

    assert _spatial_mesh_shape(kind, requested, *shape) == want


def seam_planes(rng) -> dict:
    """A well whose cells ALL lie across the mesh seams: nuclei centred on
    row 96 or column 96 of the 192 x 192 mosaic, one on the corner where
    the four shards meet."""
    side = FIELDS_X * SIZE
    seam = side // 2
    centres = [(seam, x) for x in (20, 60, 140, 176)] + \
        [(y, seam) for y in (24, 56, 150)] + [(seam, seam)]
    yy, xx = np.mgrid[0:side, 0:side].astype(np.float32)
    planes = {c: rng.normal(300.0, 25.0, (side, side)).astype(np.float32)
              for c in CONFIG["channels"]}
    for y, x in centres:
        d2 = (yy - y) ** 2 + (xx - x) ** 2
        for c in planes:
            amp, r = (4000.0, 4.0) if c == "DAPI" else (1500.0, 9.0)
            planes[c] += amp * np.exp(-d2 / (2 * r ** 2))
    return {c: np.clip(p, 0, 65535).astype(np.uint16)
            for c, p in planes.items()}, len(centres)


def test_a_well_whose_cells_all_lie_across_the_seams(tmp_path, reference,
                                                     devices):
    planes, n = seam_planes(np.random.default_rng(3))
    store, events = submit(tmp_path, "seams", planes)
    verdict = reference.check(store, list(range(9)), CONFIG,
                              jterator_result(events))
    assert all(verdict["checks"].values()), verdict["checks"]
    found = verdict["info"]["object_counts"]["nuclei"]
    assert found == n
    across = verdict["info"]["objects_across"]
    assert across["nuclei_across_mesh_seams"] == n
    assert across["cells_across_mesh_seams"] == n
    assert jterator_result(events)["seam_rounds"] >= 2


def test_a_well_whose_two_best_bins_tie_is_correct_in_either(tmp_path,
                                                             reference,
                                                             devices):
    """Seeds 10636 and 22261 at the rehearsal size: the reference's own
    criterion holds the runner-up bin within 3e-8 of the best (found by a
    scan of 40,000 seeds), under what float32 resolves, and XLA's CPU
    takes the runner-up.  The cut it says it used is an Otsu threshold
    and the mask is the plane over it: correct.  Against the centre of the
    reference's argmax bin — PR 33's check, refused on seed 1996743581 at
    the cell's own size — the same mask has pixels outside the band."""
    import scipy.ndimage as ndi

    other_bin = 0
    for seed in (10636, 22261):
        planes, _ = mosaic.draw_well(
            seed, SIZE, FIELDS_X, CONFIG["fields_per_well"], (3, 7),
            CONFIG["channels"])
        store, events = submit(tmp_path, f"tie{seed}", planes)
        verdict = reference.check(store, list(range(9)), CONFIG,
                                  jterator_result(events))
        assert all(verdict["checks"].values()), verdict["checks"]
        held = verdict["info"]["otsu"]["DAPI"]
        assert held["runner_up_below_max_rel"] < 3e-8
        assert abs(held["bin"] - held["reference_bin"]) <= 1
        if held["bin"] == held["reference_bin"]:
            continue
        other_bin += 1
        assert 0 < held["below_max_rel"] < 3e-8
        smooth = ndi.gaussian_filter(
            reference.plane(store, "DAPI", FIELDS_X, {}).astype(np.float64),
            CONFIG["jterator"]["spatial_sigma"], mode="reflect")
        nuclei = reference.stitch(store.read_labels(None, "nuclei"), FIELDS_X)
        cut = held["reference_cut"]
        outside = ((smooth > cut) != (nuclei > 0)) & (
            np.abs(smooth - cut)
            > reference.LIMITS["threshold_band_rel"][0] * cut)
        assert np.count_nonzero(outside) > 0
    assert other_bin > 0


def test_max_objects_under_the_demand_raises_naming_the_demand(devices):
    """The root table's bound is the step's ``max_objects``; a shard that
    holds more roots raises, and says how many it holds."""
    import jax
    from jax.sharding import Mesh

    from tmlibrary_tpu.errors import ShardingError
    from tmlibrary_tpu.parallel.label import segment_mosaic

    planes, n = seam_planes(np.random.default_rng(4))
    mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2),
                ("rows", "cols"))
    image = planes["DAPI"].astype(np.float32)
    labels, count, info = segment_mosaic(image, mesh, sigma=1.5,
                                         max_roots_per_shard=64)
    assert int(count) == n
    demand = info["roots_max_per_shard"]
    assert 2 <= demand <= n
    with pytest.raises(ShardingError,
                       match=f"a shard holds {demand} components > "
                             f"max_roots_per_shard={demand - 1}"):
        segment_mosaic(image, mesh, sigma=1.5,
                       max_roots_per_shard=demand - 1)


@pytest.mark.parametrize("max_objects, bound", [
    (64, 4096),      # a per-site capacity leaves the bound its old floor
    (8192, 8192),    # the configuration's: a mosaic's
])
def test_the_step_hands_max_objects_to_the_root_table(
        monkeypatch, tmp_path, devices, max_objects, bound):
    from tmlibrary_tpu.parallel import label

    seen = []
    real = label.segment_mosaic

    def segment_mosaic(*args, **kw):
        seen.append(kw["max_roots_per_shard"])
        return real(*args, **kw)

    monkeypatch.setattr(label, "segment_mosaic", segment_mosaic)
    planes, _ = seam_planes(np.random.default_rng(5))
    submit(tmp_path, "bound", planes, capacity=max_objects)
    assert seen == [bound]


def test_planes_go_from_host_memory_to_their_shards(monkeypatch, tmp_path,
                                                    devices):
    """No ``jnp.asarray`` of a whole mosaic plane: every plane the
    segmentation reads is handed to ``jax.device_put`` as a numpy array
    with the mesh's sharding."""
    import jax
    from jax.sharding import NamedSharding

    seen = []
    real = jax.device_put

    def device_put(x, device=None, **kw):
        if getattr(x, "shape", None) == (FIELDS_X * SIZE,) * 2:
            seen.append((type(x), device))
        return real(x, device, **kw)

    monkeypatch.setattr(jax, "device_put", device_put)
    planes, _ = seam_planes(np.random.default_rng(6))
    submit(tmp_path, "direct", planes)
    host = [(t, s) for t, s in seen if t is np.ndarray]
    assert len(host) == 2                       # DAPI and Actin
    for _, sharding in host:
        assert isinstance(sharding, NamedSharding)
        assert dict(sharding.mesh.shape) == {"rows": 2, "cols": 2}
        assert tuple(sharding.spec) == ("rows", "cols")
