"""What ``BENCHMARK.json`` has to say of PR 33's four-chip cell, stated by
name and never by place, on the three copies
``test_benchmark_entries.py`` builds (as committed, with the pending
serve entries, with a made-up cell and metric appended): which lists the
cell joins, which it may not join, the fourteen metrics it brings, its
configuration's file."""

import json
import re
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from benchmark import harness  # noqa: E402

entries = harness.load_module(
    str(Path(__file__).with_name("test_benchmark_entries.py")))
metric_of, PLATE, CP4 = entries.metric_of, entries.PLATE, entries.CP4

#: one well as one mosaic, ``layout: spatial``, a 2 x 2 mesh
MOSAIC = "cp3-mosaic.x4"
#: of the plate cells' metrics, those whose readers read a mosaic run
#: unchanged: steps, spans of steps 1-4, the device, the caches ...
MOSAIC_JOINS = ["sites_per_s", "engine_other_ms_per_site",
                "ingest_ms_per_site", "illum_pyramid_ms_per_site",
                "jterator_ms_per_site", "corilla_ms_per_site",
                "illuminati_prep_ms_per_site",
                "illuminati_pyramid_ms_per_site",
                "illuminati_encode_ms_per_site", "decode_mpix_per_s",
                "plate_steps_device_ms_per_site",
                "jit_in_window_ms_per_site", "device_idle_share.plate",
                "peak_hbm_gb.plate", "warm_compile_s",
                "window_compiles.plate",
                # ... and the pipelined executor's: the spatial path runs
                # its `device_block` and `persist` phases, and under
                # persist the spans `fetch`, `write_labels`,
                # `write_features` (with `rows` and `columns`) and
                # `solidity`; `batch_done` says its `h2d_bytes`
                "device_block_ms_per_site", "persist_ms_per_site",
                "persist_fetch_ms_per_site", "persist_labels_ms_per_site",
                "persist_features_ms_per_site", "h2d_mb_per_site",
                "persist_solidity_ms_per_site", "feature_values_per_site"]
#: those that read ``jit_one_site`` and the capacity router, which the
#: spatial layout does not run
MOSAIC_MAY_NOT_JOIN = [
    "escalations_per_site", "program_ms_per_site", "batch_program_roofline",
    "stage_smooth_ms_per_site", "stage_threshold_ms_per_site",
    "stage_fill_ms_per_site", "stage_label_ms_per_site",
    "stage_watershed_ms_per_site", "stage_measure_ms_per_site",
    "stage_other_ms_per_site", "persist_escalate_ms_per_site",
    "measure_intensity_ms_per_site", "measure_morphology_ms_per_site",
    "measure_texture_ms_per_site", "measure_zernike_ms_per_site",
    "measure_texture_roofline"]
#: the fourteen it brings: spans, scopes in the device trace, counters
#: (the `fetch` span is read by `persist_fetch_ms_per_site`, as a plate
#: cell's)
PR33 = ["mosaic_stitch_ms_per_site", "mosaic_upload_ms_per_site",
        "mosaic_device_wait_ms_per_site",
        "mosaic_measure_ms_per_site", "mosaic_segment_device_ms_per_site",
        "mosaic_smooth_ms_per_site", "mosaic_otsu_ms_per_site",
        "mosaic_cc_ms_per_site", "mosaic_watershed_ms_per_site",
        "mosaic_collective_share", "mosaic_seam_rounds",
        "mosaic_adopt_steps", "mosaic_roots_max_per_shard",
        "mosaic_segment_roofline"]


@pytest.fixture(scope="module",
                params=[entries.committed, entries.with_pending,
                        entries.appended],
                ids=["as_committed", "with_the_pending_serve_cell",
                     "with_a_made_up_cell_and_metric_appended"])
def checkout(request, tmp_path_factory):
    return request.param(tmp_path_factory)


@pytest.mark.parametrize("name", MOSAIC_JOINS)
def test_the_mosaic_cell_joins_the_metrics_that_read_it_unchanged(checkout,
                                                                  name):
    assert MOSAIC in metric_of(checkout[0], name)["workloads"]


@pytest.mark.parametrize("name", MOSAIC_MAY_NOT_JOIN)
def test_the_mosaic_cell_joins_no_metric_of_the_batch_program(checkout,
                                                              name):
    """``jit_one_site`` and the capacity router: the spatial layout runs
    neither."""
    assert MOSAIC not in metric_of(checkout[0], name)["workloads"]


@pytest.mark.parametrize("name", PR33)
def test_mosaic_metric_lists_the_mosaic_cell_alone(checkout, name):
    metric = metric_of(checkout[0], name)
    assert metric["workloads"] == [MOSAIC]
    assert metric["moves"] == "sites_per_s"
    assert metric["layer"] == ("kernels" if "roofline" in name
                               else "spatial layout")


def test_the_mosaic_cell_and_its_configuration(checkout):
    bench = checkout[0]
    cells = {w["name"]: w for w in bench["workloads"]}
    configs = {c["name"]: c for c in bench["configs"]}
    assert cells[MOSAIC] == {
        "name": MOSAIC, "config": "cp3-mosaic", "traffic": "x4",
        "chips": 4, "why": cells[MOSAIC]["why"]}
    assert configs["cp3-mosaic"]["reduced"] == ["wells_per_submit"]
    body = json.loads((REPO / configs["cp3-mosaic"]["file"]).read_text())
    assert body["driver"] == "mosaic" and body["mesh"] == [2, 2]
    assert body["chips"] == 4 and body["max_objects"] == 8192
    assert body["field_size"] == 2160 and body["fields_per_well"] == 9
    assert body["jterator"] == {
        "layout": "spatial", "spatial_grid": "grid",
        "spatial_channel": "DAPI", "spatial_secondary_channel": "Actin",
        "spatial_sigma": 1.5, "spatial_secondary_factor": 0.8,
        "spatial_secondary_levels": 16, "spatial_objects": "nuclei",
        "spatial_secondary_objects": "cells", "spatial_zernike_degree": 0}
    assert body["source"] == configs["cp3-mosaic"]["source"]
    assert len(body["source"]) <= 200
    assert body["rehearsal"] == {"field_size": 64, "max_objects": 64}
    # the three one-chip plate cells were there first, in their order
    names = [w["name"] for w in bench["workloads"]]
    assert names.index(MOSAIC) > max(names.index(c) for c in PLATE)
    four = [w["name"] for w in bench["workloads"] if w["chips"] == 4]
    assert MOSAIC in four and len(four) <= max(1, len(names) // 2)


def one_line(text):
    """The driver's rule for a ``why``, a ``layer`` and a ``source``:
    1 to 200 printable characters on one line (the check refused PR 33's
    first ``why`` at 204)."""
    return 1 <= len(text) <= 200 and all(32 <= ord(c) < 127 for c in text)


def test_what_pr33_appended_keeps_the_drivers_form(checkout):
    bench = checkout[0]
    cell = next(w for w in bench["workloads"] if w["name"] == MOSAIC)
    config = next(c for c in bench["configs"] if c["name"] == "cp3-mosaic")
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert one_line(cell["why"]), len(cell["why"])
    assert one_line(config["why"]), len(config["why"])
    assert one_line(config["source"]), len(config["source"])
    for name in PR33:
        metric = metric_of(bench, name)
        assert set(metric) == {"name", "unit", "better", "source", "layer",
                               "moves", "workloads"}
        assert re.fullmatch(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}", name)
        assert re.fullmatch(r"[A-Za-z0-9_/%.-]{1,16}", metric["unit"])
        assert metric["better"] in ("lower", "higher")
        assert metric["source"] in ("device_trace", "program_span",
                                    "program_counter")
        assert one_line(metric["layer"])
    assert (REPO / "BENCHMARK.json").stat().st_size <= 64 * 1024


def test_run_py_gives_the_mosaic_cell_the_metrics_that_list_it(checkout):
    saved = list(sys.path)      # run.py puts its checkout first on import
    try:
        run_py = harness.load_module(str(REPO / "benchmark" / "run.py"))
    finally:
        sys.path[:] = saved
    bench = checkout[0]
    assert run_py.metric_names(bench, "end_to_end", MOSAIC) == \
        ["sites_per_s", "setup_s"]
    per_layer = run_py.metric_names(bench, "per_layer", MOSAIC)
    assert set(per_layer) == set(MOSAIC_JOINS[1:] + PR33)
    assert per_layer[-len(PR33):] == PR33
    assert not set(PR33) & set(run_py.metric_names(bench, "per_layer", CP4))
