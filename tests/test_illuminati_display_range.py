"""illuminati on the 64x64 ``cp3-plate`` well of
``test_inside_spans_workflow.py`` (five channels on disk), through
``Workflow.run``: with default arguments the display range is corilla's
stored 0.1 / 99.9 percentiles — no ``percentile`` span, every batch says
``display_range: corilla`` — and only the first channel compiles ``prep``;
a ``clip_percent`` corilla did not compute, or ``correct: false``, brings
the mosaic's own percentiles and their span back.
"""

import importlib.util
import json
from pathlib import Path

import cv2
import jax.numpy as jnp
import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]
CONFIG = json.loads(
    (REPO / "benchmark" / "configs" / "cp3-plate.json").read_text())
SIZE, FIELDS = 64, 9
N_CHANNELS = len(CONFIG["channels"])
COMPILE_PATH = ("jit_trace", "jit_lower", "jit_compile", "cache_load")

#: illuminati's arguments -> where the display range must come from
CASES = {
    "default": ({}, "corilla"),
    "clip_99.5": ({"clip_percent": 99.5}, "mosaic"),
    "uncorrected": ({"correct": False}, "mosaic"),
}


def _recorder():
    spec = importlib.util.spec_from_file_location(
        "record_stage_trace", REPO / "scripts" / "record_stage_trace.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def source(tmp_path_factory):
    src = tmp_path_factory.mktemp("display_range") / "src"
    _recorder().write_grid_plate(str(src), FIELDS, SIZE, CONFIG["channels"],
                                 26)
    return src


@pytest.fixture(scope="module", params=sorted(CASES))
def run(request, source, tmp_path_factory):
    """(case, store, ledger events) of ingest + corilla + illuminati."""
    from tmlibrary_tpu import telemetry
    from tmlibrary_tpu.models.experiment import Experiment
    from tmlibrary_tpu.models.store import ExperimentStore
    from tmlibrary_tpu.workflow.engine import Workflow, WorkflowDescription

    store = ExperimentStore.create(
        tmp_path_factory.mktemp("display_range") / "exp",
        Experiment(name="wf", plates=[], channels=[], site_height=1,
                   site_width=1))
    desc = WorkflowDescription.canonical({
        "metaconfig": {"source_dir": str(source), "sites_per_well_x": 3},
        "imextract": {},
        "corilla": {"n_devices": 1},
        "illuminati": CASES[request.param][0],
    })
    telemetry.reset_registry(enabled=True)
    wf = Workflow(store, desc)
    wf.run()
    telemetry.reset_registry()
    return request.param, store, wf.ledger.events()


def _results(events):
    return [e["result"] for e in events
            if e.get("event") == "batch_done"
            and e.get("step") == "illuminati"]


def _spans(events, name):
    return [e for e in events if e.get("event") == "span"
            and e.get("step") == "illuminati" and e["span"] == name]


def test_every_batch_names_the_source_of_its_display_range(run):
    case, store, events = run
    results = _results(events)
    assert len(results) == N_CHANNELS
    assert {r["display_range"] for r in results} == {CASES[case][1]}
    for r in results:
        assert r["display_lower"] < r["display_upper"]


def test_percentile_span_runs_only_for_the_mosaic_range(run):
    case, store, events = run
    n = len(_spans(events, "percentile"))
    assert n == (0 if CASES[case][1] == "corilla" else N_CHANNELS)


def _mosaic(case, store, channel):
    """The channel's prepped 3x3 mosaic, recomputed outside the step."""
    from tmlibrary_tpu.models.image import IllumstatsContainer
    from tmlibrary_tpu.ops import image_ops

    stats = None
    if CASES[case][0].get("correct", True):
        stats = IllumstatsContainer.from_store(
            store.read_illumstats(channel=channel))
    sites = store.read_sites(list(range(FIELDS)), channel=channel)
    prepped = image_ops.make_batch_prep(stats)(
        jnp.asarray(sites), jnp.zeros((FIELDS, 2), jnp.int32))
    return np.asarray(image_ops.join_grid(prepped, 3, 3))


def test_bounds_are_the_stored_percentiles_or_the_mosaics_own(run):
    case, store, events = run
    clip = CASES[case][0].get("clip_percent", 99.9)
    for r in _results(events):
        bounds = (r["display_lower"], r["display_upper"])
        if CASES[case][1] == "corilla":
            stored = store.read_illumstats(channel=r["channel"])
            keys = stored["percentile_keys"]
            assert keys.dtype == np.float64   # as corilla writes them now
            table = dict(zip(keys.tolist(),
                             stored["percentile_values"].tolist()))
            assert bounds == (table[0.1], table[clip])
        else:
            lo, up = np.percentile(_mosaic(case, store, r["channel"]),
                                   [0.1, clip])
            assert bounds == (float(lo), float(up))


def test_only_the_first_channel_compiles_prep(run):
    """``prep`` is one program: from the second channel on no compile-path
    span lies under a ``prep`` span (nor, being a cache hit in the process,
    does one of ``prep`` appear anywhere)."""
    case, store, events = run
    preps = sorted(_spans(events, "prep"), key=lambda e: e["t0"])
    assert len(preps) == N_CHANNELS
    first_batch = preps[0]["batch"]
    under_prep = [e for e in events if e.get("event") == "span"
                  and e["span"] in COMPILE_PATH
                  and e.get("step") == "illuminati"
                  and (e.get("parent") == "prep"
                       or "prep" in str(e.get("program")))]
    assert {e["batch"] for e in under_prep} <= {first_batch}


def test_tiles_are_stretched_between_the_reported_bounds(run):
    """The native level's tile is ``to_uint8`` of the prepped mosaic over
    the batch's reported range (for the default arguments: corilla's stored
    0.1 and 99.9 percentiles, by the test above)."""
    from tmlibrary_tpu.ops.pyramid import to_uint8

    case, store, events = run
    result = _results(events)[0]
    channel = result["channel"]
    expected = np.asarray(to_uint8(
        _mosaic(case, store, channel),
        result["display_lower"], result["display_upper"]))
    tile = cv2.imread(str(
        store.root / "pyramids" / f"channel{channel:02d}"
        / str(result["n_levels"] - 1) / "0_0.png"), cv2.IMREAD_UNCHANGED)
    side = 3 * SIZE
    assert expected.shape == (side, side)
    np.testing.assert_array_equal(tile[:side, :side], expected)
    assert len(np.unique(expected)) > 16          # a stretch, not a flat
