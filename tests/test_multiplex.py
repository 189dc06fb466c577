"""The multiplexed plate on the normal path, at a small size on the CPU:
a three-cycle experiment through ``tmx create`` + ``tmx workflow submit``
(the ``multiplexing`` description, six steps) held to the plain reference
of ``cp3-multiplex`` — shifts, window, counts, the six stains'
intensities — over planted drifts; the batch program's shift per channel
against numpy slicing; the one-cycle path's program unchanged; a channel
asked from a cycle that holds none of it; illuminati's layer per
channel-cycle; the align step's launch size from bytes."""

import json
import sys
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from benchmark import harness, multiplex  # noqa: E402

FIELD, PAD, MAX_SHIFT, SITES = 128, 8, 6, 4
reference = harness.load_module(
    str(REPO / "benchmark" / "configs" / "cp3-multiplex.reference.py"))


def config() -> dict:
    cfg = harness.at_size(harness.load_json(
        str(REPO), "benchmark", "configs", "cp3-multiplex.json"), False)
    return {**cfg, "field_size": FIELD, "fields_per_well": SITES,
            "sites_per_well_x": 2, "max_shift": MAX_SHIFT}


#: case -> (corrections planted in cycles 1 and 2, a (SITES, 2) table
#: each; sites the align step has to zero and count)
CASES = {
    "none": ([[(0, 0)] * SITES, [(0, 0)] * SITES], []),
    "mixed_signs": ([[(2, -3), (-1, 2), (3, 1), (-2, -2)],
                     [(-3, 3), (1, -1), (0, 2), (2, 0)]], []),
    "at_max_shift": ([[(MAX_SHIFT, -MAX_SHIFT), (0, 1), (-1, 0), (1, 1)],
                      [(0, 0), (-MAX_SHIFT, 2), (1, -1), (0, 0)]], []),
    "beyond_max_shift": ([[(MAX_SHIFT + 2, 0), (1, 1), (0, -1), (-1, 0)],
                          [(0, 1), (1, 0), (0, 0), (-2, 2)]], [(1, 0)]),
}


def write_well(src: Path, cfg: dict, planted: dict, seed: int) -> None:
    """``benchmark/multiplex.py``'s well with the drifts given, not
    drawn: each cycle's field cropped from one canvas at its offset."""
    import cv2

    rng = np.random.default_rng(seed)
    src.mkdir()
    stains = sorted({s for c in cfg["cycles"] for s in c})
    for field in range(SITES):
        canvas = multiplex.draw_canvas(rng, FIELD + 2 * PAD, 7, stains)
        for cycle, imaged in enumerate(cfg["cycles"]):
            oy, ox = PAD + np.asarray(planted[cycle][field])
            for stain in imaged:
                img = canvas[stain][oy:oy + FIELD, ox:ox + FIELD] \
                    + rng.normal(300.0, 25.0, (FIELD, FIELD))
                assert cv2.imwrite(
                    str(src / f"A01_s{field}_c{cycle}_{stain}.tif"),
                    np.clip(img, 0, 65535).astype(np.uint16))


@pytest.fixture(scope="module", params=list(CASES))
def submitted(request, tmp_path_factory):
    """One case through ``tmx create`` + ``tmx workflow submit``."""
    from tmlibrary_tpu import telemetry
    from tmlibrary_tpu.models.store import ExperimentStore

    case = request.param
    tables, zeroed = CASES[case]
    cfg = config()
    planted = {0: np.zeros((SITES, 2), np.int32),
               1: np.asarray(tables[0], np.int32),
               2: np.asarray(tables[1], np.int32)}
    tmp = tmp_path_factory.mktemp(case)
    write_well(tmp / "src", cfg, planted, seed=11)
    root = tmp / "exp"
    telemetry.drain_spans()
    assert harness.tmx_exit(["create", "--name", "mx", "--root", root]) == 0
    wf = multiplex.write_description(str(root), str(tmp / "src"), cfg,
                                     cfg["max_objects"])
    assert harness.tmx_exit(["workflow", "submit", "--description", wf,
                             "--root", root]) == 0
    store = ExperimentStore.open(root)
    events = [json.loads(line) for line in
              (root / "workflow" / "ledger.jsonl").read_text().splitlines()]
    expected = {c: t.copy() for c, t in planted.items()}
    for cycle, site in zeroed:
        expected[cycle][site] = 0
    return {"case": case, "cfg": cfg, "store": store, "events": events,
            "planted": planted, "expected": expected, "zeroed": zeroed}


def collected(events: list, step: str) -> dict:
    return next(e["collected"] for e in events
                if e.get("event") == "step_done" and e.get("step") == step)


def test_description_is_the_multiplexing_type(submitted):
    steps = [e["step"] for e in submitted["events"]
             if e.get("event") == "step_done"]
    assert steps == ["metaconfig", "imextract", "corilla", "align",
                     "illuminati", "jterator"]


def test_stored_shifts_are_the_planted(submitted):
    store = submitted["store"]
    for cycle in (1, 2):
        np.testing.assert_array_equal(store.read_shifts(cycle),
                                      submitted["expected"][cycle])
    assert not store.has_shifts(0)


def test_failures_are_zeroed_and_counted(submitted):
    said = collected(submitted["events"], "align")
    assert said["sites"] == 2 * SITES
    assert said["failed_sites"] == len(submitted["zeroed"])
    assert said["max_abs_shift"] == max(
        int(np.abs(t).max()) for t in submitted["expected"].values())


def test_stored_window_is_the_references(submitted):
    stacked = np.concatenate([submitted["expected"][c] for c in (1, 2)])
    want = reference.window(stacked, submitted["cfg"]["window_quantum"])
    assert submitted["store"].read_intersection() == want
    said = collected(submitted["events"], "align")
    assert said["window"] == want
    assert said["intersection"] == reference.intersection(stacked)
    assert want["top"] == (0 if submitted["case"] == "none" else 16)


def test_reference_holds_the_unit(submitted):
    """Counts, the six stains' intensities on both object types, the
    stacks' frame, the nine layers: every number within its limit.  Where
    a field was registered beyond ``max_shift`` the stored zero is, as it
    has to be, neither the reference's shift nor the planted one."""
    verdict = reference.check(
        submitted["store"], list(range(SITES)), submitted["cfg"],
        {"planted": submitted["planted"],
         "quantum": submitted["cfg"]["window_quantum"],
         "align": collected(submitted["events"], "align")})
    over = {name: pair for name, pair in verdict["compared"].items()
            if pair[0] > pair[1]}
    if not submitted["zeroed"]:
        assert not over, over
        assert all(verdict["checks"].values())
        assert sum(verdict["info"]["object_counts"]["nuclei"]) > 0
        return
    # the zeroed field: two entries differ, one site failed; its later
    # cycle's stains are then measured a drift off, and only they
    assert verdict["compared"]["shift_entries_unlike_reference"][0] == 1
    assert verdict["compared"]["shift_entries_unlike_planted"][0] == 1
    assert verdict["compared"]["align_failed_sites"][0] == 1
    assert set(over) <= {"shift_entries_unlike_reference",
                         "shift_entries_unlike_planted",
                         "align_failed_sites", "intensity_mean_sum_rel",
                         "intensity_minmax_unlike"}


def test_jterator_says_its_cycles_and_aligned_channels(submitted):
    results = [e["result"] for e in submitted["events"]
               if e.get("event") == "batch_done"
               and e.get("step") == "jterator"]
    assert results and all(r["cycles_read"] == [0, 1, 2] for r in results)
    assert all(r["aligned_channels"] == 7 for r in results)


def test_align_spans_cover_the_step(submitted):
    spans = [e for e in submitted["events"]
             if e.get("event") == "span" and e.get("step") == "align"]
    names = {e["span"] for e in spans}
    assert {"read", "register", "write_shifts"} <= names
    assert all(e["pairs"] == SITES for e in spans
               if e["span"] == "register")


def test_illuminati_writes_a_layer_for_every_channel_cycle(submitted):
    from tmlibrary_tpu.workflow.steps.illuminati import layer_name

    store, exp = submitted["store"], submitted["store"].experiment
    want = {layer_name(c, exp.channel_index(stain))
            for c, stains in enumerate(submitted["cfg"]["cycles"])
            for stain in stains}
    got = {p.parent.name for p in (store.root / "pyramids").glob(
        "*/layer.json")}
    assert got == want and len(want) == 9
    said = [e["result"] for e in submitted["events"]
            if e.get("event") == "batch_done"
            and e.get("step") == "illuminati"]
    assert sorted((r["cycle"], r["channel"]) for r in said) == sorted(
        (c, exp.channel_index(stain))
        for c, stains in enumerate(submitted["cfg"]["cycles"])
        for stain in stains)


def test_illuminati_tiles_are_the_shifted_planes(submitted):
    """A later cycle's full-resolution tile is that cycle's planes, each
    moved by its stored correction with zero fill (no crop: the mosaic
    keeps its grid), stretched to the display range the step reports."""
    import cv2

    from tmlibrary_tpu.workflow.steps.illuminati import layer_name

    store, exp = submitted["store"], submitted["store"].experiment
    cycle, stain = 1, "Mito"
    channel = exp.channel_index(stain)
    said = next(e["result"] for e in submitted["events"]
                if e.get("event") == "batch_done"
                and e.get("step") == "illuminati"
                and (e["result"]["cycle"], e["result"]["channel"])
                == (cycle, channel))
    layer = store.root / "pyramids" / layer_name(cycle, channel)
    top = json.loads((layer / "layer.json").read_text())["max_zoom"]
    tile = cv2.imread(str(layer / str(top) / "0_0.png"),
                      cv2.IMREAD_UNCHANGED)
    planes = store.read_sites(None, cycle=cycle, channel=channel)
    no_crop = dict.fromkeys(("top", "bottom", "left", "right"), 0)
    mosaic = np.zeros((2 * FIELD, 2 * FIELD), np.float32)
    # corilla's statistics exist, so the step corrects: take the planes
    # through the program's own correction and hold the SHIFT
    from tmlibrary_tpu.models.image import IllumstatsContainer
    from tmlibrary_tpu.ops import image_ops

    stats = IllumstatsContainer.from_store(
        store.read_illumstats(cycle=cycle, channel=channel))
    corrected = np.asarray(image_ops.make_batch_prep(stats)(
        planes, np.zeros((SITES, 2), np.int32)))
    for site, (y, x) in enumerate(((0, 0), (0, 1), (1, 0), (1, 1))):
        dy, dx = submitted["expected"][cycle][site]
        mosaic[y * FIELD:(y + 1) * FIELD, x * FIELD:(x + 1) * FIELD] = \
            reference.aligned(corrected[site], int(dy), int(dx), no_crop)
    span = max(said["display_upper"] - said["display_lower"], 1e-6)
    want = np.clip((mosaic - np.float32(said["display_lower"]))
                   / np.float32(span) * np.float32(255.0), 0, 255
                   ).astype(np.uint8)
    got = tile[:2 * FIELD, :2 * FIELD].astype(np.int64)
    assert np.abs(got - want).max() <= 1
    assert np.mean(got == want) > 0.99
    assert not tile[2 * FIELD:].any() and not tile[:, 2 * FIELD:].any()


# ------------------------------------------------------- the batch program
def three_channel_pipe(cycles=(None, 1, 2)) -> dict:
    """The configuration's pipeline cut to DAPI, Actin and Mito, every
    one aligned, read from ``cycles`` (None: the step's)."""
    pipe = json.loads(json.dumps(config()["pipeline"]))
    pipe["input"]["channels"] = [
        {"name": n, "correct": False, "align": True,
         **({} if c is None else {"cycle": c})}
        for n, c in zip(("DAPI", "Actin", "Mito"), cycles)]
    known = ("DAPI", "Actin", "Mito", "dapi_sm", "nuclei", "cells")
    pipe["pipeline"] = [m for m in pipe["pipeline"]
                        if all(h.get("key", "DAPI") in known
                               for h in m["handles"]["input"])]
    return pipe


def three_channel_description(cycles=(None, 1, 2)):
    from tmlibrary_tpu.jterator.description import PipelineDescription

    return PipelineDescription.from_dict(three_channel_pipe(cycles))


@pytest.mark.parametrize("shifts", [
    [(0, 0), (0, 0), (0, 0)],
    [(0, 0), (3, -2), (-4, 5)],
    [(-6, 6), (6, -6), (1, 0)],
], ids=["zeros", "mixed", "corners"])
@pytest.mark.parametrize("window", [None, (8, 8, 8, 8)],
                         ids=["no_window", "window_8"])
def test_preprocess_shifts_every_channel_by_its_own_row(shifts, window):
    import jax.numpy as jnp

    from tmlibrary_tpu.jterator.pipeline import (ImageAnalysisPipeline,
                                                 aligned_channels)

    desc = three_channel_description()
    assert aligned_channels(desc) == ["DAPI", "Actin", "Mito"]
    rng = np.random.default_rng(3)
    raw = {n: rng.integers(0, 60000, (40, 48)).astype(np.uint16)
           for n in ("DAPI", "Actin", "Mito")}
    fn = ImageAnalysisPipeline(desc, 16).build_preprocess_fn(window)
    got = fn({n: jnp.asarray(p) for n, p in raw.items()}, {},
             jnp.asarray(shifts, jnp.int32))
    win = dict(zip(("top", "bottom", "left", "right"),
                   window or (0, 0, 0, 0)))
    for (name, plane), (dy, dx) in zip(raw.items(), shifts):
        np.testing.assert_array_equal(
            np.asarray(got[name]),
            reference.aligned(plane, dy, dx, win).astype(np.float32))


def one_cycle_description(name: str, with_cycle: bool):
    from tmlibrary_tpu.jterator.description import PipelineDescription

    pipe = harness.load_json(str(REPO), "benchmark", "configs",
                             name + ".json")["pipeline"]
    pipe = json.loads(json.dumps(pipe))
    if with_cycle:
        for channel in pipe["input"]["channels"]:
            channel["cycle"] = 0
    return PipelineDescription.from_dict(pipe)


def lowered_sha256(fn, desc, batch=2, size=32, shifts_shape=(2, 2)) -> str:
    import hashlib

    import jax
    import jax.numpy as jnp

    raw = {ch.name: jax.ShapeDtypeStruct((batch, size, size), jnp.uint16)
           for ch in desc.channels}
    text = fn.lower(raw, {}, jax.ShapeDtypeStruct(shifts_shape, jnp.int32)
                    ).as_text()
    return hashlib.sha256(text.encode()).hexdigest()


def before_the_change(desc, max_objects: int):
    """The batch program as ``build_batch_fn`` composed it before a
    channel could name its cycle: ONE (2,) shift a site, handed to every
    aligned channel (the parent commit's ``build_preprocess_fn`` and
    ``one_site``, written out)."""
    import jax
    import jax.numpy as jnp

    from tmlibrary_tpu.jterator.pipeline import ImageAnalysisPipeline
    from tmlibrary_tpu.ops import image_ops

    site_fn = ImageAnalysisPipeline(desc, max_objects).build_site_fn()

    def preprocess(raw, stats, shift):
        out = {}
        for ch in desc.channels:
            img = jnp.asarray(raw[ch.name], jnp.float32)
            if ch.correct and ch.name in stats:
                img = image_ops.correct_illumination(img, *stats[ch.name])
            if ch.align:
                img = image_ops.align(img, shift[0], shift[1], None)
            out[ch.name] = img
        return out

    def one_site(raw, stats, shift):
        with jax.named_scope("preprocess"):
            images = preprocess(raw, stats, shift)
        for key, val in raw.items():
            if key not in images:
                images[key] = val
        return site_fn(images)

    return jax.jit(jax.vmap(one_site, in_axes=(0, None, 0)))


@pytest.mark.parametrize("with_cycle", [False, True],
                         ids=["as_written", "cycle_0_spelled_out"])
@pytest.mark.parametrize("name", ["cp3-plate", "cp4-plate"])
def test_one_cycle_description_lowers_to_the_program_it_was(name, with_cycle):
    """The benchmark's one-cycle pipelines: the lowered module's sha256 is
    that of the composition before the change, whether or not the
    channels spell out cycle 0 — and the same cache and store key either
    way."""
    from tmlibrary_tpu.jterator.pipeline import (ImageAnalysisPipeline,
                                                 _description_cache_key,
                                                 aligned_channels)

    desc = one_cycle_description(name, with_cycle)
    assert aligned_channels(desc) == []
    now = lowered_sha256(ImageAnalysisPipeline(desc, 8).build_batch_fn(),
                         desc)
    assert now == lowered_sha256(before_the_change(desc, 8), desc)
    assert _description_cache_key(desc) == _description_cache_key(
        one_cycle_description(name, False))
    assert "cycle" not in _description_cache_key(desc)


# ------------------------------------------------------------ the step
def tiny_store(tmp_path, held=((0, "DAPI"), (0, "Actin"), (1, "DAPI"),
                               (1, "Mito"))):
    from tmlibrary_tpu.models.experiment import grid_experiment
    from tmlibrary_tpu.models.store import ExperimentStore

    exp = grid_experiment("mx", well_rows=1, well_cols=1,
                          sites_per_well=(1, 2),
                          channel_names=("DAPI", "Actin", "Mito"),
                          site_shape=(32, 32), n_cycles=2)
    store = ExperimentStore.create(tmp_path / "exp", exp)
    rng = np.random.default_rng(0)
    for cycle, name in held:
        store.write_sites(
            rng.integers(200, 4000, (2, 32, 32)).astype(np.uint16), [0, 1],
            cycle=cycle, channel=exp.channel_index(name))
    return store


def write_pipe(store, cycles) -> None:
    import yaml

    (store.root / "p.pipe.yaml").write_text(
        yaml.safe_dump(three_channel_pipe(cycles)))


@pytest.mark.parametrize("cycles, names", [
    ((None, None, 0), ("'Mito'", "cycle 0", "[1]")),
    ((None, 1, 1), ("'Actin'", "cycle 1", "[0]")),
], ids=["stain_of_a_later_cycle_asked_from_the_first",
        "stain_of_the_first_asked_from_a_later"])
def test_channel_asked_from_a_cycle_without_it_raises_before_launch(
        tmp_path, monkeypatch, cycles, names):
    from tmlibrary_tpu.errors import PipelineError
    from tmlibrary_tpu.workflow.registry import get_step

    store = tiny_store(tmp_path)
    write_pipe(store, cycles)
    step = get_step("jterator")(store)
    launched = []
    monkeypatch.setattr(type(step), "_launch",
                        lambda *a, **k: launched.append(a))
    monkeypatch.setattr(store, "read_sites",
                        lambda *a, **k: launched.append(a))
    # the description is first read while the batches are planned
    with pytest.raises(PipelineError) as raised:
        step.init({"pipe": "p.pipe.yaml", "max_objects": 8, "n_devices": 1})
        step.run(0)
    assert all(n in str(raised.value) for n in names)
    assert not launched


def test_channels_are_loaded_from_their_own_cycles(tmp_path):
    """``_load_inputs``: planes and the shift row of each channel from
    that channel's cycle; the reference cycle's rows are zero."""
    from tmlibrary_tpu.workflow.registry import get_step

    store = tiny_store(tmp_path)
    exp = store.experiment
    write_pipe(store, (None, None, 1))
    store.write_shifts(np.asarray([[2, -1], [-3, 4]], np.int32), cycle=1)
    step = get_step("jterator")(store)
    step.init({"pipe": "p.pipe.yaml", "max_objects": 8, "n_devices": 1})
    inputs = step._load_inputs(step.load_batch(0))
    np.testing.assert_array_equal(
        inputs["raw"]["Mito"],
        store.read_sites([0, 1], cycle=1, channel=exp.channel_index("Mito")))
    np.testing.assert_array_equal(
        inputs["raw"]["DAPI"],
        store.read_sites([0, 1], cycle=0, channel=exp.channel_index("DAPI")))
    assert inputs["shifts_np"].shape == (2, 3, 2)
    assert not inputs["shifts_np"][:, :2].any()
    np.testing.assert_array_equal(inputs["shifts_np"][:, 2],
                                  [[2, -1], [-3, 4]])
    assert inputs["cycles_read"] == [0, 1] and inputs["aligned_channels"] == 3


# ------------------------------------------------------ align's launch size
@pytest.mark.parametrize("device_free, host_free, want", [
    (None, None, 1 << 30),                   # a platform that says nothing
    (15_000_000_000, None, 25),              # 2160 x 2160 on a v5e chip
    (15_000_000_000, 300_000_000, 8),        # the host holds fewer
    (1_000_000, 1_000_000, 1),               # at least one
], ids=["unknown", "v5e", "host_bound", "floor"])
def test_pairs_in_flight_from_bytes(device_free, host_free, want):
    from tmlibrary_tpu.ops.registration import pairs_in_flight

    plane = 4 * 2160 * 2160
    assert pairs_in_flight(plane, device_free, host_free) == want


def test_align_launch_size_is_resolved_not_a_constant(tmp_path, monkeypatch):
    from tmlibrary_tpu.workflow.registry import get_step
    from tmlibrary_tpu.workflow.steps import illuminati

    store = tiny_store(tmp_path)
    step = get_step("align")(store)
    assert step.batch_args.resolve({})["batch_size"] == 0
    plane = 4 * 32 * 32
    monkeypatch.setattr(illuminati, "free_memory",
                        lambda: (2 * 16 * plane * 3, None))
    assert step._launch_size(step.batch_args.resolve({})) == 3
    assert step._launch_size(step.batch_args.resolve({"batch_size": 5})) == 5
    # one batch a cycle: its table is written once, whole
    batches = step.init({"ref_channel": 0})
    assert [(b["cycle"], b["sites"]) for b in batches] == [(1, [0, 1])]


def test_stored_window_is_one_margin_on_a_grid_of_16():
    from tmlibrary_tpu.ops.registration import stored_window

    four = ("top", "bottom", "left", "right")
    assert stored_window(dict.fromkeys(four, 0)) == dict.fromkeys(four, 0)
    assert stored_window({"top": 3, "bottom": 0, "left": 16, "right": 1}) \
        == dict.fromkeys(four, 16)
    assert stored_window({"top": 17, "bottom": 24, "left": 2, "right": 0}) \
        == dict.fromkeys(four, 32)
    assert stored_window(dict.fromkeys(four, 50)) == dict.fromkeys(four, 64)
