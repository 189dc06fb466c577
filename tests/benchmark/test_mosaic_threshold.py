"""The threshold guarantee of ``cp3-mosaic.reference.py`` (PR 34): Otsu's
threshold is an argmax, and where the reference's own criterion holds two
bins within the arithmetic's resolution of each other, each is Otsu's
threshold.  PR 33's check held the program's mask to the centre of the
reference's argmax bin and was refused on a well whose bins 129 and 130
tie to 6.8e-7.  Held now: (a) the cut the program says it used is the
centre of a bin of the reference's own histogram whose criterion is within
``otsu_tie_rel`` of the maximum, (b) the mask is the reference's plane over
THAT cut outside the band, (c) with equal masks the count is scipy's.

The planes here are made so that two adjacent bins tie to 1e-9; the
program is the sharded Otsu itself, on four host devices."""

import json
import sys
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from benchmark import harness, mosaic  # noqa: E402

CONFIG = json.loads(
    (REPO / "benchmark" / "configs" / "cp3-mosaic.json").read_text())
LO, WIDTH, SIDE = 250.0, 1.3, 200


REFERENCE = harness.load_module(
    str(REPO / "benchmark" / "configs" / CONFIG["reference"]))


@pytest.fixture(scope="module")
def reference():
    return REFERENCE


@pytest.fixture(scope="module")
def mesh(devices):
    import jax
    from jax.sharding import Mesh

    return Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2),
                ("rows", "cols"))


def between_of(hist: np.ndarray) -> np.ndarray:
    """The reference's criterion for counts at unit-spaced centres (its
    ratios do not change under an affine map of the centres)."""
    return REFERENCE.between_class(hist, np.arange(len(hist)) + 0.5)


def gap(hist: np.ndarray, a: int) -> float:
    between = between_of(hist)
    return float((between[a + 1] - between[a]) / between.max())


def tied_histogram(seed: int, n: int = SIDE * SIDE) -> tuple:
    """``(hist, a)``: 256 counts summing to ``n``, two modes and a
    populated valley, whose criterion ties bins ``a`` and ``a + 1`` to
    1e-9 of its maximum; None for a seed on which that fails.  Counts are
    whole numbers, so the tie is reached by moving single pixels from a bin into another: the three such moves
    whose effects on the gap, taken as additive, cancel it best."""
    rng = np.random.default_rng(seed)
    j = np.arange(256)
    density = (0.8 * np.exp(-0.5 * ((j - 40) / 14.0) ** 2)
               + 0.2 * np.exp(-0.5 * ((j - 190) / 30.0) ** 2) + 0.1)
    hist = rng.multinomial(n - 2, density / density.sum())
    hist[[0, 255]] += 1                 # the two pixels that set the range
    between = between_of(hist)
    k = int(np.argmax(between))
    a = k if between[k + 1] >= between[k - 1] else k - 1
    # (from, to): one pixel of a bin into another, 600 such pairs, none
    # of them of the bins at the tie (those stay as they were drawn)
    free = np.setdiff1d(np.arange(2, 254), np.arange(a - 3, a + 5))
    moves = rng.permuted(np.tile(free, (600, 1)), axis=1)[:, :2]

    def move(i, sign=1):
        hist[moves[i, 0]] -= sign
        hist[moves[i, 1]] += sign

    for _ in range(300):
        now = gap(hist, a)
        if abs(now) <= 1e-9:
            break
        effect = np.empty(len(moves))
        for i in range(len(moves)):
            move(i)
            effect[i] = gap(hist, a) - now
            move(i, -1)
        if abs(now) > 2.5 * np.abs(effect).max():
            # further than three moves reach: the one that helps most
            effect[hist[moves[:, 0]] < 1] = 0.0
            move(int(np.argmin(effect * np.sign(now))))
            continue
        first, second = np.triu_indices(len(moves), 1)
        pairs = effect[first] + effect[second]
        order = np.argsort(pairs)
        at = np.clip(np.searchsorted(pairs[order], -now - effect), 1,
                     len(order) - 1)
        best = None
        for third, p in ((t, p) for t in range(len(moves))
                         for p in (at[t] - 1, at[t])):
            trio = [third, first[order[p]], second[order[p]]]
            # three different pixels: no bin gives more than it has
            if len(set(moves[trio, 0])) < 3 \
                    or hist[moves[trio, 0]].min() < 1:
                continue
            left = abs(now + effect[third] + pairs[order[p]])
            if best is None or left < best[0]:
                best = (left, trio)
        if best is None:
            return None
        for i in best[1]:
            move(i)
    if abs(gap(hist, a)) > 1e-9:
        return None
    assert hist.sum() == n and hist.min() >= 0
    return hist, a


def plane_of(hist: np.ndarray, seed: int) -> np.ndarray:
    """A float64 ``SIDE`` x ``SIDE`` plane with ``hist`` as its 256-bin
    histogram over its own range: a bin's pixels lie in the middle three
    fifths of it (so a range moved by 1e-4 of itself moves none into
    another bin), the darkest sits on ``LO`` and the brightest on the top
    of the range; shuffled."""
    rng = np.random.default_rng(seed)
    values = np.concatenate([
        LO + WIDTH * (b + rng.uniform(0.2, 0.8, count))
        for b, count in enumerate(hist)])
    values[0] = LO                                   # a pixel of bin 0
    values[-1] = LO + 256 * WIDTH                    # a pixel of bin 255
    return rng.permutation(values).reshape(SIDE, SIDE)


def program(plane: np.ndarray, mesh) -> tuple:
    """``(mask, cut)`` as the sharded Otsu gives them for float32."""
    from tmlibrary_tpu.parallel.label import sharded_otsu_mask

    mask, reading = sharded_otsu_mask(np.asarray(plane, np.float32), mesh)
    return np.array(mask), float(reading["cut"])


def guarantee(reference, plane, mask, cut) -> dict:
    import scipy.ndimage as ndi

    n = int(ndi.label(mask, ndi.generate_binary_structure(2, 2))[1])
    compared, info = reference.threshold_guarantee(plane, mask, cut, n)
    wrong = sorted(name for name, (number, limit) in compared.items()
                   if number > limit)
    return {"wrong": wrong, "compared": compared, **info}


@pytest.fixture(scope="module")
def tied():
    """``[(plane, a)]``: planes whose bins ``a`` and ``a + 1`` tie."""
    out = []
    for seed in range(100):
        made = tied_histogram(seed)
        if made is not None:
            out.append((plane_of(made[0], seed), made[1]))
        if len(out) == 3:
            return out


def test_the_made_planes_tie_two_adjacent_bins_and_no_third(tied, reference):
    for plane, a in tied:
        between, lo, width = reference.criterion(plane)
        assert (lo, width) == (LO, pytest.approx(WIDTH, rel=1e-12))
        top = np.sort(between)[::-1]
        assert {int(np.argmax(between)),
                int(np.argsort(between)[-2])} == {a, a + 1}
        assert (top[0] - top[1]) / top[0] <= 1e-9
        # the third bin is no tie: it has to fail where it is picked
        assert (top[0] - top[2]) / top[0] > \
            2 * reference.LIMITS["otsu_tie_rel"][0]


def test_a_tie_cannot_fail_the_program(tied, reference, mesh):
    """The program's plane is the reference's in float32 with its
    brightest pixel moved by 1e-4 of itself, either way.  Whichever of the
    tied bins the program's argmax takes, it is held to its own cut and
    is correct; on some plane it takes the OTHER bin than the reference's
    argmax, which is what PR 33's check — the mask against the centre of
    the reference's argmax bin — refused."""
    other_bin = refused_before = 0
    for plane, a in tied:
        for moved in (1.0 - 1e-4, 1.0, 1.0 + 1e-4):
            seen = plane.astype(np.float32)
            seen[np.unravel_index(np.argmax(seen), seen.shape)] *= moved
            mask, cut = program(seen, mesh)
            got = guarantee(reference, plane, mask, cut)
            assert got["wrong"] == [], got["compared"]
            held = got["otsu"]
            assert held["bin"] in (a, a + 1)
            assert held["runner_up_below_max_rel"] <= 1e-9
            if held["bin"] != held["reference_bin"]:
                other_bin += 1
                old = plane > held["reference_cut"]
                band = reference.LIMITS["threshold_band_rel"][0] \
                    * held["reference_cut"]
                refused_before += int(np.count_nonzero(
                    (old != mask)
                    & (np.abs(plane - held["reference_cut"]) > band)) > 0)
    assert other_bin > 0 and refused_before == other_bin


def test_a_cut_two_bins_away_is_no_otsu_threshold(tied, reference):
    """A mask that is exactly the plane over its cut, the cut exactly a
    bin's centre — two bins beside the tie: caught by the criterion."""
    plane, a = tied[0]
    cut = LO + (a + 3 + 0.5) * WIDTH
    got = guarantee(reference, plane, plane > cut, cut)
    assert got["wrong"] == ["otsu_cut_below_max_rel"]
    assert got["otsu"]["bin"] == a + 3
    cut = LO + (a - 2 + 0.5) * WIDTH
    assert guarantee(reference, plane, plane > cut, cut)["wrong"] == \
        ["otsu_cut_below_max_rel"]


def test_a_cut_between_two_centres_is_no_otsu_threshold(tied, reference):
    """Half a bin off the centre of the tied bin: a cut that no 256-bin
    histogram over this plane's range gives."""
    plane, a = tied[0]
    cut = LO + (a + 1.0) * WIDTH
    got = guarantee(reference, plane, plane > cut, cut)
    assert "otsu_cut_off_bin_center" in got["wrong"]
    assert got["compared"]["mask_faults"] == (0, 0)


def test_one_pixel_outside_the_band_fails_the_mask(tied, reference, mesh):
    plane, _ = tied[0]
    mask, cut = program(plane, mesh)
    assert guarantee(reference, plane, mask, cut)["wrong"] == []
    band = reference.LIMITS["threshold_band_rel"][0] * cut
    y, x = np.argwhere(np.abs(plane - cut) > 2 * band)[0]
    mask[y, x] = ~mask[y, x]
    got = guarantee(reference, plane, mask, cut)
    assert got["wrong"] == ["mask_faults"]
    assert got["mask_pixels_outside_band"] == 1
    # inside the band a pixel may differ: the count is then not held
    mask[y, x] = ~mask[y, x]
    y, x = np.argwhere(np.abs(plane - cut) < band / 2)[0]
    mask[y, x] = ~mask[y, x]
    got = guarantee(reference, plane, mask, cut)
    assert got["wrong"] == [] and got["mask_pixels_differing"] == 1


def test_equal_masks_hold_the_count(tied, reference):
    """(c): the labels are scipy's of their own foreground, so with the
    masks equal a count that differs is a table that lies."""
    plane, a = tied[0]
    cut = LO + (a + 0.5) * WIDTH
    compared, info = reference.threshold_guarantee(
        plane, plane > cut, cut, n_objects=1)
    assert info["mask_pixels_differing"] == 0
    assert compared["mask_faults"] == (abs(info["nuclei_minus_chain"]), 0)
    assert compared["mask_faults"][0] > 0


def test_a_cut_from_the_unsmoothed_plane_is_no_otsu_threshold(reference):
    """The well of a rehearsal: Otsu's cut of the raw DAPI plane, held
    against the smoothed plane the chain thresholds."""
    import scipy.ndimage as ndi

    size = harness.at_size(CONFIG, on_chip=False)["field_size"]
    planes, _ = mosaic.draw_well(11, size, 3, 9, (3, 7), ["DAPI"])
    raw = planes["DAPI"].astype(np.float64)
    smooth = ndi.gaussian_filter(raw, 1.5, mode="reflect")
    between, lo, width = reference.criterion(raw)
    cut = lo + (int(np.argmax(between)) + 0.5) * width
    got = guarantee(reference, smooth, smooth > cut, cut)
    assert got["compared"]["mask_faults"] == (0, 0)
    assert set(got["wrong"]) & {"otsu_cut_off_bin_center",
                                "otsu_cut_below_max_rel"}
    # and the smoothed plane's own cut passes
    between, lo, width = reference.criterion(smooth)
    cut = lo + (int(np.argmax(between)) + 0.5) * width
    assert guarantee(reference, smooth, smooth > cut, cut)["wrong"] == []


def test_the_refused_seeds_own_well_ties_bins_129_and_130(reference):
    """Seed 1996743581 at the cell's own size (PR 33's refusal): the
    reference's criterion of the smoothed, corrected DAPI mosaic holds
    bins 129 and 130 within 1e-6 of each other, the cuts 1.31 apart.
    Half a minute and 4 GB, numpy alone; its twin at the rehearsal size,
    through the program, is ``tests/test_cp3_mosaic.py``'s tie test."""
    import scipy.ndimage as ndi

    from benchmark import plate

    traffic = json.loads(
        (REPO / "benchmark" / "traffic" / "x4.json").read_text())
    planes, n_cells = mosaic.draw_well(
        1996743581, CONFIG["field_size"], 3, 9,
        plate.parse_range(traffic["cells_per_field"]), CONFIG["channels"])
    assert n_cells == 4610
    size = CONFIG["field_size"]
    raw = np.stack([planes["DAPI"][y * size:(y + 1) * size,
                                   x * size:(x + 1) * size]
                    for y in range(3) for x in range(3)])
    del planes
    fixed = reference.corrected(raw, *reference.statistics(raw))
    smooth = ndi.gaussian_filter(
        reference.stitch(fixed.astype(np.float32), 3).astype(np.float64),
        1.5, mode="reflect")
    between, lo, width = reference.criterion(smooth)
    top = np.argsort(between)[::-1]
    assert set(top[:2].tolist()) == {129, 130}
    assert (between[top[0]] - between[top[1]]) / between[top[0]] < 1e-6
    assert (between[top[0]] - between[128]) / between[top[0]] > 3e-5
    assert width == pytest.approx(1.311, abs=2e-3)
