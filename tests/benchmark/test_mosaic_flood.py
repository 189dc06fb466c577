"""The partition guarantee of ``cp3-mosaic.reference.py`` (PR 34, after its
review): the cells are held to the reference's own plain level-ordered
flood, pixel for pixel, and to stopping only where the second stain is not
over its cut.  The structural numbers alone (nucleus id, nucleus
contained, connected, counts, nothing under the cut) pass a watershed in
which no chip hands its neighbour the labels at its edge: every cell then
stops at the mesh seam, and is still a sound cell.

The program is the sharded watershed itself, on four host devices."""

import json
import sys
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from benchmark import harness  # noqa: E402

CONFIG = json.loads(
    (REPO / "benchmark" / "configs" / "cp3-mosaic.json").read_text())
REFERENCE = harness.load_module(
    str(REPO / "benchmark" / "configs" / CONFIG["reference"]))
SIDE, LEVELS = 192, CONFIG["jterator"]["spatial_secondary_levels"]
SEAMS = [[SIDE // 2], [SIDE // 2]]
LIMIT = REFERENCE.LIMITS["cells_unlike_flood_rel"][0]


def well(seed: int) -> tuple:
    """``(stain, nuclei, cut)``: a smooth float32 plane, 3 x 3 seeds in
    scan order scattered over it (some on the seams), a cut that leaves
    two thirds of the plane open."""
    import scipy.ndimage as ndi

    rng = np.random.default_rng(seed)
    stain = (1000 * ndi.gaussian_filter(rng.random((SIDE, SIDE)), 4)
             ).astype(np.float32)
    spots = np.zeros((SIDE, SIDE), bool)
    for y, x in rng.integers(2, SIDE - 4, (60, 2)):
        spots[y:y + 3, x:x + 3] = True
    spots[SIDE // 2 - 1:SIDE // 2 + 2, 40:43] = True     # across a seam
    spots[100:103, SIDE // 2 - 1:SIDE // 2 + 2] = True
    nuclei = ndi.label(spots, np.ones((3, 3), bool))[0].astype(np.int32)
    return stain, nuclei, float(np.quantile(stain, 1 / 3))


@pytest.fixture(scope="module")
def mesh(devices):
    import jax
    from jax.sharding import Mesh

    return Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2),
                ("rows", "cols"))


def program(stain, nuclei, cut, mesh) -> np.ndarray:
    from tmlibrary_tpu.parallel.label import watershed_mosaic

    labels, _ = watershed_mosaic(stain, nuclei, stain > cut, mesh,
                                 n_levels=LEVELS)
    return np.asarray(labels)


@pytest.fixture
def halo_off(monkeypatch):
    """The watershed's adopt step with its halo left out: a chip sees
    zeros beyond its edge.  The jitted program is cached by its mesh."""
    import jax.numpy as jnp

    from tmlibrary_tpu.parallel import label

    label._cached_watershed.cache_clear()
    monkeypatch.setattr(label, "_halo1_zero_2d",
                        lambda x, row_axis, col_axis: jnp.pad(x, 1))
    yield
    label._cached_watershed.cache_clear()


# ------------------------------------------------------------ the flood
def test_a_tie_goes_to_the_larger_label_and_a_seed_keeps_its_own():
    stain = np.full((1, 7), 5.0, np.float32)
    seeds = np.array([[2, 0, 0, 0, 0, 0, 1]], np.int32)
    got = REFERENCE.flood(stain, seeds, np.ones((1, 7), bool), 4)
    # both reach the middle pixel in the third step: the larger takes it
    assert got.tolist() == [[2, 2, 2, 2, 1, 1, 1]]


def test_brighter_pixels_are_claimed_before_dimmer_ones():
    """A bright ridge runs from seed 1 to one dim pixel before seed 2:
    seed 1 takes all of it in the first band although seed 2, the larger
    label, is nearer to most of it; on one flat plane seed 2 takes the
    larger half."""
    stain = np.full((3, 9), 10.0, np.float32)
    stain[1, :7] = 100.0
    seeds = np.zeros((3, 9), np.int32)
    seeds[1, 0], seeds[1, 8] = 1, 2
    got = REFERENCE.flood(stain, seeds, np.ones((3, 9), bool), 4)
    assert (got[1, :7] == 1).all() and (got[1, 7:] == 2).all()
    flat = REFERENCE.flood(np.full((3, 9), 10.0, np.float32), seeds,
                           np.ones((3, 9), bool), 4)
    assert (flat[1, :4] == 1).all() and (flat[1, 4:] == 2).all()


def test_what_is_not_allowed_is_not_flooded():
    stain = np.array([[9, 9, 1, 9, 9]], np.float32)
    seeds = np.array([[1, 0, 0, 0, 0]], np.int32)
    got = REFERENCE.flood(stain, seeds, stain > 5, 4)
    assert got.tolist() == [[1, 1, 0, 0, 0]]


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_flood_is_the_single_device_watershed(seed):
    """The reference shares no code with ``watershed_from_seeds``; on one
    float32 plane the two give one label image."""
    import jax.numpy as jnp

    from tmlibrary_tpu.ops.segment_secondary import watershed_from_seeds

    stain, nuclei, cut = well(seed)
    got = np.asarray(watershed_from_seeds(
        jnp.asarray(stain), jnp.asarray(nuclei), jnp.asarray(stain > cut),
        n_levels=LEVELS, method="xla"))
    want = REFERENCE.flood(stain, nuclei, stain > cut, LEVELS)
    assert np.array_equal(got, want) and (want > 0).sum() > SIDE * SIDE // 2


# -------------------------------------------------------- the guarantee
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_sharded_watershed_is_the_flood(seed, mesh):
    stain, nuclei, cut = well(seed)
    cells = program(stain, nuclei, cut, mesh)
    left, share, info = REFERENCE.partition_guarantee(
        stain, nuclei, cells, cut, LEVELS, SEAMS)
    assert (left, share, info["pixels_unlike"]) == (0, 0.0, 0)
    assert info["cells_of_the_flood_across_mesh_seams"] >= 2


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_a_watershed_without_its_halo_is_not(seed, mesh, halo_off):
    """Every cell is sound — its nucleus' id, its nucleus inside, one
    piece, nothing under the cut — and stops at the seam."""
    import scipy.ndimage as ndi

    stain, nuclei, cut = well(seed)
    cells = program(stain, nuclei, cut, mesh)
    seeded = nuclei > 0
    assert np.array_equal(cells[seeded], nuclei[seeded])
    assert not ((cells > 0) & ~seeded & (stain <= cut)).any()
    for i, box in enumerate(ndi.find_objects(cells)):
        assert ndi.label(cells[box] == i + 1, np.ones((3, 3), bool))[1] == 1
    left, share, info = REFERENCE.partition_guarantee(
        stain, nuclei, cells, cut, LEVELS, SEAMS)
    assert share > 3 * LIMIT, info
    assert info["their_pixels_unlike"] == info["pixels_unlike"] > 0


def test_a_cell_that_stops_short_of_open_stain_is_counted(mesh):
    """Eat a cell back from its rim where the stain is well over the
    cut: what is left beside it is counted, pixel for pixel."""
    import scipy.ndimage as ndi

    stain, nuclei, cut = well(1)
    cells = program(stain, nuclei, cut, mesh)
    biggest = int(np.argmax(np.bincount(cells.ravel())[1:])) + 1
    rim = (cells == biggest) & ~ndi.binary_erosion(cells == biggest) \
        & (nuclei == 0) & (stain > cut * 1.02)
    assert rim.sum() > 10
    eaten = np.where(rim, 0, cells)
    left, share, _ = REFERENCE.partition_guarantee(
        stain, nuclei, eaten, cut, LEVELS, SEAMS)
    assert left >= rim.sum() * 0.5 and share > 0


def test_the_limit_says_its_two_readings():
    limit, why = REFERENCE.LIMITS["cells_unlike_flood_rel"]
    assert 0 < limit < 1e-2
    for word in ("chip", "halo", "seam"):
        assert word in why
    assert "cells_unlike_flood_rel" in \
        REFERENCE.DECIDES["cells_are_the_flood_of_their_nuclei"]
