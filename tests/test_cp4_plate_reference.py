"""``cp4-plate`` through the normal path on the CPU at its rehearsal size:
one well written by ``benchmark/plate.py``, ``tmx create`` + ``tmx workflow
submit`` with the configuration's pipeline, then the plain reference's
``check`` on the store — every check true, and each family's check false
once what it reads is tampered with (a plane rounded to bfloat16, labels
shifted by a pixel, a rim pixel taken off every object).  The reference
itself against the slow loops of ``tests/test_measure.py`` and hand-worked
hulls."""

import json
import sys
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from benchmark import harness, plate  # noqa: E402

CONFIG = json.loads(
    (REPO / "benchmark" / "configs" / "cp4-plate.json").read_text())
REFERENCE = REPO / "benchmark" / "configs" / CONFIG["reference"]
FAMILIES = ("intensity", "morphology", "texture", "zernike")


@pytest.fixture(scope="module")
def reference():
    return harness.load_module(str(REFERENCE))


@pytest.fixture(scope="module")
def store(tmp_path_factory):
    """One well at 64x64, capacity 16, 3-7 cells a field, submitted as
    ``benchmark/drivers/plate.py`` submits it."""
    from tmlibrary_tpu.models.store import ExperimentStore

    work = tmp_path_factory.mktemp("cp4")
    config = harness.at_size(CONFIG, on_chip=False)
    src = str(work / "src")
    plate.write_plate(src, plate.well_names(1), config["fields_per_well"],
                      config["field_size"], (3, 7), config["channels"], 11)
    root = str(work / "exp")
    harness.tmx(["create", "--name", "exp", "--root", root])
    wf = plate.write_description(root, src, config, config["max_objects"])
    harness.tmx(["workflow", "submit", "--description", wf, "--root", root])
    return ExperimentStore.open(Path(root))


class Tampered:
    """A store whose label stacks or pixel planes are read through
    ``labels(stack, name)`` / ``planes(stack, channel)``; the feature
    tables stay the program's."""

    def __init__(self, store, labels=None, planes=None):
        self._store, self._labels, self._planes = store, labels, planes

    def __getattr__(self, name):
        return getattr(self._store, name)

    def read_labels(self, sites, name, **kw):
        stack = self._store.read_labels(sites, name, **kw)
        return stack if self._labels is None else self._labels(stack, name)

    def read_sites(self, sites, channel=0, **kw):
        stack = self._store.read_sites(sites, channel=channel, **kw)
        return stack if self._planes is None else self._planes(stack, channel)


def to_bf16(stack, channel):
    import ml_dtypes

    return stack.astype(np.float32).astype(ml_dtypes.bfloat16) \
        .astype(np.float32)


def shifted(stack, name):
    return np.roll(stack, 1, axis=-1)


def rim_pixel_off(stack, name):
    """Every object loses its first pixel in scan order."""
    out = stack.copy()
    for site in out:
        for obj in np.unique(site)[1:]:
            ys, xs = np.nonzero(site == obj)
            if len(ys) > 1:
                site[ys[0], xs[0]] = 0
    return out


SITES = [0, 4, 8]


def test_counts_equal_the_scipy_chain(store, reference):
    verdict = reference.check(store, SITES, CONFIG)
    assert verdict["checks"]["counts_equal_scipy_chain"], verdict["info"]
    assert sum(verdict["info"]["reference_counts"]["nuclei"]) > 0


@pytest.mark.parametrize("family", FAMILIES)
def test_family_is_within_its_tolerance(store, reference, family):
    verdict = reference.check(store, SITES, CONFIG)
    worst = verdict["info"]["worst_error_over_allowed"][family]
    assert verdict["checks"][f"{family}_within_tolerance"], worst
    assert worst["values_compared"] > 0 and worst["ratio"] <= 1.0
    print(f"{family}: worst error over allowed {worst['ratio']:.3g} "
          f"at {worst['at']}")


@pytest.mark.parametrize("family,tamper", [
    ("intensity", {"planes": to_bf16}),
    ("texture", {"planes": to_bf16}),
    ("texture", {"labels": shifted}),
    ("morphology", {"labels": rim_pixel_off}),
    ("zernike", {"labels": rim_pixel_off}),
], ids=["intensity-bf16_plane", "texture-bf16_plane",
        "texture-labels_shifted", "morphology-rim_pixel_off",
        "zernike-rim_pixel_off"])
def test_family_fails_on_what_a_lower_precision_or_a_moved_pixel_gives(
        store, reference, family, tamper):
    verdict = reference.check(Tampered(store, **tamper), SITES, CONFIG)
    worst = verdict["info"]["worst_error_over_allowed"][family]
    assert not verdict["checks"][f"{family}_within_tolerance"], worst
    print(f"{family}: tampered reads {worst['ratio']:.3g} times the "
          f"allowance at {worst['at']}")


def test_reference_shares_no_code_with_the_package():
    text = REFERENCE.read_text()
    assert "tmlibrary_tpu" not in text.replace(
        "``tmlibrary_tpu", "")  # the docstring may name it
    assert "import jax" not in text and "from benchmark" not in text


def test_every_limit_is_stated_once_and_with_a_family(reference):
    families = {key.partition(".")[0] for key in reference.LIMITS}
    assert families == set(FAMILIES)
    morph = [k for k in reference.LIMITS if k.startswith("morphology.")]
    assert len(morph) == 14   # the 13 device features and solidity


# ------------------------------------------------- the reference on its own
def _blob_field(rng, size=48, n=5):
    yy, xx = np.mgrid[0:size, 0:size]
    lab = np.zeros((size, size), np.int64)
    for i in range(n):
        y, x = rng.integers(8, size - 8, 2)
        r = rng.uniform(3.0, 6.0)
        lab[(yy - y) ** 2 + ((xx - x) * rng.uniform(0.6, 1.0)) ** 2
            <= r * r] = i + 1
    ids = np.unique(lab)[1:]
    relabel = np.zeros(n + 1, np.int64)
    relabel[ids] = np.arange(1, len(ids) + 1)
    return relabel[lab], len(ids)


def test_haralick_agrees_with_the_pixel_loops_of_test_measure(reference):
    from tests.test_measure import _haralick_reference_numpy

    rng = np.random.default_rng(3)
    lab, n = _blob_field(rng)
    img = rng.integers(200, 4000, lab.shape).astype(np.float32)
    got = reference.haralick(lab, img, n, 16, 1)
    for obj in range(1, n + 1):
        want = _haralick_reference_numpy(img.astype(np.float64), lab == obj,
                                         levels=16)
        np.testing.assert_allclose(got[obj - 1], want, rtol=1e-9, atol=1e-12)


def test_zernike_agrees_with_the_reference_of_test_measure(reference):
    from tests.test_measure import _zernike_reference_numpy

    lab, n = _blob_field(np.random.default_rng(4))
    got = reference.zernike(lab, n, 6)
    for obj in range(1, n + 1):
        want = _zernike_reference_numpy(lab == obj, 6)
        for (order, m), values in got.items():
            assert values[obj - 1] == pytest.approx(
                want[f"Zernike_{order}_{m}"], rel=1e-9, abs=1e-12)


@pytest.mark.parametrize("points,want", [
    # a filled 4x4 square: itself
    ([(y, x) for y in range(4) for x in range(4)], 16),
    # the corners of that square alone: the hull fills it
    ([(0, 0), (0, 3), (3, 0), (3, 3)], 16),
    # an L of 5x5 with arm 1: the triangle under the diagonal, 15 centres
    ([(y, 0) for y in range(5)] + [(4, x) for x in range(1, 5)], 15),
    # one row
    ([(2, 1), (2, 5)], 5),
], ids=["square", "corners", "L", "row"])
def test_hull_pixel_count_of_hand_worked_shapes(reference, points, want):
    ys, xs = np.array(points).T
    assert reference.hull_pixel_count(ys, xs) == want


def test_hull_agrees_with_the_programs_monotone_chain(reference):
    """Two methods, one count: the reference's row-by-row cross-sections
    against the package's monotone chain and edge test."""
    from tmlibrary_tpu.native import hull_pixel_counts_host

    lab, n = _blob_field(np.random.default_rng(9), size=64, n=8)
    want = hull_pixel_counts_host(lab.astype(np.int32), n)
    for obj in range(1, n + 1):
        ys, xs = np.nonzero(lab == obj)
        assert reference.hull_pixel_count(ys, xs) == want[obj - 1]
