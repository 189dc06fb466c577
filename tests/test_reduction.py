"""Two-strategy suite for the segmented-reduction layer (``ops/reduction.py``).

Tier-1 runs on the CPU backend, where ``auto`` means ``scatter``; the chip
runs ``onehot``.  So everything here runs under BOTH, pinned from the test
(the measure functions that take no strategy argument ask
``measure.resolve_reduction_strategy``, which ``conftest.py``'s
``pin_strategy`` replaces):

* the determinism contract — ``onehot`` against ``scatter``: min/max,
  counts and integer sums bit-exact, fractional f32 sums within 1e-6;
* the family matrix — intensity, morphology, quantiles, Haralick and
  Zernike on dense, sparse and saturated-rung sites;
* the capacity-rung invariance the bucket router stands on
  (``capacity_segments``): rows ``0..n`` bit-identical between the rung a
  site's count selects and a rung two higher;
* the decision itself: the backend, and nothing else, chooses.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tmlibrary_tpu import capacity
from tmlibrary_tpu.ops import measure as M
from tmlibrary_tpu.ops import reduction as R

MAX_OBJECTS = 11
STRATEGIES = R.STRATEGIES


@pytest.fixture
def site(rng):
    """(labels, uint16-valued image, fractional image) on a 64x64 site."""
    labels = np.zeros((64, 64), np.int32)
    ys = rng.integers(4, 60, MAX_OBJECTS)
    xs = rng.integers(4, 60, MAX_OBJECTS)
    for i, (y, x) in enumerate(zip(ys, xs), start=1):
        labels[max(0, y - 3) : y + 3, max(0, x - 3) : x + 3] = i
    integral = rng.integers(0, 4096, (64, 64)).astype(np.float32)
    fractional = rng.random((64, 64), np.float32) * 1000.0
    return (
        jnp.asarray(labels),
        jnp.asarray(integral),
        jnp.asarray(fractional),
    )


# ------------------------------------------------------------- primitives
def test_primitives_absent_segment_identities(rng):
    vals = jnp.asarray(rng.random(100, np.float32))
    ids = jnp.zeros(100, jnp.int32)
    assert np.all(np.asarray(R.segmented_min(vals, ids, 3))[1:] == np.inf)
    assert np.all(np.asarray(R.segmented_max(vals, ids, 3))[1:] == -np.inf)
    assert np.all(np.asarray(R.segmented_sum(vals, ids, 3))[1:] == 0.0)


@pytest.mark.parametrize("name", ["bogus", "sort", "fused"])
def test_unknown_strategy_raises(name):
    """The two retired names are as unknown as any other."""
    with pytest.raises(ValueError):
        R.resolve_reduction_strategy(name)


# -------------------------------------------------------- measure parity
@pytest.mark.parametrize("strategy", STRATEGIES)
def test_grouped_sums_integral_bit_identical(site, strategy):
    """uint16-valued pixels: per-object sums < 2^24 are exact in f32, so
    both strategies are bit-identical to the one-hot matmul reference."""
    labels, integral, _ = site
    ref = M.grouped_sums(labels, [integral, integral * 2.0], MAX_OBJECTS, "matmul")
    out = M.grouped_sums(labels, [integral, integral * 2.0], MAX_OBJECTS, strategy)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_grouped_sums_fp32_tolerance_contract(site):
    """Fractional f32 values: the scatter accumulates in pixel order, the
    one-hot in the contraction's — within the documented 1e-6 relative."""
    labels, _, fractional = site
    ref = M.grouped_sums(labels, [fractional], MAX_OBJECTS, "onehot")
    sct = M.grouped_sums(labels, [fractional], MAX_OBJECTS, "scatter")
    np.testing.assert_allclose(np.asarray(sct), np.asarray(ref), rtol=1e-6)


@pytest.mark.parametrize("strategy", STRATEGIES)
def test_grouped_minmax_bit_identical(site, strategy):
    """min/max are accumulation-order-free: bit-exact for both."""
    labels, _, fractional = site
    mn_r, mx_r = M.grouped_minmax(labels, fractional, MAX_OBJECTS, "reduce")
    mn, mx = M.grouped_minmax(labels, fractional, MAX_OBJECTS, strategy)
    np.testing.assert_array_equal(np.asarray(mn), np.asarray(mn_r))
    np.testing.assert_array_equal(np.asarray(mx), np.asarray(mx_r))


@pytest.mark.parametrize("strategy", STRATEGIES)
def test_grouped_minmax_multi_bit_identical(site, strategy):
    labels, integral, fractional = site
    chans = [integral, fractional]
    mn_r, mx_r = M.grouped_minmax_multi(labels, chans, MAX_OBJECTS, "reduce")
    mn, mx = M.grouped_minmax_multi(labels, chans, MAX_OBJECTS, strategy)
    np.testing.assert_array_equal(np.asarray(mn), np.asarray(mn_r))
    np.testing.assert_array_equal(np.asarray(mx), np.asarray(mx_r))


@pytest.mark.parametrize("strategy", STRATEGIES)
def test_intensity_quantiles_bit_identical(site, strategy):
    """Histogram counts are integers — exact in f32 for both."""
    labels, integral, _ = site
    ref = M.intensity_quantiles(labels, integral, MAX_OBJECTS, method="onehot")
    out = M.intensity_quantiles(labels, integral, MAX_OBJECTS, method=strategy)
    assert set(out) == set(ref)
    for key in ref:
        np.testing.assert_array_equal(np.asarray(out[key]), np.asarray(ref[key]))


@pytest.mark.parametrize("strategy", STRATEGIES)
def test_haralick_glcm_bit_identical(site, strategy):
    """GLCM cells are integer counts; every downstream Haralick feature is
    the same f32 expression tree over them — bit-exact across methods."""
    labels, integral, _ = site
    ref = M.haralick_features(labels, integral, MAX_OBJECTS, levels=8,
                              glcm_method="matmul")
    out = M.haralick_features(labels, integral, MAX_OBJECTS, levels=8,
                              glcm_method=strategy)
    assert set(out) == set(ref)
    for key in ref:
        np.testing.assert_array_equal(np.asarray(out[key]), np.asarray(ref[key]))


# ------------------------------------------------ family x site x strategy
def _dense(rng):
    """Most pixels labeled: 9 fat blobs tiling a 64x64 site."""
    labels = np.zeros((64, 64), np.int32)
    k = 1
    for r in range(0, 63, 21):
        for c in range(0, 63, 21):
            labels[r : r + 20, c : c + 20] = k
            k += 1
    return labels


def _sparse(rng):
    """Three small objects in a mostly-background site."""
    labels = np.zeros((64, 64), np.int32)
    for i, (y, x) in enumerate([(5, 5), (30, 48), (55, 12)], start=1):
        labels[y : y + 4, x : x + 4] = i
    return labels


def _saturated(rng):
    """Every object slot up to MAX_OBJECTS populated — the full-rung
    site the bucket router escalates to."""
    labels = np.zeros((64, 64), np.int32)
    ys = rng.integers(4, 58, MAX_OBJECTS)
    xs = rng.integers(4, 58, MAX_OBJECTS)
    for i, (y, x) in enumerate(zip(ys, xs), start=1):
        labels[y : y + 5, x : x + 5] = i
    return labels


SITES = {"dense": _dense, "sparse": _sparse, "saturated": _saturated}

#: family -> (call under a pinned strategy, keys whose f32 accumulation
#: order differs between the strategies: 1e-5 relative, not bit-exact)
FAMILIES = {
    # "xla": off the CPU's native C pass, onto the grouped reductions
    "intensity": (
        lambda lab, img, cap, s: M.intensity_features(lab, img, cap, method="xla"),
        ("mean", "std"),  # ride the sum of squares, past 2^24
    ),
    # area/perimeter/bbox are exact-integer or order-free; the moment sums
    # behind the rest square fractional pixel offsets
    "morphology": (
        lambda lab, img, cap, s: M.morphology_features(lab, cap),
        ("axis_length", "eccentricity", "orientation", "form_factor",
         "extent", "equivalent_diameter", "centroid"),
    ),
    "quantiles": (
        lambda lab, img, cap, s: M.intensity_quantiles(lab, img, cap), ()),
    # the reductions follow the pin, the GLCM its own argument
    # ("onehot" there names the contraction)
    "haralick": (
        lambda lab, img, cap, s: M.haralick_features(
            lab, img, cap, levels=8, glcm_method=s),
        (),
    ),
    # "xla": off the CPU's host twin; every projection is a fractional sum
    "zernike": (
        lambda lab, img, cap, s: M.zernike_features(
            lab, cap, degree=6, method="xla"),
        ("Zernike",),
    ),
}


@pytest.fixture(params=sorted(SITES))
def family_site(request, rng):
    labels = SITES[request.param](rng)
    img = rng.integers(0, 4096, (64, 64)).astype(np.float32)
    return jnp.asarray(labels), jnp.asarray(img)


def _family(pin_strategy, family, strategy, labels, img, cap):
    pin_strategy(strategy)
    return FAMILIES[family][0](labels, img, cap, strategy)


@pytest.mark.parametrize("strategy", STRATEGIES)
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_family_parity(family_site, family, strategy, pin_strategy):
    """Every family against its ``onehot`` row — the chip's path, which
    ``auto`` never reaches on this backend."""
    labels, img = family_site
    ref = _family(pin_strategy, family, "onehot", labels, img, MAX_OBJECTS)
    out = _family(pin_strategy, family, strategy, labels, img, MAX_OBJECTS)
    loose = FAMILIES[family][1]
    assert sorted(out) == sorted(ref)
    for key in ref:
        a, b = np.asarray(out[key]), np.asarray(ref[key])
        if any(tag in key for tag in loose):
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6, err_msg=key)
        else:
            np.testing.assert_array_equal(a, b, err_msg=key)


@pytest.mark.parametrize("strategy", STRATEGIES)
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_capacity_rung_invariance(family_site, family, strategy, pin_strategy):
    """``capacity_segments``' contract, which the router's jump from rung 8
    straight to the rung the demand selects stands on: rows ``0..n`` are
    bit-identical between the rung a site's own count selects and a rung
    two higher — the padded capacity is a cost knob, never a result."""
    labels, img = family_site
    n = int(labels.max())
    ladder = capacity.resolve_bucket_ladder(1024, "auto")
    own = capacity.select_capacity(n, ladder)
    higher = ladder[ladder.index(own) + 2]
    small = _family(pin_strategy, family, strategy, labels, img, own)
    big = _family(pin_strategy, family, strategy, labels, img, higher)
    assert sorted(small) == sorted(big)
    for key in small:
        np.testing.assert_array_equal(
            np.asarray(small[key])[:n], np.asarray(big[key])[:n], err_msg=key)
        assert np.asarray(big[key]).shape[0] == higher


# ------------------------------------------------------------ the decision
@pytest.mark.parametrize("backend,strategy,glcm", [
    ("cpu", "scatter", "scatter"),
    ("tpu", "onehot", "matmul"),
    ("gpu", "onehot", "matmul"),
])
@pytest.mark.parametrize("resolver", ["reduction", "glcm"])
def test_the_backend_alone_decides(resolver, backend, strategy, glcm,
                                   monkeypatch, tmp_path):
    """Every rung of the chain that used to sit between ``method=`` and
    the backend default asks for ``sort`` here; none of them is read."""
    tuning = tmp_path / "TUNING.json"
    tuning.write_text(json.dumps({
        "written_by": "bench.py --sweep",
        "reduction_strategy": {backend: "sort"},
        "glcm_matmul_wins": backend == "cpu",
    }))
    monkeypatch.setenv("TMX_TUNING_JSON", str(tuning))
    monkeypatch.setenv("TMX_REDUCTION_STRATEGY", "sort")
    monkeypatch.setenv("TM_REDUCTION_STRATEGY", "sort")
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if resolver == "reduction":
        assert R.resolve_reduction_strategy() == strategy
        assert R.resolve_reduction_strategy("auto") == strategy
    else:
        assert M._resolve_glcm_method("auto") == glcm
        assert M._resolve_glcm_method("onehot") == "matmul"


def test_resolver_explicit_method_wins(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert R.resolve_reduction_strategy("scatter") == "scatter"
    assert M._resolve_glcm_method("scatter") == "scatter"
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert R.resolve_reduction_strategy("onehot") == "onehot"
    assert M._resolve_glcm_method("matmul") == "matmul"


# ------------------------------------------------- the tuning file's writer
def test_record_config_sweep_roundtrip(tmp_path, monkeypatch):
    """The sweep writer merges per-config rows and per-backend verdicts
    without clobbering an existing file's provenance — and no longer
    turns a ``best_strategy`` into a verdict anything could read."""
    from tmlibrary_tpu.tuning import load_tuning, record_config_sweep

    path = tmp_path / "TUNING.json"
    path.write_text(json.dumps({
        "written_by": "scripts/tune_tpu.py write_results",
        "best_batch": 128,
        "backend": "tpu",
    }))
    monkeypatch.setenv("TMX_TUNING_JSON", str(path))
    record_config_sweep("3", {
        "backend": "cpu",
        "best_pipeline": 2,
        "best_capacity": 64,
        "best_strategy": "scatter",
        "rows": [{"depth": 2, "value": 10.0}],
    })
    data = load_tuning()
    assert data["written_by"] == "scripts/tune_tpu.py write_results"
    assert data["best_batch"] == 128
    assert data["config_sweeps"]["3"]["best_pipeline"] == 2
    assert data["object_capacity"] == {"cpu": 64}
    assert "reduction_strategy" not in data
