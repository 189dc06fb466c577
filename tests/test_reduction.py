"""Strategy-parity suite for the segmented-reduction layer.

Pins the determinism contract of ``ops/reduction.py`` on CPU so
correctness never depends on an attached chip: every strategy against
the one-hot reference across grouped_sums / grouped_minmax /
grouped_minmax_multi / intensity_quantiles / GLCM, the resolver
precedence chain, and the provenance gating of the tuned verdict.

Doubles as the tier-1 CI strategy smoke (parametrized over all
strategies at small ``max_objects``).
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

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
def test_primitives_sort_scatter_bit_identical(rng):
    ids = jnp.asarray(rng.integers(0, 9, 4096))
    vals = jnp.asarray(rng.random((4096, 3), np.float32))
    for fn in (R.segmented_sum, R.segmented_min, R.segmented_max):
        a = fn(vals, ids, 10, "sort")
        b = fn(vals, ids, 10, "scatter")
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_primitives_absent_segment_identities(rng):
    vals = jnp.asarray(rng.random(100, np.float32))
    ids = jnp.zeros(100, jnp.int32)
    for strategy in ("sort", "scatter"):
        assert np.all(np.asarray(R.segmented_min(vals, ids, 3, strategy))[1:] == np.inf)
        assert np.all(np.asarray(R.segmented_max(vals, ids, 3, strategy))[1:] == -np.inf)
        assert np.all(np.asarray(R.segmented_sum(vals, ids, 3, strategy))[1:] == 0.0)


def test_unknown_strategy_raises(rng):
    vals = jnp.ones(8, jnp.float32)
    ids = jnp.zeros(8, jnp.int32)
    with pytest.raises(ValueError):
        R.segmented_sum(vals, ids, 2, "onehot")  # no generic one-hot form
    with pytest.raises(ValueError):
        R.resolve_reduction_strategy("bogus")


# -------------------------------------------------------- measure parity
@pytest.mark.parametrize("strategy", STRATEGIES)
def test_grouped_sums_integral_bit_identical(site, strategy):
    """uint16-valued pixels: per-object sums < 2^24 are exact in f32, so
    EVERY strategy is bit-identical to the one-hot matmul reference."""
    labels, integral, _ = site
    ref = M.grouped_sums(labels, [integral, integral * 2.0], MAX_OBJECTS, "matmul")
    out = M.grouped_sums(labels, [integral, integral * 2.0], MAX_OBJECTS, strategy)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_grouped_sums_fp32_tolerance_contract(site):
    """Fractional f32 values: sort and scatter accumulate in pixel order —
    bit-identical to each other — and stay within the documented 1e-6
    relative tolerance of the one-hot reference."""
    labels, _, fractional = site
    ref = M.grouped_sums(labels, [fractional], MAX_OBJECTS, "onehot")
    srt = M.grouped_sums(labels, [fractional], MAX_OBJECTS, "sort")
    sct = M.grouped_sums(labels, [fractional], MAX_OBJECTS, "scatter")
    np.testing.assert_array_equal(np.asarray(srt), np.asarray(sct))
    np.testing.assert_allclose(np.asarray(srt), np.asarray(ref), rtol=1e-6)


def test_sort_path_exactly_deterministic(site):
    labels, _, fractional = site
    a = M.grouped_sums(labels, [fractional], MAX_OBJECTS, "sort")
    b = M.grouped_sums(labels, [fractional], MAX_OBJECTS, "sort")
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("strategy", STRATEGIES)
def test_grouped_minmax_bit_identical(site, strategy):
    """min/max are accumulation-order-free: bit-exact for all strategies."""
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
    """Histogram counts are integers — exact in f32 for every strategy."""
    labels, integral, _ = site
    ref = M.intensity_quantiles(labels, integral, MAX_OBJECTS, method="onehot")
    out = M.intensity_quantiles(labels, integral, MAX_OBJECTS, method=strategy)
    assert set(out) == set(ref)
    for key in ref:
        np.testing.assert_array_equal(np.asarray(out[key]), np.asarray(ref[key]))


@pytest.mark.parametrize("strategy", STRATEGIES)
def test_haralick_glcm_bit_identical(site, strategy):
    """GLCM cells are integer counts; every downstream Haralick feature is
    the same f32 expression tree over them — bit-exact across strategies."""
    labels, integral, _ = site
    ref = M.haralick_features(labels, integral, MAX_OBJECTS, levels=8,
                              glcm_method="matmul")
    out = M.haralick_features(labels, integral, MAX_OBJECTS, levels=8,
                              glcm_method=strategy)
    assert set(out) == set(ref)
    for key in ref:
        np.testing.assert_array_equal(np.asarray(out[key]), np.asarray(ref[key]))


# ---------------------------------------------------------------- resolver
def test_resolver_backend_default(monkeypatch):
    monkeypatch.delenv("TMX_REDUCTION_STRATEGY", raising=False)
    monkeypatch.delenv("TM_REDUCTION_STRATEGY", raising=False)
    monkeypatch.setenv("TMX_TUNING_JSON", "/nonexistent/TUNING.json")
    assert R.resolve_reduction_strategy() == "scatter"  # cpu backend


def test_resolver_explicit_method_wins(monkeypatch):
    monkeypatch.setenv("TMX_REDUCTION_STRATEGY", "sort")
    assert R.resolve_reduction_strategy("onehot") == "onehot"


def test_resolver_env_beats_config(monkeypatch):
    monkeypatch.setenv("TM_REDUCTION_STRATEGY", "onehot")
    monkeypatch.setenv("TMX_REDUCTION_STRATEGY", "sort")
    assert R.resolve_reduction_strategy() == "sort"
    monkeypatch.delenv("TMX_REDUCTION_STRATEGY")
    assert R.resolve_reduction_strategy() == "onehot"


def test_resolver_invalid_explicit_request_is_loud(monkeypatch):
    monkeypatch.setenv("TMX_REDUCTION_STRATEGY", "fastest")
    with pytest.raises(ValueError):
        R.resolve_reduction_strategy()


def test_strategy_scope_freezes_resolution(monkeypatch):
    monkeypatch.delenv("TMX_REDUCTION_STRATEGY", raising=False)
    with R.strategy_scope("sort"):
        # a build pinned "sort"; env changes mid-trace must not leak in
        monkeypatch.setenv("TMX_REDUCTION_STRATEGY", "onehot")
        assert R.resolve_reduction_strategy() == "sort"
    assert R.resolve_reduction_strategy() == "onehot"


def test_strategy_scope_none_pins_no_request(monkeypatch):
    monkeypatch.setenv("TMX_TUNING_JSON", "/nonexistent/TUNING.json")
    monkeypatch.setenv("TMX_REDUCTION_STRATEGY", "sort")
    with R.strategy_scope(None):
        # the build captured "no explicit request": backend default, not
        # the env set after the build
        assert R.explicit_reduction_request() is None
        assert R.resolve_reduction_strategy() == "scatter"


# ------------------------------------------------- tuned-verdict gating
def _write_tuning(tmp_path, payload):
    path = tmp_path / "TUNING.json"
    path.write_text(json.dumps(payload))
    return str(path)


def test_auto_resolves_from_tuning_json(tmp_path, monkeypatch):
    monkeypatch.delenv("TMX_REDUCTION_STRATEGY", raising=False)
    monkeypatch.delenv("TM_REDUCTION_STRATEGY", raising=False)
    path = _write_tuning(tmp_path, {
        "written_by": "bench.py --sweep",
        "reduction_strategy": {"cpu": "sort"},
    })
    monkeypatch.setenv("TMX_TUNING_JSON", path)
    assert R.resolve_reduction_strategy() == "sort"


def test_tuning_provenance_gate_missing_written_by(tmp_path, monkeypatch):
    monkeypatch.delenv("TMX_REDUCTION_STRATEGY", raising=False)
    monkeypatch.delenv("TM_REDUCTION_STRATEGY", raising=False)
    path = _write_tuning(tmp_path, {"reduction_strategy": {"cpu": "sort"}})
    monkeypatch.setenv("TMX_TUNING_JSON", path)
    assert R.resolve_reduction_strategy() == "scatter"  # gated → default


def test_tuning_provenance_gate_smoke_methodology(tmp_path, monkeypatch):
    monkeypatch.delenv("TMX_REDUCTION_STRATEGY", raising=False)
    monkeypatch.delenv("TM_REDUCTION_STRATEGY", raising=False)
    path = _write_tuning(tmp_path, {
        "written_by": "bench.py --sweep",
        "timing_methodology": "SMOKE(depth=1)",
        "reduction_strategy": {"cpu": "sort"},
    })
    monkeypatch.setenv("TMX_TUNING_JSON", path)
    assert R.resolve_reduction_strategy() == "scatter"


def test_tuning_backend_scope(tmp_path, monkeypatch):
    """A plain-string verdict only applies when the file's backend matches;
    a verdict measured on TPU never sets the CPU default."""
    monkeypatch.delenv("TMX_REDUCTION_STRATEGY", raising=False)
    monkeypatch.delenv("TM_REDUCTION_STRATEGY", raising=False)
    path = _write_tuning(tmp_path, {
        "written_by": "bench.py --sweep",
        "backend": "tpu",
        "reduction_strategy": "sort",
    })
    monkeypatch.setenv("TMX_TUNING_JSON", path)
    assert R.resolve_reduction_strategy() == "scatter"
    path = _write_tuning(tmp_path, {
        "written_by": "bench.py --sweep",
        "backend": "cpu",
        "reduction_strategy": "sort",
    })
    assert R.resolve_reduction_strategy() == "sort"


def test_tuning_malformed_value_degrades(tmp_path, monkeypatch):
    monkeypatch.delenv("TMX_REDUCTION_STRATEGY", raising=False)
    monkeypatch.delenv("TM_REDUCTION_STRATEGY", raising=False)
    path = _write_tuning(tmp_path, {
        "written_by": "bench.py --sweep",
        "reduction_strategy": {"cpu": "quantum"},
    })
    monkeypatch.setenv("TMX_TUNING_JSON", path)
    assert R.resolve_reduction_strategy() == "scatter"


def test_glcm_dispatch_follows_explicit_request(monkeypatch):
    monkeypatch.setenv("TMX_REDUCTION_STRATEGY", "sort")
    assert M._resolve_glcm_method("auto") == "sort"
    monkeypatch.setenv("TMX_REDUCTION_STRATEGY", "onehot")
    assert M._resolve_glcm_method("auto") == "matmul"
    assert M._resolve_glcm_method("onehot") == "matmul"
    monkeypatch.delenv("TMX_REDUCTION_STRATEGY")
    monkeypatch.setenv("TMX_TUNING_JSON", "/nonexistent/TUNING.json")
    assert M._resolve_glcm_method("auto") == "scatter"  # cpu heuristic


def test_record_config_sweep_roundtrip(tmp_path, monkeypatch):
    """bench.py --sweep's writer merges per-config rows and the per-backend
    verdict without clobbering an existing file's provenance."""
    from tmlibrary_tpu.tuning import load_tuning, record_config_sweep

    path = _write_tuning(tmp_path, {
        "written_by": "scripts/tune_tpu.py write_results",
        "best_batch": 128,
        "backend": "tpu",
    })
    monkeypatch.setenv("TMX_TUNING_JSON", path)
    record_config_sweep("3", {
        "backend": "cpu",
        "best_pipeline": 2,
        "best_strategy": "scatter",
        "rows": [{"strategy": "scatter", "depth": 2, "value": 10.0}],
    })
    data = load_tuning()
    assert data["written_by"] == "scripts/tune_tpu.py write_results"
    assert data["best_batch"] == 128
    assert data["config_sweeps"]["3"]["best_pipeline"] == 2
    assert data["reduction_strategy"] == {"cpu": "scatter"}
    from tmlibrary_tpu.tuning import tuned_reduction_strategy

    assert tuned_reduction_strategy("cpu") == "scatter"
    assert tuned_reduction_strategy("tpu") is None
