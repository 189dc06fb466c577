"""The hand-off of a channel's statistics from corilla to illuminati:
``image_ops.prep`` takes the planes as arguments (one program for every
channel), and ``IllumstatsContainer.closest_percentile`` finds the stored
percentiles whatever dtype their keys were written in.
"""

from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tmlibrary_tpu.models.experiment import grid_experiment
from tmlibrary_tpu.models.image import IllumstatsContainer
from tmlibrary_tpu.models.store import ExperimentStore
from tmlibrary_tpu.ops import image_ops, named
from tmlibrary_tpu.ops.stats import welford_finalize, welford_scan

SIZE = 48
WINDOW = (1, 2, 3, 4)


def closure_prep(stats=None, apply_shift=False, window=None):
    """``make_batch_prep`` as it was before the planes became arguments:
    a fresh ``jax.jit`` that closes over them (kept as the reference)."""

    @named("prep")
    def prep(stack, shifts):
        def one(img, shift):
            out = jnp.asarray(img, jnp.float32)
            if stats is not None:
                out = image_ops.correct_illumination(
                    out, stats.mean_log, stats.std_log)
            if apply_shift:
                out = image_ops.align(out, shift[0], shift[1], window)
            return out

        return jax.vmap(one)(stack, shifts)

    return jax.jit(prep)


def planes(seed, size=SIZE):
    rng = np.random.default_rng(seed)
    return SimpleNamespace(
        mean_log=jnp.asarray(
            rng.normal(2.5, 0.1, (size, size)).astype(np.float32)),
        std_log=jnp.asarray(
            np.abs(rng.normal(0.3, 0.05, (size, size))).astype(np.float32)))


def seeded_stack(seed, n=5, size=SIZE):
    rng = np.random.default_rng(seed)
    stack = rng.integers(0, 65535, (n, size, size)).astype(np.uint16)
    shifts = rng.integers(-3, 4, (n, 2)).astype(np.int32)
    return jnp.asarray(stack), jnp.asarray(shifts)


# ------------------------------------------------ (a) the same mathematics
@pytest.mark.parametrize("apply_shift,window", [
    (False, None), (True, None), (True, WINDOW)])
def test_prep_without_statistics_equals_the_closure_bit_for_bit(
        apply_shift, window):
    stack, shifts = seeded_stack(1)
    old = np.asarray(closure_prep(None, apply_shift, window)(stack, shifts))
    new = np.asarray(
        image_ops.make_batch_prep(None, apply_shift, window)(stack, shifts))
    assert old.shape == new.shape and old.dtype == new.dtype == np.float32
    assert old.tobytes() == new.tobytes()


@pytest.mark.parametrize("apply_shift,window", [
    (False, None), (True, None), (True, WINDOW)])
def test_prep_with_statistics_is_correct_illumination_on_its_arguments(
        apply_shift, window):
    """Bit for bit what ``correct_illumination`` + ``align`` give when the
    planes are arguments of the program (jterator's preprocess passes them
    so too).  Against the closure, which baked the planes in as constants,
    the float32 arithmetic differs in the last digits: XLA folded
    ``mean(std_log)`` and ``mean(mean_log)`` at compile time with a double
    accumulator and turned the division by a constant plane into a
    multiplication by its folded reciprocal.  So the closure is held to a
    tolerance from the dtype, where the rounding happens: 8 float32 ulps of
    the corrected log10 intensity (4.77e-7 each in [4, 8): under 1e-5 of a
    pixel's value)."""
    stack, shifts = seeded_stack(2)
    stats = planes(3)

    @jax.jit
    def reference(stack, shifts, mean_log, std_log):
        def one(img, shift):
            out = image_ops.correct_illumination(
                jnp.asarray(img, jnp.float32), mean_log, std_log)
            if apply_shift:
                out = image_ops.align(out, shift[0], shift[1], window)
            return out

        return jax.vmap(one)(stack, shifts)

    new = np.asarray(
        image_ops.make_batch_prep(stats, apply_shift, window)(stack, shifts))
    ref = np.asarray(reference(stack, shifts, stats.mean_log, stats.std_log))
    assert new.tobytes() == ref.tobytes()
    old = np.asarray(closure_prep(stats, apply_shift, window)(stack, shifts))
    assert old.shape == new.shape
    log_old, log_new = (np.log10(1.0 + a.astype(np.float64))
                        for a in (old, new))
    assert np.abs(log_old - log_new).max() <= 8 * np.spacing(np.float32(4.0))


# --------------------------------------------------------- (b) one program
def test_two_channels_and_two_bindings_share_one_compiled_program():
    """Two channels' planes through two ``make_batch_prep`` calls: one new
    entry in ``prep``'s cache and one backend compile, not two."""
    import jax.monitoring

    # a shape no other test of this process sends through ``prep``
    stack, shifts = seeded_stack(4, n=3, size=40)
    compiled = []

    def on_duration(event, duration, **kwargs):
        if event == "/jax/core/compile/backend_compile_duration":
            compiled.append(str(kwargs.get("fun_name")))

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        before = image_ops.prep._cache_size()
        outs = [
            np.asarray(image_ops.make_batch_prep(planes(seed, 40))(
                stack, shifts))
            for seed in (5, 6)
        ]
        assert image_ops.prep._cache_size() == before + 1
        assert [name for name in compiled if "prep" in name] == ["jit(prep)"]
        assert outs[0].tobytes() != outs[1].tobytes()   # its own planes each
        # what changes the program's shape is static, and compiles anew
        image_ops.make_batch_prep(None)(stack, shifts)
        image_ops.make_batch_prep(planes(5, 40), apply_shift=True)(
            stack, shifts)
        assert image_ops.prep._cache_size() == before + 3
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)


def test_make_batch_prep_creates_no_jit():
    assert image_ops.make_batch_prep(None).func is image_ops.prep
    assert image_ops.make_batch_prep(
        planes(7), apply_shift=True).func is image_ops.prep


# ------------------------------------------- (c) the percentiles are found
def corilla_stats(key_dtype):
    """A statistics dict as corilla writes it (``welford_finalize``'s
    fields), its percentile keys in ``key_dtype``: float32 is what every
    store written before the keys became float64 holds."""
    rng = np.random.default_rng(8)
    stack = rng.integers(100, 4000, (6, 16, 16)).astype(np.uint16)
    out = {k: np.asarray(v) for k, v in
           welford_finalize(welford_scan(jnp.asarray(stack))).items()}
    out.pop("hist")
    out["percentile_keys"] = out["percentile_keys"].astype(key_dtype)
    return out


def test_welford_finalize_writes_its_keys_as_float64():
    keys = welford_finalize(
        welford_scan(jnp.zeros((2, 8, 8), jnp.uint16)))["percentile_keys"]
    assert keys.dtype == np.float64
    assert keys.tolist() == [0.1, 1.0, 50.0, 99.0, 99.9]


@pytest.mark.parametrize("key_dtype", [np.float32, np.float64])
def test_stored_percentiles_are_found_through_the_store(tmp_path, key_dtype):
    exp = grid_experiment("pct", well_rows=1, well_cols=1,
                          sites_per_well=(1, 1), channel_names=("DAPI",),
                          site_shape=(16, 16))
    store = ExperimentStore.create(tmp_path / "exp", exp)
    written = corilla_stats(key_dtype)
    store.write_illumstats(written, channel=0)
    stats = IllumstatsContainer.from_store(store.read_illumstats(channel=0))
    values = dict(zip([0.1, 1.0, 50.0, 99.0, 99.9],
                      written["percentile_values"].tolist()))
    assert values[99.0] < values[99.9]      # else the guard below is idle
    for q, value in values.items():
        assert stats.closest_percentile(q) == value
    assert stats.closest_percentile(99.5) is None
    assert stats.closest_percentile(99.9) != values[99.0]
    assert stats.closest_percentile(0.0) is None
    if key_dtype is np.float32:
        # the plain lookup that illuminati used misses these keys
        assert 99.9 not in stats.percentiles and 0.1 not in stats.percentiles


def test_closest_percentile_tolerance_and_empty_table():
    stats = IllumstatsContainer(
        mean_log=None, std_log=None, n=1,
        percentiles={99.0: 900.0, 99.9: 999.0})
    assert stats.closest_percentile(99.9 + 5e-5) == 999.0
    assert stats.closest_percentile(99.9 + 2e-4) is None
    assert stats.closest_percentile(99.0) == 900.0
    assert stats.closest_percentile(99.45) is None
    empty = IllumstatsContainer(mean_log=None, std_log=None, n=1,
                                percentiles={})
    assert empty.closest_percentile(99.9) is None


def test_round_trip_keeps_float64_keys_exact():
    stats = IllumstatsContainer.from_store(corilla_stats(np.float64))
    again = IllumstatsContainer.from_store(stats.to_store())
    assert sorted(again.percentiles) == [0.1, 1.0, 50.0, 99.0, 99.9]
    assert again.percentiles == stats.percentiles
