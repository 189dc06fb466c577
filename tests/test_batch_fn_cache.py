"""Keying discipline of the process-level compiled-program cache
(``jterator/pipeline.cached_batch_fn``) and the buffer-donation contract
of ``build_batch_fn``."""

import jax.numpy as jnp
import numpy as np
import pytest

from tmlibrary_tpu.benchmarks import (
    cell_painting_description,
    smooth_threshold_description,
    synthetic_cell_painting_batch,
)
from tmlibrary_tpu.jterator import pipeline as jp
from tmlibrary_tpu.jterator.pipeline import (
    ImageAnalysisPipeline,
    cached_batch_fn,
)


@pytest.fixture(autouse=True)
def _fresh_cache(monkeypatch):
    monkeypatch.setattr(jp, "_BATCH_FN_CACHE", {})
    monkeypatch.delenv("TM_DONATE_BUFFERS", raising=False)


# ------------------------------------------------------------------ keying
def test_identical_descriptions_hit(monkeypatch):
    # two separately-parsed description objects with the same content must
    # share one compiled program
    a = cached_batch_fn(smooth_threshold_description(), 64)
    b = cached_batch_fn(smooth_threshold_description(), 64)
    assert a is b
    assert len(jp._BATCH_FN_CACHE) == 1


def test_max_objects_misses(monkeypatch):
    a = cached_batch_fn(smooth_threshold_description(), 64)
    b = cached_batch_fn(smooth_threshold_description(), 128)
    assert a is not b


def test_window_misses(monkeypatch):
    a = cached_batch_fn(smooth_threshold_description(), 64)
    b = cached_batch_fn(smooth_threshold_description(), 64, (1, 1, 1, 1))
    assert a is not b


def test_donation_flag_misses(monkeypatch):
    a = cached_batch_fn(smooth_threshold_description(), 64, donate=True)
    b = cached_batch_fn(smooth_threshold_description(), 64, donate=False)
    c = cached_batch_fn(smooth_threshold_description(), 64, donate=True)
    assert a is not b
    assert a is c


def test_donation_config_default_keys_cache(monkeypatch):
    a = cached_batch_fn(smooth_threshold_description(), 64)  # default: on
    monkeypatch.setenv("TM_DONATE_BUFFERS", "0")
    b = cached_batch_fn(smooth_threshold_description(), 64)
    assert a is not b
    # and the explicit flag maps onto the same key as the config default
    assert b is cached_batch_fn(smooth_threshold_description(), 64, donate=False)


def test_strategy_environment_is_not_read(monkeypatch):
    """The backend is in the key and decides the measure kernels; the
    variable that used to request a strategy splits nothing."""
    a = cached_batch_fn(smooth_threshold_description(), 64)
    monkeypatch.setenv("TMX_REDUCTION_STRATEGY", "sort")
    assert a is cached_batch_fn(smooth_threshold_description(), 64)
    monkeypatch.setenv("TMX_REDUCTION_STRATEGY", "onehot")
    assert a is cached_batch_fn(smooth_threshold_description(), 64)
    assert len(jp._BATCH_FN_CACHE) == 1


def test_description_content_misses(monkeypatch):
    a = cached_batch_fn(smooth_threshold_description(), 64)
    other = cell_painting_description()
    b = cached_batch_fn(other, 64)
    assert a is not b


# ---------------------------------------------------------------- donation
def test_donated_run_bit_identical_to_undonated():
    """The acceptance pin: donation changes WHERE outputs live, never what
    they are — every leaf of the batch result is bit-identical."""
    desc = cell_painting_description()
    data = synthetic_cell_painting_batch(2, size=64, n_cells=4, seed=3)
    pipe = ImageAnalysisPipeline(desc, max_objects=16)
    shifts = np.zeros((2, 2), np.float32)

    def run(donate):
        fn = pipe.build_batch_fn(donate=donate)
        raw = {k: jnp.asarray(v) for k, v in data.items()}
        shift_arr = jnp.asarray(shifts)
        result = fn(raw, {}, shift_arr)
        return raw, result

    raw_plain, plain = run(donate=False)
    raw_donated, donated = run(donate=True)

    import jax

    leaves_p = jax.tree.leaves(plain)
    leaves_d = jax.tree.leaves(donated)
    assert len(leaves_p) == len(leaves_d) > 0
    for lp, ld in zip(leaves_p, leaves_d):
        np.testing.assert_array_equal(np.asarray(lp), np.asarray(ld))

    # donation is permission, not obligation: XLA only consumes a donated
    # buffer when an output can alias it (this program's outputs are int32
    # labels + feature rows, so the f32 image inputs may survive).  The
    # undonated build must never consume anything.
    assert not any(arr.is_deleted() for arr in raw_plain.values())
