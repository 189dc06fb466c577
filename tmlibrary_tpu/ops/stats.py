"""Online illumination statistics (corilla's numeric core).

Reference parity: ``tmlib/workflow/corilla/stats.py`` ``OnlineStatistics`` —
Welford per-pixel mean/variance over all sites of a channel, computed in the
log10 domain, plus intensity percentiles; results feed
``ChannelImage.correct`` (SURVEY.md §4.4).

TPU design (BASELINE north star): the per-site update loop becomes
``lax.scan`` over the site axis on each shard; shards combine with the
parallel-variance (Chan et al.) merge — deterministic fold in device order,
because floating-point Welford merging is order-sensitive (SURVEY.md §8 hard
part #2).  Percentiles are EXACT for uint16 data: a 65536-bin histogram is
accumulated alongside and inverted at finalize time.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from tmlibrary_tpu.ops import named

HIST_BINS = 65536  # exact for uint16 pixel data


class WelfordState(NamedTuple):
    """Per-pixel running statistics + global intensity histogram.

    ``mean``/``m2`` track the log-domain values SHIFTED by ``offset`` (the
    first sample seen, captured per pixel): with an fp32 carry, the raw
    running mean sits at ~4.8 (log10 of uint16-range data) where eps is
    ~5e-7, and low-contrast channels' per-sample deltas vanish below it —
    the variance of a nearly-flat channel collapses to zero.  Shifted
    deltas are ~N(0, sigma) and keep full relative precision (SURVEY.md §8
    hard part #2).  The physical mean is ``offset + mean`` (finalize).
    """

    n: jax.Array  # scalar float32 — number of sites seen
    mean: jax.Array  # (H, W) float32 — running mean MINUS offset (log domain)
    m2: jax.Array  # (H, W) float32 — running sum of squared deviations
    offset: jax.Array  # (H, W) float32 — per-pixel shift (first sample)
    hist: jax.Array  # (HIST_BINS,) float32 — raw-intensity histogram


def welford_init(shape: tuple[int, int]) -> WelfordState:
    return WelfordState(
        n=jnp.zeros((), jnp.float32),
        mean=jnp.zeros(shape, jnp.float32),
        m2=jnp.zeros(shape, jnp.float32),
        offset=jnp.zeros(shape, jnp.float32),
        hist=jnp.zeros((HIST_BINS,), jnp.float32),
    )


def welford_update(state: WelfordState, raw: jax.Array) -> WelfordState:
    """Fold one site (raw uint16-range image) into the statistics.

    The mean/variance track ``log10(1 + raw)`` (the correction domain);
    the histogram tracks raw intensities (the percentile domain) — same
    split as the reference, which keeps separate stats and percentile
    accumulators.
    """
    raw_f = jnp.asarray(raw, jnp.float32)
    x = jnp.log10(1.0 + raw_f)
    # first sample becomes the per-pixel shift (see WelfordState docstring)
    offset = jnp.where(state.n == 0, x, state.offset)
    xs = x - offset
    n = state.n + 1.0
    delta = xs - state.mean
    mean = state.mean + delta / n
    m2 = state.m2 + delta * (xs - mean)
    idx = jnp.clip(raw_f, 0, HIST_BINS - 1).astype(jnp.int32)
    # 65536-bin exact histogram: a scatter-add serializes on TPU, so the
    # bin index is factored into (hi, lo) digits and counted by one small
    # matmul per chunk (ops.histogram) — MXU instead of serialized scatter.
    # On CPU the scatter is pinned EXPLICITLY: this update runs inside
    # ``lax.scan``, where auto's native host callback would fire once per
    # scan step with no batching to amortize it (measured ~10% slower
    # than the scatter on the corilla bench).
    from tmlibrary_tpu.ops.histogram import histogram_fixed_bins

    method = "scatter" if jax.default_backend() == "cpu" else "matmul"
    hist = state.hist + histogram_fixed_bins(idx, HIST_BINS, method=method)
    return WelfordState(n=n, mean=mean, m2=m2, offset=offset, hist=hist)


@named("welford")
def welford_scan(stack: jax.Array, init: WelfordState | None = None) -> WelfordState:
    """``lax.scan`` the update over a (B, H, W) site stack."""
    stack = jnp.asarray(stack)
    if init is None:
        init = welford_init(stack.shape[1:])

    def step(state, x):
        return welford_update(state, x), None

    out, _ = lax.scan(step, init, stack)
    return out


@named("welford")
def welford_merge(a: WelfordState, b: WelfordState) -> WelfordState:
    """Chan et al. parallel combination of two disjoint-sample states.

    The shards carry different per-pixel offsets (each captured its own
    first sample), so ``b`` is re-expressed in the surviving frame before
    the combination; m2 is shift-invariant.  The general formula is
    already exact when either side is empty: the surviving offset makes
    the frame conversion a no-op for the non-empty side, and b.n/n is
    exactly 0.0 or 1.0."""
    n = a.n + b.n
    safe_n = jnp.maximum(n, 1.0)
    offset = jnp.where(a.n > 0, a.offset, b.offset)
    b_mean = b.mean + (b.offset - offset)
    delta = b_mean - a.mean
    mean = a.mean + delta * (b.n / safe_n)
    m2 = a.m2 + b.m2 + delta * delta * (a.n * b.n / safe_n)
    return WelfordState(
        n=n, mean=mean, m2=m2, offset=offset, hist=a.hist + b.hist
    )


@named("welford")
def welford_finalize(
    state: WelfordState, percentile_qs: tuple[float, ...] = (0.1, 1.0, 50.0, 99.0, 99.9)
) -> dict[str, jax.Array]:
    """Extract mean/std fields (log domain) and exact raw-intensity
    percentiles (inverted from the histogram)."""
    n = jnp.maximum(state.n, 1.0)
    var = state.m2 / n  # population variance, matching np.std(ddof=0)
    cum = jnp.cumsum(state.hist)
    total = jnp.maximum(cum[-1], 1.0)
    qs = jnp.asarray(percentile_qs, jnp.float32) / 100.0
    # smallest intensity with cumulative count >= q * total
    targets = qs * total
    values = jnp.searchsorted(cum, targets, side="left").astype(jnp.float32)
    return {
        "mean_log": state.offset + state.mean,
        "std_log": jnp.sqrt(jnp.maximum(var, 0.0)),
        "var_log": var,
        "n": state.n,
        # float64 from the Python tuple: 99.9 is stored as 99.9 (a jnp
        # array would round it to float32 and read back as 99.90000152)
        "percentile_keys": np.asarray(percentile_qs, np.float64),
        "percentile_values": jnp.clip(values, 0, HIST_BINS - 1),
        "hist": state.hist,
    }
