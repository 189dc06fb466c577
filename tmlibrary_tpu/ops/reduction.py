"""Segmented-reduction strategy layer.

Every per-object measurement in ``ops/measure.py`` is a segmented
reduction over the label image: per-object sums/min/max, the quantile
histogram rows, the GLCM cells.  Two strategies compute the same
reduction, and which one runs is a property of the backend:

``"onehot"``
    The accelerators' path.  Contract a one-hot of the segment ids
    against the values on the MXU (``jnp.einsum`` at
    ``Precision.HIGHEST``, chunked over pixels).  min/max have no matmul
    form, so "onehot" there means the dense masked-broadcast reduce (the
    same memory shape: pixels x segments).  The one-hot kernels live at
    their call sites in ``ops/measure.py`` — they exploit factored
    structure (shared GLCM row one-hots, dual label x bucket
    contractions) a generic primitive cannot.  On the CPU the one-hot
    materialization costs ~25x the scatter.
``"scatter"``
    The CPU platform's path, and the reference the parity tests compare
    ``onehot`` against.  Direct ``.at[ids].add/min/max`` scatters
    (:func:`segmented_sum` / ``_min`` / ``_max`` below) — cheap where
    scatters lower to serial element updates anyway, serialized on a TPU.

Determinism contract (pinned by ``tests/test_reduction.py`` on the CPU):
min/max agree bit-exactly (order-free); counts and integer-valued sums
(uint16 microscopy pixels, histogram/GLCM cells) are exact in f32 and
therefore bit-identical; fractional f32 sums of ``onehot`` are within
1e-6 relative of ``scatter`` (the accumulation order differs).

Resolution (:func:`resolve_reduction_strategy`): an explicit ``method=``
on the call, else ``"scatter"`` on the cpu backend and ``"onehot"`` on
every other.  Nothing else selects a strategy: no environment variable,
no configuration key, no tuning entry.  ``method=`` stays so that tests
and ``scripts/tune_measure_tpu.py`` can run the chip's path on the CPU.
(The host-callback routes documented in ``measure.py`` — ``"native"`` —
are explicit opt-ins of their call sites and never resolved to.)

Why two and not four: a ``"sort"`` strategy (stable sort by segment id,
sorted-run segment reductions) and a ``"fused"`` one (Pallas measure
megakernels) existed until PR 29, selectable through an environment
variable, a CLI flag, an INI key, a step argument and a tuning verdict.
The chip decided (``scripts/tune_measure_tpu.py``, one 2160x2160 field at
capacity 1024 on a TPU v5e, PR 27; kept in ``tuning/TUNING.json`` under
``reduction_strategy_ms`` and ``glcm_ms``): ``onehot`` wins every family
— intensity 17.7 ms against ``fused`` 37.6, ``scatter`` 128, ``sort``
192; morphology 45.8 / 87.4 / 185 / 268; Zernike 64.4 / 264 / 154 / 384
— and the XLA contraction wins the GLCM (81.0 ms against the Pallas
kernel's 161, ``scatter`` 180, ``sort`` 340).  On the CPU ``scatter`` was
the default and ``sort`` never selected, so the two won on no backend.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: the explicit strategies; "auto" resolves to one of these
STRATEGIES = ("onehot", "scatter")


def capacity_segments(capacity: int) -> int:
    """Segment count for an object-capacity of ``capacity``: one row per
    object id plus row 0 for background — the ONE place the capacity →
    ``num_segments`` convention lives for both strategies.

    Capacity-invariance contract (pinned by ``tests/test_reduction.py``
    and relied on by the bucket router in ``tmlibrary_tpu.capacity``):
    every strategy computes each segment's row independently of how many
    padded rows follow it, so for ids bounded by ``n``, any two
    capacities ``>= n`` yield bit-identical rows ``0..n``.  That makes
    the padded capacity a pure cost knob — the one-hot contraction,
    histogram and GLCM shapes all scale with it while the results do
    not."""
    return int(capacity) + 1


def resolve_reduction_strategy(method: str = "auto") -> str:
    """Resolve ``method`` to a concrete strategy name: an explicit name
    wins, ``"auto"`` is the backend's (see the module docstring)."""
    if method and method != "auto":
        if method not in STRATEGIES:
            raise ValueError(
                f"unknown reduction strategy '{method}' "
                f"(expected one of {STRATEGIES} or 'auto')"
            )
        return method
    return "scatter" if jax.default_backend() == "cpu" else "onehot"


# ------------------------------------------------------------- primitives
# The scatter strategy.  Three functions, because each hides its
# reduction's identity element (what an absent segment reads).
def segmented_sum(
    values: jax.Array, segment_ids: jax.Array, num_segments: int
) -> jax.Array:
    """Per-segment sums of ``values`` (``(P,)`` or ``(P, C)``); absent
    segments come back 0."""
    init = jnp.zeros((num_segments,) + values.shape[1:], values.dtype)
    return init.at[segment_ids].add(values)


def segmented_min(
    values: jax.Array, segment_ids: jax.Array, num_segments: int
) -> jax.Array:
    """Per-segment minima; absent segments come back +inf (the identity),
    matching ``jax.ops.segment_min``."""
    init = jnp.full((num_segments,) + values.shape[1:], jnp.inf, values.dtype)
    return init.at[segment_ids].min(values)


def segmented_max(
    values: jax.Array, segment_ids: jax.Array, num_segments: int
) -> jax.Array:
    """Per-segment maxima; absent segments come back -inf."""
    init = jnp.full((num_segments,) + values.shape[1:], -jnp.inf, values.dtype)
    return init.at[segment_ids].max(values)
