"""Secondary segmentation: grow cell objects outward from primary seeds.

Reference parity: ``jtmodules/segment_secondary.py`` — CellProfiler-style
``propagate``/watershed from primary-object seeds (nuclei) constrained to a
cell mask, keeping the **same label id** as the seed so primary and
secondary objects correspond 1:1.

TPU design (SURVEY.md §8 hard part #1b): level-ordered iterative flooding.
Intensity is bucketed into ``n_levels`` descending levels; at each level,
seed labels expand (8-neighbor max-label adoption, deterministic tie-break)
into still-unlabeled mask pixels whose intensity reaches that level, to
convergence (``lax.while_loop``), before dimmer pixels are admitted.  This
approximates priority-queue watershed flooding with compiler-friendly
control flow: O(levels x diameter) dense steps instead of a heap.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from tmlibrary_tpu.ops import named
from tmlibrary_tpu.ops.label import _neighbor_shifts, _shift_with_fill


def _adopt_step(labels: jax.Array, allowed: jax.Array, connectivity: int = 8) -> jax.Array:
    """Unlabeled allowed pixels adopt the max label among their neighbors."""
    shifts = _neighbor_shifts(connectivity)
    neigh_max = jnp.zeros_like(labels)
    for dy, dx in shifts:
        neigh_max = jnp.maximum(neigh_max, _shift_with_fill(labels, dy, dx, 0))
    return jnp.where((labels == 0) & allowed, neigh_max, labels)


def propagate_labels(
    labels: jax.Array, allowed: jax.Array, connectivity: int = 8
) -> jax.Array:
    """Expand labels into ``allowed`` until convergence."""
    labels = jnp.asarray(labels, jnp.int32)
    allowed = jnp.asarray(allowed, bool)

    def cond(state):
        _, changed = state
        return changed

    def body(state):
        lab, _ = state
        new = _adopt_step(lab, allowed, connectivity)
        return new, jnp.any(new != lab)

    out, _ = lax.while_loop(cond, body, (labels, jnp.bool_(True)))
    return out


def expand_labels(
    labels: jax.Array, iterations: int = 1, connectivity: int = 8
) -> jax.Array:
    """Morphologically expand every object by ``iterations`` pixels
    (reference ``jtmodules/expand_or_shrink.py``).  Ties between competing
    objects resolve to the larger label id (deterministic)."""
    lab = jnp.asarray(labels, jnp.int32)
    allowed = jnp.ones(lab.shape, bool)
    for _ in range(iterations):
        lab = _adopt_step(lab, allowed, connectivity)
    return lab


@named("watershed")
def watershed_from_seeds(
    intensity: jax.Array,
    seeds: jax.Array,
    mask: jax.Array,
    n_levels: int = 32,
    connectivity: int = 8,
    method: str = "auto",
    chunk: "int | None" = None,
) -> jax.Array:
    """Level-ordered flooding of ``seeds`` through ``mask``.

    Brighter mask pixels are claimed before dimmer ones, so region borders
    fall along intensity valleys — the watershed behavior the reference gets
    from CellProfiler's ``propagate``.  Seed pixels always keep their label.
    Returns int32 labels covering ``mask`` wherever a seed can reach it.

    ``method="pallas"`` runs the whole level loop in VMEM
    (:func:`~tmlibrary_tpu.ops.pallas_kernels.watershed_flood`);
    ``"native"`` calls the C++ frontier flood (``tm_watershed_levels``)
    via ``jax.pure_callback`` — the fast path on the CPU backend, where
    per-level ``lax.while_loop`` convergence is pathological.
    ``"auto"`` resolution order (pinned): native on cpu when available →
    pallas on TPU per ``pallas_kernels.pallas_enabled("watershed")`` (the
    measured per-kernel shootout — on v5e the XLA level loop edged out
    the VMEM flood, so auto stays xla there) → xla.  Identical
    schedule and tie-breaking all three ways (the native path receives
    the level thresholds computed by the same jitted expression, so band
    membership is decided by exact float comparisons).
    """
    if method == "auto":
        from tmlibrary_tpu import native

        if native.cpu_native_enabled():
            method = "native"
        else:
            from tmlibrary_tpu.ops.pallas_kernels import pallas_enabled

            method = "xla"
            if pallas_enabled("watershed", jnp.shape(intensity)):
                method = "pallas"
    if method == "pallas":
        from tmlibrary_tpu.ops.pallas_kernels import watershed_flood

        return watershed_flood(
            intensity, seeds, mask, n_levels=n_levels, connectivity=connectivity,
            interpret=jax.default_backend() == "cpu",
            chunk=chunk,
        )
    intensity = jnp.asarray(intensity, jnp.float32)
    seeds = jnp.asarray(seeds, jnp.int32)
    mask = jnp.asarray(mask, bool) | (seeds > 0)

    lo = jnp.min(jnp.where(mask, intensity, jnp.inf))
    hi = jnp.max(jnp.where(mask, intensity, -jnp.inf))
    span = jnp.maximum(hi - lo, 1e-6)

    if method == "native":
        import numpy as np

        from tmlibrary_tpu import native

        # the SAME expression level_body uses (left-assoc: (span*(i+1))/n),
        # so the host kernel compares against bit-identical thresholds
        i = jnp.arange(n_levels, dtype=jnp.int32)
        levels = hi - span * (i + 1) / n_levels
        return jax.pure_callback(
            native.batch_sites(2, 2, 2, 1)(
                lambda im, sd, mk, lv: native.watershed_levels_host(
                    np.asarray(im), np.asarray(sd), np.asarray(mk),
                    np.asarray(lv), connectivity,
                )
            ),
            jax.ShapeDtypeStruct(intensity.shape, jnp.int32),
            intensity, seeds, mask, levels,
            vmap_method=native.callback_vmap_method(),
        )

    # ONE flattened while_loop instead of {fori over levels x while to
    # convergence}: the carried level index advances the sweep after the
    # current level stops producing adoptions — exactly when the nested
    # while exited — so the final labels are bit-identical.  The payoff
    # is under the site-batch vmap: a vmapped nested loop synchronizes
    # EVERY site at EVERY level (each inner while runs until the slowest
    # site converges), while the flattened loop lets each site advance
    # its own level — total trips max-of-sums instead of sum-of-maxes
    # (round-4 VERDICT next-step #1: fewer while-loop trips).
    def cond(state):
        _, li = state
        return li <= n_levels

    def body(state):
        labels, li = state
        # descending levels: li=0 admits only the brightest band; the
        # (li + 1) -> float conversion reproduces the fori_loop
        # expression bit-for-bit (int32 counter converted, then
        # span * . / n_levels in f32 — the native path's levels use the
        # same tree)
        level = hi - span * (li + 1).astype(jnp.float32) / n_levels
        # li == n_levels is the final mop-up band: any mask pixel below
        # the lowest level (numerical edge)
        allowed = mask & ((intensity >= level) | (li >= n_levels))
        new = _adopt_step(labels, allowed, connectivity)
        li = jnp.where(jnp.any(new != labels), li, li + 1)
        return new, li

    labels, _ = lax.while_loop(cond, body, (seeds, jnp.int32(0)))
    return jnp.where(mask, labels, 0)
