"""Distributed connected-component labeling over spatially-sharded mosaics.

The reference never labels a whole plate mosaic — objects live inside one
site, so its cluster fan-out needs no cross-job connectivity (SURVEY.md §3
"Parallelism strategies").  The TPU rebuild's spatial sharding
(:mod:`tmlibrary_tpu.parallel.halo`) makes mosaic-scale segmentation
possible, and that NEEDS cross-shard labeling: a cell crossing a shard
seam must get one id on both sides.

Algorithm (the halo analogue of multi-GPU union-find CC):

1. every shard labels its block locally with GLOBAL min-linear-index
   propagation (the same fixpoint as ``ops.label.connected_components``,
   with row indices offset by the shard's global position);
2. boundary rows travel one hop up/down the mesh ring (``ppermute``); each
   shard min-joins its edge rows against the neighbor's opposite edge
   (8- or 4-connectivity) and re-runs the local fixpoint;
3. repeat until a global ``psum`` of the per-shard change flags is zero —
   a component snaking across k shards converges in <= k outer rounds;
4. dense scipy-scan-order ids: roots (label == own linear index) are
   all-gathered as sorted per-shard lists and every pixel's rank is a
   ``searchsorted`` into the merged root list — exactly the rank-by-first-
   pixel numbering of ``scipy.ndimage.label``.

Everything is jit-compiled ``shard_map``; the only allocation above a
block is the (devices x max_roots_per_shard) root table.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from tmlibrary_tpu.errors import ShardingError
from tmlibrary_tpu.ops.label import _propagate_min, _run_min_scan
from tmlibrary_tpu.parallel.compat import axis_size, pcast_varying, shard_map

_BIG = jnp.iinfo(jnp.int32).max


def _local_fixpoint(labels, mask, connectivity, axis_name=None):
    """Converge min-label propagation inside one block (global indices)."""
    shifts = [] if connectivity == 4 else [(-1, -1), (-1, 1), (1, -1), (1, 1)]

    def body(state):
        lab, _ = state
        new = _propagate_min(lab, mask, shifts) if shifts else lab
        new = _run_min_scan(new, mask, axis=1)
        new = _run_min_scan(new, mask, axis=0)
        return new, jnp.any(new != lab)

    init_flag = jnp.bool_(True)
    if axis_name is not None:
        # under shard_map the carry must be device-varying like the body's
        # output (vma typing); axis_name may be one name or a tuple (the
        # 2-D spatial layout is varying over both mesh axes)
        names = (axis_name,) if isinstance(axis_name, str) else tuple(axis_name)
        init_flag = pcast_varying(init_flag, names)
    out, _ = lax.while_loop(lambda s: s[1], body, (labels, init_flag))
    return out


def _seam_join(labels, mask, axis_name, connectivity):
    """Min-join edge rows against ring neighbors; returns (labels, changed).

    The 1-D layout is the 2-D seam join with no orthogonal mesh axis:
    ``other_axis=None`` pads the exchanged rows with masked sentinels
    instead of corner pixels, which degenerates to exactly the in-block
    diagonal window the 1-D path always used."""
    return _seam_join_2d_axis(labels, mask, axis_name, None, connectivity)


def distributed_connected_components(
    mask: jax.Array,
    mesh: Mesh,
    connectivity: int = 8,
    max_roots_per_shard: int = 4096,
    axis: str = "rows",
) -> tuple[jax.Array, jax.Array]:
    """Label a row-sharded (H, W) bool mask; ids 1..N in scipy scan order.

    Returns ``(labels, count)`` with ``labels`` sharded like the input.
    Raises :class:`ShardingError` when rows don't divide the mesh, or —
    on the sharded path — when a shard holds more than
    ``max_roots_per_shard`` components (the static root-table bound;
    raise it for dense masks).  A 1-device CPU mesh routes through the
    native union-find instead, which has no root bound.
    """
    mask = jnp.asarray(mask, bool)
    h, w = mask.shape
    n = mesh.devices.size
    if h % n != 0:
        raise ShardingError(f"mask rows {h} not divisible by mesh size {n}")
    if connectivity not in (4, 8):
        raise ValueError("connectivity must be 4 or 8")
    # a 1-device CPU mesh has no seams to join: the while-loop
    # fixpoint is pathological on XLA-CPU (the same pathology the sites
    # layout's native fallback exists for), and the native union-find is
    # bit-identical (scipy scan order — exactly what the distributed
    # path is tested against)
    if n == 1 and _native_cc_available():
        return _native_cc_shortcut(mask, mesh, connectivity,
                                   PartitionSpec(axis))
    sharded = jax.device_put(mask, NamedSharding(mesh, PartitionSpec(axis)))
    labels, count, _ = _checked_roots(
        _cached_cc_1d(mesh, h // n, w, connectivity, max_roots_per_shard,
                      axis)(sharded),
        max_roots_per_shard,
    )
    return labels, count


def _checked_roots(out, k):
    """``(labels, count, info)`` of a CC program's four outputs; raises
    :class:`ShardingError` naming the demand when a shard held more roots
    than the static table ``k`` (the labels are then clipped).  ``info``
    carries what the program counted: the fullest shard's roots and the
    outer seam loop's rounds."""
    labels, count, overflow, rounds = out
    max_local = int(overflow)
    if max_local > k:
        raise ShardingError(
            f"a shard holds {max_local} components > "
            f"max_roots_per_shard={k}; raise the bound"
        )
    return labels, count, {
        "roots_max_per_shard": max_local, "seam_rounds": int(rounds),
    }


def _native_cc_available() -> bool:
    from tmlibrary_tpu import native as native_mod

    # cpu_native_enabled already requires the loaded library (and the
    # cpu backend + the TMX_NATIVE kill switch)
    return native_mod.cpu_native_enabled()


def _native_cc_shortcut(mask, mesh, connectivity, spec):
    """1-device mesh: no seams to join, and the XLA while-loop
    fixpoint is pathological on CPU — the native union-find is
    bit-identical (scipy scan order, exactly what the distributed paths
    are tested against)."""
    from tmlibrary_tpu import native as native_mod

    labels_np, count = native_mod.cc_label_host(
        np.asarray(mask), connectivity
    )
    return (
        jax.device_put(jnp.asarray(labels_np, jnp.int32),
                       NamedSharding(mesh, spec)),
        jnp.asarray(count, jnp.int32),
    )


def _dense_ranks(labels, block, linear, k, axes):
    """Scan-order ids from converged min-index labels: roots sorted per
    shard, merged by ``all_gather``; every pixel's rank is a
    ``searchsorted`` into the merged table.  Returns ``(out, count,
    fullest shard's roots)``, the two scalars replicated (a multi-host
    caller can fetch a replicated array, but a sharded one spans devices
    it cannot address)."""
    is_root = block & (labels == linear)
    n_local = jnp.sum(is_root.astype(jnp.int32))
    roots = jnp.sort(jnp.where(is_root, linear, _BIG).reshape(-1))[:k]
    all_roots = jnp.sort(lax.all_gather(roots, axes).reshape(-1))
    rank = jnp.searchsorted(all_roots, labels.reshape(-1)).reshape(
        labels.shape
    )
    out = jnp.where(block, rank + 1, 0).astype(jnp.int32)
    return out, lax.psum(n_local, axes), lax.pmax(n_local, axes)


def _cc_1d_program(mesh, rows, w, connectivity, k, axis):
    """The jittable shard_map program behind
    :func:`distributed_connected_components` — split out so tooling
    (scripts/comm_budget.py) can lower and inspect its HLO.  Returns
    ``(labels, count, fullest shard's roots, seam rounds)``."""

    def body(block):
        with jax.named_scope("mosaic_cc"):
            idx = lax.axis_index(axis)
            row0 = idx * rows
            yy = (row0 + jnp.arange(rows, dtype=jnp.int32))[:, None]
            xx = jnp.arange(w, dtype=jnp.int32)[None, :]
            linear = yy * w + xx
            labels = jnp.where(block, linear, _BIG)
            labels = _local_fixpoint(labels, block, connectivity, axis)

            def outer(state):
                lab, _, rounds = state
                with jax.named_scope("mosaic_seam"):
                    lab, changed = _seam_join(lab, block, axis, connectivity)
                lab = _local_fixpoint(lab, block, connectivity, axis)
                return (lab, lax.psum(changed.astype(jnp.int32), axis) > 0,
                        rounds + 1)

            # psum makes the outer flag replicated, so its init stays plain
            labels, _, rounds = lax.while_loop(
                lambda s: s[1], outer, (labels, jnp.bool_(True), jnp.int32(0))
            )
            return (*_dense_ranks(labels, block, linear, k, axis), rounds)

    return shard_map(
        body,
        mesh=mesh,
        in_specs=PartitionSpec(axis),
        out_specs=(
            PartitionSpec(axis),
            PartitionSpec(),
            PartitionSpec(),
            PartitionSpec(),
        ),
    )


@functools.lru_cache(maxsize=64)
def _cached_cc_1d(mesh, rows, w, connectivity, k, axis):
    """The compiled 1-D program, built and jitted once per (mesh, shard
    shape, connectivity, bound): a fresh ``jax.jit(shard_map(...))`` per
    call re-traced, re-lowered and read the compile cache for every well
    (``halo._cached_gaussian_halo_2d`` says what that cost)."""
    return jax.jit(_cc_1d_program(mesh, rows, w, connectivity, k, axis))


def _edge_extend(vec_lab, vec_msk, other_axis):
    """Extend a boundary row ``(W,)`` with ONE corner pixel from each
    neighbor along ``other_axis`` — the missing operand for diagonal
    (8-connectivity) adjacencies that cross a seam corner where four
    shards meet.  Returns ``(W + 2,)`` arrays; the added pixels are
    masked off on the mesh's outer edge.  ``other_axis=None`` (1-D
    layout: no orthogonal neighbors exist) pads with masked sentinels."""
    if other_axis is None:
        pad_l = jnp.full((1,), _BIG, vec_lab.dtype)
        pad_m = jnp.zeros((1,), bool)
        return (
            jnp.concatenate([pad_l, vec_lab, pad_l]),
            jnp.concatenate([pad_m, vec_msk, pad_m]),
        )
    n = axis_size(other_axis)
    idx = lax.axis_index(other_axis)
    right = [(i, (i + 1) % n) for i in range(n)]
    left = [(i, (i - 1) % n) for i in range(n)]
    from_left_l = lax.ppermute(vec_lab[-1:], other_axis, right)
    from_left_m = lax.ppermute(vec_msk[-1:], other_axis, right)
    from_right_l = lax.ppermute(vec_lab[:1], other_axis, left)
    from_right_m = lax.ppermute(vec_msk[:1], other_axis, left)
    from_left_m = jnp.where(idx == 0, False, from_left_m)
    from_right_m = jnp.where(idx == n - 1, False, from_right_m)
    lab = jnp.concatenate([from_left_l, vec_lab, from_right_l])
    msk = jnp.concatenate([from_left_m, vec_msk, from_right_m])
    return lab, msk


def _seam_join_2d_axis(labels, mask, axis_name, other_axis, connectivity):
    """Min-join the top/bottom edge rows against ring neighbors along
    ``axis_name``, with the exchanged rows corner-extended along
    ``other_axis`` so diagonal adjacencies across four-shard corners are
    seen.  Transpose the block to reuse this for column seams."""
    n = axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    down = [(i, (i + 1) % n) for i in range(n)]
    up = [(i, (i - 1) % n) for i in range(n)]

    above_lab = lax.ppermute(labels[-1], axis_name, down)
    above_msk = lax.ppermute(mask[-1], axis_name, down)
    below_lab = lax.ppermute(labels[0], axis_name, up)
    below_msk = lax.ppermute(mask[0], axis_name, up)
    above_msk = jnp.where(idx == 0, False, above_msk)
    below_msk = jnp.where(idx == n - 1, False, below_msk)

    def row_min(row_lab, row_msk):
        # 4-connectivity sees only the straight-across neighbor — no
        # corner extension (and none of its ppermutes) needed
        if connectivity == 4:
            return jnp.where(row_msk, row_lab, _BIG)
        # corner-extend, then take the (W,) windowed min of the extended
        # (W+2,) row: position c sees ext[c], ext[c+1], ext[c+2] = the
        # dx in {-1,0,+1} diagonal/straight neighbors across the seam
        ext_lab, ext_msk = _edge_extend(row_lab, row_msk, other_axis)
        w = row_lab.shape[0]
        cand = jnp.full((w,), _BIG, dtype=row_lab.dtype)
        for off in range(3):
            seg_l = lax.dynamic_slice_in_dim(ext_lab, off, w)
            seg_m = lax.dynamic_slice_in_dim(ext_msk, off, w)
            cand = jnp.minimum(cand, jnp.where(seg_m, seg_l, _BIG))
        return cand

    top_cand = row_min(above_lab, above_msk)
    bot_cand = row_min(below_lab, below_msk)
    if labels.shape[0] == 1:
        new_row = jnp.where(
            mask[0],
            jnp.minimum(labels[0], jnp.minimum(top_cand, bot_cand)),
            labels[0],
        )
        changed = jnp.any(new_row != labels[0])
        return labels.at[0].set(new_row), changed
    new_top = jnp.where(mask[0], jnp.minimum(labels[0], top_cand), labels[0])
    new_bot = jnp.where(
        mask[-1], jnp.minimum(labels[-1], bot_cand), labels[-1]
    )
    changed = jnp.any(new_top != labels[0]) | jnp.any(new_bot != labels[-1])
    labels = labels.at[0].set(new_top).at[-1].set(new_bot)
    return labels, changed


def distributed_connected_components_2d(
    mask: jax.Array,
    mesh: Mesh,
    connectivity: int = 8,
    max_roots_per_shard: int = 4096,
    row_axis: str = "rows",
    col_axis: str = "cols",
) -> tuple[jax.Array, jax.Array]:
    """Label a mask sharded over BOTH spatial axes; scipy-scan-order ids.

    The 2-D twin of :func:`distributed_connected_components` for meshes
    laid out ``rows x cols`` (a v5e-8 as 4x2, a pod slice as 16x16…):
    each shard holds an ``(H/nr, W/nc)`` tile, seam joins run along both
    mesh axes with corner-extended edge rows (a component touching four
    shards only diagonally still merges), and the final scan-order
    ranking all-gathers sorted root tables over both axes.  Returns
    ``(labels, count)`` with ``labels`` sharded like the input.
    """
    mask = jnp.asarray(mask, bool)
    h, w = mask.shape
    nr = mesh.shape[row_axis]
    nc = mesh.shape[col_axis]
    if h % nr != 0 or w % nc != 0:
        raise ShardingError(
            f"mask {h}x{w} not divisible by mesh {nr}x{nc}"
        )
    if connectivity not in (4, 8):
        raise ValueError("connectivity must be 4 or 8")
    if nr * nc == 1 and _native_cc_available():
        # same degenerate-mesh pathology as the 1-D entry point
        return _native_cc_shortcut(mask, mesh, connectivity,
                                   PartitionSpec(row_axis, col_axis))
    sharded = jax.device_put(
        mask, NamedSharding(mesh, PartitionSpec(row_axis, col_axis))
    )
    labels, count, _ = _checked_roots(
        _cached_cc_2d(mesh, h // nr, w // nc, w, connectivity,
                      max_roots_per_shard, row_axis, col_axis)(sharded),
        max_roots_per_shard,
    )
    return labels, count


def _cc_2d_program(mesh, rows, cols, w, connectivity, k, row_axis, col_axis):
    """The 2-D twin of :func:`_cc_1d_program`: ``(rows, cols)`` tiles of a
    mosaic ``w`` wide."""
    axes = (row_axis, col_axis)

    def body(block):
        with jax.named_scope("mosaic_cc"):
            ridx = lax.axis_index(row_axis)
            cidx = lax.axis_index(col_axis)
            yy = (ridx * rows + jnp.arange(rows, dtype=jnp.int32))[:, None]
            xx = (cidx * cols + jnp.arange(cols, dtype=jnp.int32))[None, :]
            linear = yy * w + xx
            labels = jnp.where(block, linear, _BIG)
            labels = _local_fixpoint(labels, block, connectivity, axes)

            def outer(state):
                lab, _, rounds = state
                with jax.named_scope("mosaic_seam"):
                    lab, ch_r = _seam_join_2d_axis(
                        lab, block, row_axis, col_axis, connectivity
                    )
                    lab_t, ch_c = _seam_join_2d_axis(
                        lab.T, block.T, col_axis, row_axis, connectivity
                    )
                lab = _local_fixpoint(lab_t.T, block, connectivity, axes)
                changed = ch_r.astype(jnp.int32) + ch_c.astype(jnp.int32)
                return lab, lax.psum(changed, axes) > 0, rounds + 1

            labels, _, rounds = lax.while_loop(
                lambda s: s[1], outer, (labels, jnp.bool_(True), jnp.int32(0))
            )
            return (*_dense_ranks(labels, block, linear, k, axes), rounds)

    return shard_map(
        body,
        mesh=mesh,
        in_specs=PartitionSpec(row_axis, col_axis),
        out_specs=(
            PartitionSpec(row_axis, col_axis),
            PartitionSpec(),
            PartitionSpec(),
            PartitionSpec(),
        ),
    )


@functools.lru_cache(maxsize=64)
def _cached_cc_2d(mesh, rows, cols, w, connectivity, k, row_axis, col_axis):
    """See :func:`_cached_cc_1d`."""
    return jax.jit(_cc_2d_program(
        mesh, rows, cols, w, connectivity, k, row_axis, col_axis))


def _otsu_program(mesh, axes, bins):
    """``(plane, factor) -> (plane > otsu * factor, reading)`` over a
    plane sharded on ``axes``: global range by ``pmin`` / ``pmax``, every
    shard's fixed-bin histogram ``psum``-med, the between-class argmax on
    the (bins,) result — :func:`~tmlibrary_tpu.ops.threshold.otsu_value`'s
    XLA formulation with the plane never leaving its shards.  ``reading``
    is what the cut was taken from, replicated: ``cut`` (before
    ``factor``), ``hist``, ``lo``, ``hi``."""
    from tmlibrary_tpu.ops.histogram import histogram_fixed_bins
    from tmlibrary_tpu.ops.threshold import _otsu_argmax

    spec = PartitionSpec(*axes)

    def body(block, factor):
        with jax.named_scope("mosaic_otsu"):
            lo = lax.pmin(jnp.min(block), axes)
            hi = lax.pmax(jnp.max(block), axes)
            span = jnp.maximum(hi - lo, 1e-6)
            idx = jnp.clip(
                ((block - lo) / span * bins).astype(jnp.int32), 0, bins - 1
            )
            hist = lax.psum(histogram_fixed_bins(
                idx, bins,
                method="scatter" if jax.default_backend() == "cpu"
                else "matmul",
            ), axes)
            centers = (
                lo + (jnp.arange(bins, dtype=jnp.float32) + 0.5) / bins * span
            )
            t = _otsu_argmax(hist, centers)
            return block > t * factor, {
                "cut": t, "hist": hist, "lo": lo, "hi": hi}

    return shard_map(
        body,
        mesh=mesh,
        in_specs=(spec, PartitionSpec()),
        out_specs=(spec, PartitionSpec()),
        # the matmul histogram's loop starts from a plain zeros carry,
        # which the varying-axis checker refuses inside a shard; the
        # reading is replicated by the psum of the histogram
        check_vma=False,
    )


@functools.lru_cache(maxsize=64)
def _cached_otsu(mesh, axes, bins):
    return jax.jit(_otsu_program(mesh, axes, bins))


def sharded_otsu_mask(
    image: jax.Array, mesh: Mesh, correction_factor: float = 1.0,
    bins: int = 256,
) -> tuple[jax.Array, dict]:
    """``(image > otsu(image) * correction_factor, reading)`` for a plane
    sharded over every axis of ``mesh``; the mask is sharded like the
    plane, ``reading`` holds the ``cut`` (Otsu's, before the factor) as a
    device scalar and, from the sharded program, the ``hist``, ``lo`` and
    ``hi`` it was taken from.  A 1-device CPU mesh has nothing sharded,
    and the fused native histogram pass is ~4x faster there (the shortcut
    the distributed CC takes)."""
    img = jnp.asarray(image, jnp.float32)
    if mesh.devices.size == 1 and _native_cc_available():
        from tmlibrary_tpu.ops.threshold import otsu_value

        cut = otsu_value(img, bins=bins)
        return img > cut * correction_factor, {"cut": cut}
    return _cached_otsu(mesh, tuple(mesh.axis_names), bins)(
        img, jnp.float32(correction_factor)
    )


def segment_mosaic(
    intensity: jax.Array,
    mesh: Mesh,
    sigma: float = 1.5,
    threshold: float | None = None,
    connectivity: int = 8,
    max_roots_per_shard: int = 4096,
) -> tuple[jax.Array, jax.Array, dict]:
    """Smooth + threshold + label a mosaic sharded over ``mesh`` — one
    axis (row shards) or two (rows x cols tiles): halo-exact Gaussian
    smoothing (corners included), a global Otsu cut when ``threshold`` is
    None, then the distributed connected components.  Returns ``(labels,
    count, info)``: ``info`` as :func:`_checked_roots` gives it, and under
    ``otsu`` the reading of :func:`sharded_otsu_mask` (un-fetched device
    arrays; the ``cut`` alone under an explicit ``threshold``)."""
    from tmlibrary_tpu.parallel.halo import (
        sharded_gaussian_smooth,
        sharded_gaussian_smooth_2d,
    )

    img = jnp.asarray(intensity, jnp.float32)
    axes = tuple(mesh.axis_names)
    h, w = img.shape
    if len(axes) == 2:
        smoothed = sharded_gaussian_smooth_2d(
            img, mesh, sigma, row_axis=axes[0], col_axis=axes[1]
        )
    else:
        smoothed = sharded_gaussian_smooth(img, mesh, sigma, axis=axes[0])
    if threshold is None:
        mask, otsu = sharded_otsu_mask(smoothed, mesh)
    else:
        otsu = {"cut": jnp.float32(threshold)}
        mask = smoothed > otsu["cut"]
    if mesh.devices.size == 1 and _native_cc_available():
        labels, count = _native_cc_shortcut(
            mask, mesh, connectivity, PartitionSpec(*axes))
        return labels, count, {"otsu": otsu}
    k = max_roots_per_shard
    if len(axes) == 2:
        nr, nc = mesh.shape[axes[0]], mesh.shape[axes[1]]
        program = _cached_cc_2d(
            mesh, h // nr, w // nc, w, connectivity, k, *axes)
    else:
        program = _cached_cc_1d(
            mesh, h // mesh.devices.size, w, connectivity, k, axes[0])
    labels, count, info = _checked_roots(program(mask), k)
    return labels, count, {**info, "otsu": otsu}


# ------------------------------------------------------------- watershed
def _halo1_zero(x, axis_name):
    """1-row halo exchange along one mesh axis with ZERO fill at the
    mesh's outer edges (the global-border semantics of the single-device
    ``_shift_with_fill(…, 0)``, unlike :func:`halo.halo_exchange`'s
    symmetric reflection).  Returns ``(rows + 2, cols)``.  Shared by the
    1-D and 2-D sharded adopt steps — one home for the border rule."""
    n = axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    down = [(i, (i + 1) % n) for i in range(n)]
    up = [(i, (i - 1) % n) for i in range(n)]
    above = lax.ppermute(x[-1:], axis_name, down)
    below = lax.ppermute(x[:1], axis_name, up)
    above = jnp.where(idx == 0, 0, above)
    below = jnp.where(idx == n - 1, 0, below)
    return jnp.concatenate([above, x, below], axis=0)


def _halo1_zero_2d(x, row_axis, col_axis):
    """Zero-filled 1-pixel halo on both axes: the vertical exchange runs
    first, so the horizontal exchange of the extended block carries the
    diagonal corner pixels.  Returns ``(rows + 2, cols + 2)``."""
    ext = _halo1_zero(x, row_axis)
    return _halo1_zero(ext.T, col_axis).T


def _sharded_adopt_2d(labels, allowed, row_axis, col_axis, connectivity):
    """One synchronous adopt step over a 2-D-sharded block, bit-matching
    the single-device ``_adopt_step`` on the gathered image: labels get a
    zero-filled 1-pixel halo on all four sides (corners included via the
    two-step exchange); ``allowed`` needs no exchange — the halo ring is
    cropped off, so only the interior's allowed mask matters."""
    from tmlibrary_tpu.ops.segment_secondary import _adopt_step

    ext = _halo1_zero_2d(labels, row_axis, col_axis)
    allowed_ext = jnp.pad(allowed, 1, constant_values=False)
    new_ext = _adopt_step(ext, allowed_ext, connectivity)
    return new_ext[1:-1, 1:-1]


def watershed_mosaic(
    intensity: jax.Array,
    seeds: jax.Array,
    mask: jax.Array,
    mesh: Mesh,
    n_levels: int = 32,
    connectivity: int = 8,
) -> tuple[jax.Array, "jax.Array | None"]:
    """Level-ordered watershed flooding over a mosaic sharded over
    ``mesh`` (row shards, or rows x cols tiles), bit-identical to the
    single-device ``watershed_from_seeds`` on the gathered image: the
    level thresholds are global (``pmin``/``pmax`` of the masked
    intensity), and every adopt step exchanges 1-pixel zero-filled halos
    so the synchronous adoption schedule — and therefore every tie-break
    — matches the single-device iteration exactly.  Returns ``(labels,
    adopt steps)``, both un-fetched; the steps are None on a 1-device CPU
    mesh, where the native frontier flood counts none."""
    intensity = jnp.asarray(intensity, jnp.float32)
    seeds = jnp.asarray(seeds, jnp.int32)
    mask = jnp.asarray(mask, bool)
    axes = tuple(mesh.axis_names)
    h, w = intensity.shape
    nr = mesh.shape[axes[0]]
    nc = mesh.shape[axes[1]] if len(axes) == 2 else 1
    if h % nr != 0 or w % nc != 0:
        raise ShardingError(
            f"mosaic {h}x{w} not divisible by mesh {nr}x{nc}"
        )
    spec = NamedSharding(mesh, PartitionSpec(*axes))
    if nr * nc == 1 and _native_cc_available():
        # 1-device CPU mesh: the single-device twin IS the semantics
        # this function is tested bit-identical against, and its auto
        # dispatch routes to the native frontier flood on cpu
        from tmlibrary_tpu.ops.segment_secondary import watershed_from_seeds

        out = watershed_from_seeds(
            intensity, seeds, mask,
            n_levels=n_levels, connectivity=connectivity,
        )
        return jax.device_put(out, spec), None
    return _cached_watershed(mesh, n_levels, connectivity, axes)(
        jax.device_put(intensity, spec),
        jax.device_put(seeds, spec),
        jax.device_put(mask, spec),
    )


def _watershed_program(mesh, n_levels, connectivity, axes):
    """The jittable shard_map program behind both distributed watersheds
    (``axes``: one mesh axis of row shards, or rows x cols).  Returns
    ``(labels, adopt steps)``: the synchronous adopt steps of every level
    and of the last unrestricted flood, each one a halo exchange and a
    ``psum`` (a replicated scalar)."""
    if len(axes) == 2:
        def adopt(lab, allowed):
            return _sharded_adopt_2d(lab, allowed, *axes, connectivity)
    else:
        def adopt(lab, allowed):
            return _sharded_adopt(lab, allowed, axes[0], connectivity)

    def body(int_block, seed_block, mask_block):
        with jax.named_scope("mosaic_watershed"):
            mask_b = mask_block | (seed_block > 0)
            lo = lax.pmin(
                jnp.min(jnp.where(mask_b, int_block, jnp.inf)), axes
            )
            hi = lax.pmax(
                jnp.max(jnp.where(mask_b, int_block, -jnp.inf)), axes
            )
            span = jnp.maximum(hi - lo, 1e-6)

            def flood(labels, allowed, steps):
                def inner(state):
                    lab, _, n = state
                    new = adopt(lab, allowed)
                    changed = lax.psum(
                        jnp.any(new != lab).astype(jnp.int32), axes
                    )
                    return new, changed > 0, n + 1

                out, _, steps = lax.while_loop(
                    lambda s: s[1], inner, (labels, jnp.bool_(True), steps)
                )
                return out, steps

            def level_body(i, state):
                level = hi - span * (i + 1) / n_levels
                allowed = mask_b & (int_block >= level)
                return flood(state[0], allowed, state[1])

            labels, steps = lax.fori_loop(
                0, n_levels, level_body, (seed_block, jnp.int32(0))
            )
            labels, steps = flood(labels, mask_b, steps)
            return jnp.where(mask_b, labels, 0), steps

    spec = PartitionSpec(*axes)
    return shard_map(
        body,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=(spec, PartitionSpec()),
    )


@functools.lru_cache(maxsize=64)
def _cached_watershed(mesh, n_levels, connectivity, axes):
    """See :func:`_cached_cc_1d`."""
    return jax.jit(_watershed_program(mesh, n_levels, connectivity, axes))


def _sharded_adopt(labels, allowed, axis_name, connectivity):
    """One synchronous adopt step with 1-row halos, bit-matching the
    single-device :func:`~tmlibrary_tpu.ops.segment_secondary._adopt_step`
    on the gathered image (global border fill = 0 falls out of zeroing the
    ring-wrapped rows)."""
    from tmlibrary_tpu.ops.segment_secondary import _adopt_step

    ext = _halo1_zero(labels, axis_name)
    false_row = jnp.zeros((1, allowed.shape[1]), bool)
    allowed_ext = jnp.concatenate([false_row, allowed, false_row], axis=0)
    new_ext = _adopt_step(ext, allowed_ext, connectivity)
    return new_ext[1:-1]
