"""Spatial sharding with halo exchange for mosaic-scale images.

SURVEY.md §6 ("long-context"): the reference's scaling axis is image/mosaic
size — it cuts work into per-site jobs and per-level waves.  For a single
image too large for one chip (stitched plate mosaics are tens of
gigapixels), the TPU-native answer is the sequence-parallelism analogue:
shard the row axis across the mesh and exchange boundary rows with
``lax.ppermute`` so neighborhood ops (smoothing, downsampling, local
thresholds) stay exact at shard seams — the microscopy equivalent of ring
attention's block-wise neighbor exchange.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec

from tmlibrary_tpu.parallel.compat import axis_size, shard_map

from tmlibrary_tpu import telemetry
from tmlibrary_tpu.errors import ShardingError


def halo_exchange(block: jax.Array, halo: int, axis_name: str) -> jax.Array:
    """Extend a row-sharded block with ``halo`` rows from each neighbor.

    Boundary shards fill their outer halo by symmetric reflection of their
    own edge rows, so the assembled result matches a global
    ``mode='symmetric'`` pad (the scipy-compatible boundary the ops use).
    Returns ``(rows + 2*halo, W)``.
    """
    n = axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    # neighbor edges travel one hop down/up the ring
    from_prev = lax.ppermute(
        block[-halo:], axis_name, [(i, (i + 1) % n) for i in range(n)]
    )
    from_next = lax.ppermute(
        block[:halo], axis_name, [(i, (i - 1) % n) for i in range(n)]
    )
    reflect_top = block[:halo][::-1]
    reflect_bottom = block[-halo:][::-1]
    top = jnp.where(idx == 0, reflect_top, from_prev)
    bottom = jnp.where(idx == n - 1, reflect_bottom, from_next)
    return jnp.concatenate([top, block, bottom], axis=0)


def halo_exchange_2d(
    block: jax.Array, halo: int, row_axis: str, col_axis: str
) -> jax.Array:
    """Extend a 2-D-sharded block with ``halo`` rows AND columns from its
    neighbors, including the diagonal corners.

    Corner data needs no extra collective: the vertical exchange runs
    first, so when the horizontal exchange then ships the vertically
    extended block's edge columns, those columns already carry the halo
    rows the column-neighbor received from ITS vertical neighbors — i.e.
    exactly this shard's diagonal neighbors' corner pixels.  Boundary
    shards reflect symmetrically on their outer edges, matching a global
    ``mode='symmetric'`` pad.  Returns ``(rows + 2*halo, cols + 2*halo)``.
    """
    ext = halo_exchange(block, halo, row_axis)
    n = axis_size(col_axis)
    idx = lax.axis_index(col_axis)
    from_prev = lax.ppermute(
        ext[:, -halo:], col_axis, [(i, (i + 1) % n) for i in range(n)]
    )
    from_next = lax.ppermute(
        ext[:, :halo], col_axis, [(i, (i - 1) % n) for i in range(n)]
    )
    reflect_left = ext[:, :halo][:, ::-1]
    reflect_right = ext[:, -halo:][:, ::-1]
    left = jnp.where(idx == 0, reflect_left, from_prev)
    right = jnp.where(idx == n - 1, reflect_right, from_next)
    return jnp.concatenate([left, ext, right], axis=1)


def sharded_halo_map_2d(
    fn,
    image: jax.Array,
    mesh: Mesh,
    halo: int,
    row_axis: str = "rows",
    col_axis: str = "cols",
):
    """2-D twin of :func:`sharded_halo_map`: apply a neighborhood op with
    reach <= ``halo`` over an image sharded on BOTH spatial axes.  Both
    image dimensions must divide their mesh axis."""
    h, w = image.shape
    nr = mesh.shape[row_axis]
    nc = mesh.shape[col_axis]
    if h % nr != 0 or w % nc != 0:
        raise ShardingError(
            f"image {h}x{w} not divisible by mesh {nr}x{nc}"
        )

    def body(block):
        extended = halo_exchange_2d(block, halo, row_axis, col_axis)
        out = fn(extended)
        return out[halo:-halo, halo:-halo]

    mapped = shard_map(
        body,
        mesh=mesh,
        in_specs=PartitionSpec(row_axis, col_axis),
        out_specs=PartitionSpec(row_axis, col_axis),
    )
    with telemetry.collective_span("halo_exchange_2d"):
        return jax.jit(mapped)(image)


@functools.lru_cache(maxsize=64)
def _cached_gaussian_halo_2d(mesh: Mesh, sigma: float, radius: int,
                             row_axis: str, col_axis: str):
    """Compiled 2-D halo smooth, cached by (mesh, sigma, axes) — a fresh
    ``jit(shard_map(partial(...)))`` per call retraced AND recompiled the
    program every well (~230 ms of XLA compile per spatial run)."""
    from tmlibrary_tpu.ops.smooth import gaussian_smooth

    def body(block):
        with jax.named_scope("mosaic_smooth"):
            extended = halo_exchange_2d(block, radius, row_axis, col_axis)
            return gaussian_smooth(extended, sigma)[
                radius:-radius, radius:-radius]

    return jax.jit(shard_map(
        body,
        mesh=mesh,
        in_specs=PartitionSpec(row_axis, col_axis),
        out_specs=PartitionSpec(row_axis, col_axis),
    ))


def sharded_gaussian_smooth_2d(
    image: jax.Array,
    mesh: Mesh,
    sigma: float,
    row_axis: str = "rows",
    col_axis: str = "cols",
) -> jax.Array:
    """Gaussian blur over an image sharded on both spatial axes,
    bit-matching the single-device ``ops.smooth.gaussian_smooth``."""
    from tmlibrary_tpu.ops.smooth import gaussian_radius

    radius = gaussian_radius(sigma)
    h, w = image.shape
    nr = mesh.shape[row_axis]
    nc = mesh.shape[col_axis]
    if h % nr or w % nc:
        raise ShardingError(
            f"image {h}x{w} not divisible by mesh {nr}x{nc}"
        )
    with telemetry.collective_span("halo_exchange_2d", op="gaussian_smooth"):
        return _cached_gaussian_halo_2d(
            mesh, float(sigma), radius, row_axis, col_axis
        )(image)


def sharded_halo_map(
    fn,
    image: jax.Array,
    mesh: Mesh,
    halo: int,
    axis: str = "rows",
):
    """Apply ``fn`` (a (H', W) → (H', W) neighborhood op with reach <=
    ``halo``) over a row-sharded image with exact seams.

    ``fn`` receives the halo-extended block and must return it same-shaped;
    the wrapper crops the halos back off.  The row count must divide by the
    mesh size.
    """
    h = image.shape[0]
    n = mesh.devices.size
    if h % n != 0:
        raise ShardingError(f"image rows {h} not divisible by mesh size {n}")

    def body(block):
        extended = halo_exchange(block, halo, axis)
        out = fn(extended)
        return out[halo:-halo]

    mapped = shard_map(
        body,
        mesh=mesh,
        in_specs=PartitionSpec(axis),
        out_specs=PartitionSpec(axis),
    )
    with telemetry.collective_span("halo_exchange"):
        return jax.jit(mapped)(image)


@functools.lru_cache(maxsize=64)
def _cached_gaussian_halo(mesh: Mesh, sigma: float, radius: int, axis: str):
    """Compiled row-sharded halo smooth, cached by (mesh, sigma, axis) —
    see :func:`_cached_gaussian_halo_2d` for why."""
    from tmlibrary_tpu.ops.smooth import gaussian_smooth

    def body(block):
        with jax.named_scope("mosaic_smooth"):
            extended = halo_exchange(block, radius, axis)
            return gaussian_smooth(extended, sigma)[radius:-radius]

    return jax.jit(shard_map(
        body,
        mesh=mesh,
        in_specs=PartitionSpec(axis),
        out_specs=PartitionSpec(axis),
    ))


def sharded_gaussian_smooth(
    image: jax.Array, mesh: Mesh, sigma: float, axis: str = "rows"
) -> jax.Array:
    """Row-sharded Gaussian blur, bit-matching the single-device
    ``ops.smooth.gaussian_smooth`` (and thus scipy) including edges."""
    from tmlibrary_tpu.ops.smooth import gaussian_radius

    radius = gaussian_radius(sigma)
    h = image.shape[0]
    n = mesh.devices.size
    if h % n != 0:
        raise ShardingError(f"image rows {h} not divisible by mesh size {n}")
    with telemetry.collective_span("halo_exchange", op="gaussian_smooth"):
        return _cached_gaussian_halo(mesh, float(sigma), radius, axis)(image)


def sharded_downsample_2x(image: jax.Array, mesh: Mesh, axis: str = "rows") -> jax.Array:
    """Row-sharded 2x2 mean downsample (pyramid level step) for mosaics
    larger than one chip's HBM.  Shard row counts must be even."""
    from tmlibrary_tpu.ops.pyramid import downsample_2x

    h, w = image.shape
    n = mesh.devices.size
    if h % n != 0 or (h // n) % 2 != 0:
        raise ShardingError(
            f"rows {h} must split into even-sized shards over {n} devices"
        )

    mapped = shard_map(
        downsample_2x,
        mesh=mesh,
        in_specs=PartitionSpec(axis),
        out_specs=PartitionSpec(axis),
    )
    with telemetry.collective_span("downsample_2x"):
        return jax.jit(mapped)(image)


def sharded_pyramid_levels(
    mosaic: jax.Array, mesh: Mesh, n_levels: int | None = None, axis: str = "rows"
) -> list[jax.Array]:
    """Full pyramid level chain over a row-sharded mosaic — the distributed
    twin of ``ops.pyramid.pyramid_levels`` (reference: illuminati's
    per-level job waves, SURVEY.md §4.5, re-expressed as mesh-sharded
    ``reduce_window`` steps).

    Levels stay sharded while each shard keeps an even row count (2x2
    windows then never straddle shard seams, so every sharded level is
    bit-identical to the single-device chain); the small tail levels fall
    back to plain ``downsample_2x`` — XLA gathers the by-then-tiny array
    automatically.  Level 0 (native resolution) is returned sharded.
    """
    from jax.sharding import NamedSharding

    from tmlibrary_tpu.ops.pyramid import (
        _display_dtype,
        downsample_2x,
        n_pyramid_levels,
    )

    # same display dtype as the single-device chain, or the bit-identical
    # guarantee below breaks under compute_dtype=bfloat16
    mosaic = jnp.asarray(mosaic, _display_dtype())
    if n_levels is None:
        n_levels = n_pyramid_levels(*mosaic.shape)
    n = mesh.devices.size
    h = mosaic.shape[0]
    if h % n == 0:
        mosaic = jax.device_put(mosaic, NamedSharding(mesh, PartitionSpec(axis)))
    levels = [mosaic]
    from tmlibrary_tpu.ops.pyramid import downsample_2x_jit as plain
    for _ in range(n_levels - 1):
        cur = levels[-1]
        h = cur.shape[0]
        if h % n == 0 and (h // n) % 2 == 0:
            levels.append(sharded_downsample_2x(cur, mesh, axis))
        else:
            levels.append(plain(cur))
    return levels
