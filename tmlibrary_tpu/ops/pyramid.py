"""Pyramid tiling ops (illuminati).

Reference parity: ``tmlib/workflow/illuminati/api.py`` ``PyramidBuilder`` —
zoomify-style pyramid: level 0 is the corrected/aligned/stitched well
mosaic cut into 256-px tiles; each higher level is a 2x2 mean downsample of
the previous, with per-level jobs and inter-level dependencies in the
reference (SURVEY.md §4.5).

TPU design: the mosaic is one array (sharded for big plates);
``lax.reduce_window`` mean-pooling builds the level chain on device; only
PNG encoding of tiles is host-side.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from tmlibrary_tpu.ops import named

TILE_SIZE = 256


def _display_dtype() -> jnp.dtype:
    """dtype for the display-only pyramid math (``LibraryConfig``
    ``compute_dtype``, default float32).

    Trade-off of opting into bfloat16 here: it halves the pyramid's HBM
    traffic, but its ~8-bit mantissa is RELATIVE to pixel value, not to
    the display window — a dim channel stretched over a narrow clip
    window (e.g. span 40 around intensity 1000, where the bf16 ulp is 8)
    will show banding in the viewer.  Fine for well-exposed channels;
    keep float32 when narrow stretches matter.  The analysis path
    (segmentation/measurement) ignores this knob entirely: it is fp32
    with HIGHEST-precision convs because bit-identical goldens gate it
    (DESIGN.md)."""
    from tmlibrary_tpu.config import cfg

    return jnp.dtype(cfg.compute_dtype)


@named("pyramid")
def downsample_2x(img: jax.Array) -> jax.Array:
    """2x2 mean pooling (one pyramid level step).  Odd trailing row/col are
    edge-padded first so shape halving rounds up, matching zoomify."""
    h, w = img.shape
    ph, pw = h % 2, w % 2
    img_f = jnp.asarray(img, _display_dtype())
    if ph or pw:
        img_f = jnp.pad(img_f, ((0, ph), (0, pw)), mode="edge")
    summed = lax.reduce_window(
        img_f, jnp.asarray(0.0, img_f.dtype), lax.add,
        window_dimensions=(2, 2), window_strides=(2, 2),
        padding="VALID",
    )
    return summed / 4.0


#: module-level jit (public: parallel/halo.py shares it): a per-call
#: ``jax.jit(downsample_2x)`` would create a fresh wrapper with an empty
#: cache and re-trace every level shape on every illuminati batch
#: (measured as re-run overhead in the workflow bench); one shared
#: wrapper re-traces each level shape once per process
downsample_2x_jit = jax.jit(downsample_2x)


def pyramid_levels(mosaic: jax.Array, n_levels: int | None = None) -> list[jax.Array]:
    """Full level chain, level 0 (native) first.  ``n_levels=None`` builds
    until the image fits in a single tile."""
    levels = [jnp.asarray(mosaic, _display_dtype())]
    if n_levels is None:
        n_levels = n_pyramid_levels(*mosaic.shape)
    for _ in range(n_levels - 1):
        levels.append(downsample_2x_jit(levels[-1]))
    return levels


def n_pyramid_levels(height: int, width: int) -> int:
    """Level count ``pyramid_levels`` builds for an image of this size
    (native level + halvings until it fits one tile)."""
    n, h, w = 1, height, width
    while max(h, w) > TILE_SIZE:
        h, w = (h + 1) // 2, (w + 1) // 2
        n += 1
    return n


def cut_tiles(level: np.ndarray) -> dict[tuple[int, int], np.ndarray]:
    """Cut one level into 256-px tiles (host-side; edge tiles zero-padded to
    full size, matching the reference's fixed tile geometry).  Keys are
    (row, col) tile indices."""
    level = np.asarray(level)
    h, w = level.shape
    tiles: dict[tuple[int, int], np.ndarray] = {}
    for ty in range(0, max(h, 1), TILE_SIZE):
        for tx in range(0, max(w, 1), TILE_SIZE):
            tile = level[ty : ty + TILE_SIZE, tx : tx + TILE_SIZE]
            if tile.shape != (TILE_SIZE, TILE_SIZE):
                full = np.zeros((TILE_SIZE, TILE_SIZE), level.dtype)
                full[: tile.shape[0], : tile.shape[1]] = tile
                tile = full
            tiles[(ty // TILE_SIZE, tx // TILE_SIZE)] = tile
    return tiles


@named("pyramid")
def to_uint8(level: jax.Array, lower: float, upper: float) -> jax.Array:
    """Percentile-stretch to display range (reference ``ChannelImage.scale``
    with corilla's clip percentiles)."""
    span = max(upper - lower, 1e-6)
    return jnp.clip((jnp.asarray(level, jnp.float32) - lower) / span * 255.0, 0, 255).astype(
        jnp.uint8
    )
