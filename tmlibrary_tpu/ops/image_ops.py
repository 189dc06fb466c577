"""Core per-image pixel operations.

Reference parity: methods of ``tmlib.image.ChannelImage`` —
``correct`` (illumination), ``align`` (shift+crop), ``clip``, ``scale``,
``extract``/``insert``, ``join``, ``pad`` (``tmlib/image.py``).

All functions here are pure ``jnp`` element-wise/window ops on a single 2-D
image so they fuse into one XLA program under ``jit`` and batch with ``vmap``
over the site axis.  Static shapes only: crops/windows take Python-int sizes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from tmlibrary_tpu.ops import named

UINT16_MAX = 65535.0


# --------------------------------------------------------------- illumination
def correct_illumination(
    img: jax.Array,
    mean_log: jax.Array,
    std_log: jax.Array,
) -> jax.Array:
    """Apply illumination correction in the log10 domain.

    The reference's corilla statistics are per-pixel mean and std images over
    all sites of a channel, applied in log-space
    (``tmlib/image.py`` ``ChannelImage.correct`` +
    ``tmlib/workflow/corilla/stats.py`` ``OnlineStatistics``): each pixel's
    log-intensity is z-scored against its per-pixel illumination field, then
    re-expressed against the global (field-average) scale so corrected images
    across the field of view are comparable.

    corrected = 10 ** ( (log10(1+img) - mean_log) / std_log * mean(std_log)
                        + mean(mean_log) ) - 1
    """
    img_f = jnp.asarray(img, jnp.float32)
    log_img = jnp.log10(1.0 + img_f)
    std_safe = jnp.where(std_log > 1e-6, std_log, 1.0)
    z = (log_img - mean_log) / std_safe
    corrected_log = z * jnp.mean(std_log) + jnp.mean(mean_log)
    corrected = jnp.power(10.0, corrected_log) - 1.0
    return jnp.clip(corrected, 0.0, UINT16_MAX)


# -------------------------------------------------------------------- aligned
def shift_image(img: jax.Array, dy: jax.Array, dx: jax.Array) -> jax.Array:
    """Translate by integer (dy, dx), zero-filling exposed borders.

    Reference: ``ChannelImage.align`` / ``ShiftedImage`` — the registration
    step stores per-site integer shifts; alignment rolls the image and blanks
    wrapped-in pixels.  ``dy``/``dx`` may be traced values (same compiled
    program serves every site).
    """
    h, w = img.shape
    rolled = jnp.roll(img, shift=(dy, dx), axis=(0, 1))
    rows = jnp.arange(h)[:, None]
    cols = jnp.arange(w)[None, :]
    valid_rows = jnp.where(dy >= 0, rows >= dy, rows < h + dy)
    valid_cols = jnp.where(dx >= 0, cols >= dx, cols < w + dx)
    return jnp.where(valid_rows & valid_cols, rolled, 0)


def crop_window(img: jax.Array, top: int, bottom: int, left: int, right: int) -> jax.Array:
    """Crop the inter-cycle intersection window (static offsets).

    Reference: ``SiteIntersection`` — after alignment every cycle's images
    are cropped to the common overlapping region.
    """
    h, w = img.shape
    return img[top : h - bottom, left : w - right]


def align(
    img: jax.Array,
    dy: jax.Array,
    dx: jax.Array,
    window: tuple[int, int, int, int] | None = None,
) -> jax.Array:
    """Shift then (optionally) crop: the full reference ``align`` semantic."""
    out = shift_image(img, dy, dx)
    if window is not None:
        out = crop_window(out, *window)
    return out


# --------------------------------------------------------------------- scale
def clip_values(img: jax.Array, lower: jax.Array, upper: jax.Array) -> jax.Array:
    """Clip to [lower, upper] (reference ``ChannelImage.clip`` with
    percentile values computed by corilla)."""
    return jnp.clip(img, lower, upper)


def rescale(img: jax.Array, lower: jax.Array, upper: jax.Array) -> jax.Array:
    """Linear stretch of [lower, upper] to [0, 1] float32
    (reference ``ChannelImage.scale`` rescales to uint8 for tiling;
    we keep float on device, quantizing only at PNG-encode time)."""
    img_f = jnp.asarray(img, jnp.float32)
    span = jnp.maximum(upper - lower, 1e-6)
    return jnp.clip((img_f - lower) / span, 0.0, 1.0)


# ----------------------------------------------------------- extract / insert
def extract(img: jax.Array, y: int, x: int, height: int, width: int) -> jax.Array:
    """Static crop (reference ``Image.extract``)."""
    return jax.lax.dynamic_slice(img, (y, x), (height, width))


def insert(img: jax.Array, patch: jax.Array, y: int, x: int) -> jax.Array:
    """Insert ``patch`` at (y, x) (reference ``Image.insert``)."""
    return jax.lax.dynamic_update_slice(img, patch.astype(img.dtype), (y, x))


def pad(img: jax.Array, top: int, bottom: int, left: int, right: int, value=0) -> jax.Array:
    """Constant-pad (reference ``Image.pad_with_background``)."""
    return jnp.pad(img, ((top, bottom), (left, right)), constant_values=value)


def join_grid(tiles: jax.Array, grid_rows: int, grid_cols: int) -> jax.Array:
    """Stitch a ``(rows*cols, H, W)`` stack into one mosaic (reference
    ``Image.join`` used by illuminati's level-0 stitching).  Tile order is
    row-major."""
    n, h, w = tiles.shape
    assert n == grid_rows * grid_cols, (n, grid_rows, grid_cols)
    return (
        tiles.reshape(grid_rows, grid_cols, h, w)
        .transpose(0, 2, 1, 3)
        .reshape(grid_rows * h, grid_cols * w)
    )


@functools.partial(jax.jit, static_argnames=("apply_shift", "window"))
@named("prep")
def prep(stack, shifts, mean_log=None, std_log=None, *,
         apply_shift: bool = False,
         window: tuple[int, int, int, int] | None = None):
    """The ONE jitted, vmapped site-preprocessing program: illumination
    correction when the statistic planes are given, per-site shift and
    intersection crop when asked for.  The planes are arguments, so every
    channel, plate and submit of a process runs the same executable; only
    what changes the program's shape is static (planes given or not,
    ``apply_shift``, ``window``)."""

    def one(img, shift):
        out = jnp.asarray(img, jnp.float32)
        if mean_log is not None:
            out = correct_illumination(out, mean_log, std_log)
        if apply_shift:
            out = align(out, shift[0], shift[1], window)
        return out

    return jax.vmap(one)(stack, shifts)


def make_batch_prep(stats=None, apply_shift: bool = False,
                    window: tuple[int, int, int, int] | None = None):
    """``prep`` with a channel's corilla ``stats`` container (or none) and
    the alignment choices bound: ``fn(stack, shifts)``.  Binding arguments
    creates no program — the illuminati mosaic prep and the image exporter
    share ``prep``'s jit cache (jterator's multi-channel preprocess
    composes the same ops per channel inside its fused program)."""
    return functools.partial(
        prep,
        mean_log=None if stats is None else stats.mean_log,
        std_log=None if stats is None else stats.std_log,
        apply_shift=apply_shift, window=window)
