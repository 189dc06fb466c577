"""Primary object segmentation (nuclei).

Reference parity: ``jtmodules/segment_primary.py`` — CellProfiler-style
IdentifyPrimaryObjects: global/adaptive threshold → fill holes → size
filter → label (declumping of touching nuclei via distance-transform maxima
is the reference's optional extra; here it is the optional ``declump`` path
built on the same level-flooding watershed as secondary segmentation).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from tmlibrary_tpu.ops import label as label_ops
from tmlibrary_tpu.ops import threshold as threshold_ops
from tmlibrary_tpu.ops.segment_secondary import watershed_from_seeds
from tmlibrary_tpu.ops.smooth import gaussian_smooth
from tmlibrary_tpu.ops import named


@named("distance")
def distance_transform_approx(
    mask: jax.Array, max_distance: int = 64, method: str = "auto"
) -> jax.Array:
    """Chamfer-style 8-neighbor distance-to-background, by iterative
    erosion counting (distance in "erosion rings"; exact for the
    chessboard metric which is what seed detection needs).

    The XLA path erodes under ``lax.while_loop`` with an early exit once
    everything has eroded away (bounded by ``max_distance``);
    ``method="pallas"`` (or ``"auto"`` + ``TMX_PALLAS=1`` on TPU) runs the
    identical fixpoint in VMEM; ``"native"`` computes the same values via
    a two-pass chamfer in C++ (``tm_chebyshev_dt``) — the fast path on
    the CPU backend.  ``"auto"`` resolution order (pinned): native on cpu
    when available → pallas on TPU per
    ``pallas_kernels.pallas_enabled("distance")`` (measured per-kernel
    shootout) → xla.
    """
    mask = jnp.asarray(mask, bool)
    if method == "auto":
        from tmlibrary_tpu import native

        if native.cpu_native_enabled():
            method = "native"
        else:
            from tmlibrary_tpu.ops.pallas_kernels import pallas_enabled

            method = (
                "pallas" if pallas_enabled("distance", mask.shape) else "xla"
            )
    if method == "native":
        import numpy as np

        from tmlibrary_tpu import native

        return jax.pure_callback(
            native.batch_sites(2)(
                lambda m: native.chebyshev_dt_host(np.asarray(m), max_distance)
            ),
            jax.ShapeDtypeStruct(mask.shape, jnp.float32),
            mask,
            vmap_method=native.callback_vmap_method(),
        )
    if method == "pallas":
        from tmlibrary_tpu.ops.pallas_kernels import distance_transform

        return distance_transform(
            mask, max_distance, interpret=jax.default_backend() == "cpu"
        )

    def cond(state):
        _, cur, i = state
        return jnp.any(cur) & (i < max_distance)

    def body(state):
        dist, cur, i = state
        nxt = label_ops.binary_erode(cur, connectivity=8, iterations=1)
        return dist + nxt.astype(jnp.float32), nxt, i + 1

    dist, _, _ = jax.lax.while_loop(
        cond, body, (mask.astype(jnp.float32), mask, jnp.int32(0))
    )
    return dist


def local_maxima_seeds(
    surface: jax.Array,
    mask: jax.Array,
    min_distance: int = 5,
    smooth_sigma: float = 0.0,
) -> jax.Array:
    """Find peaks of ``surface`` within ``mask`` separated by at least
    ``min_distance`` (max-filter comparison), returning a labeled seed image.

    ``smooth_sigma`` pre-blurs the surface (CellProfiler-style): on chamfer
    distance transforms the saddle between touching objects forms a flat
    plateau that would otherwise register as a spurious third maximum.
    """
    from jax import lax

    if smooth_sigma > 0:
        surface = gaussian_smooth(surface, smooth_sigma)
    size = 2 * min_distance + 1
    # windowed max via reduce_window (one fused VPU pass instead of a
    # size^2 slice-gather); -inf pad outside the image cannot beat any
    # real value, so border maxima match the old reflect-pad gather
    neigh_max = lax.reduce_window(
        jnp.asarray(surface, jnp.float32),
        -jnp.inf,
        lax.max,
        window_dimensions=(size, size),
        window_strides=(1, 1),
        padding="SAME",
    )
    is_max = (surface >= neigh_max) & jnp.asarray(mask, bool)
    seeds, _ = label_ops.connected_components(is_max, connectivity=8)
    return seeds


def segment_primary(
    intensity_image: jax.Array,
    threshold_method: str = "otsu",
    threshold_value: float = 0.0,
    correction_factor: float = 1.0,
    kernel_size: int = 31,
    constant: float = 0.0,
    smooth_sigma: float = 1.0,
    fill: bool = True,
    min_area: int = 0,
    max_area: int | None = None,
    declump: bool = False,
    declump_min_distance: int = 5,
    max_objects: int = 256,
    return_demand: bool = False,
) -> tuple[jax.Array, ...]:
    """Segment primary objects; returns (labels, count).

    ``return_demand=True`` appends the *demand*: the number of objects
    the field held before ``max_objects`` clipped them — the component
    count of the mask, or with ``declump`` the seed count of the
    watershed.  It is a function of the mask alone, so it reads the same
    at every capacity, and it is taken before the area filter: a
    capacity above it holds every object the filter gets to judge."""
    img = jnp.asarray(intensity_image, jnp.float32)
    if smooth_sigma > 0:
        img = gaussian_smooth(img, smooth_sigma)
    if threshold_method == "otsu":
        mask = threshold_ops.threshold_otsu(img, correction_factor=correction_factor)
    elif threshold_method == "manual":
        mask = threshold_ops.threshold_manual(img, threshold_value)
    elif threshold_method == "adaptive":
        mask = threshold_ops.threshold_adaptive(
            img, kernel_size=kernel_size, constant=constant
        )
    else:
        raise ValueError(f"unknown threshold method '{threshold_method}'")
    if fill:
        mask = label_ops.fill_holes(mask)
    labels, demand = label_ops.connected_components(mask, connectivity=8)
    if declump:
        # split touching objects: watershed on the distance transform from
        # its local maxima (CellProfiler shape-based declumping)
        dist = distance_transform_approx(mask)
        seeds = local_maxima_seeds(
            dist, mask, min_distance=declump_min_distance,
            smooth_sigma=declump_min_distance / 2.0,
        )
        demand = jnp.max(seeds)  # seeds are numbered 1..N
        labels = watershed_from_seeds(dist, seeds, mask)
        # watershed labels carry seed ids (peak scan order); re-rank by
        # each region's first pixel so declumped output keeps the
        # scipy-scan-order convention of the bit-identical gate.  Clip
        # first: ids beyond capacity must drop, not alias onto the last id.
        labels = label_ops.clip_label_count(labels, max_objects)
        labels = label_ops.relabel_by_scan_order(labels, max_objects)
    labels = label_ops.clip_label_count(labels, max_objects)
    if min_area > 0 or max_area is not None:
        labels = label_ops.filter_by_area(
            labels, max_objects=max_objects, min_area=min_area, max_area=max_area
        )
    count = jnp.max(labels)
    labels = labels.astype(jnp.int32)
    if return_demand:
        return labels, count, demand.astype(jnp.int32)
    return labels, count
