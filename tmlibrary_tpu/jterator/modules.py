"""Module registry and the TPU ("jtmodules twin") implementations.

Reference parity: the external ``jtmodules`` package (one file per module,
each exposing ``main()`` + ``VERSION``) and
``tmlib/workflow/jterator/module.py`` (``ImageAnalysisModule`` import/bind/
call machinery).  The reference dispatches by module source path and
supports Python/Matlab/R; here modules register under a name + ``backend``
key (``backend: tpu`` per BASELINE's north star) and must be jit/vmap-safe
JAX functions.  Matlab/R bridges are out of scope (SURVEY.md §8 non-goals).

Module contract: ``fn(**kwargs) -> dict`` mapping output-handle names to
arrays (or, for ``Measurement`` outputs, to ``{feature_name: (max_objects,)
array}`` dicts).  Array kwargs are traced; everything else is a static
compile-time constant from the handle description.
"""

from __future__ import annotations

import inspect
from typing import Callable

import jax
import jax.numpy as jnp

from tmlibrary_tpu.errors import RegistryError
from tmlibrary_tpu.ops import label as label_ops
from tmlibrary_tpu.ops import smooth as smooth_ops
from tmlibrary_tpu.ops import threshold as threshold_ops

#: name -> backend -> (fn, version)
_REGISTRY: dict[str, dict[str, tuple[Callable, str]]] = {}


def register_module(name: str, version: str = "0.1.0", backend: str = "tpu"):
    def deco(fn: Callable) -> Callable:
        _REGISTRY.setdefault(name, {})[backend] = (fn, version)
        return fn

    return deco


def get_module(name: str, backend: str = "tpu") -> Callable:
    try:
        return _REGISTRY[name][backend][0]
    except KeyError:
        have = {n: list(b) for n, b in _REGISTRY.items()}
        raise RegistryError(
            f"no module '{name}' for backend '{backend}' (registered: {have})"
        ) from None


def get_module_version(name: str, backend: str = "tpu") -> str:
    return _REGISTRY[name][backend][1]


def list_modules(backend: str | None = None) -> list[str]:
    if backend is None:
        return sorted(_REGISTRY)
    return sorted(n for n, b in _REGISTRY.items() if backend in b)


def module_accepts(name: str, backend: str, kwarg: str) -> bool:
    fn = get_module(name, backend)
    params = inspect.signature(fn).parameters
    return kwarg in params or any(
        p.kind is inspect.Parameter.VAR_KEYWORD for p in params.values()
    )


# --------------------------------------------------------------------------
# module implementations (jtmodules twins)
# --------------------------------------------------------------------------


@register_module("smooth")
def smooth(intensity_image, method: str = "gaussian", sigma: float = 2.0, size: int = 3):
    """Smoothing (reference ``jtmodules/smooth.py``): gaussian | median |
    average | bilateral."""
    if method == "gaussian":
        out = smooth_ops.gaussian_smooth(intensity_image, sigma)
    elif method == "median":
        out = smooth_ops.median_smooth(intensity_image, size)
    elif method == "average":
        out = smooth_ops.uniform_smooth(intensity_image, size)
    elif method == "bilateral":
        out = smooth_ops.bilateral_smooth(intensity_image, size=size, sigma_space=sigma)
    else:
        raise ValueError(f"unknown smooth method '{method}'")
    return {"smoothed_image": out}


@register_module("threshold_manual")
def threshold_manual(intensity_image, threshold: float = 0.0):
    """Reference ``jtmodules/threshold_manual.py``."""
    return {"mask": threshold_ops.threshold_manual(intensity_image, threshold)}


@register_module("threshold_otsu")
def threshold_otsu(intensity_image, correction_factor: float = 1.0, bins: int = 256):
    """Reference ``jtmodules/threshold_otsu.py``."""
    return {
        "mask": threshold_ops.threshold_otsu(
            intensity_image, bins=bins, correction_factor=correction_factor
        )
    }


@register_module("threshold_adaptive")
def threshold_adaptive(
    intensity_image,
    method: str = "gaussian",
    kernel_size: int = 31,
    constant: float = 0.0,
    min_threshold: float | None = None,
    max_threshold: float | None = None,
):
    """Reference ``jtmodules/threshold_adaptive.py``."""
    return {
        "mask": threshold_ops.threshold_adaptive(
            intensity_image,
            method=method,
            kernel_size=kernel_size,
            constant=constant,
            min_threshold=min_threshold,
            max_threshold=max_threshold,
        )
    }


@register_module("label")
def label(mask, connectivity: int = 8):
    """Reference ``jtmodules/label.py``."""
    return {"label_image": label_ops.label(mask, connectivity)}


@register_module("fill")
def fill(mask):
    """Reference ``jtmodules/fill.py`` (fill holes in binary mask)."""
    return {"filled_mask": label_ops.fill_holes(mask)}


@register_module("filter")
def filter_objects(
    label_image,
    feature: str = "area",
    lower_threshold: float | None = None,
    upper_threshold: float | None = None,
    max_objects: int = 256,
):
    """Reference ``jtmodules/filter.py`` — remove objects whose measured
    feature falls outside ``[lower_threshold, upper_threshold]``; any
    on-device morphology feature is accepted (``area``, ``eccentricity``,
    ``form_factor``, ``extent``, ``perimeter``, axis lengths, ...)."""
    if lower_threshold is None and upper_threshold is None:
        raise ValueError(
            "filter needs lower_threshold and/or upper_threshold"
        )
    if feature in ("area", "Morphology_area"):
        # dedicated path (pixel counting only — no moment/perimeter math);
        # float thresholds compare exactly like the generic path's
        out = label_ops.filter_by_area(
            label_image,
            max_objects=max_objects,
            min_area=lower_threshold if lower_threshold is not None else 0,
            max_area=upper_threshold,
        )
    else:
        out = label_ops.filter_by_feature(
            label_image, feature, max_objects,
            lower=lower_threshold, upper=upper_threshold,
        )
    return {"filtered_label_image": out}


@register_module("register_objects")
def register_objects(label_image):
    """Reference ``jtmodules/register_objects.py``: promote a label image to
    registered SegmentedObjects (persistence + measurement attachment)."""
    return {"objects": jnp.asarray(label_image, jnp.int32)}


@register_module("invert")
def invert(image):
    """Reference ``jtmodules/invert.py`` (invert intensities/mask)."""
    img = jnp.asarray(image)
    if img.dtype == jnp.bool_:
        return {"inverted_image": ~img}
    return {"inverted_image": jnp.max(img) - img}


@register_module("rescale")
def rescale(intensity_image, lower: float = 0.0, upper: float = 65535.0):
    """Linear rescale to [0,1] (reference uses jtlib rescaling helpers)."""
    from tmlibrary_tpu.ops import image_ops

    return {"rescaled_image": image_ops.rescale(intensity_image, lower, upper)}


@register_module("mask")
def apply_mask(image, mask):
    """Zero out pixels outside ``mask`` (reference ``jtmodules/mask.py``)."""
    img = jnp.asarray(image)
    return {"masked_image": jnp.where(jnp.asarray(mask, bool), img, jnp.zeros_like(img))}


@register_module("combine_masks")
def combine_masks(mask_1, mask_2, operation: str = "AND"):
    """Reference ``jtmodules/combine_masks.py``."""
    a = jnp.asarray(mask_1, bool)
    b = jnp.asarray(mask_2, bool)
    if operation.upper() == "AND":
        return {"combined_mask": a & b}
    if operation.upper() == "OR":
        return {"combined_mask": a | b}
    if operation.upper() == "XOR":
        return {"combined_mask": a ^ b}
    raise ValueError(f"unknown combine operation '{operation}'")


@register_module("segment_primary")
def segment_primary(
    intensity_image,
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
):
    """Reference ``jtmodules/segment_primary.py`` (nuclei)."""
    from tmlibrary_tpu.ops.segment_primary import segment_primary as _sp

    labels, _count, demand = _sp(
        intensity_image,
        threshold_method=threshold_method,
        threshold_value=threshold_value,
        correction_factor=correction_factor,
        kernel_size=kernel_size,
        constant=constant,
        smooth_sigma=smooth_sigma,
        fill=fill,
        min_area=min_area,
        max_area=max_area,
        declump=declump,
        declump_min_distance=declump_min_distance,
        max_objects=max_objects,
        return_demand=True,
    )
    return {"objects": labels, MODULE_DEMAND_KEY: demand}


@register_module("segment_secondary")
def segment_secondary(
    primary_label_image,
    intensity_image,
    method: str = "watershed",
    threshold_method: str = "otsu",
    threshold_value: float = 0.0,
    correction_factor: float = 1.0,
    n_levels: int = 32,
):
    """Reference ``jtmodules/segment_secondary.py`` (cells grown from
    nuclei seeds, same label ids as seeds)."""
    from tmlibrary_tpu.ops import threshold as _t
    from tmlibrary_tpu.ops.segment_secondary import watershed_from_seeds

    img = jnp.asarray(intensity_image, jnp.float32)
    if threshold_method == "otsu":
        mask = _t.threshold_otsu(img, correction_factor=correction_factor)
    elif threshold_method == "manual":
        mask = _t.threshold_manual(img, threshold_value)
    else:
        raise ValueError(f"unknown threshold method '{threshold_method}'")
    if method != "watershed":
        raise ValueError(f"unknown secondary method '{method}'")
    labels = watershed_from_seeds(img, primary_label_image, mask, n_levels=n_levels)
    return {"objects": labels}


@register_module("measure_intensity")
def measure_intensity(
    objects_image, intensity_image, max_objects: int = 256, quantiles: bool = False
):
    """Reference ``jtmodules/measure_intensity.py``.

    ``quantiles=True`` additionally exports per-object p25/median/p75
    (quantile-type intensity statistics some jtlib versions ship)."""
    from tmlibrary_tpu.ops.measure import intensity_features, intensity_quantiles

    feats = intensity_features(objects_image, intensity_image, max_objects)
    if quantiles:
        feats.update(
            intensity_quantiles(objects_image, intensity_image, max_objects)
        )
    return {"measurements": feats}


@register_module("measure_morphology")
def measure_morphology(objects_image, max_objects: int = 256):
    """Reference ``jtmodules/measure_morphology.py``."""
    from tmlibrary_tpu.ops.measure import morphology_features

    return {"measurements": morphology_features(objects_image, max_objects)}


@register_module("measure_texture")
def measure_texture(
    objects_image,
    intensity_image,
    levels: int = 32,
    distance: int = 1,
    max_objects: int = 256,
):
    """Reference ``jtmodules/measure_texture.py`` (Haralick).

    Multi-scale texture (the reference computes Haralick at several pixel
    distances): a non-default ``distance`` suffixes every feature with
    ``_d<distance>`` so two module instances at different scales coexist
    in one feature table instead of overwriting each other."""
    from tmlibrary_tpu.ops.measure import haralick_features

    feats = haralick_features(
        objects_image, intensity_image, max_objects, levels=levels, distance=distance
    )
    if distance != 1:
        feats = {f"{k}_d{distance}": v for k, v in feats.items()}
    return {"measurements": feats}


@register_module("measure_zernike")
def measure_zernike(objects_image, degree: int = 9, patch: int = 64, max_objects: int = 256):
    """Reference ``jtmodules/measure_zernike.py``."""
    from tmlibrary_tpu.ops.measure import zernike_features

    return {
        "measurements": zernike_features(
            objects_image, max_objects, degree=degree, patch=patch
        )
    }


@register_module("measure_point_pattern")
def measure_point_pattern(
    objects_image,
    points_image,
    max_objects: int = 256,
    max_points: int = 256,
):
    """Reference ``jtlib/features/point_pattern.py`` — spatial statistics
    of child point objects (spots) within parent objects: count, density,
    nearest-neighbor distances, Clark–Evans aggregation index, distances
    to the parent centroid and border."""
    from tmlibrary_tpu.ops.measure import point_pattern_features

    return {
        "measurements": point_pattern_features(
            objects_image, points_image, max_objects, max_points
        )
    }


@register_module("project")
def project(zstack, method: str = "max"):
    """Z-projection of a (Z, H, W) volume (reference ``jtmodules/project.py``)."""
    v = jnp.asarray(zstack, jnp.float32)
    if method == "max":
        return {"projected_image": jnp.max(v, axis=0)}
    if method == "mean":
        return {"projected_image": jnp.mean(v, axis=0)}
    if method == "sum":
        return {"projected_image": jnp.sum(v, axis=0)}
    raise ValueError(f"unknown projection method '{method}'")


@register_module("morphology")
def morphology(mask, operation: str = "open", iterations: int = 1):
    """Binary morphology (reference ``jtmodules/morphology.py``):
    open | close | dilate | erode."""
    m = jnp.asarray(mask, bool)
    if operation == "dilate":
        out = label_ops.binary_dilate(m, 8, iterations)
    elif operation == "erode":
        out = label_ops.binary_erode(m, 8, iterations)
    elif operation == "open":
        out = label_ops.binary_dilate(
            label_ops.binary_erode(m, 8, iterations), 8, iterations
        )
    elif operation == "close":
        out = label_ops.binary_erode(
            label_ops.binary_dilate(m, 8, iterations), 8, iterations
        )
    else:
        raise ValueError(f"unknown morphology operation '{operation}'")
    return {"output_mask": out}


@register_module("filter_edges")
def filter_edges(intensity_image, method: str = "sobel"):
    """Edge enhancement (reference ``jtmodules/filter.py`` edge options):
    sobel gradient magnitude or Laplacian-of-Gaussian."""
    img = jnp.asarray(intensity_image, jnp.float32)
    if method == "sobel":
        # 3x3 sobel on an edge-replicated pad: flat borders yield zero
        # gradient (zero-fill shifts would ring the frame with false edges)
        p = jnp.pad(img, 1, mode="edge")
        h, w = img.shape

        def s(dy, dx):
            return p[1 + dy : 1 + dy + h, 1 + dx : 1 + dx + w]

        gy =(s(1, -1) + 2 * s(1, 0) + s(1, 1)) - (s(-1, -1) + 2 * s(-1, 0) + s(-1, 1))
        gx = (s(-1, 1) + 2 * s(0, 1) + s(1, 1)) - (s(-1, -1) + 2 * s(0, -1) + s(1, -1))
        return {"filtered_image": jnp.sqrt(gy**2 + gx**2)}
    if method == "log":
        sm = smooth_ops.gaussian_smooth(img, 2.0)
        # edge-replicated padding keeps the Laplacian zero on flat borders
        p = jnp.pad(sm, 1, mode="edge")
        lap = p[:-2, 1:-1] + p[2:, 1:-1] + p[1:-1, :-2] + p[1:-1, 2:] - 4.0 * sm
        return {"filtered_image": lap}
    raise ValueError(f"unknown edge filter '{method}'")


@register_module("separate_clumps")
def separate_clumps(
    label_image,
    min_distance: int = 5,
    max_objects: int = 256,
    max_form_factor: float = 1.0,
    min_area_to_cut: int = 0,
):
    """Split touching objects by distance-transform watershed
    (reference ``jtmodules/separate_clumps.py`` shape-based declumping).

    The reference cuts only objects that LOOK like clumps; here an object
    is eligible when its form factor (4*pi*area/perimeter^2 — low for the
    peanut shapes fused cells make) is below ``max_form_factor`` AND its
    area is at least ``min_area_to_cut``.  The defaults make every object
    eligible (pure distance-watershed declumping); tightening
    ``max_form_factor`` to ~0.55-0.65 preserves round single cells
    (which measure ~0.6+ under the exposed-edge perimeter below)
    untouched, matching the reference's selectivity.  Everything stays
    inside jit: the eligibility test is a per-object lookup, the watershed
    runs once on the eligible pixels, and the two label spaces compact by
    first-pixel scan order (scipy numbering).
    """
    from tmlibrary_tpu.ops.measure import grouped_sums
    from tmlibrary_tpu.ops.label import shift_with_fill
    from tmlibrary_tpu.ops.segment_primary import (
        distance_transform_approx,
        local_maxima_seeds,
    )
    from tmlibrary_tpu.ops.segment_secondary import watershed_from_seeds

    labels = label_ops.clip_label_count(
        jnp.asarray(label_image, jnp.int32), max_objects
    )
    mask = labels > 0

    # per-object form factor from one grouped MXU pass.  The perimeter is
    # the EXPOSED-EDGE count (each of a pixel's 4 sides facing another
    # label counts separately): a boundary-pixel count underestimates
    # length so badly that digital disks measure ff > 1; with edge
    # counting a disk measures ~0.6 and fused-cell dumbbells fall well
    # below it, so a single cutoff separates the two.
    edge_count = jnp.zeros(labels.shape, jnp.float32)
    for dy, dx in ((-1, 0), (1, 0), (0, -1), (0, 1)):
        edge_count = edge_count + (
            shift_with_fill(labels, dy, dx, 0) != labels
        ).astype(jnp.float32)
    edge_count = jnp.where(mask, edge_count, 0.0)
    ones = jnp.ones(labels.shape, jnp.float32)
    sums = grouped_sums(labels, [ones, edge_count], max_objects)
    area, perim = sums[:, 0], sums[:, 1]
    ff = 4.0 * jnp.pi * area / jnp.maximum(perim**2, 1.0)
    eligible = (ff < max_form_factor) & (area >= min_area_to_cut) & (area > 0)
    # max_form_factor >= 1.0 means "cut everything" (form factor <= 1 by
    # the isoperimetric inequality, but discretization can push it past 1)
    eligible = eligible | jnp.full_like(eligible, max_form_factor >= 1.0)
    elig_pix = jnp.concatenate(
        [jnp.zeros((1,), bool), eligible]
    )[labels] & mask

    dist = distance_transform_approx(elig_pix)
    seeds = local_maxima_seeds(
        dist, elig_pix, min_distance=min_distance, smooth_sigma=min_distance / 2.0
    )
    split = watershed_from_seeds(dist, seeds, elig_pix)
    # merge: kept objects keep their pixels, split pixels get offset ids,
    # then compact to scipy scan order over the combined label space.
    # Clip BEFORE relabeling: watershed seed ids are unbounded by
    # max_objects, and relabel's gather would alias over-capacity ids onto
    # 2*max_objects (merging distinct fragments) instead of dropping them
    # — same overflow rule as segment_primary.
    combined = jnp.where(elig_pix, split + max_objects, labels)
    combined = jnp.where(mask, combined, 0)
    combined = label_ops.clip_label_count(combined, 2 * max_objects)
    out = label_ops.relabel_by_scan_order(combined, 2 * max_objects)
    return {"separated_label_image": label_ops.clip_label_count(out, max_objects)}


@register_module("generate_volume_image")
def generate_volume_image(
    zstack, focus_window: int = 5, mode: str = "volume"
):
    """Build a volume image from a z-stack
    (reference ``jtmodules/generate_volume_image.py``: surface estimation
    from focus so downstream 3-D segmentation works on real heights, not
    raw plane order).

    TPU-idiomatic focus estimation: per-plane local focus energy is the
    box-filtered squared Laplacian (the classic variance-of-Laplacian
    sharpness measure, all ``conv``s); outputs are

    - ``volume_image`` — the (Z, H, W) stack unchanged (``mode="volume"``,
      default) or focus-weighted (``mode="focus"``: planes scaled by their
      per-pixel focus weight so out-of-focus light is suppressed);
    - ``depth_image`` — per-pixel argmax-focus plane index (H, W) float32,
      the height-map the reference derives from its bead surface fit;
    - ``focus_image`` — the all-in-focus composite (each pixel from its
      sharpest plane).
    """
    vol = jnp.asarray(zstack, jnp.float32)  # (Z, H, W)

    def plane_focus(img):
        # 5-point Laplacian on an edge-replicated pad: a constant-0 fill
        # would make border focus track intensity (|lap| ~ v at edges) and
        # the height map near every image edge would pick the BRIGHTEST
        # plane, not the sharpest
        padded = jnp.pad(img, 1, mode="edge")
        lap = (
            -4.0 * img
            + padded[:-2, 1:-1]
            + padded[2:, 1:-1]
            + padded[1:-1, :-2]
            + padded[1:-1, 2:]
        )
        return smooth_ops.uniform_smooth(lap * lap, focus_window)

    focus = jax.vmap(plane_focus)(vol)  # one batched subgraph, any Z
    depth = jnp.argmax(focus, axis=0).astype(jnp.float32)  # (H, W)
    best = jnp.max(focus, axis=0)
    in_focus = jnp.take_along_axis(
        vol, depth[None].astype(jnp.int32), axis=0
    )[0]
    if mode == "focus":
        # degenerate pixels (uniform in every plane -> focus 0 everywhere)
        # keep full weight instead of being zeroed out of the volume
        weights = jnp.where(
            best[None] > 1e-6, focus / jnp.maximum(best[None], 1e-6), 1.0
        )
        out_vol = vol * weights
    elif mode == "volume":
        out_vol = vol
    else:
        raise ValueError(f"unknown volume mode '{mode}'")
    return {
        "volume_image": out_vol,
        "depth_image": depth,
        "focus_image": in_focus,
    }


@register_module("segment_volume")
def segment_volume(
    volume_image,
    threshold_method: str = "otsu",
    threshold_value: float = 0.0,
    correction_factor: float = 1.0,
    connectivity: int = 26,
    max_objects: int = 256,
):
    """3-D segmentation: threshold + 3-D connected components
    (BASELINE config 5 stretch; see ops/volume.py)."""
    from tmlibrary_tpu.ops.volume import connected_components_3d

    if connectivity not in (6, 18, 26):
        raise ValueError(
            f"3-D connectivity must be 6, 18 or 26, got {connectivity} "
            f"(2-D values 4/8 do not apply to volumes)"
        )
    vol = jnp.asarray(volume_image, jnp.float32)
    if threshold_method == "otsu":
        t = threshold_ops.otsu_value(vol) * correction_factor
        mask = vol > t
    elif threshold_method == "manual":
        mask = vol > threshold_value
    else:
        raise ValueError(f"unknown threshold method '{threshold_method}'")
    labels, _ = connected_components_3d(mask, connectivity)
    return {"objects": label_ops.clip_label_count(labels, max_objects)}


@register_module("segment_volume_secondary")
def segment_volume_secondary(
    volume_image,
    primary_label_image,
    threshold_value: float = 0.0,
    correction_factor: float = 1.0,
    n_levels: int = 16,
    max_objects: int = 256,
):
    """3-D secondary segmentation: grow cell volumes outward from primary
    3-D seeds by level-ordered flooding, keeping seed ids (the volume twin
    of ``segment_secondary``; reference jtmodules pairs primary/secondary
    segmentation in 3-D via the same CellProfiler propagate scheme)."""
    from tmlibrary_tpu.ops.volume import watershed_from_seeds_3d

    vol = jnp.asarray(volume_image, jnp.float32)
    if threshold_value > 0.0:
        t = jnp.float32(threshold_value) * correction_factor
    else:
        t = threshold_ops.otsu_value(vol) * correction_factor
    mask = vol > t
    out = watershed_from_seeds_3d(
        vol, label_ops.clip_label_count(primary_label_image, max_objects),
        mask, n_levels=n_levels,
    )
    return {"objects": label_ops.clip_label_count(out, max_objects)}


@register_module("measure_volume")
def measure_volume(objects_image, intensity_image, max_objects: int = 256):
    """3-D per-object measurements (volume, centroid, intensity stats)."""
    from tmlibrary_tpu.ops.volume import volume_features

    return {
        "measurements": volume_features(objects_image, intensity_image, max_objects)
    }


@register_module("expand_or_shrink")
def expand_or_shrink(label_image, n: int = 1, max_objects: int = 256):
    """Reference ``jtmodules/expand_or_shrink.py``: morphological expansion
    (n>0) or shrinkage (n<0) of labeled objects.

    Expansion assigns background pixels to the nearest label iteratively
    (ties go to the larger label id via max-propagation, deterministic).
    """
    from tmlibrary_tpu.ops.segment_secondary import expand_labels

    lab = jnp.asarray(label_image, jnp.int32)
    if n == 0:
        return {"expanded_image": lab}
    if n > 0:
        return {"expanded_image": expand_labels(lab, iterations=n)}
    mask = lab > 0
    eroded = label_ops.binary_erode(mask, connectivity=8, iterations=-n)
    return {"expanded_image": jnp.where(eroded, lab, 0)}


@register_module("clip")
def clip(intensity_image, lower: float = 0.0, upper: float = 65535.0):
    """Reference ``jtmodules/clip.py``: clip intensities to [lower, upper]."""
    from tmlibrary_tpu.ops import image_ops

    return {"clipped_image": image_ops.clip_values(intensity_image, lower, upper)}


@register_module("combine_channels")
def combine_channels(image_1, image_2, weight_1: float = 1.0, weight_2: float = 1.0):
    """Reference ``jtmodules/combine_channels.py``: weighted sum of two
    channel images (used to pool correlated stains before segmentation)."""
    a = jnp.asarray(image_1, jnp.float32)
    b = jnp.asarray(image_2, jnp.float32)
    return {"combined_image": weight_1 * a + weight_2 * b}


@register_module("expand")
def expand(label_image, n: int = 1):
    """Reference ``jtmodules/expand.py``: grow labeled objects by ``n``
    pixels (nearest-label assignment, deterministic tie-break)."""
    return {"expanded_image": expand_or_shrink(label_image, n=n)["expanded_image"]}


@register_module("shrink")
def shrink(label_image, n: int = 1):
    """Reference ``jtmodules/shrink.py``: erode labeled objects by ``n``
    pixels (labels kept where the object mask survives erosion)."""
    return {"shrunken_image": expand_or_shrink(label_image, n=-n)["expanded_image"]}


@register_module("mip")
def mip(zstack):
    """Reference ``jtmodules/mip.py``: maximum-intensity projection of a
    z-stack (alias for ``project(method="max")``)."""
    return {"mip_image": project(zstack, method="max")["projected_image"]}


@register_module("detect_blobs")
def detect_blobs(
    intensity_image,
    threshold: float = 10.0,
    min_distance: int = 3,
    sigma_min: float = 1.5,
    sigma_max: float = 4.0,
    n_scales: int = 3,
    max_objects: int = 256,
):
    """Reference ``jtmodules/detect_blobs.py`` (LoG spot detection for
    punctate structures)."""
    from tmlibrary_tpu.ops.blobs import detect_blobs as _db

    lo, hi, n = float(sigma_min), float(sigma_max), int(n_scales)
    sigmas = tuple(lo + (hi - lo) * i / max(n - 1, 1) for i in range(n))
    blobs, centers, _count = _db(
        intensity_image,
        sigmas=sigmas,
        threshold=threshold,
        min_distance=min_distance,
        max_objects=max_objects,
    )
    return {"objects": blobs, "centers": centers}


#: reserved output-key prefix for module-diagnostic QC streams: outputs
#: named ``__qc__<stat>`` are NOT pipeline handles — ``build_site_fn``
#: collects them (QC-enabled builds only) and the qc session sketches
#: them under the ``__model__`` pseudo-objects, giving model-output
#: drift detection (``tmx qc --profile-kind model``) a zero-copy ride on
#: the batch program.  QC-off builds ignore the keys, so XLA dead-code
#: eliminates the stats and the label outputs stay bit-identical.
MODULE_QC_PREFIX = "__qc__"

#: reserved output key for a module's *demand*: the number of objects it
#: saw before ``max_objects`` clipped them (an int32 scalar, a function
#: of the module's inputs and never of the capacity).  Like the QC keys
#: it is no pipeline handle: ``build_site_fn`` folds the reporting
#: modules' demands into ``SiteResult.demand``, which the capacity
#: router reads to pick the rung of a re-launch (``capacity.py``).  A
#: module that clips and does not report is still safe — its clipped
#: count reaches the router through ``counts`` — it only makes the
#: router climb one rung at a time.
MODULE_DEMAND_KEY = "__demand__"


def _qc_sample(values, k: int = 64):
    """Deterministic fixed-size sample of a stat image for the QC
    sketches: ``k`` evenly-strided pixels in scan order (static gather —
    no data-dependent shapes, no randomness)."""
    flat = jnp.ravel(jnp.asarray(values, jnp.float32))
    n = flat.shape[0]
    idx = (jnp.arange(k, dtype=jnp.int32) * (n // k)) % n
    return flat[idx]


@register_module("segment_dl_primary")
def segment_dl_primary(
    intensity_image,
    weights: str = "seed:0",
    prob_threshold: float = 0.5,
    flow_steps: int = 24,
    min_seed_hits: int = 2,
    min_area: int = 0,
    max_objects: int = 256,
):
    """Deep-learning primary segmentation (nuclei): the pure-JAX
    flow-field U-Net + deterministic decoder (``tmlibrary_tpu.nn``,
    DESIGN.md §23).

    ``weights`` is a checkpoint spec (``nn/weights.py``): a named
    ``.npz`` in the weights directory, an explicit path, or
    ``seed:<n>[:base=C][:depth=D]`` for deterministic random weights.
    The parameters resolve at trace time and close over the program as
    resident constants — donation-safe (only the image arguments are
    donated) — while their content digest joins the compiled-program
    cache key via ``pipeline.program_digest_extras``.
    """
    from tmlibrary_tpu import nn

    params, _digest, config = nn.resolve_weights(weights)
    img = nn.normalize_image(intensity_image)
    head = nn.unet_apply(params, img, config)
    flow = head[..., :2]
    cellprob = jax.nn.sigmoid(head[..., 2])
    labels, _count = nn.decode_flows(
        flow,
        cellprob,
        prob_threshold=prob_threshold,
        flow_steps=flow_steps,
        min_seed_hits=min_seed_hits,
        min_area=min_area,
        max_objects=max_objects,
    )
    flow_mag = jnp.sqrt(flow[..., 0] ** 2 + flow[..., 1] ** 2)
    return {
        "objects": labels,
        f"{MODULE_QC_PREFIX}flow_mag": _qc_sample(flow_mag),
        f"{MODULE_QC_PREFIX}cell_prob": _qc_sample(cellprob),
    }


@register_module("segment_dl_secondary")
def segment_dl_secondary(
    primary_label_image,
    intensity_image,
    weights: str = "seed:0",
    prob_threshold: float = 0.5,
    max_objects: int = 256,
):
    """Deep-learning secondary segmentation: grow primary objects across
    the U-Net's cell-probability foreground (``nn.decode_secondary``),
    keeping primary label ids so feature rows stay aligned."""
    from tmlibrary_tpu import nn

    params, _digest, config = nn.resolve_weights(weights)
    img = nn.normalize_image(intensity_image)
    head = nn.unet_apply(params, img, config)
    cellprob = jax.nn.sigmoid(head[..., 2])
    labels, _count = nn.decode_secondary(
        primary_label_image,
        cellprob,
        prob_threshold=prob_threshold,
        max_objects=max_objects,
    )
    return {
        "objects": labels,
        f"{MODULE_QC_PREFIX}cell_prob_secondary": _qc_sample(cellprob),
    }
