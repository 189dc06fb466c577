"""Benchmark pipelines and synthetic data (shared by bench.py and tests).

The flagship configuration is BASELINE.json config 3: the Cell Painting
segment+measure pipeline — ``segment_primary`` (nuclei from DAPI) →
``segment_secondary`` (cells grown from nuclei through the actin channel) →
``measure_intensity`` on both channels.  The benchmark metric is
sites/sec/chip (reference: jterator's per-site job throughput).

The other ``BENCH_CONFIG`` values cover the rest of the BASELINE ladder:
``2`` (the minimum end-to-end slice: smooth + adaptive threshold +
label, single channel), ``4`` (5-channel full feature stack), ``volume``
(3-D z-stack pipeline, config 5 stretch), ``corilla`` (illumination
statistics, channels/sec — the reference's second headline metric) and
``pyramid`` (config 5's other half: illuminati mosaic stitch + zoomify
level chain, Mpix/sec).
"""

from __future__ import annotations

import numpy as np

from tmlibrary_tpu.jterator.description import PipelineDescription

CELL_PAINTING_PIPE = {
    "description": "Cell Painting: segment nuclei + cells, measure intensity",
    "input": {
        "channels": [
            {"name": "DAPI", "correct": False, "align": False},
            {"name": "Actin", "correct": False, "align": False},
        ]
    },
    "pipeline": [
        {
            "handles": {
                "module": "smooth",
                "input": [
                    {"name": "intensity_image", "type": "IntensityImage", "key": "DAPI"},
                    {"name": "sigma", "type": "Numeric", "value": 1.5},
                ],
                "output": [
                    {"name": "smoothed_image", "type": "IntensityImage", "key": "dapi_sm"}
                ],
            }
        },
        {
            "handles": {
                "module": "segment_primary",
                "input": [
                    {"name": "intensity_image", "type": "IntensityImage", "key": "dapi_sm"},
                    {"name": "threshold_method", "type": "Character", "value": "otsu"},
                    {"name": "smooth_sigma", "type": "Numeric", "value": 0.0},
                    {"name": "min_area", "type": "Numeric", "value": 20},
                ],
                "output": [
                    {
                        "name": "objects",
                        "type": "SegmentedObjects",
                        "key": "nuclei",
                        "objects": "nuclei",
                    }
                ],
            }
        },
        {
            "handles": {
                "module": "segment_secondary",
                "input": [
                    {"name": "primary_label_image", "type": "LabelImage", "key": "nuclei"},
                    {"name": "intensity_image", "type": "IntensityImage", "key": "Actin"},
                    {"name": "correction_factor", "type": "Numeric", "value": 0.8},
                    {"name": "n_levels", "type": "Numeric", "value": 16},
                ],
                "output": [
                    {
                        "name": "objects",
                        "type": "SegmentedObjects",
                        "key": "cells",
                        "objects": "cells",
                    }
                ],
            }
        },
        {
            "handles": {
                "module": "measure_intensity",
                "input": [
                    {"name": "objects_image", "type": "LabelImage", "key": "nuclei"},
                    {"name": "intensity_image", "type": "IntensityImage", "key": "DAPI"},
                ],
                "output": [
                    {
                        "name": "measurements",
                        "type": "Measurement",
                        "objects": "nuclei",
                        "channel": "DAPI",
                    }
                ],
            }
        },
        {
            "handles": {
                "module": "measure_intensity",
                "input": [
                    {"name": "objects_image", "type": "LabelImage", "key": "cells"},
                    {"name": "intensity_image", "type": "IntensityImage", "key": "Actin"},
                ],
                "output": [
                    {
                        "name": "measurements",
                        "type": "Measurement",
                        "objects": "cells",
                        "channel": "Actin",
                    }
                ],
            }
        },
    ],
    "output": {
        "objects": [{"name": "nuclei"}, {"name": "cells"}]
    },
}


def cell_painting_description() -> PipelineDescription:
    return PipelineDescription.from_dict(CELL_PAINTING_PIPE)


def dl_description(
    weights: str = "seed:0",
    prob_threshold: float = 0.6,
    min_area: int = 4,
) -> PipelineDescription:
    """BENCH_CONFIG ``dl``: deep-learning segmentation + measurement —
    ``segment_dl_primary`` (the pure-JAX flow-field U-Net +
    deterministic decoder, ``tmlibrary_tpu.nn``) on DAPI, then
    ``measure_intensity`` on the decoded nuclei.  The conv workload is
    the repo's first MXU-resident bench config (``bound_by=compute``
    roofline rungs); ``weights`` is an ``nn/weights.py`` checkpoint
    spec, defaulting to deterministic seeded weights so the config runs
    anywhere without a trained checkpoint."""
    return PipelineDescription.from_dict({
        "description": "DL segmentation: U-Net nuclei, measure intensity",
        "input": {
            "channels": [{"name": "DAPI", "correct": False, "align": False}]
        },
        "pipeline": [
            {
                "handles": {
                    "module": "segment_dl_primary",
                    "input": [
                        {"name": "intensity_image", "type": "IntensityImage",
                         "key": "DAPI"},
                        {"name": "weights", "type": "Character",
                         "value": weights},
                        {"name": "prob_threshold", "type": "Numeric",
                         "value": prob_threshold},
                        {"name": "min_area", "type": "Numeric",
                         "value": min_area},
                    ],
                    "output": [
                        {"name": "objects", "type": "SegmentedObjects",
                         "key": "cells", "objects": "cells"}
                    ],
                }
            },
            {
                "handles": {
                    "module": "measure_intensity",
                    "input": [
                        {"name": "objects_image", "type": "LabelImage",
                         "key": "cells"},
                        {"name": "intensity_image", "type": "IntensityImage",
                         "key": "DAPI"},
                    ],
                    "output": [
                        {"name": "measurements", "type": "Measurement",
                         "objects": "cells", "channel": "DAPI"}
                    ],
                }
            },
        ],
        "output": {"objects": [{"name": "cells"}]},
    })


#: the five canonical Cell Painting stains (BASELINE.json config 4)
FULL_STACK_CHANNELS = ("DAPI", "Actin", "Tubulin", "ER", "Mito")


def full_feature_description(
    channels: tuple[str, ...] = FULL_STACK_CHANNELS,
    texture_levels: int = 16,
    zernike_degree: int = 6,
) -> PipelineDescription:
    """BASELINE.json config 4: the full feature stack — nuclei + cells
    segmentation, then measure_intensity on every channel for both object
    types, measure_morphology on both, Haralick texture and Zernike
    moments.  5-channel 384-well plate is the target geometry; channel
    count is configurable for tests."""
    nucleus_ch, cell_ch = channels[0], channels[1]

    def _measure(module, inputs, objects, channel=None):
        out = {"name": "measurements", "type": "Measurement", "objects": objects}
        if channel:
            out["channel"] = channel
        return {"handles": {"module": module, "input": inputs, "output": [out]}}

    pipeline = [
        {
            "handles": {
                "module": "smooth",
                "input": [
                    {"name": "intensity_image", "type": "IntensityImage",
                     "key": nucleus_ch},
                    {"name": "sigma", "type": "Numeric", "value": 1.5},
                ],
                "output": [
                    {"name": "smoothed_image", "type": "IntensityImage",
                     "key": "nuc_sm"}
                ],
            }
        },
        {
            "handles": {
                "module": "segment_primary",
                "input": [
                    {"name": "intensity_image", "type": "IntensityImage",
                     "key": "nuc_sm"},
                    {"name": "threshold_method", "type": "Character",
                     "value": "otsu"},
                    {"name": "smooth_sigma", "type": "Numeric", "value": 0.0},
                    {"name": "min_area", "type": "Numeric", "value": 20},
                ],
                "output": [
                    {"name": "objects", "type": "SegmentedObjects",
                     "key": "nuclei", "objects": "nuclei"}
                ],
            }
        },
        {
            "handles": {
                "module": "segment_secondary",
                "input": [
                    {"name": "primary_label_image", "type": "LabelImage",
                     "key": "nuclei"},
                    {"name": "intensity_image", "type": "IntensityImage",
                     "key": cell_ch},
                    {"name": "correction_factor", "type": "Numeric", "value": 0.8},
                    {"name": "n_levels", "type": "Numeric", "value": 16},
                ],
                "output": [
                    {"name": "objects", "type": "SegmentedObjects",
                     "key": "cells", "objects": "cells"}
                ],
            }
        },
    ]
    # intensity on every channel for both object types
    for objects in ("nuclei", "cells"):
        for ch in channels:
            pipeline.append(
                _measure(
                    "measure_intensity",
                    [
                        {"name": "objects_image", "type": "LabelImage",
                         "key": objects},
                        {"name": "intensity_image", "type": "IntensityImage",
                         "key": ch},
                    ],
                    objects,
                    channel=ch,
                )
            )
    # morphology on both object types
    for objects in ("nuclei", "cells"):
        pipeline.append(
            _measure(
                "measure_morphology",
                [{"name": "objects_image", "type": "LabelImage", "key": objects}],
                objects,
            )
        )
    # Haralick texture: cells on the cytoskeleton channel
    pipeline.append(
        _measure(
            "measure_texture",
            [
                {"name": "objects_image", "type": "LabelImage", "key": "cells"},
                {"name": "intensity_image", "type": "IntensityImage",
                 "key": cell_ch},
                {"name": "levels", "type": "Numeric", "value": texture_levels},
            ],
            "cells",
            channel=cell_ch,
        )
    )
    # Zernike moments: nuclei shape
    pipeline.append(
        _measure(
            "measure_zernike",
            [
                {"name": "objects_image", "type": "LabelImage", "key": "nuclei"},
                {"name": "degree", "type": "Numeric", "value": zernike_degree},
            ],
            "nuclei",
        )
    )
    return PipelineDescription.from_dict(
        {
            "description": "Cell Painting full feature stack (config 4)",
            "input": {
                "channels": [
                    {"name": ch, "correct": False, "align": False}
                    for ch in channels
                ]
            },
            "pipeline": pipeline,
            "output": {"objects": [{"name": "nuclei"}, {"name": "cells"}]},
        }
    )


def synthetic_full_stack_batch(
    n_sites: int,
    size: int = 256,
    n_cells: int = 12,
    channels: tuple[str, ...] = FULL_STACK_CHANNELS,
    seed: int = 0,
) -> dict[str, np.ndarray]:
    """Synthetic multi-channel Cell Painting batch: nuclei in channel 0,
    cell bodies in every other channel (varying radius/brightness)."""
    rng = np.random.default_rng(seed)
    yy, xx = np.mgrid[0:size, 0:size].astype(np.float32)
    out = {
        ch: rng.normal(300.0, 25.0, (n_sites, size, size)).astype(np.float32)
        for ch in channels
    }
    margin = size // 10
    for s in range(n_sites):
        ys = rng.integers(margin, size - margin, n_cells)
        xs = rng.integers(margin, size - margin, n_cells)
        for y, x in zip(ys, xs):
            r_n = rng.uniform(3.5, 5.5)
            d2 = (yy - y) ** 2 + (xx - x) ** 2
            out[channels[0]][s] += 4000.0 * np.exp(-d2 / (2 * r_n**2))
            for k, ch in enumerate(channels[1:]):
                r_c = r_n * rng.uniform(1.8, 3.0)
                amp = rng.uniform(900.0, 1800.0)
                out[ch][s] += amp * np.exp(-d2 / (2 * r_c**2))
    return {ch: np.clip(v, 0, 65535) for ch, v in out.items()}


def synthetic_cell_painting_batch(
    n_sites: int, size: int = 256, n_cells: int = 12, seed: int = 0,
    dapi_only: bool = False,
) -> dict[str, np.ndarray]:
    """Synthetic DAPI (nuclei) + Actin (cell body) site images, float32.

    ``dapi_only`` skips the Actin channel's per-cell splats (config 2
    uses one channel; half the generator time would be thrown away).
    Same rng draw sequence either way, so the DAPI images are identical.
    """
    rng = np.random.default_rng(seed)
    yy, xx = np.mgrid[0:size, 0:size].astype(np.float32)
    dapi = rng.normal(300.0, 25.0, (n_sites, size, size)).astype(np.float32)
    actin = rng.normal(300.0, 25.0, (n_sites, size, size)).astype(np.float32)
    margin = size // 10
    for s in range(n_sites):
        ys = rng.integers(margin, size - margin, n_cells)
        xs = rng.integers(margin, size - margin, n_cells)
        for y, x in zip(ys, xs):
            r_n = rng.uniform(3.5, 5.5)
            r_c = r_n * rng.uniform(2.0, 3.0)
            d2 = (yy - y) ** 2 + (xx - x) ** 2
            dapi[s] += 4000.0 * np.exp(-d2 / (2 * r_n**2))
            if not dapi_only:
                actin[s] += 1500.0 * np.exp(-d2 / (2 * r_c**2))
    out = {"DAPI": np.clip(dapi, 0, 65535)}
    if not dapi_only:
        out["Actin"] = np.clip(actin, 0, 65535)
    return out


# ------------------------------------------------------------------ CPU golden
def _otsu_numpy(img: np.ndarray, bins: int = 256) -> float:
    """Pure-numpy Otsu (same fixed-bin formulation as ops.threshold)."""
    lo, hi = float(img.min()), float(img.max())
    span = max(hi - lo, 1e-6)
    idx = np.clip(((img - lo) / span * bins).astype(np.int32), 0, bins - 1)
    hist = np.bincount(idx.ravel(), minlength=bins).astype(np.float64)
    centers = lo + (np.arange(bins) + 0.5) / bins * span
    w0 = np.cumsum(hist)
    w1 = w0[-1] - w0
    sum0 = np.cumsum(hist * centers)
    mu0 = sum0 / np.maximum(w0, 1e-12)
    mu1 = (sum0[-1] - sum0) / np.maximum(w1, 1e-12)
    between = np.where((w0 > 0) & (w1 > 0), w0 * w1 * (mu0 - mu1) ** 2, -1.0)
    return float(centers[int(np.argmax(between))])


def _zernike_numpy(mask: np.ndarray, degree: int = 6, patch: int = 64) -> np.ndarray:
    """Independent numpy Zernike magnitudes of one object mask (reference:
    mahotas ``zernike_moments``) — used only as the single-CPU throughput
    denominator for config 4."""
    from math import factorial

    ys, xs = np.nonzero(mask)
    if len(ys) == 0:
        return np.zeros(1)
    cy, cx = ys.mean(), xs.mean()
    r = max(np.sqrt(((ys - cy) ** 2 + (xs - cx) ** 2)).max(), 1.0)
    rho = np.sqrt((ys - cy) ** 2 + (xs - cx) ** 2) / r
    theta = np.arctan2(ys - cy, xs - cx)
    vals = []
    for n in range(degree + 1):
        for m in range(0, n + 1):
            if (n - m) % 2:
                continue
            rad = np.zeros_like(rho)
            for k in range((n - m) // 2 + 1):
                c = ((-1) ** k * factorial(n - k)) / (
                    factorial(k)
                    * factorial((n + m) // 2 - k)
                    * factorial((n - m) // 2 - k)
                )
                rad += c * rho ** (n - 2 * k)
            z = (rad * np.exp(-1j * m * theta)).sum() * (n + 1) / np.pi
            vals.append(np.abs(z))
    return np.asarray(vals)


def _haralick_numpy(img: np.ndarray, mask: np.ndarray, levels: int = 16) -> np.ndarray:
    """Independent numpy GLCM Haralick summary of one object (reference:
    mahotas ``haralick``) — throughput denominator only."""
    lo, hi = img.min(), img.max()
    q = np.clip(((img - lo) / max(hi - lo, 1e-6) * levels).astype(np.int32),
                0, levels - 1)
    feats = []
    for dy, dx in ((0, 1), (1, 0), (1, 1), (1, -1)):
        h, w = q.shape
        y0, x0 = max(0, -dy), max(0, -dx)
        y1, x1 = min(h, h - dy), min(w, w - dx)
        src = q[y0:y1, x0:x1]
        dst = q[y0 + dy:y1 + dy, x0 + dx:x1 + dx]
        m = mask[y0:y1, x0:x1] & mask[y0 + dy:y1 + dy, x0 + dx:x1 + dx]
        pairs = src[m] * levels + dst[m]
        glcm = np.bincount(pairs, minlength=levels * levels).astype(np.float64)
        glcm = glcm.reshape(levels, levels)
        glcm = glcm + glcm.T
        total = max(glcm.sum(), 1.0)
        p = glcm / total
        i_idx, j_idx = np.mgrid[0:levels, 0:levels]
        contrast = (p * (i_idx - j_idx) ** 2).sum()
        energy = (p ** 2).sum()
        homogeneity = (p / (1.0 + np.abs(i_idx - j_idx))).sum()
        entropy = -(p[p > 0] * np.log(p[p > 0])).sum()
        feats.extend([contrast, energy, homogeneity, entropy])
    return np.asarray(feats)


def cpu_reference_site_full(
    channels: dict[str, np.ndarray], texture_levels: int = 16,
    zernike_degree: int = 6,
) -> tuple[int, int]:
    """Single-threaded scipy/numpy implementation of the config-4 full
    feature stack (segment nuclei+cells, intensity on every channel for
    both object types, morphology, Haralick texture, Zernike) — the
    honest single-CPU denominator for ``BENCH_CONFIG=4``."""
    import scipy.ndimage as ndi

    names = list(channels)
    dapi, cell_ch = channels[names[0]], channels[names[1]]

    # segmentation exactly once (same chain as cpu_reference_site,
    # including its min_area >= 20 filter)
    sm = ndi.gaussian_filter(dapi.astype(np.float32), 1.5, mode="reflect")
    mask = ndi.binary_fill_holes(sm > _otsu_numpy(sm))
    labels, _ = ndi.label(mask, ndi.generate_binary_structure(2, 2))
    sizes = np.bincount(labels.ravel())[1:]
    n_nuclei = int((sizes >= 20).sum())
    t2 = _otsu_numpy(cell_ch) * 0.8
    dist, (iy, ix) = ndi.distance_transform_edt(labels == 0, return_indices=True)
    cells = np.where(cell_ch > t2, labels[iy, ix], 0)

    for lab_img in (labels, cells):
        ids = np.unique(lab_img)[1:]
        if not len(ids):
            continue
        # intensity on every channel
        for img in channels.values():
            ndi.mean(img, lab_img, ids)
            ndi.standard_deviation(img, lab_img, ids)
            ndi.maximum(img, lab_img, ids)
            ndi.minimum(img, lab_img, ids)
            ndi.sum(img, lab_img, ids)
        # morphology
        ndi.center_of_mass(lab_img > 0, lab_img, ids)
        slices = ndi.find_objects(lab_img)
        np.bincount(lab_img.ravel())
        eroded = ndi.binary_erosion(lab_img > 0)
        ((lab_img > 0) & ~eroded).sum()
        # texture + zernike per object
        for lab in ids:
            sl = slices[lab - 1]
            if sl is None:
                continue
            obj_mask = lab_img[sl] == lab
            if lab_img is cells:
                _haralick_numpy(cell_ch[sl], obj_mask, texture_levels)
            else:
                _zernike_numpy(obj_mask, zernike_degree)
    return n_nuclei, len(np.unique(cells)) - 1


def cpu_reference_site(dapi: np.ndarray, actin: np.ndarray) -> tuple[int, int]:
    """Single-threaded scipy/numpy implementation of the same pipeline —
    the single-CPU denominator (BASELINE.md: measured, not published).
    Returns (n_nuclei, n_cells)."""
    import scipy.ndimage as ndi

    sm = ndi.gaussian_filter(dapi, 1.5, mode="reflect")
    t = _otsu_numpy(sm)
    mask = ndi.binary_fill_holes(sm > t)
    labels, n = ndi.label(mask, ndi.generate_binary_structure(2, 2))
    # size filter >= 20
    sizes = np.bincount(labels.ravel())
    keep = np.flatnonzero(sizes >= 20)[1:]
    n_nuclei = len(keep)
    # secondary: nearest-seed growth through actin mask (approximate golden)
    t2 = _otsu_numpy(actin) * 0.8
    cell_mask = actin > t2
    dist, (iy, ix) = ndi.distance_transform_edt(labels == 0, return_indices=True)
    cells = np.where(cell_mask, labels[iy, ix], 0)
    n_cells = len(np.unique(cells)) - 1
    # intensity stats per object (numpy)
    for lab_img, img in ((labels, dapi), (cells, actin)):
        ids = np.unique(lab_img)[1:]
        if len(ids):
            ndi.mean(img, lab_img, ids)
            ndi.standard_deviation(img, lab_img, ids)
            ndi.maximum(img, lab_img, ids)
            ndi.minimum(img, lab_img, ids)
            ndi.sum(img, lab_img, ids)
    return n_nuclei, n_cells


def _conv2d_numpy(
    x: np.ndarray, w: np.ndarray, b: np.ndarray, stride: int = 1
) -> np.ndarray:
    """SAME-padded (H, W, Cin) conv via im2col + one BLAS matmul — the
    honest single-thread shape of the same MXU work (numpy matmul may
    thread; the caller pins OMP threads where that matters, and the
    denominator convention is "naive library code", not "hand-crippled")."""
    kh, kw, cin, cout = w.shape
    h, wd = x.shape[:2]
    xp = np.pad(x, ((kh // 2, kh // 2), (kw // 2, kw // 2), (0, 0)))
    oh, ow = -(-h // stride), -(-wd // stride)
    cols = np.empty((oh, ow, kh * kw * cin), np.float32)
    i = 0
    for dy in range(kh):
        for dx in range(kw):
            cols[..., i:i + cin] = xp[dy:dy + h:stride, dx:dx + wd:stride]
            i += cin
    y = cols.reshape(oh * ow, -1) @ w.reshape(-1, cout) + b
    return y.reshape(oh, ow, cout).astype(np.float32)


def cpu_reference_site_dl(dapi: np.ndarray, weights: str = "seed:0") -> int:
    """Single-threaded numpy mirror of the ``dl`` config's per-site work
    — U-Net forward as im2col matmuls, sigmoid mask, flow-followed
    seeds, scipy connected components, per-object intensity stats
    (approximate golden, same convention as the other
    ``cpu_reference_site_*`` denominators).  Returns the object count."""
    import scipy.ndimage as ndi

    from tmlibrary_tpu.nn import resolve_weights

    params, _digest, cfg = resolve_weights(weights)
    img = np.asarray(dapi, np.float32)
    x = (img - img.mean()) / (img.std() + 1e-6)
    h, w = x.shape
    mult = 1 << cfg.depth
    ph, pw = (-h) % mult, (-w) % mult
    a = np.pad(x[..., None], ((0, ph), (0, pw), (0, 0)), mode="edge")

    def conv(t, name, stride=1):
        return _conv2d_numpy(
            t, params[f"{name}/w"], params[f"{name}/b"], stride
        )

    relu = lambda t: np.maximum(t, 0.0)  # noqa: E731
    a = relu(conv(a, "enc0/conv1"))
    a = relu(conv(a, "enc0/conv2"))
    skips = []
    for i in range(1, cfg.depth + 1):
        skips.append(a)
        a = relu(conv(a, f"down{i}", stride=2))
        a = relu(conv(a, f"enc{i}/conv1"))
        a = relu(conv(a, f"enc{i}/conv2"))
    for i in range(cfg.depth, 0, -1):
        a = a.repeat(2, axis=0).repeat(2, axis=1)
        a = relu(conv(a, f"up{i}"))
        a = np.concatenate([a, skips[i - 1]], axis=-1)
        a = relu(conv(a, f"dec{i}"))
    y = conv(a, "head")[:h, :w]

    flow, prob = y[..., :2], 1.0 / (1.0 + np.exp(-y[..., 2]))
    mask = prob > 0.6
    py, px = np.mgrid[0:h, 0:w]
    for _ in range(24):
        py = np.clip(py + np.sign(flow[py, px, 0]).astype(np.int64), 0, h - 1)
        px = np.clip(px + np.sign(flow[py, px, 1]).astype(np.int64), 0, w - 1)
    hits = np.zeros((h, w), np.int64)
    np.add.at(hits, (py[mask], px[mask]), 1)
    seeds, _n = ndi.label(hits >= 2, ndi.generate_binary_structure(2, 2))
    labels = np.where(mask, seeds[py, px], 0)
    ids = np.unique(labels)[1:]
    if len(ids):
        ndi.mean(img, labels, ids)
        ndi.standard_deviation(img, labels, ids)
        ndi.maximum(img, labels, ids)
        ndi.minimum(img, labels, ids)
        ndi.sum(img, labels, ids)
    return len(ids)


# ------------------------------------------------------------- volume config
def volume_description(n_levels: int = 8) -> PipelineDescription:
    """BASELINE config 5 (stretch): the 3-D z-stack pipeline — focus-based
    volume generation, 3-D primary segmentation (Otsu + 26-connected CC),
    3-D secondary growth by level-ordered flooding, volumetric
    measurements."""
    def h(module, inputs, outputs):
        return {"handles": {"module": module, "input": inputs, "output": outputs}}

    return PipelineDescription.from_dict(
        {
            "description": "3-D volume segment+measure",
            "input": {
                "channels": [{"name": "DAPI", "correct": False, "zstack": True}]
            },
            "pipeline": [
                h(
                    "generate_volume_image",
                    [
                        {"name": "zstack", "type": "IntensityImage", "key": "DAPI"},
                        {"name": "mode", "type": "Character", "value": "focus"},
                    ],
                    [{"name": "volume_image", "type": "IntensityImage", "key": "vol"}],
                ),
                h(
                    "segment_volume",
                    [
                        {"name": "volume_image", "type": "IntensityImage", "key": "vol"},
                        {"name": "threshold_method", "type": "Character", "value": "otsu"},
                    ],
                    [
                        {
                            "name": "objects",
                            "type": "SegmentedObjects",
                            "key": "nuclei3d",
                            "objects": "nuclei3d",
                        }
                    ],
                ),
                h(
                    "segment_volume_secondary",
                    [
                        {"name": "volume_image", "type": "IntensityImage", "key": "vol"},
                        {"name": "primary_label_image", "type": "LabelImage", "key": "nuclei3d"},
                        {"name": "correction_factor", "type": "Numeric", "value": 0.8},
                        {"name": "n_levels", "type": "Numeric", "value": n_levels},
                    ],
                    [
                        {
                            "name": "objects",
                            "type": "SegmentedObjects",
                            "key": "cells3d",
                            "objects": "cells3d",
                        }
                    ],
                ),
                h(
                    "measure_volume",
                    [
                        {"name": "objects_image", "type": "LabelImage", "key": "nuclei3d"},
                        {"name": "intensity_image", "type": "IntensityImage", "key": "vol"},
                    ],
                    [
                        {
                            "name": "measurements",
                            "type": "Measurement",
                            "objects": "nuclei3d",
                        }
                    ],
                ),
            ],
            "output": {"objects": [{"name": "nuclei3d"}, {"name": "cells3d"}]},
        }
    )


def synthetic_volume_batch(
    n_sites: int, size: int = 128, depth: int = 16, n_cells: int = 8, seed: int = 0
) -> dict[str, np.ndarray]:
    """Synthetic (B, Z, H, W) DAPI z-stacks: 3-D Gaussian nuclei at random
    depths over a noisy background."""
    rng = np.random.default_rng(seed)
    zz, yy, xx = np.mgrid[0:depth, 0:size, 0:size].astype(np.float32)
    out = rng.normal(300.0, 25.0, (n_sites, depth, size, size)).astype(np.float32)
    margin = size // 8
    for s in range(n_sites):
        for _ in range(n_cells):
            y = rng.integers(margin, size - margin)
            x = rng.integers(margin, size - margin)
            z = rng.integers(depth // 4, 3 * depth // 4)
            r_xy = rng.uniform(4.0, 6.0)
            r_z = rng.uniform(1.5, 2.5)
            out[s] += 4000.0 * np.exp(
                -(
                    ((zz - z) ** 2) / (2 * r_z**2)
                    + ((yy - y) ** 2 + (xx - x) ** 2) / (2 * r_xy**2)
                )
            )
    return {"DAPI": np.clip(out, 0, 65535)}


def cpu_reference_site_volume(zstack: np.ndarray) -> tuple[int, int]:
    """Single-CPU scipy equivalent of the volume pipeline (denominator):
    variance-of-Laplacian focus weighting, Otsu, 26-connected 3-D label,
    seeded 3-D watershed growth, per-object volume/intensity stats."""
    import scipy.ndimage as ndi

    # focus weighting per plane (box-filtered squared Laplacian)
    lap = np.stack([ndi.laplace(p) for p in zstack])
    focus = np.stack([ndi.uniform_filter(l * l, 5) for l in lap])
    w = focus / np.maximum(focus.max(axis=0, keepdims=True), 1e-6)
    vol = zstack * w

    t = _otsu_numpy(vol)
    labels, n = ndi.label(vol > t, structure=np.ones((3, 3, 3)))

    # secondary: grow from seeds through the lower-threshold mask
    mask2 = vol > t * 0.8
    inv = (vol.max() - vol).astype(np.uint16)
    cells = ndi.watershed_ift(inv, markers=labels.astype(np.int32),
                              structure=np.ones((3, 3, 3), int))
    cells = np.where(mask2, cells, 0)

    # volumetric stats per object
    for lab in range(1, n + 1):
        sel = vol[labels == lab]
        if sel.size:
            sel.mean(), sel.std(), sel.max(), sel.min(), sel.sum()
    return n, len(np.unique(cells)) - 1


# ------------------------------------------------------------ corilla config
def synthetic_channel_stack(
    n_channels: int, n_sites: int, size: int, seed: int = 0
) -> np.ndarray:
    """(C, S, H, W) float32 uint16-range site stack for the corilla
    benchmark (BASELINE config 1)."""
    rng = np.random.default_rng(seed)
    return rng.integers(
        0, 5000, (n_channels, n_sites, size, size)
    ).astype(np.float32)


def cpu_reference_channel(sites: np.ndarray) -> dict[str, np.ndarray]:
    """Single-thread numpy equivalent of one corilla channel job: online
    log-domain Welford mean/std plus the exact 65536-bin raw-intensity
    histogram (reference ``OnlineStatistics.update`` per site)."""
    mean = np.zeros(sites.shape[1:], np.float64)
    m2 = np.zeros_like(mean)
    hist = np.zeros(65536, np.int64)
    for i, raw in enumerate(sites):
        x = np.log10(1.0 + raw)
        delta = x - mean
        mean += delta / (i + 1)
        m2 += delta * (x - mean)
        hist += np.bincount(
            np.clip(raw, 0, 65535).astype(np.int64).ravel(), minlength=65536
        )
    return {
        "mean_log": mean,
        "std_log": np.sqrt(m2 / max(len(sites), 1)),
        "hist": hist,
    }


# --------------------------------------------------- config 2 (milestone)
#: BASELINE.json config 2: the minimum end-to-end slice — smooth +
#: adaptive threshold on 2-D single-channel sites
SMOOTH_THRESHOLD_PIPE = {
    "description": "smooth + adaptive threshold (BASELINE config 2)",
    "input": {"channels": [{"name": "DAPI", "correct": False, "align": False}]},
    "pipeline": [
        {
            "handles": {
                "module": "smooth",
                "input": [
                    {"name": "intensity_image", "type": "IntensityImage",
                     "key": "DAPI"},
                    {"name": "sigma", "type": "Numeric", "value": 1.5},
                ],
                "output": [
                    {"name": "smoothed_image", "type": "IntensityImage",
                     "key": "sm"}
                ],
            }
        },
        {
            "handles": {
                "module": "threshold_adaptive",
                "input": [
                    {"name": "intensity_image", "type": "IntensityImage",
                     "key": "sm"},
                    {"name": "method", "type": "Character", "value": "mean"},
                    {"name": "kernel_size", "type": "Numeric", "value": 31},
                    {"name": "constant", "type": "Numeric", "value": 2},
                ],
                "output": [
                    {"name": "mask", "type": "BinaryImage", "key": "mask"}
                ],
            }
        },
        {
            "handles": {
                "module": "label",
                "input": [
                    {"name": "mask", "type": "BinaryImage", "key": "mask"},
                ],
                "output": [
                    {"name": "label_image", "type": "SegmentedObjects",
                     "key": "fg", "objects": "fg"}
                ],
            }
        },
    ],
}


def smooth_threshold_description():
    from tmlibrary_tpu.jterator.description import PipelineDescription

    return PipelineDescription.from_dict(SMOOTH_THRESHOLD_PIPE)


def cpu_reference_site_smooth_threshold(dapi: "np.ndarray") -> int:
    """Single-threaded scipy twin of config 2 (denominator)."""
    import scipy.ndimage as ndi

    sm = ndi.gaussian_filter(dapi, 1.5, mode="reflect")
    local_mean = ndi.uniform_filter(sm, 31, mode="reflect")
    mask = sm > local_mean + 2
    _, n = ndi.label(mask, ndi.generate_binary_structure(2, 2))
    return n


def cpu_reference_pyramid(
    sites: np.ndarray, grid: tuple[int, int], n_levels: int,
    lower: float, upper: float,
) -> list[np.ndarray]:
    """Single-thread numpy equivalent of one illuminati mosaic job:
    stitch the site grid, then the zoomify level chain (2x2 mean pool,
    edge-padded odd dims) with each level display-stretched to uint8 —
    the same math the device chain runs (BASELINE config 5's pyramid
    half)."""
    gy, gx = grid
    n, h, w = sites.shape
    mosaic = (
        sites.reshape(gy, gx, h, w).transpose(0, 2, 1, 3)
        .reshape(gy * h, gx * w).astype(np.float32)
    )
    span = max(upper - lower, 1e-6)

    def stretch(lvl):
        return np.clip((lvl - lower) / span * 255.0, 0, 255).astype(np.uint8)

    levels = [stretch(mosaic)]
    cur = mosaic
    for _ in range(n_levels - 1):
        hh, ww = cur.shape
        if hh % 2 or ww % 2:
            cur = np.pad(cur, ((0, hh % 2), (0, ww % 2)), mode="edge")
        cur = cur.reshape(cur.shape[0] // 2, 2, cur.shape[1] // 2, 2).mean((1, 3))
        levels.append(stretch(cur))
    return levels


# ------------------------------------------------------------ spatial config
def synthetic_mosaic_well(
    grid_y: int, grid_x: int, size: int = 256, cells_per_site: float = 8.0,
    seed: int = 0,
) -> tuple[np.ndarray, np.ndarray]:
    """One well's mosaic with blobs scattered ACROSS site seams (the case
    the spatial layout exists for), plus its site tiles.

    Returns ``(mosaic (Hm, Wm) uint16, tiles (gy*gx, size, size) uint16)``
    with tiles in row-major site order.
    """
    rng = np.random.default_rng(seed)
    hm, wm = grid_y * size, grid_x * size
    mosaic = rng.normal(300.0, 25.0, (hm, wm)).astype(np.float32)
    n_cells = int(cells_per_site * grid_y * grid_x)
    ys = rng.uniform(4, hm - 4, n_cells)
    xs = rng.uniform(4, wm - 4, n_cells)
    rr = rng.uniform(3.5, 5.5, n_cells)
    # local splats only: a full (Hm, Wm) gaussian per cell would make the
    # generator quadratic in mosaic area
    for y, x, r in zip(ys, xs, rr):
        rad = int(4 * r)
        y0, y1 = max(0, int(y) - rad), min(hm, int(y) + rad + 1)
        x0, x1 = max(0, int(x) - rad), min(wm, int(x) + rad + 1)
        yy, xx = np.mgrid[y0:y1, x0:x1].astype(np.float32)
        mosaic[y0:y1, x0:x1] += 4000.0 * np.exp(
            -((yy - y) ** 2 + (xx - x) ** 2) / (2 * r**2)
        )
    mosaic = np.clip(mosaic, 0, 65535).astype(np.uint16)
    tiles = (
        mosaic.reshape(grid_y, size, grid_x, size)
        .transpose(0, 2, 1, 3)
        .reshape(grid_y * grid_x, size, size)
    )
    return mosaic, np.ascontiguousarray(tiles)


def cpu_reference_mosaic(mosaic: np.ndarray) -> int:
    """Single-threaded scipy twin of the spatial-layout chain on one
    stitched mosaic: smooth -> otsu -> 8-connected global label ->
    per-object morphology (area/centroid/bbox) + intensity stats
    (mean/std/min/max/sum).  The denominator for BENCH_CONFIG=spatial."""
    import scipy.ndimage as ndi

    img = mosaic.astype(np.float32)
    sm = ndi.gaussian_filter(img, 1.5, mode="reflect")
    t = _otsu_numpy(sm)
    labels, n = ndi.label(sm > t, ndi.generate_binary_structure(2, 2))
    if n:
        ids = np.arange(1, n + 1)
        np.bincount(labels.ravel())
        ndi.center_of_mass(np.ones_like(labels), labels, ids)
        ndi.find_objects(labels)
        img64 = img.astype(np.float64)
        ndi.mean(img64, labels, ids)
        ndi.standard_deviation(img64, labels, ids)
        ndi.minimum(img64, labels, ids)
        ndi.maximum(img64, labels, ids)
        ndi.sum(img64, labels, ids)
    return n
