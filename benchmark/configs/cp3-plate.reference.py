"""The plain reference of ``cp3-plate``: BASELINE config 3 as a
single-threaded scipy/numpy chain (smooth sigma 1.5, 256-bin Otsu, fill
holes, 8-connected label, drop objects under 20 px; cells by nearest-seed
growth through the Actin Otsu mask at 0.8), copied from
``tmlibrary_tpu/benchmarks.py`` (``cpu_reference_site``, ``_otsu_numpy``)
and ``chip_smoke.py`` (``check_counts_and_features``) so that the
yardstick cannot move with the program.  It shares no code with the
system under test.

``check(store, sites, config)`` holds the system to the configuration's
guarantees on the seeded sample ``sites``: object counts bit-identical to
the chain, nuclei intensity features within 1e-5 relative (min and max
exact) of numpy on the stored label stack."""

import time

import numpy as np


def otsu(img: np.ndarray, bins: int = 256) -> float:
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


def reference_site(dapi: np.ndarray, actin: np.ndarray) -> tuple:
    """``(n_nuclei, n_cells)`` of one field, float32 pixels in."""
    import scipy.ndimage as ndi

    sm = ndi.gaussian_filter(dapi, 1.5, mode="reflect")
    mask = ndi.binary_fill_holes(sm > otsu(sm))
    labels, _ = ndi.label(mask, ndi.generate_binary_structure(2, 2))
    sizes = np.bincount(labels.ravel())
    n_nuclei = len(np.flatnonzero(sizes >= 20)[1:])
    cell_mask = actin > otsu(actin) * 0.8
    _, (iy, ix) = ndi.distance_transform_edt(labels == 0,
                                             return_indices=True)
    cells = np.where(cell_mask, labels[iy, ix], 0)
    n_cells = len(np.unique(cells)) - 1
    # the measurements, so the chain's seconds are the whole problem's
    for lab_img, img in ((labels, dapi), (cells, actin)):
        ids = np.unique(lab_img)[1:]
        if len(ids):
            ndi.mean(img, lab_img, ids)
            ndi.standard_deviation(img, lab_img, ids)
            ndi.maximum(img, lab_img, ids)
            ndi.minimum(img, lab_img, ids)
            ndi.sum(img, lab_img, ids)
    return n_nuclei, n_cells


def object_counts(store) -> dict:
    """Feature rows per site and object type, as the store holds them."""
    got = {}
    for name in ("nuclei", "cells"):
        per_site = store.read_features(name).groupby("site_index").size()
        got[name] = [int(per_site.get(s, 0)) for s in range(store.n_sites)]
    return got


def check(store, sites, config) -> dict:
    exp = store.experiment
    got = object_counts(store)
    want = {"nuclei": [], "cells": []}
    chain_s = 0.0
    worst, minmax_exact = 0.0, True
    table = store.read_features("nuclei").set_index(["site_index", "label"])
    for s in sites:
        dapi = store.read_sites(
            [s], channel=exp.channel_index("DAPI"))[0].astype(np.float32)
        actin = store.read_sites(
            [s], channel=exp.channel_index("Actin"))[0].astype(np.float32)
        t0 = time.perf_counter()
        n_nuclei, n_cells = reference_site(dapi, actin)
        chain_s += time.perf_counter() - t0
        want["nuclei"].append(n_nuclei)
        want["cells"].append(n_cells)

        lab = store.read_labels([s], "nuclei")[0].ravel()
        img = dapi.ravel().astype(np.float64)
        n = int(lab.max())
        if n == 0:
            continue
        area = np.bincount(lab, minlength=n + 1)[1:]
        total = np.bincount(lab, weights=img, minlength=n + 1)[1:]
        order = np.argsort(lab, kind="stable")
        starts = np.searchsorted(lab[order], np.arange(1, n + 1))
        mins = np.minimum.reduceat(img[order], starts)
        maxs = np.maximum.reduceat(img[order], starts)
        rows = table.loc[s].sort_index()
        ids = rows.index.to_numpy()
        mean = (total / area)[ids - 1]
        worst = max(
            worst,
            float(np.max(np.abs(rows["Intensity_mean_DAPI"].to_numpy()
                                - mean) / mean)),
            float(np.max(np.abs(rows["Intensity_sum_DAPI"].to_numpy()
                                - total[ids - 1]) / total[ids - 1])))
        minmax_exact &= bool(
            np.array_equal(rows["Intensity_min_DAPI"].to_numpy(),
                           mins[ids - 1])
            and np.array_equal(rows["Intensity_max_DAPI"].to_numpy(),
                               maxs[ids - 1]))
    sampled = {name: [got[name][s] for s in sites] for name in got}
    return {
        "checks": {
            "counts_equal_scipy_chain": sampled == want,
            "intensity_within_tolerance": worst <= 1e-5 and minmax_exact,
        },
        "info": {"sampled_sites": list(sites), "object_counts": got,
                 "reference_counts": want,
                 "intensity_worst_rel_err": worst,
                 "scipy_chain_s_per_site": chain_s / max(len(sites), 1)},
    }
