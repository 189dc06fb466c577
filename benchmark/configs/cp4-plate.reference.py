"""The plain reference of ``cp4-plate``: BASELINE config 4.

Two parts, neither sharing code with the system under test.

*Counts*: the single-threaded scipy chain of ``cp3-plate.reference.py``
(smooth sigma 1.5, 256-bin Otsu, fill holes, 8-connected label, drop
objects under 20 px; cells by nearest-seed growth through the Actin Otsu
mask at 0.8), copied.

*Features*: numpy float64 on the **stored** label stacks and pixel planes
of the sampled sites — what the timed path itself wrote, at the timed size:

- intensity (mean, sum, std, min, max) of five stains x two object types;
- morphology: area, bounding box, centroid, perimeter (object pixels with
  a 4-neighbour of another label), second-moment ellipse (axes,
  eccentricity, orientation; regionprops' 1/12 on the diagonal), extent,
  form factor, equivalent diameter, and solidity against a convex hull
  taken row by row in integer arithmetic;
- Haralick texture of cells on Actin: per-object stretch
  ``(v - min) * (L - 1) // (max - min)`` in integers, symmetric GLCMs in
  four directions at distance 1, the 13 features (f7 with f8 as mahotas
  has it) averaged over the directions — the arithmetic of
  ``tests/test_measure.py``'s ``_haralick_reference_numpy``, copied, the
  pixel loops as ``bincount``;
- Zernike magnitudes of nuclei to degree 6 on the unit disk at the
  object's largest centroid distance — ``_zernike_reference_numpy``,
  copied, all objects at once.

Every tolerance is in ``LIMITS`` with its reason.  A family's error is
its worst ``|got - want| / allowed`` over the sample; the check holds
where that is at most 1, and the ``info`` carries every family's reading
so that ``PERF.md`` can state how far the limit is from it."""

from math import factorial

import numpy as np

#: the error ratio of a value that is not exact where it must be, or not
#: a number (finite, so that the result line stays JSON)
BROKEN = 1e30

#: family.feature -> (relative, absolute) allowance, or "exact".  The
#: program computes in float32 from exact integer pixels and labels; the
#: reference in float64.  Each limit lies between what float32 arithmetic
#: of the stated inputs gives (1e-7 .. 1e-5) and what the next precision
#: down gives (a bf16 plane or a one-pass bf16 contraction: 1e-3 .. 4e-3;
#: a stretch one bin low for one pixel: 1e-3 of a Haralick feature).
LIMITS = {
    # one object's pixels are integers: min and max are copied, not computed
    "intensity.min": "exact", "intensity.max": "exact",
    # up to ~5,000 integers below 65,536 summed in float32 (chunked MXU
    # contraction at HIGHEST): every partial sum rounds to 24 bits
    "intensity.sum": (1e-5, 0.0), "intensity.mean": (1e-5, 0.0),
    # sqrt(E[v^2] - mean^2) cancels: the allowance is per object,
    # 4e-6 * E[v^2] / std (eight roundings of E[v^2] through the sqrt's
    # derivative) + 1e-5 * std — see _std_allowed
    "intensity.std": "per object",
    # integer counts below 2^24: exact in float32
    "morphology.area": "exact", "morphology.perimeter": "exact",
    "morphology.bbox_height": "exact", "morphology.bbox_width": "exact",
    # an exact integer sum over the area: one float32 division at ~2,000
    "morphology.centroid_y": (0.0, 1e-3), "morphology.centroid_x": (0.0, 1e-3),
    # one or two float32 operations on exact integers
    "morphology.extent": (1e-5, 0.0), "morphology.form_factor": (1e-5, 0.0),
    "morphology.equivalent_diameter": (1e-5, 0.0),
    # float32 second moments about a float32 centroid, then a sqrt; as
    # E[y^2] - cy^2 in field coordinates they would be off by per cents
    "morphology.major_axis_length": (1e-4, 0.0),
    "morphology.minor_axis_length": (1e-4, 0.0),
    # sqrt(1 - l2/l1) has no derivative at a circle: held as its square
    "morphology.eccentricity": "square within 5e-5",
    # atan2 of the moments' differences is undetermined for a circle: the
    # allowance grows as (l1 + l2) / (l1 - l2), modulo pi
    "morphology.orientation": "1e-5 * (l1 + l2) / (l1 - l2) + 1e-5 rad",
    # two exact integers and one division
    "morphology.solidity": (1e-6, 0.0),
    # 13 float32 features of exact integer counts: 256 products and logs
    # a direction; the information measures and the correlation cancel
    "texture": (1e-4, 2e-5),
    # float32 projections of ~100 unit-disk terms an object; magnitudes
    # of odd or high orders of a round nucleus are near zero: absolute
    "zernike": (1e-4, 2e-5),
}

CHANNELS = ("DAPI", "Actin", "Tubulin", "ER", "Mito")
OBJECTS = ("nuclei", "cells")
HARALICK = (
    "angular_second_moment", "contrast", "correlation",
    "sum_of_squares_variance", "inverse_difference_moment", "sum_average",
    "sum_variance", "sum_entropy", "entropy", "difference_variance",
    "difference_entropy", "info_measure_corr_1", "info_measure_corr_2")


# ------------------------------------------------------------ the scipy chain
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


def reference_counts(dapi: np.ndarray, actin: np.ndarray) -> tuple:
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
    return n_nuclei, len(np.unique(cells)) - 1


def object_counts(store) -> dict:
    """Feature rows per site and object type, as the store holds them."""
    got = {}
    for name in OBJECTS:
        per_site = store.read_features(name).groupby("site_index").size()
        got[name] = [int(per_site.get(s, 0)) for s in range(store.n_sites)]
    return got


# ------------------------------------------------------- features, in float64
def _by_label(lab: np.ndarray, n: int, weights=None) -> np.ndarray:
    return np.bincount(lab.ravel(), weights=None if weights is None
                       else weights.ravel(), minlength=n + 1)[1:n + 1]


def intensity(lab: np.ndarray, img: np.ndarray, n: int) -> dict:
    v = img.astype(np.float64)
    area = _by_label(lab, n)
    total = _by_label(lab, n, v)
    square = _by_label(lab, n, v * v)
    flat, vals = lab.ravel(), v.ravel()
    order = np.argsort(flat, kind="stable")
    starts = np.searchsorted(flat[order], np.arange(1, n + 1))
    mean = total / area
    return {"min": np.minimum.reduceat(vals[order], starts),
            "max": np.maximum.reduceat(vals[order], starts),
            "sum": total, "mean": mean,
            "std": np.sqrt(np.maximum(square / area - mean * mean, 0.0)),
            "mean_square": square / area}


def hull_pixel_count(ys: np.ndarray, xs: np.ndarray) -> int:
    """Pixel centres inside or on the convex hull of one object's pixel
    centres.  Row by row: the hull's cross-section at height ``t`` runs
    from the least to the greatest ``x`` that any segment between two of
    the object's row ends reaches there; ceil and floor of those
    rationals in integer arithmetic."""
    rows = np.unique(ys)
    left = np.array([xs[ys == r].min() for r in rows], np.int64)
    right = np.array([xs[ys == r].max() for r in rows], np.int64)
    rows = rows.astype(np.int64)
    if len(rows) == 1:
        return int(right[0] - left[0] + 1)
    t = np.arange(rows[0], rows[-1] + 1, dtype=np.int64)[:, None, None]
    yi, yj = rows[None, :, None], rows[None, None, :]
    den = yj - yi
    spans = (den > 0) & (yi <= t) & (t <= yj)
    den = np.where(spans, den, 1)
    lo_num = left[None, :, None] * den + \
        (left[None, None, :] - left[None, :, None]) * (t - yi)
    hi_num = right[None, :, None] * den + \
        (right[None, None, :] - right[None, :, None]) * (t - yi)
    big = np.int64(1) << 40
    lo = np.where(spans, -((-lo_num) // den), big).min(axis=(1, 2))
    hi = np.where(spans, hi_num // den, -big).max(axis=(1, 2))
    return int(np.sum(hi - lo + 1))


def morphology(lab: np.ndarray, n: int) -> dict:
    h, w = lab.shape
    yy, xx = np.mgrid[0:h, 0:w].astype(np.float64)
    area = _by_label(lab, n)
    cy = _by_label(lab, n, yy) / area
    cx = _by_label(lab, n, xx) / area
    padded = np.pad(lab, 1)
    edge = np.zeros(lab.shape, bool)
    for dy, dx in ((-1, 0), (1, 0), (0, -1), (0, 1)):
        edge |= padded[1 + dy:1 + dy + h, 1 + dx:1 + dx + w] != lab
    perimeter = _by_label(lab, n, (edge & (lab > 0)).astype(np.float64))
    ys, xs = np.nonzero(lab)
    ids = lab[ys, xs]
    order = np.argsort(ids, kind="stable")
    ys, xs, ids = ys[order], xs[order], ids[order]
    bounds = np.searchsorted(ids, np.arange(1, n + 2))
    height, width, hull = np.zeros(n), np.zeros(n), np.zeros(n)
    mu = np.zeros((n, 3))
    for k in range(n):
        oy, ox = ys[bounds[k]:bounds[k + 1]], xs[bounds[k]:bounds[k + 1]]
        height[k] = oy.max() - oy.min() + 1
        width[k] = ox.max() - ox.min() + 1
        hull[k] = hull_pixel_count(oy, ox)
        dy, dx = oy - cy[k], ox - cx[k]
        mu[k] = (np.mean(dy * dy) + 1 / 12, np.mean(dx * dx) + 1 / 12,
                 np.mean(dy * dx))
    common = np.sqrt((mu[:, 0] - mu[:, 1]) ** 2 + 4 * mu[:, 2] ** 2)
    l1 = (mu[:, 0] + mu[:, 1] + common) / 2
    l2 = np.maximum((mu[:, 0] + mu[:, 1] - common) / 2, 1e-12)
    return {"area": area, "perimeter": perimeter, "bbox_height": height,
            "bbox_width": width, "centroid_y": cy, "centroid_x": cx,
            "extent": area / (height * width),
            "form_factor": 4 * np.pi * area / np.maximum(perimeter ** 2, 1),
            "equivalent_diameter": np.sqrt(4 * area / np.pi),
            "major_axis_length": 4 * np.sqrt(l1),
            "minor_axis_length": 4 * np.sqrt(l2),
            "eccentricity": np.sqrt(np.clip(1 - l2 / l1, 0, 1)),
            "orientation": 0.5 * np.arctan2(2 * mu[:, 2],
                                            mu[:, 1] - mu[:, 0]),
            "solidity": area / hull, "l1": l1, "l2": l2}


def haralick(lab: np.ndarray, img: np.ndarray, n: int, levels: int,
             distance: int) -> np.ndarray:
    """``(n, 13)``: mahotas semantics, every object at once."""
    h, w = lab.shape
    v = img.astype(np.int64)
    if not np.array_equal(v, img):
        raise ValueError("the stored plane is not integer-valued")
    flat, vals = lab.ravel(), v.ravel()
    order = np.argsort(flat, kind="stable")
    starts = np.searchsorted(flat[order], np.arange(1, n + 1))
    lo = np.concatenate([[0], np.minimum.reduceat(vals[order], starts)])
    hi = np.concatenate([[1], np.maximum.reduceat(vals[order], starts)])
    span = np.maximum(hi - lo, 1)
    q = np.clip((v - lo[lab]) * (levels - 1) // span[lab], 0, levels - 1)
    eps = 1e-10
    k = np.arange(levels, dtype=np.float64)
    i_idx, j_idx = k[None, :, None], k[None, None, :]
    ks = np.arange(2 * levels - 1, dtype=np.float64)
    sum_of = (np.arange(levels)[:, None] + np.arange(levels)[None, :])
    diff_of = np.abs(np.arange(levels)[:, None] - np.arange(levels)[None, :])
    out = np.zeros((n, 13))
    d = distance
    for dy, dx in ((0, d), (d, 0), (d, d), (d, -d)):
        y0, y1 = max(0, -dy), min(h, h - dy)
        x0, x1 = max(0, -dx), min(w, w - dx)
        a = (slice(y0, y1), slice(x0, x1))
        b = (slice(y0 + dy, y1 + dy), slice(x0 + dx, x1 + dx))
        pair = (lab[a] > 0) & (lab[a] == lab[b])
        cell = (lab[a][pair].astype(np.int64) * levels + q[a][pair]) \
            * levels + q[b][pair]
        glcm = np.bincount(cell, minlength=(n + 1) * levels * levels) \
            .reshape(n + 1, levels, levels)[1:].astype(np.float64)
        glcm = glcm + glcm.transpose(0, 2, 1)
        p = glcm / np.maximum(glcm.sum(axis=(1, 2), keepdims=True), eps)
        px, py = p.sum(2), p.sum(1)
        mu_x, mu_y = (px * k).sum(1), (py * k).sum(1)
        sd_x = np.sqrt(np.maximum((px * (k - mu_x[:, None]) ** 2).sum(1), 0))
        sd_y = np.sqrt(np.maximum((py * (k - mu_y[:, None]) ** 2).sum(1), 0))
        asm = (p ** 2).sum((1, 2))
        contrast = (p * (i_idx - j_idx) ** 2).sum((1, 2))
        corr = (p * (i_idx - mu_x[:, None, None])
                * (j_idx - mu_y[:, None, None])).sum((1, 2)) \
            / np.maximum(sd_x * sd_y, eps)
        variance = (p * (i_idx - mu_x[:, None, None]) ** 2).sum((1, 2))
        idm = (p / (1.0 + (i_idx - j_idx) ** 2)).sum((1, 2))
        entropy = -(p * np.log(p + eps)).sum((1, 2))
        p_sum = np.stack([p[:, sum_of == s].sum(1)
                          for s in range(2 * levels - 1)], 1)
        p_diff = np.stack([p[:, diff_of == s].sum(1)
                           for s in range(levels)], 1)
        sum_avg = (p_sum * ks).sum(1)
        sum_entropy = -(p_sum * np.log(p_sum + eps)).sum(1)
        sum_var = (p_sum * (ks - sum_entropy[:, None]) ** 2).sum(1)
        diff_avg = (p_diff * k).sum(1)
        diff_var = (p_diff * (k - diff_avg[:, None]) ** 2).sum(1)
        diff_entropy = -(p_diff * np.log(p_diff + eps)).sum(1)
        hx = -(px * np.log(px + eps)).sum(1)
        hy = -(py * np.log(py + eps)).sum(1)
        pxpy = px[:, :, None] * py[:, None, :]
        hxy1 = -(p * np.log(pxpy + eps)).sum((1, 2))
        hxy2 = -(pxpy * np.log(pxpy + eps)).sum((1, 2))
        imc1 = (entropy - hxy1) / np.maximum(np.maximum(hx, hy), eps)
        imc2 = np.sqrt(np.clip(1 - np.exp(-2 * (hxy2 - entropy)), 0, 1))
        out += np.stack([asm, contrast, corr, variance, idm, sum_avg,
                         sum_var, sum_entropy, entropy, diff_var,
                         diff_entropy, imc1, imc2], 1) / 4.0
    return out


def zernike(lab: np.ndarray, n: int, degree: int) -> dict:
    """``{(n, m): (objects,) magnitudes}``: mass-normalised projection on
    the unit disk at the object's largest centroid distance (at least one
    pixel), ``* (n + 1) / pi``."""
    ys, xs = np.nonzero(lab)
    ids = lab[ys, xs] - 1
    area = np.bincount(ids, minlength=n).astype(np.float64)
    cy = np.bincount(ids, weights=ys, minlength=n) / area
    cx = np.bincount(ids, weights=xs, minlength=n) / area
    dy, dx = ys - cy[ids], xs - cx[ids]
    dist = np.sqrt(dy * dy + dx * dx)
    radius = np.ones(n)
    np.maximum.at(radius, ids, dist)
    rho, theta = dist / radius[ids], np.arctan2(dy, dx)
    out = {}
    for order in range(degree + 1):
        for m in range(order % 2, order + 1, 2):
            rad = np.zeros_like(rho)
            for k in range((order - m) // 2 + 1):
                c = ((-1) ** k * factorial(order - k)) / (
                    factorial(k) * factorial((order + m) // 2 - k)
                    * factorial((order - m) // 2 - k))
                rad += c * rho ** (order - 2 * k)
            term = rad * np.exp(-1j * m * theta)
            z = (np.bincount(ids, weights=term.real, minlength=n)
                 + 1j * np.bincount(ids, weights=term.imag, minlength=n))
            out[(order, m)] = np.abs(z) / area * (order + 1) / np.pi
    return out


# ------------------------------------------------------------------ comparing
class Family:
    """The worst ``|got - want| / allowed`` of one family, and where."""

    def __init__(self):
        self.worst, self.where, self.compared = 0.0, "", 0

    def exact(self, name, got, want):
        self.compared += got.size
        if not np.array_equal(got, want):
            self.worst, self.where = BROKEN, name

    def within(self, name, got, want, rel=0.0, absolute=0.0, allowed=None):
        if allowed is None:
            allowed = rel * np.abs(want) + absolute
        self.compared += got.size
        ratio = np.abs(got - want) / allowed
        ratio = np.where(np.isfinite(got) & np.isfinite(ratio), ratio, BROKEN)
        if ratio.size and float(ratio.max()) > self.worst:
            self.worst, self.where = float(ratio.max()), name

    @property
    def ok(self) -> bool:
        return self.compared > 0 and self.worst <= 1.0


def _std_allowed(ref: dict) -> np.ndarray:
    return 4e-6 * ref["mean_square"] / np.maximum(ref["std"], 1.0) \
        + 1e-5 * ref["std"]


def check(store, sites, config) -> dict:
    exp = store.experiment
    got_counts = object_counts(store)
    want_counts = {name: [] for name in OBJECTS}
    families = {name: Family() for name in
                ("intensity", "morphology", "texture", "zernike")}
    levels, degree = config["texture_levels"], config["zernike_degree"]
    tables = {name: store.read_features(name)
              .set_index(["site_index", "label"]) for name in OBJECTS}
    for s in sites:
        planes = {c: store.read_sites([s], channel=exp.channel_index(c))[0]
                  .astype(np.float32) for c in CHANNELS}
        n_nuclei, n_cells = reference_counts(planes["DAPI"], planes["Actin"])
        want_counts["nuclei"].append(n_nuclei)
        want_counts["cells"].append(n_cells)
        for name in OBJECTS:
            lab = store.read_labels([s], name)[0].astype(np.int64)
            n = int(lab.max())
            if n == 0:
                continue
            index = tables[name].index
            rows = tables[name].loc[s].sort_index() \
                if s in index.get_level_values(0) else index[:0].to_frame()
            if not np.array_equal(rows.index.to_numpy(), np.arange(1, n + 1)):
                families["morphology"].exact(f"{name} rows", np.zeros(1),
                                             np.ones(1))
                continue
            col = lambda c: rows[c].to_numpy(np.float64)  # noqa: E731

            fam = families["intensity"]
            for chan in CHANNELS:
                ref = intensity(lab, planes[chan], n)
                for stat in ("min", "max"):
                    fam.exact(f"{name} {stat} {chan}",
                              col(f"Intensity_{stat}_{chan}"), ref[stat])
                for stat in ("sum", "mean"):
                    fam.within(f"{name} {stat} {chan}",
                               col(f"Intensity_{stat}_{chan}"), ref[stat],
                               *LIMITS[f"intensity.{stat}"])
                fam.within(f"{name} std {chan}",
                           col(f"Intensity_std_{chan}"), ref["std"],
                           allowed=_std_allowed(ref))

            fam, ref = families["morphology"], morphology(lab, n)
            for feature, limit in LIMITS.items():
                family, _, feature = feature.partition(".")
                if family != "morphology":
                    continue
                got, want = col(f"Morphology_{feature}"), ref[feature]
                where = f"{name} {feature}"
                if limit == "exact":
                    fam.exact(where, got, want)
                elif feature == "eccentricity":
                    fam.within(where, got ** 2, want ** 2, absolute=5e-5)
                elif feature == "orientation":
                    turn = np.abs(got - want)
                    turn = np.minimum(turn, np.pi - turn)
                    spread = (ref["l1"] + ref["l2"]) / np.maximum(
                        ref["l1"] - ref["l2"], 1e-12)
                    fam.within(where, turn, np.zeros(n),
                               allowed=1e-5 * spread + 1e-5)
                else:
                    fam.within(where, got, want, *limit)

            if name == "cells":
                ref = haralick(lab, planes["Actin"], n, levels,
                               config["texture_distance"])
                for i, feature in enumerate(HARALICK):
                    families["texture"].within(
                        f"cells {feature}",
                        col(f"Texture_{feature}_Actin"), ref[:, i],
                        *LIMITS["texture"])
            else:
                for (order, m), want in zernike(lab, n, degree).items():
                    families["zernike"].within(
                        f"nuclei Zernike_{order}_{m}",
                        col(f"Zernike_{order}_{m}"), want,
                        *LIMITS["zernike"])
    sampled = {name: [got_counts[name][s] for s in sites]
               for name in OBJECTS}
    checks = {"counts_equal_scipy_chain": sampled == want_counts}
    checks.update({f"{name}_within_tolerance": fam.ok
                   for name, fam in families.items()})
    return {
        "checks": checks,
        "info": {"sampled_sites": list(sites), "object_counts": got_counts,
                 "reference_counts": want_counts,
                 "worst_error_over_allowed": {
                     name: {"ratio": fam.worst, "at": fam.where,
                            "values_compared": fam.compared}
                     for name, fam in families.items()}},
    }
