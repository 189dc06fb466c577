import jax
import jax.numpy as jnp
import numpy as np
import pytest
import scipy.ndimage as ndi

from tmlibrary_tpu.ops import reduction as R
from tmlibrary_tpu.ops.measure import (
    haralick_features,
    intensity_features,
    morphology_features,
    quantize_per_object,
    zernike_features,
)

MAX_OBJ = 16


@pytest.fixture(params=R.STRATEGIES)
def strategy(request, pin_strategy):
    """Run the test under both reduction strategies (``conftest.py``'s
    ``pin_strategy``); the value also names a ``glcm_method``."""
    pin_strategy(request.param)
    return request.param


@pytest.fixture(params=("auto",) + R.STRATEGIES)
def path(request, pin_strategy):
    """For the families with a CPU route of their own (intensity's native
    C pass, Zernike's host twin): ``"auto"`` is that route, as the CPU
    backend resolves it; a strategy name is the XLA path under that
    strategy — pass the value as ``method=``."""
    if request.param == "auto":
        return "auto"
    pin_strategy(request.param)
    return "xla"


@pytest.fixture
def labeled_scene(rng):
    labels = np.zeros((64, 64), np.int32)
    labels[5:15, 5:15] = 1  # 10x10 square
    labels[30:40, 20:45] = 2  # 10x25 rectangle
    labels[50:54, 50:54] = 3  # 4x4 square
    intensity = rng.integers(100, 5000, size=(64, 64)).astype(np.float32)
    return jnp.asarray(labels), jnp.asarray(intensity), labels, intensity


def test_intensity_matches_numpy(labeled_scene, path):
    jl, ji, labels, intensity = labeled_scene
    feats = intensity_features(jl, ji, MAX_OBJ, method=path)
    for lab in (1, 2, 3):
        sel = intensity[labels == lab]
        i = lab - 1
        np.testing.assert_allclose(float(feats["Intensity_mean"][i]), sel.mean(), rtol=1e-5)
        np.testing.assert_allclose(float(feats["Intensity_sum"][i]), sel.sum(), rtol=1e-5)
        assert float(feats["Intensity_max"][i]) == sel.max()
        assert float(feats["Intensity_min"][i]) == sel.min()
        np.testing.assert_allclose(float(feats["Intensity_std"][i]), sel.std(), rtol=1e-4)
    # padded rows are zeros
    assert float(feats["Intensity_mean"][5]) == 0.0


def test_morphology_basics(labeled_scene, strategy):
    jl, _, labels, _ = labeled_scene
    feats = morphology_features(jl, MAX_OBJ)
    areas = np.asarray(feats["Morphology_area"])
    assert list(areas[:3]) == [100.0, 250.0, 16.0]
    np.testing.assert_allclose(float(feats["Morphology_centroid_y"][0]), 9.5)
    np.testing.assert_allclose(float(feats["Morphology_centroid_x"][0]), 9.5)
    assert float(feats["Morphology_bbox_height"][1]) == 10.0
    assert float(feats["Morphology_bbox_width"][1]) == 25.0
    np.testing.assert_allclose(float(feats["Morphology_extent"][0]), 1.0)
    # perimeter of a filled 10x10 square, 4-connected boundary = 36 pixels
    assert float(feats["Morphology_perimeter"][0]) == 36.0


def test_morphology_ellipse_matches_regionprops_math(strategy):
    # ellipse mask: a=12 (x), b=6 (y)
    yy, xx = np.mgrid[0:64, 0:64]
    mask = ((xx - 32) / 12.0) ** 2 + ((yy - 32) / 6.0) ** 2 <= 1.0
    labels = jnp.asarray(mask.astype(np.int32))
    feats = morphology_features(labels, MAX_OBJ)
    major = float(feats["Morphology_major_axis_length"][0])
    minor = float(feats["Morphology_minor_axis_length"][0])
    # regionprops-style: major ~ 2a = 24, minor ~ 2b = 12
    assert abs(major - 24.0) < 1.5
    assert abs(minor - 12.0) < 1.0
    ecc = float(feats["Morphology_eccentricity"][0])
    assert abs(ecc - np.sqrt(1 - (6 / 12) ** 2)) < 0.03
    # orientation: measured from the x axis -> 0 for an x-aligned major axis
    ori = float(feats["Morphology_orientation"][0])
    assert abs(ori) < 0.05


def test_haralick_flat_vs_noisy_texture(rng, strategy):
    labels = np.zeros((64, 64), np.int32)
    labels[4:28, 4:28] = 1  # flat region
    labels[36:60, 36:60] = 2  # noisy region
    img = np.full((64, 64), 1000.0, np.float32)
    img[36:60, 36:60] = rng.integers(0, 5000, size=(24, 24)).astype(np.float32)
    img[0, 0] = 0.0
    img[1, 0] = 5000.0  # pin global range so quantization spreads
    feats = haralick_features(jnp.asarray(labels), jnp.asarray(img), MAX_OBJ,
                              glcm_method=strategy)
    # flat object: max homogeneity (ASM=1, contrast=0, entropy~0)
    np.testing.assert_allclose(float(feats["Texture_angular_second_moment"][0]), 1.0, atol=1e-5)
    np.testing.assert_allclose(float(feats["Texture_contrast"][0]), 0.0, atol=1e-5)
    # noisy object: high entropy, high contrast, low ASM
    assert float(feats["Texture_entropy"][1]) > 2.0
    assert float(feats["Texture_contrast"][1]) > 10.0
    assert float(feats["Texture_angular_second_moment"][1]) < 0.1


def test_haralick_correlation_of_smooth_gradient(strategy):
    labels = np.zeros((64, 64), np.int32)
    labels[8:56, 8:56] = 1
    yy, _ = np.mgrid[0:64, 0:64]
    img = yy.astype(np.float32) * 100  # smooth vertical gradient
    feats = haralick_features(jnp.asarray(labels), jnp.asarray(img), MAX_OBJ,
                              glcm_method=strategy)
    # neighboring pixels strongly correlated along the gradient
    assert float(feats["Texture_correlation"][0]) > 0.9


def _haralick_reference_numpy(img, mask, levels=32, distance=1):
    """Independent numpy implementation of per-object Haralick features with
    mahotas semantics: per-object gray stretch (``mh.stretch``:
    floor((v-min)*(levels-1)/(max-min))), symmetric GLCM per direction,
    Haralick's 13 features (f7 sum-variance uses f8 sum-entropy per the
    original paper, as mahotas does), averaged over the 4 directions."""
    sel = img[mask]
    lo, hi = sel.min(), sel.max()
    span = max(hi - lo, 1e-6)
    q = np.clip(np.floor((img - lo) * (levels - 1) / span), 0, levels - 1).astype(int)
    eps = 1e-10
    acc = np.zeros(13)
    h, w = img.shape
    for dy, dx in ((0, distance), (distance, 0), (distance, distance), (distance, -distance)):
        glcm = np.zeros((levels, levels))
        for y in range(h):
            for x in range(w):
                y2, x2 = y + dy, x + dx
                if 0 <= y2 < h and 0 <= x2 < w and mask[y, x] and mask[y2, x2]:
                    glcm[q[y, x], q[y2, x2]] += 1
        glcm = glcm + glcm.T
        p = glcm / max(glcm.sum(), eps)
        i_idx, j_idx = np.mgrid[0:levels, 0:levels].astype(float)
        px, py = p.sum(1), p.sum(0)
        k = np.arange(levels, dtype=float)
        mu_x, mu_y = (px * k).sum(), (py * k).sum()
        sd_x = np.sqrt(max((px * (k - mu_x) ** 2).sum(), 0.0))
        sd_y = np.sqrt(max((py * (k - mu_y) ** 2).sum(), 0.0))
        asm = (p ** 2).sum()
        contrast = (p * (i_idx - j_idx) ** 2).sum()
        corr = (p * (i_idx - mu_x) * (j_idx - mu_y)).sum() / max(sd_x * sd_y, eps)
        variance = (p * (i_idx - mu_x) ** 2).sum()
        idm = (p / (1.0 + (i_idx - j_idx) ** 2)).sum()
        entropy = -(p * np.log(p + eps)).sum()
        p_sum = np.zeros(2 * levels - 1)
        p_diff = np.zeros(levels)
        for i in range(levels):
            for j in range(levels):
                p_sum[i + j] += p[i, j]
                p_diff[abs(i - j)] += p[i, j]
        ks = np.arange(2 * levels - 1, dtype=float)
        sum_avg = (p_sum * ks).sum()
        sum_entropy = -(p_sum * np.log(p_sum + eps)).sum()
        sum_var = (p_sum * (ks - sum_entropy) ** 2).sum()
        diff_avg = (p_diff * k).sum()
        diff_var = (p_diff * (k - diff_avg) ** 2).sum()
        diff_entropy = -(p_diff * np.log(p_diff + eps)).sum()
        hx = -(px * np.log(px + eps)).sum()
        hy = -(py * np.log(py + eps)).sum()
        pxpy = px[:, None] * py[None, :]
        hxy1 = -(p * np.log(pxpy + eps)).sum()
        hxy2 = -(pxpy * np.log(pxpy + eps)).sum()
        imc1 = (entropy - hxy1) / max(hx, hy, eps)
        imc2 = np.sqrt(np.clip(1.0 - np.exp(-2.0 * (hxy2 - entropy)), 0.0, 1.0))
        acc += np.array([asm, contrast, corr, variance, idm, sum_avg, sum_var,
                         sum_entropy, entropy, diff_var, diff_entropy, imc1, imc2]) / 4.0
    return acc


_HARALICK_KEYS = [
    "Texture_angular_second_moment", "Texture_contrast", "Texture_correlation",
    "Texture_sum_of_squares_variance", "Texture_inverse_difference_moment",
    "Texture_sum_average", "Texture_sum_variance", "Texture_sum_entropy",
    "Texture_entropy", "Texture_difference_variance", "Texture_difference_entropy",
    "Texture_info_measure_corr_1", "Texture_info_measure_corr_2",
]


def test_haralick_golden_vs_numpy_reference(rng, strategy):
    """Fidelity gate (round-1 VERDICT #4): per-object quantization must
    reproduce an independent numpy implementation of the mahotas-semantics
    pipeline on a multi-object scene, including an object whose local gray
    range is a narrow slice of the image's global range."""
    labels = np.zeros((48, 48), np.int32)
    labels[4:20, 4:20] = 1     # full-range noise
    labels[26:42, 26:42] = 2   # narrow-range texture (global quant would crush it)
    img = np.zeros((48, 48), np.float32)
    img[4:20, 4:20] = rng.integers(0, 5000, (16, 16))
    img[26:42, 26:42] = 2000 + rng.integers(0, 64, (16, 16))
    feats = haralick_features(
        jnp.asarray(labels), jnp.asarray(img), MAX_OBJ, levels=8,
        glcm_method=strategy,
    )
    for obj in (1, 2):
        want = _haralick_reference_numpy(img, labels == obj, levels=8)
        got = np.array([float(feats[k][obj - 1]) for k in _HARALICK_KEYS])
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_haralick_per_object_quantization_sees_local_contrast(rng, strategy):
    """An object occupying a tiny slice of the global gray range must still
    spread across quantization bins (the round-1 global-range bug made such
    objects look flat)."""
    labels = np.zeros((32, 32), np.int32)
    labels[8:24, 8:24] = 1
    img = np.full((32, 32), 0.0, np.float32)
    img[8:24, 8:24] = 1000 + rng.integers(0, 10, (16, 16))  # 1% of global span
    img[0, 0] = 100000.0  # blow out the global range
    feats = haralick_features(jnp.asarray(labels), jnp.asarray(img), MAX_OBJ,
                              glcm_method=strategy)
    assert float(feats["Texture_entropy"][0]) > 1.0
    assert float(feats["Texture_angular_second_moment"][0]) < 0.5


def test_lookup_by_label_matmul_matches_gather(rng):
    """The one-hot-at-HIGHEST matmul branch (the production TPU path of
    per-pixel float table lookups) must be BIT-identical to the gather
    branch for finite tables — including non-chunk-multiple pixel counts
    (pad/reshape logic) and multi-column tables.  Non-finite sentinel
    rows are sanitized to 0 on the matmul path (documented contract)."""
    from tmlibrary_tpu.ops.measure import lookup_by_label

    for shape, mo, cols in [((64, 64), 16, 1), ((33, 77), 8, 3),
                            ((300, 300), 600, 2)]:
        labels = jnp.asarray(
            rng.integers(0, mo + 1, size=shape).astype(np.int32))
        table = jnp.asarray(
            (rng.standard_normal((mo + 1, cols)) * 1e3).astype(np.float32))
        g = np.asarray(lookup_by_label(labels, table, method="gather"))
        m = np.asarray(lookup_by_label(labels, table, method="matmul"))
        np.testing.assert_array_equal(g, m)
    # a ±inf sentinel row must not NaN-poison other pixels' values
    labels = jnp.asarray(np.array([[0, 1], [2, 1]], np.int32))
    table = jnp.asarray(np.array([[0.0], [5.0], [np.inf]], np.float32))
    m = np.asarray(lookup_by_label(labels, table, method="matmul"))
    np.testing.assert_array_equal(
        m[..., 0], np.array([[0.0, 5.0], [0.0, 5.0]], np.float32))


def test_glcm_matmul_matches_scatter(rng):
    """The fused all-directions matmul kernel (the production TPU path)
    must agree exactly with the per-direction scatter path on every
    direction's GLCM."""
    from tmlibrary_tpu.ops.measure import (
        _glcm_matmul_all,
        _glcm_scatter,
        quantize_per_object,
    )

    labels = np.zeros((64, 64), np.int32)
    labels[4:30, 4:30] = 1
    labels[34:60, 10:50] = 2
    img = rng.integers(0, 4000, (64, 64)).astype(np.float32)
    q = quantize_per_object(jnp.asarray(labels), jnp.asarray(img), MAX_OBJ, 16)
    offsets = [(0, 1), (1, 0), (1, 1), (1, -1)]
    fused = _glcm_matmul_all(jnp.asarray(labels), q, MAX_OBJ, 16, offsets)
    for off, a in zip(offsets, fused):
        b = np.asarray(_glcm_scatter(jnp.asarray(labels), q, MAX_OBJ, 16, off))
        np.testing.assert_array_equal(np.asarray(a), b)


def test_glcm_hand_computed_micro_case():
    """2x3 image, one object, horizontal direction — GLCM counted by hand."""
    from tmlibrary_tpu.ops.measure import _glcm_scatter

    labels = jnp.ones((2, 3), jnp.int32)
    #  q = [[0, 1, 1],
    #       [2, 0, 1]]
    q = jnp.asarray([[0, 1, 1], [2, 0, 1]], jnp.int32)
    glcm = np.asarray(_glcm_scatter(labels, q, 4, 3, (0, 1)))[0]
    # directed pairs (0,1): (0,1),(1,1),(2,0),(0,1) -> symmetric doubles
    want = np.zeros((3, 3))
    for a, b in ((0, 1), (1, 1), (2, 0), (0, 1)):
        want[a, b] += 1
    want = want + want.T
    np.testing.assert_array_equal(glcm, want)


def test_zernike_rotation_invariance(path):
    # |Z_nm| must be (approximately) invariant under rotation of the mask
    yy, xx = np.mgrid[0:64, 0:64]
    blob = (((xx - 32) / 14.0) ** 2 + ((yy - 32) / 7.0) ** 2) <= 1.0
    blob_rot = (((yy - 32) / 14.0) ** 2 + ((xx - 32) / 7.0) ** 2) <= 1.0  # 90° rotation
    f1 = zernike_features(jnp.asarray(blob.astype(np.int32)), MAX_OBJ,
                          degree=6, method=path)
    f2 = zernike_features(jnp.asarray(blob_rot.astype(np.int32)), MAX_OBJ,
                          degree=6, method=path)
    for k in f1:
        v1, v2 = float(f1[k][0]), float(f2[k][0])
        assert abs(v1 - v2) < 0.05, (k, v1, v2)


def test_zernike_distinguishes_shapes(path):
    yy, xx = np.mgrid[0:64, 0:64]
    disk = ((xx - 32) ** 2 + (yy - 32) ** 2) <= 14**2
    ellipse = (((xx - 32) / 14.0) ** 2 + ((yy - 32) / 5.0) ** 2) <= 1.0
    fd = zernike_features(jnp.asarray(disk.astype(np.int32)), MAX_OBJ,
                          degree=4, method=path)
    fe = zernike_features(jnp.asarray(ellipse.astype(np.int32)), MAX_OBJ,
                          degree=4, method=path)
    # Z_2_2 captures elongation: near zero for disk, large for ellipse
    assert float(fd["Zernike_2_2"][0]) < 0.05
    assert float(fe["Zernike_2_2"][0]) > 0.1


def _zernike_reference_numpy(mask, degree):
    """Independent numpy Zernike magnitudes with mahotas semantics
    (``zernike_moments``): unit disk at the object's max centroid distance,
    mass-normalized projection, ``*(n+1)/pi``."""
    from math import factorial

    ys, xs = np.nonzero(mask)
    cy, cx = ys.mean(), xs.mean()
    r = max(np.sqrt((ys - cy) ** 2 + (xs - cx) ** 2).max(), 1.0)
    rho = np.sqrt((ys - cy) ** 2 + (xs - cx) ** 2) / r
    theta = np.arctan2(ys - cy, xs - cx)
    frac = np.ones(len(ys)) / len(ys)
    out = {}
    for n in range(degree + 1):
        for m in range(n % 2, n + 1, 2):
            rad = np.zeros_like(rho)
            for k in range((n - m) // 2 + 1):
                c = ((-1) ** k * factorial(n - k)) / (
                    factorial(k)
                    * factorial((n + m) // 2 - k)
                    * factorial((n - m) // 2 - k)
                )
                rad += c * rho ** (n - 2 * k)
            z = (frac * rad * np.exp(-1j * m * theta)).sum() * (n + 1) / np.pi
            out[f"Zernike_{n}_{m}"] = abs(z)
    return out


def test_zernike_golden_vs_numpy_reference(path):
    """Fidelity gate (round-1 VERDICT missing item #5): device Zernike must
    reproduce the mahotas-semantics numpy implementation exactly."""
    yy, xx = np.mgrid[0:96, 0:96]
    labels = np.zeros((96, 96), np.int32)
    ellipse = (((xx - 30) / 16.0) ** 2 + ((yy - 28) / 8.0) ** 2) <= 1.0
    labels[ellipse] = 1
    crescent = (((xx - 66) ** 2 + (yy - 66) ** 2) <= 196) & ~(
        ((xx - 72) ** 2 + (yy - 62) ** 2) <= 120
    )
    labels[crescent & (labels == 0)] = 2
    feats = zernike_features(jnp.asarray(labels), MAX_OBJ, degree=6,
                             method=path)
    for obj, mask in ((1, labels == 1), (2, labels == 2)):
        want = _zernike_reference_numpy(mask, 6)
        for k, v in want.items():
            got = float(feats[k][obj - 1])
            np.testing.assert_allclose(got, v, rtol=2e-3, atol=2e-4), k


def test_zernike_oversize_object_not_cropped(path):
    """Objects larger than the old 64-px static patch must measure exactly
    (the round-1 implementation silently cropped them)."""
    yy, xx = np.mgrid[0:160, 0:160]
    big = (((xx - 80) / 70.0) ** 2 + ((yy - 80) / 35.0) ** 2) <= 1.0
    feats = zernike_features(jnp.asarray(big.astype(np.int32)), 4, degree=4,
                             method=path)
    want = _zernike_reference_numpy(big, 4)
    for k, v in want.items():
        np.testing.assert_allclose(float(feats[k][0]), v, rtol=2e-3, atol=2e-4)
    # scale quasi-invariance: the same shape at 1/4 area gives close moments
    small = (((xx - 40) / 35.0) ** 2 + ((yy - 40) / 17.5) ** 2) <= 1.0
    f_small = zernike_features(jnp.asarray(small.astype(np.int32)), 4,
                               degree=4, method=path)
    for k in want:
        assert abs(float(feats[k][0]) - float(f_small[k][0])) < 0.02, k


def test_zernike_disk_analytic_values(path):
    """Uniform disk: Z_00 = 1/pi (mass-normalized), all higher moments ~0
    except radial aliasing at the pixel level."""
    yy, xx = np.mgrid[0:64, 0:64]
    disk = ((xx - 32) ** 2 + (yy - 32) ** 2) <= 20**2
    feats = zernike_features(jnp.asarray(disk.astype(np.int32)), 4, degree=2,
                             method=path)
    np.testing.assert_allclose(float(feats["Zernike_0_0"][0]), 1 / np.pi, rtol=1e-3)
    assert float(feats["Zernike_2_2"][0]) < 0.02


def test_zernike_counts_every_object_pixel(path):
    """Z_00 must be EXACTLY area/(pi*area) = 1/pi for any shape: every
    object pixel contributes, including those at exactly the max radius.
    Guards the TPU regression where x/y lowered to x*(1/y) pushed the
    extremal rim pixel's rho one ulp above 1.0 and the old ``rho <= 1``
    mask dropped it (9% shift in Zernike_6_0 of a 177-px object); rho is
    clamped now, so no pixel can fall out."""
    rng = np.random.default_rng(23)
    labels = np.zeros((48, 48), np.int32)
    labels[2:12, 3:9] = 1                       # bar: max radius on corner
    yy, xx = np.mgrid[0:48, 0:48]
    labels[((xx - 30) ** 2 + (yy - 30) ** 2) <= 100] = 2  # disk: rim ring
    labels[40:41, 2:44] = 3                     # 1-px line: all pixels extremal
    feats = zernike_features(jnp.asarray(labels), 8, degree=2, method=path)
    z00 = np.asarray(feats["Zernike_0_0"][:3])
    np.testing.assert_allclose(z00, 1 / np.pi, rtol=1e-5)


def test_measure_under_jit_vmap(labeled_scene, path):
    jl, ji, _, _ = labeled_scene
    batch_l = jnp.stack([jl, jl])
    batch_i = jnp.stack([ji, ji * 2.0])

    @jax.jit
    @jax.vmap
    def run(l, i):
        return intensity_features(l, i, MAX_OBJ, method=path)

    feats = run(batch_l, batch_i)
    assert feats["Intensity_mean"].shape == (2, MAX_OBJ)
    np.testing.assert_allclose(
        np.asarray(feats["Intensity_mean"][1]),
        np.asarray(feats["Intensity_mean"][0]) * 2.0,
        rtol=1e-5,
    )


def test_intensity_quantiles_match_numpy(rng, strategy):
    """Histogram-read quantiles vs numpy per-object percentiles."""
    import numpy as np

    from tmlibrary_tpu.ops.measure import intensity_quantiles

    labels = np.zeros((64, 64), np.int32)
    labels[4:20, 4:24] = 1
    labels[30:60, 10:40] = 2
    img = rng.integers(100, 4000, (64, 64)).astype(np.float32)

    out = {k: np.asarray(v) for k, v in intensity_quantiles(
        labels, img, max_objects=4).items()}
    for lab in (1, 2):
        vals = img[labels == lab]
        lo, hi = vals.min(), vals.max()
        tol = (hi - lo) / 255.0 + 1e-3  # one histogram bucket
        assert abs(out["Intensity_median"][lab - 1]
                   - np.percentile(vals, 50, method="inverted_cdf")) <= tol
        assert abs(out["Intensity_p25"][lab - 1]
                   - np.percentile(vals, 25, method="inverted_cdf")) <= tol
        assert abs(out["Intensity_p75"][lab - 1]
                   - np.percentile(vals, 75, method="inverted_cdf")) <= tol
    # absent object rows are zeroed
    assert out["Intensity_median"][2] == 0.0


def test_intensity_quantiles_constant_object(strategy):
    """An object with one gray value reports that value at every quantile."""
    import numpy as np

    from tmlibrary_tpu.ops.measure import intensity_quantiles

    labels = np.zeros((16, 16), np.int32)
    labels[2:10, 2:10] = 1
    img = np.full((16, 16), 7.0, np.float32)
    out = intensity_quantiles(labels, img, max_objects=2)
    assert float(out["Intensity_median"][0]) == 7.0
    assert float(out["Intensity_p25"][0]) == 7.0


def test_grouped_minmax_multi_paths_agree(rng):
    """The chunked masked-reduce path (TPU) and the scatter path (CPU)
    produce identical per-object min/max, including absent-label rows."""
    from tmlibrary_tpu.ops.measure import grouped_minmax_multi

    labels = np.zeros((40, 50), np.int32)
    labels[2:10, 3:9] = 1
    labels[20:35, 10:40] = 3  # label 2 absent
    vals = [rng.normal(size=(40, 50)).astype(np.float32),
            rng.integers(0, 1000, (40, 50)).astype(np.float32)]
    mn_r, mx_r = grouped_minmax_multi(labels, vals, 4, method="reduce")
    mn_s, mx_s = grouped_minmax_multi(labels, vals, 4, method="scatter")
    assert np.array_equal(np.asarray(mn_r), np.asarray(mn_s))
    assert np.array_equal(np.asarray(mx_r), np.asarray(mx_s))
    for j, v in enumerate(vals):
        assert np.asarray(mn_r)[0, j] == v[labels == 1].min()
        assert np.asarray(mx_r)[2, j] == v[labels == 3].max()
    assert np.isinf(np.asarray(mn_r)[1]).all()  # absent label -> +inf


def test_measure_texture_distance_suffix():
    """distance != 1 suffixes feature names so multi-scale instances
    coexist in one table."""
    from tmlibrary_tpu.jterator.modules import measure_texture

    labels = np.zeros((32, 32), np.int32)
    labels[4:28, 4:28] = 1
    img = np.arange(32 * 32, dtype=np.float32).reshape(32, 32)
    d1 = measure_texture(labels, img, levels=8, distance=1, max_objects=2)
    d3 = measure_texture(labels, img, levels=8, distance=3, max_objects=2)
    assert "Texture_contrast" in d1["measurements"]
    assert "Texture_contrast_d3" in d3["measurements"]
    assert not (set(d1["measurements"]) & set(d3["measurements"]))


def test_point_pattern_two_parents(strategy):
    """Hand-computed scene: two rectangular parents, spots at known
    centroids; NN distances, Clark-Evans, centroid and border distances
    all verified against independent numpy arithmetic."""
    from tmlibrary_tpu.ops.measure import point_pattern_features

    parents = np.zeros((48, 48), np.int32)
    parents[2:22, 2:42] = 1   # 20x40 rect
    parents[26:46, 2:42] = 2  # 20x40 rect
    points = np.zeros((48, 48), np.int32)
    # parent 1: three 1-px spots in a line, 8 px apart
    points[10, 10] = 1
    points[10, 18] = 2
    points[10, 26] = 3
    # parent 2: two spots 5 px apart (3-4-5 triangle)
    points[32, 10] = 4
    points[35, 14] = 5
    feats = jax.jit(
        lambda a, b: point_pattern_features(a, b, 4, 8)
    )(parents, points)
    f = {k: np.asarray(v) for k, v in feats.items()}

    assert np.array_equal(f["PointPattern_count"][:2], [3.0, 2.0])
    assert f["PointPattern_count"][2:].sum() == 0
    # NN: parent 1 -> [8, 8, 8]; parent 2 -> [5, 5]
    assert np.allclose(f["PointPattern_nn_dist_mean"][:2], [8.0, 5.0])
    assert np.allclose(f["PointPattern_nn_dist_std"][:2], [0.0, 0.0])
    # density + Clark-Evans, independent arithmetic
    area = 20.0 * 40.0
    for k, (n, nn) in enumerate([(3.0, 8.0), (2.0, 5.0)]):
        assert np.isclose(f["PointPattern_density"][k], n / area)
        ce = nn / (0.5 / np.sqrt(n / area))
        assert np.isclose(f["PointPattern_clark_evans"][k], ce, rtol=1e-5)
    # centroid distances: parent 1 centroid (11.5, 21.5)
    d = [np.hypot(10 - 11.5, x - 21.5) for x in (10, 18, 26)]
    assert np.isclose(f["PointPattern_centroid_dist_mean"][0], np.mean(d), rtol=1e-5)
    # border distance: chessboard distance to the nearest boundary pixel
    # (parent-1 outline rows are y=2/21; all three spots sit 8 away)
    assert np.isclose(f["PointPattern_border_dist_mean"][0], 8.0)


def test_point_pattern_background_and_singleton(strategy):
    """Spots on background are unassigned; a parent with one spot has no
    NN sample (nn stats 0) but still counts/centroid-distances."""
    from tmlibrary_tpu.ops.measure import point_pattern_features

    parents = np.zeros((32, 32), np.int32)
    parents[4:16, 4:16] = 1
    points = np.zeros((32, 32), np.int32)
    points[8, 8] = 1    # inside parent 1
    points[25, 25] = 2  # on background -> ignored
    feats = point_pattern_features(parents, points, 3, 4)
    f = {k: np.asarray(v) for k, v in feats.items()}
    assert f["PointPattern_count"][0] == 1.0
    assert f["PointPattern_nn_dist_mean"][0] == 0.0
    assert f["PointPattern_clark_evans"][0] == 0.0
    assert f["PointPattern_centroid_dist_mean"][0] > 0.0
    assert f["PointPattern_count"][1:].sum() == 0


def test_point_pattern_module_registration():
    from tmlibrary_tpu.jterator.modules import get_module

    fn = get_module("measure_point_pattern")
    parents = np.zeros((32, 32), np.int32)
    parents[4:28, 4:28] = 1
    points = np.zeros((32, 32), np.int32)
    points[10, 10] = 1
    points[20, 20] = 2
    out = fn(parents, points, max_objects=4, max_points=4)
    assert out["measurements"]["PointPattern_count"][0] == 2.0


def test_point_pattern_border_distance_euclidean(strategy):
    """Border distance is exact Euclidean (not chamfer rings): a 1-px hole
    diagonally offset from a spot must yield the sqrt-form distance,
    verified against an independent numpy min over boundary pixels."""
    from tmlibrary_tpu.ops.measure import point_pattern_features

    parents = np.ones((40, 40), np.int32)
    parents[20 + 5, 20 + 5] = 0  # diagonal 1-px hole
    points = np.zeros((40, 40), np.int32)
    points[20, 20] = 1
    feats = point_pattern_features(parents, points, 2, 2)
    got = float(np.asarray(feats["PointPattern_border_dist_mean"])[0])

    # independent numpy golden: same boundary definition, exact Euclidean
    lab = parents
    boundary = np.zeros_like(lab, bool)
    for dy, dx in ((-1, 0), (1, 0), (0, -1), (0, 1)):
        shifted = np.full_like(lab, -1)
        ys = slice(max(dy, 0), lab.shape[0] + min(dy, 0))
        xs = slice(max(dx, 0), lab.shape[1] + min(dx, 0))
        yd = slice(max(-dy, 0), lab.shape[0] + min(-dy, 0))
        xd = slice(max(-dx, 0), lab.shape[1] + min(-dx, 0))
        shifted[yd, xd] = lab[ys, xs]
        boundary |= shifted != lab
    by, bx = np.nonzero(boundary)
    exp = np.sqrt(((by - 20.0) ** 2 + (bx - 20.0) ** 2)).min()
    assert np.isclose(got, exp, rtol=1e-5), (got, exp)
    # and it IS the diagonal neighbor of the hole, not a chamfer ring count
    assert np.isclose(exp, np.sqrt(4.0**2 + 5.0**2))


def test_zernike_host_matches_xla():
    """The foreground-only host twin must agree with the device basis
    projection (f64 vs f32 summation: tolerance, not bit-identity)."""
    from tmlibrary_tpu.ops.measure import zernike_features

    labels = np.zeros((96, 96), np.int32)
    yy, xx = np.mgrid[0:96, 0:96]
    for i, (cy, cx, ry, rx) in enumerate(
        [(25, 25, 12, 7), (70, 30, 9, 9), (50, 70, 14, 6)]
    ):
        labels[(((xx - cx) / rx) ** 2 + ((yy - cy) / ry) ** 2) <= 1.0] = i + 1
    host = zernike_features(jnp.asarray(labels), 8, degree=6, method="host")
    xla = zernike_features(jnp.asarray(labels), 8, degree=6, method="xla")
    assert set(host) == set(xla)
    for k in host:
        np.testing.assert_allclose(
            np.asarray(host[k]), np.asarray(xla[k]), rtol=2e-3, atol=2e-4
        )


def test_zernike_host_features_matches_fg_twin():
    """The row-blocked ragged API must reproduce _zernike_host exactly
    (same math, different blocking)."""
    from tmlibrary_tpu.ops.measure import _zernike_host, zernike_host_features

    labels = np.zeros((96, 96), np.int32)
    yy, xx = np.mgrid[0:96, 0:96]
    for i, (cy, cx, ry, rx) in enumerate(
        [(25, 25, 12, 7), (70, 30, 9, 9), (50, 70, 14, 6)]
    ):
        labels[(((xx - cx) / rx) ** 2 + ((yy - cy) / ry) ** 2) <= 1.0] = i + 1
    for block in (8, 33, 512):
        got = zernike_host_features(labels, 3, degree=6, row_block=block)
        want = _zernike_host(labels, 3, 6)
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-7)


# ------------------------------------------- a full field's far corner (PR 27)
def _far_corner_objects(size=2160, n=6):
    """Ellipses of nucleus size at y, x ~ 2,000-2,140: where float32 field
    coordinates are coarsest (half an ulp of 1.2e-4 px)."""
    rng = np.random.default_rng(27)
    lab = np.zeros((size, size), np.int32)
    yy, xx = np.mgrid[0:size, 0:size]
    for i in range(n):
        y = 2000 + 24 * (i // 3) + rng.uniform(-2, 2)
        x = 2000 + 40 * (i % 3) + rng.uniform(-2, 2)
        a, b = rng.uniform(4.0, 6.5), rng.uniform(3.0, 4.5)
        sel = ((yy[1980:2160, 1980:2160] - y) / a) ** 2 \
            + ((xx[1980:2160, 1980:2160] - x) / b) ** 2 <= 1.0
        lab[1980:2160, 1980:2160][sel] = i + 1
    return lab, n


def test_zernike_holds_at_the_far_corner_of_a_full_field(strategy):
    """The projection is on offsets from the centroid: with a float32
    centroid at ~2,000 they were good to 1.2e-4 px, and ``Zernike_6_0``
    of a 5-px nucleus moved by 3e-4 on the chip (PERF.md, PR 27)."""
    lab, n = _far_corner_objects()
    got = zernike_features(jnp.asarray(lab), 8, degree=6, method="xla")
    for obj in range(1, n + 1):
        want = _zernike_reference_numpy(lab == obj, 6)
        for key, value in want.items():
            assert float(got[key][obj - 1]) == pytest.approx(
                value, rel=1e-4, abs=2e-5), (obj, key)


def test_second_moments_hold_at_the_far_corner_of_a_full_field(strategy):
    """As ``E[y^2] - cy^2`` in field coordinates the central moments
    cancel in float32 (both terms ~4e6, one ulp 0.5, a nucleus's
    variance ~4): the axes were off by per cents at 2160x2160."""
    lab, n = _far_corner_objects()
    got = morphology_features(jnp.asarray(lab), 8)
    for obj in range(1, n + 1):
        ys, xs = np.nonzero(lab == obj)
        dy, dx = ys - ys.mean(), xs - xs.mean()
        myy, mxx = (dy * dy).mean() + 1 / 12, (dx * dx).mean() + 1 / 12
        myx = (dy * dx).mean()
        common = np.sqrt((myy - mxx) ** 2 + 4 * myx ** 2)
        l1, l2 = (myy + mxx + common) / 2, (myy + mxx - common) / 2
        assert float(got["Morphology_major_axis_length"][obj - 1]) \
            == pytest.approx(4 * np.sqrt(l1), rel=1e-5)
        assert float(got["Morphology_minor_axis_length"][obj - 1]) \
            == pytest.approx(4 * np.sqrt(l2), rel=1e-5)
        assert float(got["Morphology_eccentricity"][obj - 1]) ** 2 \
            == pytest.approx(1 - l2 / l1, abs=1e-5)
        assert float(got["Morphology_centroid_y"][obj - 1]) \
            == pytest.approx(ys.mean(), abs=2e-4)
        assert float(got["Morphology_centroid_x"][obj - 1]) \
            == pytest.approx(xs.mean(), abs=2e-4)


def test_stretch_is_floor_in_integer_arithmetic_on_integer_pixels(rng, strategy):
    """``floor((v - min)(L - 1) / (max - min))`` held to its definition:
    on uint16-valued pixels every bin equals the integer quotient, also
    where a division lands one ulp under a whole number."""
    lab = rng.integers(0, 9, (64, 64)).astype(np.int32)
    img = rng.integers(200, 5000, (64, 64)).astype(np.float32)
    got = np.asarray(quantize_per_object(jnp.asarray(lab), jnp.asarray(img),
                                         8, 16))
    vi = img.astype(np.int64)
    for obj in range(1, 9):
        sel = lab == obj
        lo, hi = vi[sel].min(), vi[sel].max()
        np.testing.assert_array_equal(
            got[sel], (vi[sel] - lo) * 15 // (hi - lo))
