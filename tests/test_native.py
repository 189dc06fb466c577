import pathlib
import numpy as np
import pytest
import scipy.ndimage as ndi

from tmlibrary_tpu import native


@pytest.fixture(scope="module", autouse=True)
def require_native():
    if not native.available():
        pytest.skip("native library unavailable (no g++?)")


def blobs(rng, shape=(128, 128), n=15, r=6):
    img = np.zeros(shape, bool)
    yy, xx = np.mgrid[0 : shape[0], 0 : shape[1]]
    for y, x in zip(rng.integers(r, shape[0] - r, n), rng.integers(r, shape[1] - r, n)):
        img |= (yy - y) ** 2 + (xx - x) ** 2 <= r**2
    return img


@pytest.mark.parametrize("connectivity", [4, 8])
def test_cc_label_matches_scipy(rng, connectivity):
    mask = blobs(rng)
    structure = ndi.generate_binary_structure(2, 1 if connectivity == 4 else 2)
    expected, n_exp = ndi.label(mask, structure=structure)
    labels, n = native.cc_label_host(mask, connectivity)
    assert n == n_exp
    np.testing.assert_array_equal(labels, expected)


def test_cc_label_snake(rng):
    mask = np.zeros((64, 64), bool)
    for row in range(0, 64, 2):
        mask[row, :] = True
        if row + 1 < 64:
            mask[row + 1, 63 if (row // 2) % 2 == 0 else 0] = True
    labels, n = native.cc_label_host(mask, 8)
    expected, n_exp = ndi.label(mask, ndi.generate_binary_structure(2, 2))
    assert n == n_exp == 1
    np.testing.assert_array_equal(labels, expected)


def test_cc_label_empty():
    labels, n = native.cc_label_host(np.zeros((8, 8), bool), 8)
    assert n == 0 and labels.sum() == 0


def test_trace_boundary_square():
    labels = np.zeros((16, 16), np.int32)
    labels[4:9, 4:9] = 1  # 5x5 square
    pts = native.trace_boundary_host(labels, 1)
    assert pts is not None
    # boundary of a 5x5 square = 16 pixels
    assert len(pts) == 16
    # all points on the perimeter, start at first scan pixel
    assert tuple(pts[0]) == (4, 4)
    for y, x in pts:
        assert labels[y, x] == 1
        assert y in (4, 8) or x in (4, 8)


def test_trace_boundary_single_pixel():
    labels = np.zeros((8, 8), np.int32)
    labels[3, 3] = 7
    pts = native.trace_boundary_host(labels, 7)
    assert len(pts) == 1 and tuple(pts[0]) == (3, 3)


def test_trace_boundary_absent_label():
    labels = np.zeros((8, 8), np.int32)
    pts = native.trace_boundary_host(labels, 5)
    assert len(pts) == 0


def test_trace_matches_mask_outline(rng):
    mask = blobs(rng, n=1, r=10)
    labels, n = native.cc_label_host(mask, 8)
    assert n >= 1
    pts = native.trace_boundary_host(labels, 1)
    # every traced point is a boundary pixel of the object (touches bg)
    for y, x in pts:
        assert labels[y, x] == 1
        neigh = labels[max(0, y - 1) : y + 2, max(0, x - 1) : x + 2]
        assert (neigh == 0).any() or y in (0, 127) or x in (0, 127)


def test_bounding_boxes(rng):
    labels = np.zeros((32, 32), np.int32)
    labels[2:5, 3:9] = 1
    labels[20:30, 15:18] = 2
    boxes = native.bounding_boxes_host(labels, max_label=3)
    np.testing.assert_array_equal(boxes[0], [2, 3, 4, 8])
    np.testing.assert_array_equal(boxes[1], [20, 15, 29, 17])
    np.testing.assert_array_equal(boxes[2], [-1, -1, -1, -1])


def test_native_vs_python_fallback(rng):
    """Fallback path must agree with the native path."""
    mask = blobs(rng)
    native_labels, n1 = native.cc_label_host(mask, 8)
    import tmlibrary_tpu.native as nat

    saved, saved_attempt = nat._lib, nat._load_attempted
    try:
        nat._lib, nat._load_attempted = None, True  # force fallback
        fb_labels, n2 = native.cc_label_host(mask, 8)
    finally:
        nat._lib, nat._load_attempted = saved, saved_attempt
    assert n1 == n2
    np.testing.assert_array_equal(native_labels, fb_labels)


def test_hull_counts_rectangle_solidity_one():
    from tmlibrary_tpu.native import hull_pixel_counts_host, solidity_host

    labels = np.zeros((20, 20), np.int32)
    labels[3:9, 4:14] = 1  # 6x10 rectangle: hull == itself
    counts = hull_pixel_counts_host(labels, 4)
    assert counts[0] == 60
    assert list(counts[1:]) == [0, 0, 0]
    sol = solidity_host(labels, 4)
    np.testing.assert_allclose(sol[0], 1.0)


def test_hull_counts_l_shape_hand_computed():
    from tmlibrary_tpu.native import hull_pixel_counts_host, solidity_host

    # L: column (0..2, 0) plus row (2, 1..2); area 5.  Hull of pixel
    # centers is the triangle (0,0),(2,0),(2,2); pixel centers inside-or-on
    # it: the 5 L pixels + (1,1) on the diagonal edge -> 6.
    labels = np.zeros((5, 5), np.int32)
    labels[0:3, 0] = 1
    labels[2, 1:3] = 1
    counts = hull_pixel_counts_host(labels, 1)
    assert counts[0] == 6
    np.testing.assert_allclose(solidity_host(labels, 1)[0], 5.0 / 6.0)


def test_hull_counts_plus_shape():
    from tmlibrary_tpu.native import hull_pixel_counts_host

    # plus in a 3x3: hull is the diamond over the 4 extremes; corners of
    # the 3x3 are strictly outside -> hull pixel count = 5
    labels = np.zeros((5, 5), np.int32)
    labels[1, 2] = labels[3, 2] = labels[2, 1] = labels[2, 3] = labels[2, 2] = 1
    assert hull_pixel_counts_host(labels, 1)[0] == 5


def test_hull_counts_degenerate_objects():
    from tmlibrary_tpu.native import hull_pixel_counts_host

    labels = np.zeros((8, 8), np.int32)
    labels[1, 1] = 1          # single pixel
    labels[4, 2:7] = 2        # horizontal line
    labels[2:5, 7] = 3        # vertical line (collinear)
    counts = hull_pixel_counts_host(labels, 3)
    assert list(counts) == [1, 5, 3]


def test_hull_native_matches_numpy_fallback(rng):
    import tmlibrary_tpu.native as native
    from tmlibrary_tpu.native import hull_pixel_counts_host

    if not native.available():
        import pytest

        pytest.skip("native library unavailable")
    labels = np.zeros((64, 64), np.int32)
    # random blobby objects
    for lab, (cy, cx, r) in enumerate([(16, 16, 9), (40, 20, 7), (30, 48, 11)], 1):
        yy, xx = np.mgrid[0:64, 0:64]
        blob = ((yy - cy) ** 2 + (xx - cx) ** 2) <= r * r
        jitter = rng.random((64, 64)) > 0.2
        labels[blob & jitter & (labels == 0)] = lab
    got = hull_pixel_counts_host(labels, 8)
    # numpy twin: force the fallback by computing directly
    lib, native._lib = native._lib, None
    attempted = native._load_attempted
    native._load_attempted = True
    try:
        fallback = hull_pixel_counts_host(labels, 8)
    finally:
        native._lib = lib
        native._load_attempted = attempted
    np.testing.assert_array_equal(got, fallback)


# -------------------------------------------------------------- tiff reader
class TestTiffReader:
    """First-party TIFF decode vs cv2 golden (SURVEY.md §3 readers row)."""

    @pytest.mark.parametrize("dtype,hi", [(np.uint8, 255), (np.uint16, 65535)])
    @pytest.mark.parametrize("comp", [1, 5, 32773])  # none, LZW, PackBits
    def test_matches_cv2(self, tmp_path, rng, dtype, hi, comp):
        import cv2

        from tmlibrary_tpu.native import tiff_info, tiff_read

        img = rng.integers(0, hi, (48, 80)).astype(dtype)
        p = tmp_path / "x.tif"
        cv2.imwrite(str(p), img, [cv2.IMWRITE_TIFF_COMPRESSION, comp])
        info = tiff_info(p)
        if info is None:
            pytest.skip("native library unavailable")
        assert info == (1, 48, 80, 8 * dtype().itemsize)
        out = tiff_read(p, 0, 48, 80)
        assert out is not None
        assert np.array_equal(out, img.astype(np.uint16))

    def test_multipage(self, tmp_path, rng):
        import cv2

        from tmlibrary_tpu.native import tiff_info, tiff_read

        pages = [rng.integers(0, 65535, (16, 24)).astype(np.uint16)
                 for _ in range(3)]
        p = tmp_path / "stack.tif"
        cv2.imwritemulti(str(p), pages)
        info = tiff_info(p)
        if info is None:
            pytest.skip("native library unavailable")
        assert info[0] == 3
        for i, page in enumerate(pages):
            out = tiff_read(p, i, 16, 24)
            assert out is not None and np.array_equal(out, page)
        # out-of-range page declines instead of crashing
        assert tiff_read(p, 5, 16, 24) is None

    def test_declines_non_tiff_and_wrong_shape(self, tmp_path, rng):
        import cv2

        from tmlibrary_tpu.native import tiff_read

        img = rng.integers(0, 255, (16, 16)).astype(np.uint8)
        png = tmp_path / "x.png"
        cv2.imwrite(str(png), img)
        assert tiff_read(png, 0, 16, 16) is None  # not a TIFF -> fallback
        tif = tmp_path / "y.tif"
        cv2.imwrite(str(tif), img)
        assert tiff_read(tif, 0, 32, 32) is None  # shape mismatch -> decline


def test_simplify_polygon_square_to_corners():
    """Collinear mid-edge vertices collapse; the 4 corners survive."""
    from tmlibrary_tpu import native

    ring = np.array(
        [[0, 0], [0, 2], [0, 4], [2, 4], [4, 4], [4, 2], [4, 0], [2, 0]],
        np.int32,
    )
    s = native.simplify_polygon_host(ring, 0.5)
    assert s.tolist() == [[0, 0], [0, 4], [4, 4], [4, 0]]
    # tolerance 0 and tiny rings are no-ops
    assert np.array_equal(native.simplify_polygon_host(ring, 0.0), ring)
    tiny = ring[:2]
    assert np.array_equal(native.simplify_polygon_host(tiny, 5.0), tiny)


def test_simplify_polygon_native_matches_numpy(rng):
    """The C++ and numpy implementations agree vertex-for-vertex on real
    traced blob contours at several tolerances."""
    from tmlibrary_tpu import native

    if not native.available():
        pytest.skip("native library unavailable")
    labels = np.zeros((96, 96), np.int32)
    yy, xx = np.mgrid[0:96, 0:96]
    labels[((yy - 48) / 30.0) ** 2 + ((xx - 48) / 18.0) ** 2 <= 1.0] = 1
    contour = native.trace_boundary_host(labels, 1)
    assert len(contour) > 40
    for tol in (0.5, 1.0, 2.5):
        a = native.simplify_polygon_host(contour, tol)
        b = native._simplify_numpy(contour.astype(np.int32), tol)
        assert np.array_equal(a, b), tol
        assert 3 <= len(a) < len(contour)
    # max deviation of dropped vertices from the simplified ring is
    # bounded by the tolerance (DP guarantee), checked for tol=2.5
    closed = np.vstack([a, a[:1]]).astype(float)

    def seg_dist(p, s0, s1):
        d = s1 - s0
        t = np.clip(np.dot(p - s0, d) / max(np.dot(d, d), 1e-9), 0, 1)
        return np.linalg.norm(p - (s0 + t * d))

    for p in contour.astype(float):
        dmin = min(
            seg_dist(p, closed[i], closed[i + 1]) for i in range(len(closed) - 1)
        )
        assert dmin <= 2.5 + 1e-6


def test_simplify_polygon_never_degenerate():
    """A huge tolerance must still leave >= 3 vertices (valid GeoJSON
    linear ring), re-adding the farthest-from-chord vertex."""
    from tmlibrary_tpu import native

    ring = np.array(
        [[0, 0], [0, 10], [3, 20], [10, 10], [10, 0], [5, 1]], np.int32
    )
    s = native.simplify_polygon_host(ring, 1000.0)
    assert len(s) >= 3
    # the kept vertices are a subset of the input ring
    in_set = {tuple(p) for p in ring.tolist()}
    assert all(tuple(p) in in_set for p in s.tolist())


def test_mosaic_stats_reject_out_of_range_labels():
    """rc=-1 from the native kernels means CORRUPT INPUT (a label
    outside [0, count]), not 'kernel unavailable' — the hosts must raise
    a clear ValueError instead of paying a second plate-scale pass and
    dying with an incidental bincount/ufunc error (round-4 advisor)."""
    from tmlibrary_tpu import native

    lib = native._load()
    if lib is None or not hasattr(lib, "tm_mosaic_intensity"):
        pytest.skip("native library unavailable")
    labels = np.zeros((4, 5), np.int32)
    labels[1, 2] = 9  # > count
    vals = np.ones((4, 5), np.float32)
    with pytest.raises(ValueError, match="outside"):
        native.mosaic_intensity_host(labels, vals, 3)
    with pytest.raises(ValueError, match="outside"):
        native.mosaic_morph_host(labels, 3)


def test_mosaic_stats_native_matches_fallback_and_golden(rng):
    """tm_mosaic_intensity / tm_mosaic_morph vs the chunked-numpy twins
    vs direct per-label numpy — the spatial layout's feature
    accumulators (one C pass instead of an O(H) interpreter loop)."""
    from tmlibrary_tpu import native

    labels = rng.integers(0, 7, (40, 55)).astype(np.int32)
    labels[labels == 5] = 0  # absent id keeps sentinels
    vals = rng.normal(500, 90, (40, 55)).astype(np.float32)
    count = 8  # ids 7..8 absent too

    s, q, mn, mx = native.mosaic_intensity_host(labels, vals, count)
    s2, q2, mn2, mx2 = native._mosaic_intensity_py(labels, vals, count)
    np.testing.assert_allclose(s, s2, rtol=1e-12)
    np.testing.assert_allclose(q, q2, rtol=1e-12)
    np.testing.assert_array_equal(mn, mn2)
    np.testing.assert_array_equal(mx, mx2)

    morph_n = native.mosaic_morph_host(labels, count)
    morph_p = native._mosaic_morph_py(labels, count)
    for got, want in zip(morph_n, morph_p):
        np.testing.assert_array_equal(got, want)

    v64 = vals.astype(np.float64)
    area, cy, cx, ymin, ymax, xmin, xmax = morph_n
    for l in range(count + 1):
        sel = v64[labels == l]
        if not len(sel):
            assert s[l] == 0 and mn[l] == np.inf and mx[l] == -np.inf
            assert area[l] == 0 and ymax[l] == -1 and xmin[l] == 55
            continue
        np.testing.assert_allclose(s[l], sel.sum(), rtol=1e-12)
        np.testing.assert_allclose(q[l], (sel * sel).sum(), rtol=1e-12)
        assert mn[l] == sel.min() and mx[l] == sel.max()
        ys, xs = np.nonzero(labels == l)
        assert area[l] == len(ys)
        assert cy[l] == ys.sum() and cx[l] == xs.sum()
        assert (ymin[l], ymax[l], xmin[l], xmax[l]) == (
            ys.min(), ys.max(), xs.min(), xs.max())


def test_mosaic_morph_fallback_chunks_on_wide_mosaics(rng):
    """A mosaic wide enough to force multiple row blocks through the
    fallback (rows_per = 4M // W) must agree with the native pass."""
    from tmlibrary_tpu import native

    w = (1 << 21) + 7  # rows_per == 1: every row is its own block
    labels = np.zeros((3, w), np.int32)
    labels[0, :100] = 1
    labels[1, 50:200] = 2
    labels[2, w - 5:] = 1
    got = native._mosaic_morph_py(labels, 2)
    want = native.mosaic_morph_host(labels, 2)
    for g, x in zip(got, want):
        np.testing.assert_array_equal(g, x)
    area, cy, cx, ymin, ymax, xmin, xmax = got
    assert area[1] == 105 and ymax[1] == 2 and xmax[1] == w - 1


def _tiff_lzw_encode(data: bytes) -> bytes:
    """Full TIFF-LZW encoder: exists so the decoder's 10-12-bit widths,
    wide-width KwKwK, and table-cap paths have in-suite coverage — the
    round-trip fixtures written by cv2 never leave 9-bit codes.

    The code width used for each emission is decided by SIMULATING the
    decoder's state (its table lags the encoder's by one code, which is
    exactly what the TIFF early-change convention compensates for), so
    encoder and decoder agree by construction."""
    out = bytearray()
    acc = 0
    nbits = 0
    # decoder-side state the emitter mirrors
    dec_next = 258
    dec_width = 9
    dec_prev = False

    def emit_raw(code):
        nonlocal acc, nbits
        acc = (acc << dec_width) | code
        nbits += dec_width
        while nbits >= 8:
            nbits -= 8
            out.append((acc >> nbits) & 0xFF)

    def emit_data(code):
        nonlocal dec_next, dec_width, dec_prev
        emit_raw(code)
        # what our decoder does after consuming a data code
        if dec_prev and dec_next < 4096:
            dec_next += 1
            if dec_next + 1 >= (1 << dec_width) and dec_width < 12:
                dec_width += 1
        dec_prev = True

    def emit_clear():
        nonlocal dec_next, dec_width, dec_prev
        emit_raw(256)
        dec_next, dec_width, dec_prev = 258, 9, False

    def fresh_table():
        return {bytes([i]): i for i in range(256)}

    table = fresh_table()
    next_code = 258
    emit_clear()
    w = b""
    for byte in data:
        wc = w + bytes([byte])
        if wc in table:
            w = wc
            continue
        emit_data(table[w])
        table[wc] = next_code
        next_code += 1
        if next_code >= 4093:  # table nearly full: restart
            emit_clear()
            table = fresh_table()
            next_code = 258
        w = bytes([byte])
    if w:
        emit_data(table[w])
    emit_raw(257)  # EOI
    if nbits:
        out.append((acc << (8 - nbits)) & 0xFF)
    return bytes(out)


def test_lzw_full_width_round_trip(rng):
    """Native and Python LZW decoders on streams that grow the code
    width to 12 bits, hit the table cap (mid-stream Clear), and contain
    KwKwK chains — none of which the cv2-written fixtures exercise."""
    from tmlibrary_tpu import native

    random_part = bytes(rng.integers(0, 256, 30000, dtype=np.uint8))
    kwkwk_part = b"abababab" * 64 + bytes([7]) * 512
    for data in (
        random_part,                      # table cap + width 12 + Clear
        kwkwk_part,                       # KwKwK chains
        kwkwk_part + random_part,         # both, across a Clear
        b"",                              # empty stream
    ):
        encoded = _tiff_lzw_encode(data)
        got_native = native.lzw_decode(encoded, len(data))
        got_py = native._lzw_decode_py(encoded, len(data))
        assert got_native == data, f"native mismatch on {len(data)}-byte input"
        assert got_py == data, f"python twin mismatch on {len(data)}-byte input"

    # truncations of a wide-width stream must fail cleanly, never crash,
    # and native/python must agree
    encoded = _tiff_lzw_encode(random_part)
    for cut in (1, 100, len(encoded) // 2, len(encoded) - 2):
        n = native.lzw_decode(encoded[:cut], len(random_part))
        p = native._lzw_decode_py(encoded[:cut], len(random_part))
        assert n == p


def test_site_stats_kernels_bit_identical_to_xla(rng):
    """The round-5 measurement kernels (tm_site_stats, tm_hist_counts,
    tm_otsu_hist) promise BIT parity with their XLA twins — the dispatch
    swap must not be able to move a single feature value or threshold.
    Covers out-of-range labels (dropped like segment ids), negative
    histogram indices (jnp wraps once), and the Otsu span floor."""
    from tmlibrary_tpu import native
    from tmlibrary_tpu.ops.histogram import histogram_fixed_bins
    from tmlibrary_tpu.ops.measure import intensity_features
    from tmlibrary_tpu.ops.threshold import otsu_value

    if not native.has_site_stats():
        pytest.skip("native measurement kernels unavailable")
    import jax

    labels = rng.integers(0, 70, (3, 64, 64)).astype(np.int32)  # ids > 48
    img = rng.normal(500, 100, (3, 64, 64)).astype(np.float32)
    f_nat = jax.jit(jax.vmap(
        lambda l, i: intensity_features(l, i, 48, method="native")
    ))(labels, img)
    f_xla = jax.jit(jax.vmap(
        lambda l, i: intensity_features(l, i, 48, method="xla")
    ))(labels, img)
    for k in f_nat:
        np.testing.assert_array_equal(np.asarray(f_nat[k]), np.asarray(f_xla[k]))

    idx = rng.integers(-600, 600, (3, 64, 64)).astype(np.int32)
    h_nat = jax.jit(jax.vmap(
        lambda a: histogram_fixed_bins(a, 256, method="native")
    ))(idx)
    h_sca = jax.jit(jax.vmap(
        lambda a: histogram_fixed_bins(a, 256, method="scatter")
    ))(idx)
    np.testing.assert_array_equal(np.asarray(h_nat), np.asarray(h_sca))

    probes = [
        img,
        np.zeros((1, 8, 8), np.float32),           # span floor
        np.full((1, 8, 8), 7.25, np.float32),      # constant image
    ]
    for p in probes:
        a = jax.vmap(lambda x: otsu_value(x, method="native"))(p)
        b = jax.vmap(lambda x: otsu_value(x, method="xla"))(p)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # unbatched (no vmap) shape contract
    a = otsu_value(img[0], method="native")
    b = otsu_value(img[0], method="xla")
    assert np.asarray(a) == np.asarray(b)


def test_batched_callbacks_single_device_subprocess():
    """Under the suite's 8-virtual-device backend the measurement
    callbacks must pick the SPMD-safe ``sequential`` method (expand_dims
    deadlocks the partitioner's collective rendezvous — round-5 abort in
    test_determinism), while a single-device process gets the batched
    ``expand_dims`` fast path.  The subprocess runs WITHOUT the
    8-device flag to pin the fast path's correctness."""
    import os
    import subprocess
    import sys

    from tmlibrary_tpu import native as nat

    assert nat.callback_vmap_method() == "sequential"  # 8-device suite env
    if not nat.has_site_stats():
        pytest.skip("native measurement kernels unavailable")
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    code = """
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
from tmlibrary_tpu import native
from tmlibrary_tpu.ops.measure import intensity_features
assert native.callback_vmap_method() == "expand_dims", jax.devices()
rng = np.random.default_rng(3)
labels = rng.integers(0, 20, (4, 32, 32)).astype(np.int32)
img = rng.normal(100, 10, (4, 32, 32)).astype(np.float32)
nat = jax.jit(jax.vmap(lambda l, i: intensity_features(l, i, 16, method="native")))(labels, img)
xla = jax.jit(jax.vmap(lambda l, i: intensity_features(l, i, 16, method="xla")))(labels, img)
for k in nat:
    np.testing.assert_array_equal(np.asarray(nat[k]), np.asarray(xla[k]))
print("OK")
"""
    r = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True,
        text=True, timeout=300, cwd=str(pathlib.Path(__file__).parent.parent),
    )
    assert r.returncode == 0 and "OK" in r.stdout, r.stderr[-1500:]


def test_channel_sums_minmax_bit_identical_to_scatter(rng):
    """The multi-channel reduction kernels (tm_site_channel_sums /
    tm_site_channel_minmax) are bit-identical to the XLA segment
    scatters.  They are EXPLICIT opt-in (method="native") — auto-routing
    them hung XLA-CPU inside morphology's program (see grouped_sums) —
    but the kernels themselves stay correct and covered."""
    import jax
    import jax.numpy as jnp

    from tmlibrary_tpu import native as nat
    from tmlibrary_tpu.ops.measure import grouped_minmax_multi, grouped_sums

    if not nat.has_site_stats():
        pytest.skip("native measurement kernels unavailable")
    labels = rng.integers(0, 20, (3, 64, 64)).astype(np.int32)
    a = rng.normal(100, 10, (3, 64, 64)).astype(np.float32)
    b = rng.normal(5, 2, (3, 64, 64)).astype(np.float32)
    gs_n = jax.jit(jax.vmap(lambda l, x, y: grouped_sums(
        l, [jnp.ones_like(x), x, y], 16, method="native")))(labels, a, b)
    gs_s = jax.jit(jax.vmap(lambda l, x, y: grouped_sums(
        l, [jnp.ones_like(x), x, y], 16, method="scatter")))(labels, a, b)
    np.testing.assert_array_equal(np.asarray(gs_n), np.asarray(gs_s))
    mm_n = jax.jit(jax.vmap(lambda l, x, y: grouped_minmax_multi(
        l, [x, y], 16, method="native")))(labels, a, b)
    mm_s = jax.jit(jax.vmap(lambda l, x, y: grouped_minmax_multi(
        l, [x, y], 16, method="scatter")))(labels, a, b)
    for got, want in zip(mm_n, mm_s):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_site_glcm_bit_identical_to_scatter(rng):
    """tm_site_glcm (fused per-object quantization + 4-direction GLCMs)
    is bit-identical to the scatter path — GLCM counts are exact
    integers and the stretch replicates quantize_per_object's f32
    expression tree.  Explicit opt-in (see _resolve_glcm_method)."""
    import jax
    import jax.numpy as jnp

    from tmlibrary_tpu import native as nat
    from tmlibrary_tpu.ops.measure import haralick_features

    if not nat.has_site_glcm():
        pytest.skip("native GLCM kernel unavailable")
    labels = rng.integers(0, 70, (4, 96, 96)).astype(np.int32)  # ids > 48
    img = rng.normal(500, 100, (4, 96, 96)).astype(np.float32)
    f_nat = jax.jit(jax.vmap(lambda l, i: haralick_features(
        l, i, 48, levels=16, glcm_method="native")))(labels, img)
    f_sca = jax.jit(jax.vmap(lambda l, i: haralick_features(
        l, i, 48, levels=16, glcm_method="scatter")))(labels, img)
    for k in f_nat:
        np.testing.assert_array_equal(np.asarray(f_nat[k]), np.asarray(f_sca[k]))


# ------------------------------------------------------------------ loader
@pytest.fixture
def fresh_loader(tmp_path, monkeypatch):
    """The loader's module state reset, its build cache moved to a tmp
    directory; the real library comes back afterwards."""
    from tmlibrary_tpu import utils

    saved = native._lib, native._load_attempted, dict(native._status)
    native._lib, native._load_attempted = None, False
    monkeypatch.setattr(
        utils, "checkout_cache_dir", lambda name: str(tmp_path / name))
    yield tmp_path
    native._lib, native._load_attempted = saved[0], saved[1]
    native._status.clear()
    native._status.update(saved[2])


def test_loader_builds_from_tracked_source_not_a_leftover_binary(
        fresh_loader, monkeypatch):
    """A ``libtmnative.so`` left in ``native/`` (git-ignored, from any
    older source) is never loaded: the library in use is built from
    ``native/tmnative.cpp`` under a name that carries its digest."""
    import hashlib

    fake_native = fresh_loader / "native"
    fake_native.mkdir()
    source = fake_native / "tmnative.cpp"
    source.write_bytes(native._SOURCE.read_bytes())
    (fake_native / "libtmnative.so").write_bytes(b"stale, not even ELF")
    monkeypatch.setattr(native, "_NATIVE_DIR", fake_native)
    monkeypatch.setattr(native, "_SOURCE", source)

    st = native.status()
    digest = hashlib.sha256(source.read_bytes()).hexdigest()[:16]
    assert st["state"] == "loaded" and st["source_digest"] == digest
    assert st["path"] == str(
        fresh_loader / "native" / f"libtmnative-{digest}.so")
    labels, n = native.cc_label_host(np.eye(4, dtype=bool), 8)
    assert n == 1

    # an edited source is a different digest, hence a different library
    source.write_bytes(source.read_bytes() + b"\n// edited\n")
    native._lib, native._load_attempted = None, False
    assert native.status()["source_digest"] != digest


def test_missing_compiler_is_logged_and_reported(fresh_loader, monkeypatch,
                                                 caplog):
    import subprocess

    def no_gxx(*a, **k):
        raise FileNotFoundError("g++")

    monkeypatch.setattr(subprocess, "run", no_gxx)
    with caplog.at_level("WARNING", logger="tmlibrary_tpu.native"):
        st = native.status()
    assert st["state"] == "no_compiler" and st["path"] is None
    assert st["source_digest"] == native._source_digest()
    assert any("no g++" in r.getMessage() for r in caplog.records)
    assert not native.available()
    # the scipy fallback still answers
    labels, n = native.cc_label_host(np.eye(4, dtype=bool), 4)
    assert n == 4
