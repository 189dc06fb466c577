"""ctypes loader for the first-party native host kernels.

See ``native/tmnative.cpp``.  The library in use is always the one built
from that tracked source: on first use it is compiled with ``g++`` into
``<checkout>/.cache/native/libtmnative-<source digest>.so`` and loaded
from there, so a binary left over from an older source is never picked
up.  Every entry point has a pure-Python/scipy fallback, so the
framework works without a compiler — but that state is logged and
reported by :func:`status`, never silent (the native path is a
performance + golden-reference layer, mirroring how the reference leans
on cv2/mahotas binaries).
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
import threading
from pathlib import Path

import numpy as np

logger = logging.getLogger(__name__)

_NATIVE_DIR = Path(__file__).resolve().parent.parent / "native"
_SOURCE = _NATIVE_DIR / "tmnative.cpp"
#: a wheel carries no source tree: setup.py compiled the same source
#: into the package at install time, and only then is this copy used
_PACKAGED_SO = Path(__file__).resolve().parent / "libtmnative.so"
_lib = None
_load_attempted = False
#: what the loader found, for :func:`status`: ``state`` is ``loaded``,
#: ``no_source``, ``no_compiler``, ``build_failed`` or ``load_failed``
_status: dict = {"state": "not_loaded", "source_digest": None, "path": None}
#: first load may g++-build the library; concurrent callers (e.g. the
#: imextract decode thread pool) must not race that build
_load_lock = threading.Lock()


def _source_digest() -> str | None:
    """First 16 hex digits of the sha256 of ``native/tmnative.cpp``."""
    try:
        return hashlib.sha256(_SOURCE.read_bytes()).hexdigest()[:16]
    except OSError:
        return None


def _build(target: Path) -> "str | None":
    """Compile the tracked source into ``target``; returns None when it
    is built, else the state that says why not."""
    target.parent.mkdir(parents=True, exist_ok=True)
    tmp = target.with_suffix(f".{os.getpid()}.tmp")
    try:
        subprocess.run(
            # -ffp-contract=off: several kernels promise bit-parity with
            # an XLA or numpy float twin (tm_site_stats most strictly);
            # a fused multiply-add would round differently than the twin
            ["g++", "-O3", "-ffp-contract=off", "-fPIC", "-std=c++17",
             "-shared", "-o", str(tmp), str(_SOURCE)],
            check=True, capture_output=True, timeout=300,
        )
        os.replace(tmp, target)  # concurrent processes see whole files
        return None
    except FileNotFoundError:
        logger.warning("native: no g++ on this machine — the Python "
                       "fallbacks of every native kernel are in use")
        return "no_compiler"
    except subprocess.SubprocessError as e:
        logger.warning("native: building %s failed (%s) — the Python "
                       "fallbacks are in use", _SOURCE, e)
        return "build_failed"
    finally:
        tmp.unlink(missing_ok=True)


def _load():
    global _lib, _load_attempted
    # fast-path ONLY on a published library: checking _load_attempted here
    # would let callers slip past the lock mid-build and wrongly conclude
    # the library is unavailable while another thread is still compiling it
    if _lib is not None:
        return _lib
    with _load_lock:
        if _lib is not None or _load_attempted:
            return _lib
        return _load_locked()


def _load_locked():
    global _lib, _load_attempted
    _load_attempted = True
    from tmlibrary_tpu.utils import checkout_cache_dir

    digest = _source_digest()
    _status.update(source_digest=digest, path=None)
    if digest is not None:
        so_path = Path(checkout_cache_dir("native")) / \
            f"libtmnative-{digest}.so"
        if not so_path.exists():
            failure = _build(so_path)
            if failure is not None:
                _status["state"] = failure
                return None
    elif _PACKAGED_SO.exists():
        so_path = _PACKAGED_SO
    else:
        logger.warning("native: neither %s nor a packaged library exists "
                       "— the Python fallbacks are in use", _SOURCE)
        _status["state"] = "no_source"
        return None
    _status["path"] = str(so_path)
    try:
        lib = ctypes.CDLL(str(so_path))
    except OSError as e:
        logger.warning("native library %s failed to load: %s", so_path, e)
        _status["state"] = "load_failed"
        return None
    _status["state"] = "loaded"
    lib.tm_cc_label.restype = ctypes.c_int32
    lib.tm_cc_label.argtypes = [
        ctypes.POINTER(ctypes.c_uint8), ctypes.c_int32, ctypes.c_int32,
        ctypes.c_int32, ctypes.POINTER(ctypes.c_int32),
    ]
    lib.tm_trace_boundary.restype = ctypes.c_int32
    lib.tm_trace_boundary.argtypes = [
        ctypes.POINTER(ctypes.c_int32), ctypes.c_int32, ctypes.c_int32,
        ctypes.c_int32, ctypes.POINTER(ctypes.c_int32), ctypes.c_int32,
    ]
    lib.tm_bounding_boxes.restype = None
    lib.tm_bounding_boxes.argtypes = [
        ctypes.POINTER(ctypes.c_int32), ctypes.c_int32, ctypes.c_int32,
        ctypes.c_int32, ctypes.POINTER(ctypes.c_int32),
    ]
    lib.tm_hull_pixel_counts.restype = ctypes.c_int32
    lib.tm_hull_pixel_counts.argtypes = [
        ctypes.POINTER(ctypes.c_int32), ctypes.c_int32, ctypes.c_int32,
        ctypes.c_int32, ctypes.POINTER(ctypes.c_int32),
    ]
    # newer entry points may be absent from stale prebuilt libraries; probe
    try:
        lib.tm_simplify_polygon.restype = ctypes.c_int32
        lib.tm_simplify_polygon.argtypes = [
            ctypes.POINTER(ctypes.c_int32), ctypes.c_int32, ctypes.c_double,
            ctypes.POINTER(ctypes.c_uint8),
        ]
    except AttributeError:
        logger.info("native library predates polygon simplify; rebuild native/")
    try:
        lib.tm_tiff_info.restype = ctypes.c_int32
        lib.tm_tiff_info.argtypes = [
            ctypes.c_char_p, ctypes.POINTER(ctypes.c_int32),
        ]
        lib.tm_tiff_read.restype = ctypes.c_int32
        lib.tm_tiff_read.argtypes = [
            ctypes.c_char_p, ctypes.c_int32,
            ctypes.POINTER(ctypes.c_uint16), ctypes.c_int32, ctypes.c_int32,
        ]
        lib.tm_tiff_read2.restype = ctypes.c_int32
        lib.tm_tiff_read2.argtypes = [
            ctypes.c_char_p, ctypes.c_int32,
            ctypes.POINTER(ctypes.c_uint16), ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int32),
        ]
    except AttributeError:
        logger.info("native library predates the TIFF reader; rebuild native/")
    try:
        for name in ("tm_lzw_decode", "tm_packbits_decode"):
            fn = getattr(lib, name)
            fn.restype = ctypes.c_int32
            fn.argtypes = [
                ctypes.POINTER(ctypes.c_uint8), ctypes.c_int64,
                ctypes.POINTER(ctypes.c_uint8), ctypes.c_int64,
            ]
    except AttributeError:
        logger.info("native library predates strip decoders; rebuild native/")
    try:
        lib.tm_fill_holes.restype = ctypes.c_int32
        lib.tm_fill_holes.argtypes = [
            ctypes.POINTER(ctypes.c_uint8), ctypes.c_int32, ctypes.c_int32,
            ctypes.c_int32, ctypes.POINTER(ctypes.c_uint8),
        ]
        lib.tm_chebyshev_dt.restype = ctypes.c_int32
        lib.tm_chebyshev_dt.argtypes = [
            ctypes.POINTER(ctypes.c_uint8), ctypes.c_int32, ctypes.c_int32,
            ctypes.c_int32, ctypes.POINTER(ctypes.c_float),
        ]
        lib.tm_watershed_levels.restype = ctypes.c_int32
        lib.tm_watershed_levels.argtypes = [
            ctypes.POINTER(ctypes.c_float), ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_uint8), ctypes.c_int32, ctypes.c_int32,
            ctypes.POINTER(ctypes.c_float), ctypes.c_int32,
            ctypes.c_int32, ctypes.POINTER(ctypes.c_int32),
        ]
    except AttributeError:
        logger.info(
            "native library predates the CPU segmentation kernels; "
            "rebuild native/"
        )
    try:
        _d = ctypes.POINTER(ctypes.c_double)
        _i64 = ctypes.POINTER(ctypes.c_int64)
        lib.tm_mosaic_intensity.restype = ctypes.c_int32
        lib.tm_mosaic_intensity.argtypes = [
            ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_float),
            ctypes.c_int64, ctypes.c_int32, _d, _d, _d, _d,
        ]
        lib.tm_mosaic_morph.restype = ctypes.c_int32
        lib.tm_mosaic_morph.argtypes = [
            ctypes.POINTER(ctypes.c_int32), ctypes.c_int32, ctypes.c_int32,
            ctypes.c_int32, _i64, _d, _d, _i64, _i64, _i64, _i64,
        ]
    except AttributeError:
        logger.info(
            "native library predates the mosaic stats kernels; "
            "rebuild native/"
        )
    try:
        _f = ctypes.POINTER(ctypes.c_float)
        lib.tm_site_stats.restype = ctypes.c_int32
        lib.tm_site_stats.argtypes = [
            ctypes.POINTER(ctypes.c_int32), _f,
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int32,
            _f, _f, _f, _f, _f,
        ]
        lib.tm_hist_counts.restype = ctypes.c_int32
        lib.tm_hist_counts.argtypes = [
            ctypes.POINTER(ctypes.c_int32), ctypes.c_int64, ctypes.c_int64,
            ctypes.c_int32, _f,
        ]
        lib.tm_otsu_hist.restype = ctypes.c_int32
        lib.tm_otsu_hist.argtypes = [
            _f, ctypes.c_int64, ctypes.c_int64, ctypes.c_int32,
            _f, _f, _f,
        ]
        lib.tm_box_mean.restype = ctypes.c_int32
        lib.tm_box_mean.argtypes = [
            _f, ctypes.c_int64, ctypes.c_int32, ctypes.c_int32,
            ctypes.c_int32, _f,
        ]
        _i32p = ctypes.POINTER(ctypes.c_int32)
        lib.tm_site_channel_sums.restype = ctypes.c_int32
        lib.tm_site_channel_sums.argtypes = [
            _i32p, _f, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_int32, _f,
        ]
        lib.tm_site_channel_minmax.restype = ctypes.c_int32
        lib.tm_site_channel_minmax.argtypes = [
            _i32p, _f, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_int32, _f, _f,
        ]
        lib.tm_site_glcm.restype = ctypes.c_int32
        lib.tm_site_glcm.argtypes = [
            _i32p, _f, ctypes.c_int64, ctypes.c_int32, ctypes.c_int32,
            ctypes.c_int32, ctypes.c_int32, ctypes.c_int32, _f,
        ]
    except AttributeError:
        logger.info(
            "native library predates the site stats kernels; "
            "rebuild native/"
        )
    try:
        lib.tm_cc_label3d.restype = ctypes.c_int32
        lib.tm_cc_label3d.argtypes = [
            ctypes.POINTER(ctypes.c_uint8), ctypes.c_int32, ctypes.c_int32,
            ctypes.c_int32, ctypes.c_int32, ctypes.POINTER(ctypes.c_int32),
        ]
        lib.tm_watershed_levels3d.restype = ctypes.c_int32
        lib.tm_watershed_levels3d.argtypes = [
            ctypes.POINTER(ctypes.c_float), ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_uint8), ctypes.c_int32, ctypes.c_int32,
            ctypes.c_int32, ctypes.POINTER(ctypes.c_float), ctypes.c_int32,
            ctypes.POINTER(ctypes.c_int32),
        ]
    except AttributeError:
        logger.info(
            "native library predates the 3-D segmentation kernels; "
            "rebuild native/"
        )
    _lib = lib
    return _lib


def available() -> bool:
    return _load() is not None


def status() -> dict:
    """What the loader ended up with: ``state`` (``loaded`` or the reason
    the Python fallbacks are in use), the ``source_digest`` of
    ``native/tmnative.cpp`` the library was built from, and its
    ``path``."""
    _load()
    return dict(_status)


# ----------------------------------------------------------------- wrappers
def cc_label_host(mask: np.ndarray, connectivity: int = 8) -> tuple[np.ndarray, int]:
    """Host connected-component labeling, scipy scan order.

    Native union-find when available; ``scipy.ndimage.label`` fallback.
    """
    mask = np.ascontiguousarray(mask.astype(np.uint8))
    lib = _load()
    if lib is None:
        import scipy.ndimage as ndi

        structure = ndi.generate_binary_structure(2, 1 if connectivity == 4 else 2)
        labels, n = ndi.label(mask, structure=structure)
        return labels.astype(np.int32), int(n)
    h, w = mask.shape
    out = np.empty((h, w), np.int32)
    n = lib.tm_cc_label(
        mask.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)), h, w, connectivity,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
    )
    if n < 0:
        raise ValueError("tm_cc_label: invalid arguments")
    return out, int(n)


def trace_boundary_host(
    labels: np.ndarray, label: int, max_pts: int = 1 << 16
) -> np.ndarray:
    """Moore boundary trace → (K, 2) int32 (y, x); empty if label absent.
    Returns None when the native library is unavailable (callers fall back
    to cv2).  The buffer grows automatically if the boundary exceeds
    ``max_pts`` (the C function reports the true count)."""
    lib = _load()
    if lib is None:
        return None
    labels = np.ascontiguousarray(labels.astype(np.int32))
    h, w = labels.shape
    while True:
        buf = np.empty((max_pts, 2), np.int32)
        n = lib.tm_trace_boundary(
            labels.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)), h, w, int(label),
            buf.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)), max_pts,
        )
        if n < 0:
            raise ValueError("tm_trace_boundary: invalid arguments")
        if n <= max_pts:
            return buf[:n].copy()
        max_pts = n  # truncated: retry with the exact required size


def _monotone_chain(points: np.ndarray) -> np.ndarray:
    """Andrew's monotone chain over (x, y) int points → CCW hull vertices.
    Same pop rule (cross <= 0) as the C++ twin."""
    pts = sorted(map(tuple, points))
    if len(pts) <= 2:
        return np.asarray(pts, np.int64)

    def cross(o, a, b):
        return (a[0] - o[0]) * (b[1] - o[1]) - (a[1] - o[1]) * (b[0] - o[0])

    lower: list = []
    for p in pts:
        while len(lower) >= 2 and cross(lower[-2], lower[-1], p) <= 0:
            lower.pop()
        lower.append(p)
    upper: list = []
    for p in reversed(pts):
        while len(upper) >= 2 and cross(upper[-2], upper[-1], p) <= 0:
            upper.pop()
        upper.append(p)
    return np.asarray(lower[:-1] + upper[:-1], np.int64)


def hull_pixel_counts_host(labels: np.ndarray, max_label: int) -> np.ndarray:
    """Per-object rasterized convex hull pixel counts (skimage
    ``convex_hull_image`` semantics over pixel centers): element ``l-1`` is
    the number of pixels whose center lies inside or on the hull of object
    ``l``'s pixel centers.  Solidity = area / hull_count (reference:
    ``jtlib/features/morphology`` solidity via regionprops).

    Native monotone-chain + rasterize when available; numpy fallback with
    identical semantics."""
    labels = np.ascontiguousarray(labels.astype(np.int32))
    h, w = labels.shape
    lib = _load()
    if lib is not None:
        out = np.zeros((max_label,), np.int32)
        rc = lib.tm_hull_pixel_counts(
            labels.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)), h, w,
            max_label, out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        )
        if rc < 0:
            raise ValueError("tm_hull_pixel_counts: invalid arguments")
        return out

    out = np.zeros((max_label,), np.int32)
    for lab in range(1, max_label + 1):
        ys, xs = np.nonzero(labels == lab)
        n = len(ys)
        if n == 0:
            continue
        if n <= 2:
            out[lab - 1] = n
            continue
        hull = _monotone_chain(np.stack([xs, ys], axis=1))
        if len(hull) <= 2:
            out[lab - 1] = n
            continue
        gy, gx = np.mgrid[ys.min():ys.max() + 1, xs.min():xs.max() + 1]
        inside = np.ones(gy.shape, bool)
        m = len(hull)
        for i in range(m):
            x0, y0 = hull[i]
            x1, y1 = hull[(i + 1) % m]
            crossv = (x1 - x0) * (gy - y0) - (y1 - y0) * (gx - x0)
            inside &= crossv >= 0
        out[lab - 1] = int(inside.sum())
    return out


def solidity_host(
    labels: np.ndarray, max_label: int, areas: "np.ndarray | None" = None
) -> np.ndarray:
    """Per-object solidity = area / convex_hull_pixel_count → (max_label,)
    float32; absent labels get 0.  ``areas`` (``(max_label,)`` pixel
    counts for ids 1..max_label) skips the label-mask + bincount passes
    when the caller already accumulated them (the mosaic persist path
    has them from ``mosaic_morph_host`` — three full-mosaic passes saved
    at plate scale)."""
    labels = np.asarray(labels)
    if areas is None:
        flat = labels.ravel()
        # ids beyond max_label are dropped (hull counting skips them
        # too); clipping would alias their pixels onto object
        # max_label's area
        flat = np.where((flat >= 0) & (flat <= max_label), flat, 0)
        areas = np.bincount(flat, minlength=max_label + 1)[1:]
    areas = np.asarray(areas, np.float64)
    hull = hull_pixel_counts_host(labels, max_label).astype(np.float64)
    return np.where(hull > 0, areas / np.maximum(hull, 1.0), 0.0).astype(np.float32)


def bounding_boxes_host(labels: np.ndarray, max_label: int) -> np.ndarray:
    """(max_label, 4) int32 (min_y, min_x, max_y, max_x); -1 rows = absent."""
    lib = _load()
    labels = np.ascontiguousarray(labels.astype(np.int32))
    h, w = labels.shape
    if lib is None:
        out = np.full((max_label, 4), -1, np.int32)
        for lab in range(1, max_label + 1):
            ys, xs = np.nonzero(labels == lab)
            if len(ys):
                out[lab - 1] = (ys.min(), xs.min(), ys.max(), xs.max())
        return out
    out = np.empty((max_label, 4), np.int32)
    lib.tm_bounding_boxes(
        labels.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)), h, w, max_label,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
    )
    return out


# -------------------------------------------------------------- tiff reader
def tiff_info(path) -> tuple[int, int, int, int] | None:
    """(n_pages, height, width, bits) of a TIFF the native reader handles,
    else None (caller falls back to cv2)."""
    lib = _load()
    if lib is None or not hasattr(lib, "tm_tiff_info"):
        return None
    out = np.zeros((4,), np.int32)
    rc = lib.tm_tiff_info(
        str(path).encode(), out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32))
    )
    if rc != 0:
        return None
    return tuple(int(v) for v in out)


def tiff_read(path, page: int, height: int, width: int) -> np.ndarray | None:
    """Decode one grayscale TIFF page to (height, width) uint16 with the
    first-party native reader (classic TIFF, strips, none/LZW/PackBits,
    horizontal predictor, 8/16-bit).  None = unsupported file; caller
    falls back to cv2.  Reference parity: the Bio-Formats/cv2 plane-decode
    role of ``tmlib/readers.py`` (SURVEY.md §3 readers row)."""
    lib = _load()
    if lib is None or not hasattr(lib, "tm_tiff_read"):
        return None
    out = np.empty((height, width), np.uint16)
    rc = lib.tm_tiff_read(
        str(path).encode(), int(page),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint16)),
        int(height), int(width),
    )
    return out if rc == 0 else None


#: scratch for tiff_read_page — sized for a 2048² page up front, grown on
#: demand; one allocation reused across the whole ingest run
_TIFF_SCRATCH = threading.local()


def tiff_read_page(path, page: int) -> "np.ndarray | None":
    """Decode one grayscale TIFF page with dims discovered in the SAME
    file load (``tm_tiff_read2``) — the ``tiff_info`` + ``tiff_read``
    protocol loaded and walked the file twice per page.  None =
    unsupported file; caller falls back."""
    lib = _load()
    if lib is None or not hasattr(lib, "tm_tiff_read2"):
        return None
    scratch = getattr(_TIFF_SCRATCH, "buf", None)
    if scratch is None:
        scratch = np.empty(2048 * 2048, np.uint16)
        _TIFF_SCRATCH.buf = scratch
    hwb = np.zeros((3,), np.int32)
    for _ in range(2):
        rc = lib.tm_tiff_read2(
            str(path).encode(), int(page),
            scratch.ctypes.data_as(ctypes.POINTER(ctypes.c_uint16)),
            scratch.shape[0],
            hwb.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        )
        if rc == 0:
            h, w = int(hwb[0]), int(hwb[1])
            out = scratch[: h * w].reshape(h, w)
            return (
                out.astype(np.uint8) if int(hwb[2]) == 8 else out.copy()
            )
        if rc != -2:
            return None
        scratch = np.empty(int(hwb[0]) * int(hwb[1]), np.uint16)
        _TIFF_SCRATCH.buf = scratch
    return None


def _lzw_decode_py(src: bytes, expect: int) -> bytes | None:
    """Pure-Python TIFF LZW (MSB-first codes, 256=Clear, 257=EOI, early
    code-width change) — fallback twin of ``tm_lzw_decode``.  The bit
    reader is a small sliding accumulator fed byte-by-byte (O(n); a
    whole-strip bigint would make every shift O(strip size))."""
    table: list[bytes] = []

    def reset():
        table.clear()
        table.extend(bytes([i]) for i in range(256))
        table.extend((b"", b""))  # 256 Clear, 257 EOI

    reset()
    out = bytearray()
    width = 9
    prev: bytes | None = None
    acc = nbits = 0
    pos = 0
    n = len(src)
    while len(out) < expect:
        while nbits < width and pos < n:
            acc = (acc << 8) | src[pos]
            pos += 1
            nbits += 8
        if nbits < width:
            break
        nbits -= width
        code = (acc >> nbits) & ((1 << width) - 1)
        acc &= (1 << nbits) - 1
        if code == 257:
            break
        if code == 256:
            reset()
            width = 9
            prev = None
            continue
        if code < len(table) and code != 256 and code != 257:
            entry = table[code]
        elif code == len(table) and prev is not None:
            entry = prev + prev[:1]
        else:
            return None  # corrupt stream
        out += entry
        if prev is not None:
            table.append(prev + entry[:1])
        if len(table) + 1 >= (1 << width) and width < 12:
            width += 1
        prev = entry
    # the final entry can overrun expect; the native path truncates too
    return bytes(out[:expect]) if len(out) >= expect else None


def _packbits_decode_py(src: bytes, expect: int) -> bytes | None:
    out = bytearray()
    i = 0
    n = len(src)
    while i < n and len(out) < expect:
        c = src[i]
        i += 1
        if c < 128:
            cnt = c + 1
            if i + cnt > n:
                return None
            out += src[i:i + cnt]
            i += cnt
        elif c != 128:
            if i >= n:
                return None
            out += bytes([src[i]]) * (257 - c)
            i += 1
    # a literal/replicate run can cross the expect boundary; truncate like
    # the native path
    return bytes(out[:expect]) if len(out) >= expect else None


def lzw_decode(src: bytes, expect: int) -> bytes | None:
    """Decode a TIFF LZW strip to exactly ``expect`` bytes (None on corrupt
    input).  Native fast path, pure-Python fallback — used by the Python
    container readers (Zeiss LSM) whose strip layout the C++ page reader
    does not model."""
    lib = _load()
    if lib is not None and hasattr(lib, "tm_lzw_decode"):
        buf = np.frombuffer(src, np.uint8)
        out = np.empty(expect, np.uint8)
        rc = lib.tm_lzw_decode(
            buf.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)), len(src),
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)), expect,
        )
        return out.tobytes() if rc == 1 else None
    return _lzw_decode_py(src, expect)


def packbits_decode(src: bytes, expect: int) -> bytes | None:
    """Decode a PackBits strip to exactly ``expect`` bytes (None on corrupt
    input); native fast path with pure-Python fallback."""
    lib = _load()
    if lib is not None and hasattr(lib, "tm_packbits_decode"):
        buf = np.frombuffer(src, np.uint8)
        out = np.empty(expect, np.uint8)
        rc = lib.tm_packbits_decode(
            buf.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)), len(src),
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)), expect,
        )
        return out.tobytes() if rc == 1 else None
    return _packbits_decode_py(src, expect)


def _simplify_numpy(contour: np.ndarray, tolerance: float) -> np.ndarray:
    """Pure-numpy Douglas-Peucker fallback with the same ring-splitting
    semantics as ``tm_simplify_polygon`` (split at vertex 0 and its
    farthest vertex; the closing edge is simplified like any other)."""
    n = len(contour)
    keep = np.zeros(n, bool)
    if n <= 2:
        return contour
    pts = contour.astype(np.float64)
    tol2 = tolerance * tolerance

    def dist2(idx, a, b_pt):
        ay, ax = pts[a]
        by, bx = b_pt
        dy, dx = by - ay, bx - ax
        len2 = dy * dy + dx * dx
        ey = pts[idx, 0] - ay
        ex = pts[idx, 1] - ax
        if len2 == 0.0:
            return ey * ey + ex * ex
        cross = dx * ey - dy * ex
        return cross * cross / len2

    d0 = ((pts - pts[0]) ** 2).sum(axis=1)
    far_i = int(d0[1:].argmax()) + 1
    keep[0] = keep[far_i] = True
    stack = [(0, far_i), (far_i, n)]  # b == n: chord ends at vertex 0
    while stack:
        a, b = stack.pop()
        b_pt = pts[0] if b == n else pts[b]
        worst, worst_d = -1, tol2
        for i in range(a + 1, b):
            d = dist2(i, a, b_pt)
            if d > worst_d:
                worst_d, worst = d, i
        if worst >= 0:
            keep[worst] = True
            stack.append((a, worst))
            stack.append((worst, b))
    return contour[keep]


def simplify_polygon_host(contour: np.ndarray, tolerance: float) -> np.ndarray:
    """Douglas-Peucker simplification of a closed (K, 2) (y, x) contour
    ring to the given perpendicular-distance tolerance (pixels).

    Reference parity: the reference serves viewer-scale geometries through
    PostGIS simplification of ``MapobjectSegmentation`` polygons
    (``tmlib/models/mapobject.py`` row, SURVEY.md §3); here the native
    C++ routine does it at export time.  Falls back to an identical
    numpy implementation when the native library is unavailable."""
    contour = np.ascontiguousarray(contour, np.int32)
    if tolerance <= 0 or len(contour) <= 3:
        return contour
    lib = _load()
    if lib is None or not hasattr(lib, "tm_simplify_polygon"):
        out = _simplify_numpy(contour, tolerance)
    else:
        keep = np.zeros(len(contour), np.uint8)
        kept = lib.tm_simplify_polygon(
            contour.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            len(contour), float(tolerance),
            keep.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        )
        if kept < 0:
            raise ValueError("tm_simplify_polygon: invalid arguments")
        out = contour[keep.astype(bool)]
    if len(out) >= 3:
        return out
    # a large tolerance can collapse the ring to its two always-kept
    # split vertices (vertex 0 and the vertex farthest from it), which is
    # not a valid polygon (GeoJSON linear rings need >= 4 positions incl.
    # closure): re-add the vertex farthest from that chord so downstream
    # consumers always get a real ring
    pts = contour.astype(np.float64)
    far = int(((pts - pts[0]) ** 2).sum(axis=1).argmax())
    d = pts[far] - pts[0]
    len2 = max(float(d @ d), 1e-9)
    cross = np.abs(
        d[1] * (pts[:, 0] - pts[0, 0]) - d[0] * (pts[:, 1] - pts[0, 1])
    ) / np.sqrt(len2)
    cross[0] = cross[far] = -1.0
    picked = contour[sorted({0, far, int(cross.argmax())})]
    # an all-collinear contour (e.g. a 1-px-wide object's out-and-back
    # Moore trace) leaves every candidate on the chord: the picked "ring"
    # would still have zero area, or fewer than 3 distinct vertices.
    # Return the unsimplified contour instead — downstream consumers
    # handle it the same way they handle any unsimplified trace.
    if len(picked) < 3:
        return contour
    a, b, c = picked[:3].astype(np.float64)
    if abs((b[0] - a[0]) * (c[1] - a[1]) - (b[1] - a[1]) * (c[0] - a[0])) < 1e-12:
        return contour
    return picked


# --------------------------------------------- per-site measurement kernels
def callback_vmap_method() -> str:
    """``vmap_method`` for the measurement host callbacks.

    ``expand_dims`` turns the whole vmapped site batch into ONE host call
    (the per-site dispatch overhead of ``sequential`` is most of a
    sequential callback's cost) — but it DEADLOCKS XLA-CPU's SPMD
    partitioner when the jitted program executes over sharded inputs:
    the partitioner reshards the batch to device 0 around the callback
    with cross-device collectives, device 0 parks inside the callback,
    the other devices' rendezvous times out, and the runtime aborts the
    process ("Termination timeout for all reduce ... only 7 of them
    arrived").  Any multi-device process might hand this traced program
    sharded inputs (workflow steps shard whenever >1 device is visible),
    so batched callbacks are reserved for single-device processes — the
    single-chip bench and production single-device runs.  ``sequential``
    is the SPMD-safe method the segmentation callbacks have always used.
    """
    import jax

    return "expand_dims" if len(jax.devices()) == 1 else "sequential"


def align_batch(
    args: "list[tuple]",
) -> "tuple[tuple, list[np.ndarray]]":
    """Flatten the shared vmap lead axes of callback operands to ONE
    batch axis.  ``expand_dims`` inserts SIZE-1 lead dims for operands
    that are constant across the vmapped axis (e.g. coordinate grids),
    so per-operand lead sizes may be 1 — those broadcast to the true
    batch size (vmap semantics: the constant operand is shared).
    ``args`` is ``[(array, per_site_ndim), ...]``; returns the batched
    operand's lead shape (for reshaping results) and the aligned
    ``(n, *site_shape)`` arrays."""
    flats = []
    leads = []
    for a, nd in args:
        a = np.asarray(a)
        lead = a.shape[: a.ndim - nd]
        m = int(np.prod(lead, dtype=np.int64)) if lead else 1
        flats.append(a.reshape((m,) + a.shape[a.ndim - nd:]))
        leads.append(lead)
    n = max(f.shape[0] for f in flats)
    out_lead = next(
        (l for l, f in zip(leads, flats) if f.shape[0] == n), ()
    )
    aligned = [
        np.broadcast_to(f, (n,) + f.shape[1:])
        if f.shape[0] == 1 and n > 1 else f
        for f in flats
    ]
    return out_lead, aligned


def batch_sites(*arg_ndims: int):
    """Wrap a per-site host function so a ``pure_callback`` can use it
    under BOTH vmap methods: with ``sequential`` it sees bare site
    shapes; with ``expand_dims`` (single-device fast path —
    :func:`callback_vmap_method`) every argument arrives with shared
    leading vmap axes, which this wrapper flattens (via
    :func:`align_batch` — size-1 leads broadcast), loops over, and
    stacks back — turning a whole site batch into ONE callback dispatch.
    ``arg_ndims[i]`` is argument ``i``'s trailing per-site rank."""
    def wrap(site_fn):
        def host(*args):
            lead, flat = align_batch(list(zip(args, arg_ndims)))
            n = flat[0].shape[0]
            outs = [site_fn(*(f[i] for f in flat)) for i in range(n)]
            single = not isinstance(outs[0], tuple)
            if single:
                outs = [(o,) for o in outs]
            stacked = tuple(
                np.stack([np.asarray(o[j]) for o in outs]).reshape(
                    lead + np.asarray(outs[0][j]).shape
                )
                for j in range(len(outs[0]))
            )
            return stacked[0] if single else stacked
        return host
    return wrap


def has_site_stats() -> bool:
    """Whether the loaded library carries the round-5 measurement kernels
    (``tm_site_stats`` + ``tm_hist_counts`` + ``tm_otsu_hist``).
    ``TMX_SITE_STATS=0`` disables them independently of the segmentation
    kernels (diagnostic kill switch)."""
    import os

    if os.environ.get("TMX_SITE_STATS") == "0":
        return False
    lib = _load()
    return (
        lib is not None
        and hasattr(lib, "tm_site_stats")
        and hasattr(lib, "tm_hist_counts")
        and hasattr(lib, "tm_otsu_hist")
        and hasattr(lib, "tm_site_channel_sums")
    )


def site_stats_host(
    labels: np.ndarray, vals: np.ndarray, count: int
) -> tuple[np.ndarray, ...]:
    """Per-label (count, sum, sq_sum, min, max) for a batch of flattened
    sites — ``labels``/``vals`` are ``(n_sites, px)``; each output is
    ``(n_sites, count)`` float32 for label ids 1..count (background
    dropped).  Bit-identical to XLA-CPU's segment_sum/min/max over the
    same pixels (see ``tm_site_stats``); no numpy fallback — callers gate
    on :func:`has_site_stats` and keep the XLA path as the portable twin.
    """
    lib = _load()
    if lib is None or not hasattr(lib, "tm_site_stats"):
        raise RuntimeError("native tm_site_stats unavailable")
    labels32 = np.ascontiguousarray(labels, np.int32)
    vals32 = np.ascontiguousarray(vals, np.float32)
    n, px = labels32.shape
    k1 = count + 1
    outs = [np.empty((n, k1), np.float32) for _ in range(5)]
    fp = ctypes.POINTER(ctypes.c_float)
    rc = lib.tm_site_stats(
        labels32.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        vals32.ctypes.data_as(fp), n, px, count,
        *(o.ctypes.data_as(fp) for o in outs),
    )
    if rc != 0:
        raise ValueError("tm_site_stats: invalid arguments")
    return tuple(np.ascontiguousarray(o[:, 1:]) for o in outs)


def has_box_mean() -> bool:
    """Whether the loaded library carries ``tm_box_mean`` (honors the
    ``TMX_SITE_STATS=0`` kill switch with the other measurement
    kernels)."""
    import os

    if os.environ.get("TMX_SITE_STATS") == "0":
        return False
    lib = _load()
    return lib is not None and hasattr(lib, "tm_box_mean")


def box_mean_host(img: np.ndarray, size: int) -> np.ndarray:
    """scipy-``uniform_filter``-semantics box mean for a site batch —
    ``img`` is ``(n_sites, h, w)`` float32; O(1) per pixel (see
    ``tm_box_mean``; tolerance-tier vs the XLA tap pass)."""
    lib = _load()
    if lib is None or not hasattr(lib, "tm_box_mean"):
        raise RuntimeError("native tm_box_mean unavailable")
    img32 = np.ascontiguousarray(img, np.float32)
    n, h, w = img32.shape
    out = np.empty_like(img32)
    fp = ctypes.POINTER(ctypes.c_float)
    rc = lib.tm_box_mean(
        img32.ctypes.data_as(fp), n, h, w, size, out.ctypes.data_as(fp)
    )
    if rc != 0:
        raise ValueError("tm_box_mean: invalid arguments")
    return out


def site_channel_sums_host(
    labels: np.ndarray, vals: np.ndarray, count: int
) -> np.ndarray:
    """Per-label sums of several pixel channels — ``labels`` is
    ``(n, px)``, ``vals`` ``(n, C, px)``; returns ``(n, C, count)``
    float32 for label ids 1..count.  Bit-identical to XLA-CPU's
    ``segment_sum`` over the stacked channels (see
    ``tm_site_channel_sums``)."""
    lib = _load()
    if lib is None or not hasattr(lib, "tm_site_channel_sums"):
        raise RuntimeError("native tm_site_channel_sums unavailable")
    labels32 = np.ascontiguousarray(labels, np.int32)
    vals32 = np.ascontiguousarray(vals, np.float32)
    n, c, px = vals32.shape
    out = np.empty((n, c, count + 1), np.float32)
    fp = ctypes.POINTER(ctypes.c_float)
    rc = lib.tm_site_channel_sums(
        labels32.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        vals32.ctypes.data_as(fp), n, c, px, count,
        out.ctypes.data_as(fp),
    )
    if rc != 0:
        raise ValueError("tm_site_channel_sums: invalid arguments")
    return np.ascontiguousarray(out[:, :, 1:])


def site_channel_minmax_host(
    labels: np.ndarray, vals: np.ndarray, count: int
) -> tuple[np.ndarray, np.ndarray]:
    """Per-label (min, max) of several pixel channels — same layout as
    :func:`site_channel_sums_host`; absent labels keep (+inf, -inf)."""
    lib = _load()
    if lib is None or not hasattr(lib, "tm_site_channel_minmax"):
        raise RuntimeError("native tm_site_channel_minmax unavailable")
    labels32 = np.ascontiguousarray(labels, np.int32)
    vals32 = np.ascontiguousarray(vals, np.float32)
    n, c, px = vals32.shape
    mn = np.empty((n, c, count + 1), np.float32)
    mx = np.empty((n, c, count + 1), np.float32)
    fp = ctypes.POINTER(ctypes.c_float)
    rc = lib.tm_site_channel_minmax(
        labels32.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        vals32.ctypes.data_as(fp), n, c, px, count,
        mn.ctypes.data_as(fp), mx.ctypes.data_as(fp),
    )
    if rc != 0:
        raise ValueError("tm_site_channel_minmax: invalid arguments")
    return (
        np.ascontiguousarray(mn[:, :, 1:]),
        np.ascontiguousarray(mx[:, :, 1:]),
    )


def has_site_glcm() -> bool:
    """Whether the loaded library carries ``tm_site_glcm`` (honors the
    ``TMX_SITE_STATS=0`` kill switch)."""
    import os

    if os.environ.get("TMX_SITE_STATS") == "0":
        return False
    lib = _load()
    return lib is not None and hasattr(lib, "tm_site_glcm")


def site_glcm_host(
    labels: np.ndarray, img: np.ndarray, count: int, levels: int,
    distance: int,
) -> np.ndarray:
    """Per-object quantization + 4-direction symmetrized GLCMs for a
    site batch — ``labels``/``img`` are ``(n, h, w)``; returns
    ``(n, 4, count, levels, levels)`` float32 counts, bit-identical to
    the scatter path (integer counts; quantization replicated —
    see ``tm_site_glcm``)."""
    lib = _load()
    if lib is None or not hasattr(lib, "tm_site_glcm"):
        raise RuntimeError("native tm_site_glcm unavailable")
    labels32 = np.ascontiguousarray(labels, np.int32)
    img32 = np.ascontiguousarray(img, np.float32)
    n, h, w = labels32.shape
    out = np.empty((n, 4, count, levels, levels), np.float32)
    fp = ctypes.POINTER(ctypes.c_float)
    rc = lib.tm_site_glcm(
        labels32.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        img32.ctypes.data_as(fp), n, h, w, count, levels, distance,
        out.ctypes.data_as(fp),
    )
    if rc != 0:
        raise ValueError("tm_site_glcm: invalid arguments")
    return out


def otsu_hist_host(
    img: np.ndarray, bins: int
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Fused per-site (histogram, lo, hi) for the Otsu cut — ``img`` is
    ``(n_sites, px)`` float32; returns ``((n_sites, bins) f32 hist,
    (n_sites,) lo, (n_sites,) hi)``.  Bit-identical to the XLA
    normalize+histogram path in ``ops/threshold.py`` (see
    ``tm_otsu_hist``)."""
    lib = _load()
    if lib is None or not hasattr(lib, "tm_otsu_hist"):
        raise RuntimeError("native tm_otsu_hist unavailable")
    img32 = np.ascontiguousarray(img, np.float32)
    n, px = img32.shape
    hist = np.empty((n, bins), np.float32)
    lo = np.empty((n,), np.float32)
    hi = np.empty((n,), np.float32)
    fp = ctypes.POINTER(ctypes.c_float)
    rc = lib.tm_otsu_hist(
        img32.ctypes.data_as(fp), n, px, bins,
        hist.ctypes.data_as(fp), lo.ctypes.data_as(fp),
        hi.ctypes.data_as(fp),
    )
    if rc != 0:
        raise ValueError("tm_otsu_hist: invalid arguments")
    return hist, lo, hi


def hist_counts_host(idx: np.ndarray, bins: int) -> np.ndarray:
    """Per-site exact histograms of int32 bin indices — ``idx`` is
    ``(n_sites, px)``; returns ``(n_sites, bins)`` float32 counts.
    Bit-identical to the XLA scatter histogram (out-of-range indices
    dropped, float32 +1.0 adds)."""
    lib = _load()
    if lib is None or not hasattr(lib, "tm_hist_counts"):
        raise RuntimeError("native tm_hist_counts unavailable")
    idx32 = np.ascontiguousarray(idx, np.int32)
    n, px = idx32.shape
    out = np.empty((n, bins), np.float32)
    rc = lib.tm_hist_counts(
        idx32.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        n, px, bins, out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
    )
    if rc != 0:
        raise ValueError("tm_hist_counts: invalid arguments")
    return out


# ------------------------------------------- CPU-fallback segmentation path
def cpu_native_enabled() -> bool:
    """``method="auto"`` dispatch gate for the iterative segmentation ops
    (connected components, watershed, hole fill, distance transform).

    The XLA ``lax.while_loop`` twins are pathological on the CPU backend
    (round-2 bench: 0.39x single-thread scipy), so on ``cpu`` auto routes
    to these native kernels via ``jax.pure_callback``.  ``TMX_NATIVE=0``
    forces the portable XLA path; TPU/GPU backends never take this branch
    (resolution order pinned in each op's docstring)."""
    import jax

    if jax.default_backend() != "cpu":
        return False
    lib = _load()
    if lib is None or not hasattr(lib, "tm_watershed_levels"):
        return False
    return tmx_native_env_enabled()


def tmx_native_env_enabled() -> bool:
    """The ONE parser of the ``TMX_NATIVE`` kill switch — every
    cpu-fallback host routing (native kernels, zernike host twin) shares
    it so the flag disables them all at once."""
    import os

    return os.environ.get("TMX_NATIVE", "1") not in ("0", "false", "no")


def fill_holes_host(mask: np.ndarray, connectivity: int = 4) -> np.ndarray:
    """Fill background holes (native BFS; scipy fallback)."""
    mask = np.ascontiguousarray(mask.astype(np.uint8))
    h, w = mask.shape
    lib = _load()
    if lib is None or not hasattr(lib, "tm_fill_holes"):
        import scipy.ndimage as ndi

        structure = ndi.generate_binary_structure(2, 1 if connectivity == 4 else 2)
        return ndi.binary_fill_holes(mask, structure=structure)
    out = np.empty((h, w), np.uint8)
    rc = lib.tm_fill_holes(
        mask.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)), h, w, connectivity,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
    )
    if rc != 0:
        raise ValueError("tm_fill_holes: invalid arguments")
    return out.astype(bool)


def chebyshev_dt_host(mask: np.ndarray, max_distance: int = 64) -> np.ndarray:
    """Erosion-ring (chessboard) distance transform matching
    ``ops.segment_primary.distance_transform_approx`` exactly."""
    mask = np.ascontiguousarray(mask.astype(np.uint8))
    h, w = mask.shape
    lib = _load()
    if lib is None or not hasattr(lib, "tm_chebyshev_dt"):
        raise RuntimeError("native chebyshev_dt unavailable; use the XLA path")
    out = np.empty((h, w), np.float32)
    rc = lib.tm_chebyshev_dt(
        mask.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)), h, w,
        int(max_distance),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
    )
    if rc != 0:
        raise ValueError("tm_chebyshev_dt: invalid arguments")
    return out


def watershed_levels_host(
    intensity: np.ndarray,
    seeds: np.ndarray,
    mask: np.ndarray,
    levels: np.ndarray,
    connectivity: int = 8,
) -> np.ndarray:
    """Level-ordered watershed flooding, bit-identical to the XLA path of
    ``ops.segment_secondary.watershed_from_seeds``.  ``levels`` must be the
    descending threshold values computed by the same jitted expression the
    XLA path uses (band membership is then decided by exact comparisons)."""
    intensity = np.ascontiguousarray(intensity, np.float32)
    seeds = np.ascontiguousarray(seeds, np.int32)
    mask = np.ascontiguousarray(mask.astype(np.uint8))
    levels = np.ascontiguousarray(levels, np.float32)
    h, w = mask.shape
    lib = _load()
    if lib is None or not hasattr(lib, "tm_watershed_levels"):
        raise RuntimeError("native watershed unavailable; use the XLA path")
    out = np.empty((h, w), np.int32)
    rc = lib.tm_watershed_levels(
        intensity.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        seeds.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        mask.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)), h, w,
        levels.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), len(levels),
        connectivity,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
    )
    if rc != 0:
        raise ValueError("tm_watershed_levels: invalid arguments")
    return out


def has_3d_kernels() -> bool:
    lib = _load()
    return lib is not None and hasattr(lib, "tm_watershed_levels3d")


def cc_label3d_host(
    mask: np.ndarray, connectivity: int = 26
) -> tuple[np.ndarray, int]:
    """3-D connected components, scipy scan order (native union-find)."""
    mask = np.ascontiguousarray(mask.astype(np.uint8))
    z, h, w = mask.shape
    lib = _load()
    if lib is None or not hasattr(lib, "tm_cc_label3d"):
        raise RuntimeError("native 3-D CC unavailable; use the XLA path")
    out = np.empty((z, h, w), np.int32)
    n = lib.tm_cc_label3d(
        mask.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)), z, h, w,
        connectivity, out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
    )
    if n < 0:
        raise ValueError("tm_cc_label3d: invalid arguments")
    return out, int(n)


def watershed_levels3d_host(
    intensity: np.ndarray,
    seeds: np.ndarray,
    mask: np.ndarray,
    levels: np.ndarray,
) -> np.ndarray:
    """3-D level-ordered watershed flooding, bit-identical to the XLA
    path of ``ops.volume.watershed_from_seeds_3d`` (26-neighbor)."""
    intensity = np.ascontiguousarray(intensity, np.float32)
    seeds = np.ascontiguousarray(seeds, np.int32)
    mask = np.ascontiguousarray(mask.astype(np.uint8))
    levels = np.ascontiguousarray(levels, np.float32)
    z, h, w = mask.shape
    lib = _load()
    if lib is None or not hasattr(lib, "tm_watershed_levels3d"):
        raise RuntimeError("native 3-D watershed unavailable; use the XLA path")
    out = np.empty((z, h, w), np.int32)
    rc = lib.tm_watershed_levels3d(
        intensity.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        seeds.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        mask.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)), z, h, w,
        levels.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), len(levels),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
    )
    if rc != 0:
        raise ValueError("tm_watershed_levels3d: invalid arguments")
    return out


def _mosaic_intensity_py(labels: np.ndarray, vals: np.ndarray, count: int):
    """Chunked-vectorized twin of ``tm_mosaic_intensity``: whole row
    blocks per bincount (a handful of interpreter iterations on a
    plate-scale mosaic, not O(H)) with float64 accumulation and
    O(chunk + count) transients."""
    i_sum = np.zeros(count + 1)
    i_sq = np.zeros(count + 1)
    i_min = np.full(count + 1, np.inf)
    i_max = np.full(count + 1, -np.inf)
    flat_l = labels.reshape(-1)
    flat_v = vals.reshape(-1)
    step = 1 << 22  # ~4M pixels per block bounds the float64 transients
    for start in range(0, flat_l.size, step):
        ll = flat_l[start:start + step]
        vv = flat_v[start:start + step].astype(np.float64)
        i_sum += np.bincount(ll, weights=vv, minlength=count + 1)
        i_sq += np.bincount(ll, weights=vv * vv, minlength=count + 1)
        np.minimum.at(i_min, ll, vv)
        np.maximum.at(i_max, ll, vv)
    return i_sum, i_sq, i_min, i_max


def mosaic_intensity_host(labels: np.ndarray, vals: np.ndarray, count: int):
    """Per-label intensity accumulators over a label mosaic:
    ``(sum, sq_sum, min, max)``, each ``(count + 1,)`` float64 with
    index 0 = background (included in every accumulator; callers slice
    ``[1:]``).  One native C pass, chunked-numpy fallback."""
    labels32 = np.ascontiguousarray(labels, np.int32)
    vals32 = np.ascontiguousarray(vals, np.float32)
    lib = _load()
    if lib is not None and hasattr(lib, "tm_mosaic_intensity"):
        s = np.empty(count + 1)
        q = np.empty(count + 1)
        mn = np.empty(count + 1)
        mx = np.empty(count + 1)
        dp = ctypes.POINTER(ctypes.c_double)
        rc = lib.tm_mosaic_intensity(
            labels32.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            vals32.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            labels32.size, count,
            s.ctypes.data_as(dp), q.ctypes.data_as(dp),
            mn.ctypes.data_as(dp), mx.ctypes.data_as(dp),
        )
        if rc == 0:
            return s, q, mn, mx
        # rc=-1 is the kernel DETECTING corrupt input (a label outside
        # [0, count]), not the kernel being unavailable: falling through
        # to the numpy twin would pay a second plate-scale pass and then
        # die with an incidental bincount/ufunc error
        raise ValueError(
            f"mosaic_intensity_host: label outside [0, {count}] "
            "(corrupt label mosaic)"
        )
    return _mosaic_intensity_py(labels32, vals32, count)


def _mosaic_morph_py(labels: np.ndarray, count: int):
    """Chunked-vectorized twin of ``tm_mosaic_morph``."""
    h, w = labels.shape
    area = np.zeros(count + 1, np.int64)
    cy = np.zeros(count + 1)
    cx = np.zeros(count + 1)
    ymin = np.full(count + 1, h, np.int64)
    ymax = np.full(count + 1, -1, np.int64)
    xmin = np.full(count + 1, w, np.int64)
    xmax = np.full(count + 1, -1, np.int64)
    rows_per = max(1, (1 << 22) // max(w, 1))
    for y0 in range(0, h, rows_per):
        block = labels[y0:y0 + rows_per]
        hb = block.shape[0]
        flat = block.reshape(-1)
        area += np.bincount(flat, minlength=count + 1).astype(np.int64)
        yi = np.repeat(np.arange(y0, y0 + hb, dtype=np.int64), w)
        xi = np.tile(np.arange(w, dtype=np.int64), hb)
        cy += np.bincount(flat, weights=yi.astype(np.float64),
                          minlength=count + 1)
        cx += np.bincount(flat, weights=xi.astype(np.float64),
                          minlength=count + 1)
        np.minimum.at(ymin, flat, yi)
        np.maximum.at(ymax, flat, yi)
        np.minimum.at(xmin, flat, xi)
        np.maximum.at(xmax, flat, xi)
    return area, cy, cx, ymin, ymax, xmin, xmax


def mosaic_morph_host(labels: np.ndarray, count: int):
    """Per-label morphology accumulators over a label mosaic:
    ``(area, cy_sum, cx_sum, ymin, ymax, xmin, xmax)``, each
    ``(count + 1,)`` (index 0 = background; absent labels keep the
    ``h/-1/w/-1`` bbox sentinels).  One native C pass, chunked-numpy
    fallback."""
    labels32 = np.ascontiguousarray(labels, np.int32)
    h, w = labels32.shape
    lib = _load()
    if lib is not None and hasattr(lib, "tm_mosaic_morph"):
        area = np.empty(count + 1, np.int64)
        cy = np.empty(count + 1)
        cx = np.empty(count + 1)
        ymin = np.empty(count + 1, np.int64)
        ymax = np.empty(count + 1, np.int64)
        xmin = np.empty(count + 1, np.int64)
        xmax = np.empty(count + 1, np.int64)
        dp = ctypes.POINTER(ctypes.c_double)
        ip = ctypes.POINTER(ctypes.c_int64)
        rc = lib.tm_mosaic_morph(
            labels32.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            h, w, count,
            area.ctypes.data_as(ip), cy.ctypes.data_as(dp),
            cx.ctypes.data_as(dp), ymin.ctypes.data_as(ip),
            ymax.ctypes.data_as(ip), xmin.ctypes.data_as(ip),
            xmax.ctypes.data_as(ip),
        )
        if rc == 0:
            return area, cy, cx, ymin, ymax, xmin, xmax
        # same contract as mosaic_intensity_host: rc=-1 means corrupt
        # labels, not an unavailable kernel
        raise ValueError(
            f"mosaic_morph_host: label outside [0, {count}] "
            "(corrupt label mosaic)"
        )
    return _mosaic_morph_py(labels32, count)
