"""Thresholding ops.

Reference parity: ``jtmodules/threshold_manual.py``,
``threshold_otsu.py``, ``threshold_adaptive.py`` (cv2/mahotas-backed in the
reference).

All return boolean masks; all are pure ``jnp`` and jit/vmap-safe.  Histogram
computations use fixed bin counts so shapes stay static under jit.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from tmlibrary_tpu.ops import named
from tmlibrary_tpu.ops.smooth import gaussian_smooth, uniform_smooth


def threshold_manual(img: jax.Array, value) -> jax.Array:
    """Fixed global threshold (reference ``jtmodules/threshold_manual``)."""
    return jnp.asarray(img) > value


@named("otsu")
def otsu_value(img: jax.Array, bins: int = 256, method: str = "auto") -> jax.Array:
    """Otsu threshold value over a fixed-bin histogram.

    Matches the classic formulation (maximize between-class variance) used by
    mahotas/cv2 in the reference; with ``bins=256`` on 8-bit-scaled data the
    cut matches cv2's within one bin.  Returns a scalar in image units.

    ``method="auto"``: on the CPU backend the min/max + normalize +
    histogram run as ONE fused native pass (``tm_otsu_hist`` — the
    elementwise normalization alone cost ~0.8 ms/site as XLA-CPU passes;
    the C pass is bit-identical, so the cut cannot move); accelerators
    keep the factored one-hot matmul histogram (MXU).  The between-class
    argmax stays in XLA on the (bins,) histogram either way.
    """
    img_f = jnp.asarray(img, jnp.float32)
    if method == "auto":
        from tmlibrary_tpu import native

        method = (
            "native"
            if native.cpu_native_enabled() and native.has_site_stats()
            else "xla"
        )
    if method == "native":
        import numpy as np

        from tmlibrary_tpu import native

        nd = img_f.ndim  # unbatched rank at trace time

        if not isinstance(img_f, jax.core.Tracer):
            # EAGER caller (the spatial mosaic paths compute their
            # global threshold outside jit): one direct C pass — routing
            # an eager op through the pure_callback machinery measured
            # pathologically slow at mosaic scale (minutes for a 4 Mpix
            # well)
            hist_h, lo_h, hi_h = native.otsu_hist_host(
                np.asarray(img_f).reshape(1, -1), bins
            )
            hist = jnp.asarray(hist_h[0])
            lo = jnp.asarray(lo_h[0])
            hi = jnp.asarray(hi_h[0])
            span = jnp.maximum(hi - lo, 1e-6)
            centers = (
                lo + (jnp.arange(bins, dtype=jnp.float32) + 0.5)
                / bins * span
            )
            return _otsu_argmax(hist, centers)

        def host(a):
            from tmlibrary_tpu import native

            a = np.asarray(a)
            lead = a.shape[: a.ndim - nd]
            n = int(np.prod(lead, dtype=np.int64)) if lead else 1
            hist, lo, hi = native.otsu_hist_host(a.reshape(n, -1), bins)
            return (
                hist.reshape(lead + (bins,)),
                lo.reshape(lead),
                hi.reshape(lead),
            )

        hist, lo, hi = jax.pure_callback(
            host,
            (
                jax.ShapeDtypeStruct((bins,), jnp.float32),
                jax.ShapeDtypeStruct((), jnp.float32),
                jax.ShapeDtypeStruct((), jnp.float32),
            ),
            img_f,
            vmap_method=native.callback_vmap_method(),
        )
        span = jnp.maximum(hi - lo, 1e-6)
    else:
        lo = jnp.min(img_f)
        hi = jnp.max(img_f)
        span = jnp.maximum(hi - lo, 1e-6)
        idx = jnp.clip(
            ((img_f - lo) / span * bins).astype(jnp.int32), 0, bins - 1
        )
        # factored one-hot matmul histogram (MXU) on TPU, scatter on CPU.
        # The method is pinned callback-free: ``method="xla"`` promises a
        # pure-XLA program (the distributed paths call it on globally
        # SHARDED arrays, where a host callback cannot run), so the
        # histogram must not re-introduce one via its own auto dispatch.
        from tmlibrary_tpu.ops.histogram import histogram_fixed_bins

        hist = histogram_fixed_bins(
            idx, bins,
            method="scatter" if jax.default_backend() == "cpu" else "matmul",
        )
    centers = lo + (jnp.arange(bins, dtype=jnp.float32) + 0.5) / bins * span
    return _otsu_argmax(hist, centers)


def _otsu_argmax(hist: jax.Array, centers: jax.Array) -> jax.Array:
    """Between-class-variance argmax over a (bins,) histogram — shared
    by the traced and eager otsu paths (bit-identical math)."""
    w0 = jnp.cumsum(hist)
    w1 = w0[-1] - w0
    sum0 = jnp.cumsum(hist * centers)
    mu0 = sum0 / jnp.maximum(w0, 1e-12)
    mu1 = (sum0[-1] - sum0) / jnp.maximum(w1, 1e-12)
    between = w0 * w1 * (mu0 - mu1) ** 2
    between = jnp.where((w0 > 0) & (w1 > 0), between, -1.0)
    k = jnp.argmax(between)
    return centers[k]


@named("otsu")
def threshold_otsu(img: jax.Array, bins: int = 256, correction_factor: float = 1.0) -> jax.Array:
    """Otsu global threshold (reference ``jtmodules/threshold_otsu``).

    ``correction_factor`` scales the computed threshold, mirroring the
    reference module's knob for biasing the cut.
    """
    t = otsu_value(img, bins=bins) * correction_factor
    return jnp.asarray(img, jnp.float32) > t


@named("threshold_adaptive")
def threshold_adaptive(
    img: jax.Array,
    method: str = "gaussian",
    kernel_size: int = 31,
    constant: float = 0.0,
    min_threshold: float | None = None,
    max_threshold: float | None = None,
) -> jax.Array:
    """Local (adaptive) threshold (reference ``jtmodules/threshold_adaptive``).

    The local threshold at each pixel is the ``method``-weighted mean of its
    ``kernel_size`` neighborhood **plus** ``constant``: a pixel is foreground
    when it exceeds its local background by at least ``constant``.  (This is
    cv2.adaptiveThreshold's ``mean - C`` with the sign flipped: cv2's
    document-binarization convention marks flat regions as foreground, which
    is wrong for spot/cell detection.)  ``min_threshold``/``max_threshold``
    clamp the local threshold like the reference module's bounds.
    """
    img_f = jnp.asarray(img, jnp.float32)
    if method == "gaussian":
        # cv2 derives sigma from the block size this way
        sigma = 0.3 * ((kernel_size - 1) * 0.5 - 1) + 0.8
        local = gaussian_smooth(img_f, sigma=sigma)
    elif method == "mean":
        local = uniform_smooth(img_f, size=kernel_size)
    else:
        raise ValueError(f"unknown adaptive threshold method '{method}'")
    t = local + constant
    if min_threshold is not None:
        t = jnp.maximum(t, min_threshold)
    if max_threshold is not None:
        t = jnp.minimum(t, max_threshold)
    return img_f > t
