"""Smoothing filters.

Reference parity: ``jtmodules/smooth.py`` (gaussian / median / average /
bilateral methods backed by cv2 + mahotas in the reference) and the filter
helpers in ``jtlib/filter/``.

TPU design: separable convolutions lowered through
``lax.conv_general_dilated`` (XLA maps them to the VPU/MXU), window-gather
median for small apertures.  Boundary handling matches
``scipy.ndimage``'s default ``mode='reflect'`` (== ``jnp.pad`` ``symmetric``)
so golden tests compare against scipy directly.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from tmlibrary_tpu.ops import named


def _gaussian_kernel1d(sigma: float, radius: int) -> jnp.ndarray:
    x = jnp.arange(-radius, radius + 1, dtype=jnp.float32)
    k = jnp.exp(-0.5 * (x / sigma) ** 2)
    return k / jnp.sum(k)


def _conv1d(img: jax.Array, kernel: jnp.ndarray, axis: int) -> jax.Array:
    """Correlate a 2-D image with a 1-D kernel along ``axis`` (reflect pad).

    Implemented as K static shifted-slice multiply-adds rather than
    ``lax.conv_general_dilated``: a single-channel (1,1,H,W) conv hits
    XLA-CPU's slow conv path (~10 ms per 256-px image — it dominated the
    whole CPU-fallback pipeline), while the unrolled form fuses into one
    vector pass on both CPU and TPU (VPU).  Accumulation is plain f32
    multiply-add, so the TPU result cannot drop to bf16 passes the way
    MXU convs default to — same guarantee HIGHEST precision gave the conv.
    """
    size = kernel.shape[0]
    r = size // 2
    pad = [(0, 0), (0, 0)]
    pad[axis] = (r, r)
    padded = jnp.pad(jnp.asarray(img, jnp.float32), pad, mode="symmetric")
    h, w = img.shape
    out = jnp.zeros((h, w), jnp.float32)
    for i in range(size):
        sl = lax.slice_in_dim(padded, i, i + (h if axis == 0 else w), axis=axis)
        out = out + kernel[i] * sl
    return out


def gaussian_radius(sigma: float, truncate: float = 4.0) -> int:
    """Kernel reach of :func:`gaussian_smooth` — ``int(truncate * sigma
    + 0.5)`` exactly as scipy computes it.  The sharded halo wrappers
    size their exchange from THIS helper so the halo can never drift
    out of lockstep with the kernel radius."""
    return int(truncate * float(sigma) + 0.5)


@named("smooth")
def gaussian_smooth(img: jax.Array, sigma: float, truncate: float = 4.0) -> jax.Array:
    """Separable Gaussian blur matching ``scipy.ndimage.gaussian_filter``.

    ``sigma``/``truncate`` are static (compile-time) parameters — radius
    comes from :func:`gaussian_radius`.
    """
    radius = gaussian_radius(sigma, truncate)
    k = _gaussian_kernel1d(float(sigma), radius)
    img = jnp.asarray(img, jnp.float32)
    # NO native fast path here, deliberately: gaussian_smooth feeds the
    # Otsu cut in the bit-identical Cell Painting label gate, and
    # XLA-CPU contracts the unrolled multiply-adds into FMAs a C twin
    # cannot reproduce with separate rounding (measured 1-2 ulp apart) —
    # while the callback round-trip made the C pass a net LOSS anyway
    # (117 ms vs 77 ms per 128-site batch).
    out = _conv1d(img, k, axis=0)
    return _conv1d(out, k, axis=1)


@named("smooth")
def uniform_smooth(img: jax.Array, size: int) -> jax.Array:
    """Separable box (mean) filter matching ``scipy.ndimage.uniform_filter``."""
    if size < 1:
        raise ValueError("size must be >= 1")
    # scipy centers even-sized windows with the extra tap on the left
    left = size // 2
    right = size - left - 1
    img = jnp.asarray(img, jnp.float32)
    h, w = img.shape
    if size <= min(h, w):
        from tmlibrary_tpu import native

        if native.cpu_native_enabled() and native.has_box_mean():
            # O(1)-per-pixel double running sums in C (tm_box_mean) —
            # the 31-tap XLA pass cost ~0.64 ms/site on 1 CPU core.
            # Tolerance-tier vs the XLA taps (like the zernike host
            # twin), within the scipy golden contract.  An XLA
            # prefix-sum version was tried first and measured SLOWER
            # than the taps (cumsum lowers to log-depth passes, and x64
            # is disabled so its accumulator silently ran f32).
            import numpy as np

            def host(a):
                a = np.asarray(a)
                lead = a.shape[: a.ndim - 2]
                n = int(np.prod(lead, dtype=np.int64)) if lead else 1
                return native.box_mean_host(
                    a.reshape((n, h, w)), size
                ).reshape(a.shape)

            return jax.pure_callback(
                host,
                jax.ShapeDtypeStruct((h, w), jnp.float32),
                img,
                vmap_method=native.callback_vmap_method(),
            )
    k = jnp.full((size,), 1.0 / size, jnp.float32)
    # shifted-slice accumulation for the same reason as _conv1d (slow
    # XLA-CPU conv path for single-channel shapes)
    padded = jnp.pad(img, ((left, right), (0, 0)), mode="symmetric")
    out = jnp.zeros((h, w), jnp.float32)
    for i in range(size):
        out = out + k[i] * lax.slice_in_dim(padded, i, i + h, axis=0)
    padded = jnp.pad(out, ((0, 0), (left, right)), mode="symmetric")
    out = jnp.zeros((h, w), jnp.float32)
    for i in range(size):
        out = out + k[i] * lax.slice_in_dim(padded, i, i + w, axis=1)
    return out


def _window_stack(img: jax.Array, size: int) -> jax.Array:
    """Gather the ``size*size`` neighborhood of every pixel → (k*k, H, W)."""
    r = size // 2
    padded = jnp.pad(img, ((r, r), (r, r)), mode="symmetric")
    h, w = img.shape
    views = [
        lax.dynamic_slice(padded, (dy, dx), (h, w))
        for dy in range(size)
        for dx in range(size)
    ]
    return jnp.stack(views)


@named("smooth")
def median_smooth(img: jax.Array, size: int) -> jax.Array:
    """Median filter (odd ``size``) matching ``scipy.ndimage.median_filter``.

    Implemented as a window-gather + sort: fine for the small apertures
    (3–9 px) microscopy pipelines use; the gather unrolls to ``size**2``
    static slices that XLA fuses.
    """
    if size % 2 != 1:
        raise ValueError("median filter size must be odd")
    stack = _window_stack(jnp.asarray(img, jnp.float32), size)
    return jnp.median(stack, axis=0)


@named("smooth")
def bilateral_smooth(
    img: jax.Array, size: int = 5, sigma_space: float = 2.0, sigma_range: float = 50.0
) -> jax.Array:
    """Bilateral filter (edge-preserving smoothing).

    Reference exposes cv2's bilateral option in ``jtmodules/smooth.py``; here
    it is an explicit window-gather with Gaussian space × range weights.
    """
    img_f = jnp.asarray(img, jnp.float32)
    stack = _window_stack(img_f, size)
    r = size // 2
    dy, dx = jnp.meshgrid(
        jnp.arange(-r, r + 1, dtype=jnp.float32),
        jnp.arange(-r, r + 1, dtype=jnp.float32),
        indexing="ij",
    )
    w_space = jnp.exp(-(dy**2 + dx**2) / (2.0 * sigma_space**2)).reshape(-1, 1, 1)
    w_range = jnp.exp(-((stack - img_f[None]) ** 2) / (2.0 * sigma_range**2))
    w = w_space * w_range
    return jnp.sum(w * stack, axis=0) / jnp.maximum(jnp.sum(w, axis=0), 1e-12)
