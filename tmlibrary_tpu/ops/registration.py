"""Cycle-to-cycle image registration.

Reference parity: ``tmlib/workflow/align/registration.py`` — per-site shift
between acquisition cycles (the reference registers each cycle's site
against the reference cycle and stores ``SiteShift`` rows plus the
``SiteIntersection`` crop window).

TPU design: FFT phase correlation in ``jnp.fft`` (XLA-native), batched over
sites with ``vmap``.  Subpixel refinement is unnecessary for the reference's
integer-shift semantics.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def phase_correlation(
    reference: jax.Array, target: jax.Array, upsample_hint: None = None
) -> tuple[jax.Array, jax.Array]:
    """Integer (dy, dx) such that rolling ``target`` by (dy, dx) aligns it
    with ``reference`` (i.e. ``reference[y, x] ≈ target[y - dy, x - dx]``).

    Classic cross-power-spectrum method; shifts are returned in the
    signed range [-H/2, H/2) / [-W/2, W/2).
    """
    a = jnp.asarray(reference, jnp.float32)
    b = jnp.asarray(target, jnp.float32)
    fa = jnp.fft.rfft2(a)
    fb = jnp.fft.rfft2(b)
    cross = fa * jnp.conj(fb)
    denom = jnp.maximum(jnp.abs(cross), 1e-12)
    corr = jnp.fft.irfft2(cross / denom, s=a.shape)
    idx = jnp.argmax(corr)
    h, w = a.shape
    dy = idx // w
    dx = idx % w
    dy = jnp.where(dy > h // 2, dy - h, dy).astype(jnp.int32)
    dx = jnp.where(dx > w // 2, dx - w, dx).astype(jnp.int32)
    return dy, dx


def phase_correlation_quality(
    reference: jax.Array, target: jax.Array
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """(dy, dx, quality): quality is the normalized correlation-surface
    peak in [0, 1] — 1.0 for a pure circular shift of identical content,
    near 1/sqrt(H*W) for unrelated images.  A confidence the reference's
    integer-shift registration lacks; the align step uses it to zero out
    unreliable sites (empty wells, debris)."""
    a = jnp.asarray(reference, jnp.float32)
    b = jnp.asarray(target, jnp.float32)
    fa = jnp.fft.rfft2(a)
    fb = jnp.fft.rfft2(b)
    cross = fa * jnp.conj(fb)
    denom = jnp.maximum(jnp.abs(cross), 1e-12)
    corr = jnp.fft.irfft2(cross / denom, s=a.shape)
    idx = jnp.argmax(corr)
    h, w = a.shape
    dy = idx // w
    dx = idx % w
    quality = jnp.clip(corr.reshape(-1)[idx], 0.0, 1.0)
    dy = jnp.where(dy > h // 2, dy - h, dy).astype(jnp.int32)
    dx = jnp.where(dx > w // 2, dx - w, dx).astype(jnp.int32)
    return dy, dx, quality


def phase_correlation_subpixel(
    reference: jax.Array,
    target: jax.Array,
    upsample: int = 10,
) -> tuple[jax.Array, jax.Array]:
    """(dy, dx) float32 with 1/``upsample`` pixel resolution.

    Beyond the reference's integer-shift registration: the correlation
    peak is refined by evaluating the cross-power inverse DFT on an
    upsampled grid around the integer peak via two small matrix products
    (Guizar-Sicairos matrix-multiply DFT) — MXU-friendly, no giant
    zero-padded FFT.  Deterministic, jit/vmap-safe.
    """
    a = jnp.asarray(reference, jnp.float32)
    b = jnp.asarray(target, jnp.float32)
    h, w = a.shape
    fa = jnp.fft.rfft2(a)
    fb = jnp.fft.rfft2(b)
    cross_r = fa * jnp.conj(fb)
    cross = jnp.fft.fft2(a) * jnp.conj(jnp.fft.fft2(b))
    cross = cross / jnp.maximum(jnp.abs(cross), 1e-12)
    corr = jnp.fft.irfft2(cross_r / jnp.maximum(jnp.abs(cross_r), 1e-12), s=a.shape)
    idx = jnp.argmax(corr)
    dy0 = idx // w
    dx0 = idx % w
    dy0 = jnp.where(dy0 > h // 2, dy0 - h, dy0).astype(jnp.float32)
    dx0 = jnp.where(dx0 > w // 2, dx0 - w, dx0).astype(jnp.float32)

    # 1.5-pixel neighborhood around the integer peak, upsampled
    n = int(3 * upsample)
    offsets = (jnp.arange(n, dtype=jnp.float32) - n / 2.0) / upsample
    fy = jnp.fft.fftfreq(h).astype(jnp.float32)  # cycles/pixel
    fx = jnp.fft.fftfreq(w).astype(jnp.float32)
    # E_y[k, m] = exp(2i pi fy[m] (dy0 + offsets[k])) etc.
    ey = jnp.exp(
        2j * jnp.pi * (dy0 + offsets)[:, None] * fy[None, :]
    )  # (n, H)
    ex = jnp.exp(
        2j * jnp.pi * (dx0 + offsets)[:, None] * fx[None, :]
    )  # (n, W)
    # inverse-DFT evaluation: corr(u, v) = Re Σ C[h,w] e^{2iπ(fy u + fx v)}
    local = jnp.real(jnp.einsum("kh,hw,lw->kl", ey, cross, ex))
    pk = jnp.argmax(local)
    dy = dy0 + offsets[pk // n]
    dx = dx0 + offsets[pk % n]
    return dy, dx


@functools.lru_cache(maxsize=1)
def _batch_pc_jit():
    # shared jit wrappers: per-call ``jax.jit(vmap(...))`` creates a fresh
    # cache and re-traces every batch shape on every align run
    def one(a, b):
        dy, dx = phase_correlation(a, b)
        return jnp.stack([dy, dx])

    return jax.jit(jax.vmap(one))


@functools.lru_cache(maxsize=1)
def _batch_pcq_jit():
    # ONE jitted program a process (a step instance comes and goes with
    # every submit; the program and its executables stay).  The stacks
    # arrive in the store's dtype (uint16: half the float32 bytes over
    # the host link) and are converted on the device.
    def one(a, b):
        dy, dx, q = phase_correlation_quality(a, b)
        return jnp.stack([dy, dx]), q

    def phase_correlation_batch(reference_stack, target_stack):
        # the name a device trace reads the registration's time by
        with jax.named_scope("phase_correlation"):
            return jax.vmap(one)(reference_stack, target_stack)

    return jax.jit(phase_correlation_batch)


def batch_phase_correlation(
    reference_stack: jax.Array, target_stack: jax.Array
) -> jax.Array:
    """vmap over the site axis → (B, 2) int32 shifts."""
    return _batch_pc_jit()(reference_stack, target_stack)


def batch_phase_correlation_quality(
    reference_stack: jax.Array, target_stack: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """vmap over the site axis → ((B, 2) int32 shifts, (B,) quality).
    The stacks may be uint16 (converted on the device)."""
    return _batch_pcq_jit()(reference_stack, target_stack)


def pairs_in_flight(plane_bytes_f32: int, device_free: int | None,
                    host_free: int | None) -> int:
    """How many (reference, target) pairs one registration launch may
    hold.  A pair in flight holds, of one float32 plane's bytes: on the
    device its two uint16 planes (1), their float32 copies (2), the two
    half spectra (2), the cross-power spectrum and its normalised twin
    (2), the correlation surface (1) and the transforms' workspace, taken
    as much again (8): 16; on the host the two uint16 stacks (1).  Half of
    what is free is planned with, as ``illuminati.channels_in_flight``
    does.  At least one; a platform that says nothing of its memory (the
    CPU backend has no ``memory_stats``) bounds nothing."""
    bounds = []
    if device_free is not None:
        bounds.append(device_free // 2 // max(1, 16 * plane_bytes_f32))
    if host_free is not None:
        bounds.append(host_free // 2 // max(1, plane_bytes_f32))
    return max(1, min(bounds)) if bounds else 1 << 30


def intersection_window(all_shifts: jax.Array) -> dict[str, int]:
    """Crop window covering the overlap of all cycles at all sites
    (reference ``SiteIntersection``).

    ``all_shifts`` are the stored *corrections* (the roll
    ``shift_image`` applies at analysis time, i.e. the negated drift):
    rolling DOWN by a positive dy exposes invalid rows at the TOP, so
    the top margin absorbs the largest positive dy, the bottom margin
    the largest negative dy, and likewise left/right for dx.

    ``all_shifts``: (N, 2) stacked (dy, dx) over every cycle and site
    (host-side; returns Python ints for static crop shapes).
    """
    import numpy as np

    s = np.asarray(all_shifts)
    if s.size == 0:
        return {"top": 0, "bottom": 0, "left": 0, "right": 0}
    return {
        "top": int(np.clip(s[:, 0].max(), 0, None)),
        "bottom": int(np.clip(-s[:, 0].min(), 0, None)),
        "left": int(np.clip(s[:, 1].max(), 0, None)),
        "right": int(np.clip(-s[:, 1].min(), 0, None)),
    }


#: a stored window's margin is a multiple of this many pixels
WINDOW_QUANTUM = 16


def stored_window(window: dict[str, int]) -> dict[str, int]:
    """The window an experiment stores and every consumer crops to: the
    intersection's largest margin, widened to the next multiple of
    :data:`WINDOW_QUANTUM`, on all four sides (no drift: no crop).

    The window is static in the batch programs, so each distinct window
    is a set of compiled rungs of its own (minutes of compile, hundreds
    of megabytes of executable store at a 2160 x 2160 field).  The exact
    intersection is four numbers that differ from plate to plate; the
    stage's repositioning error that produces them does not.  One margin
    on a grid of 16 leaves ``max_shift // 16 + 2`` windows an instrument
    can ever produce (five at ``max_shift`` 50), and a plate of the same
    instrument lands on the same one or its neighbour.  Every pixel
    inside it is inside the intersection: what is given up is at most
    the margins' difference plus 15 pixels a side (DESIGN.md §30)."""
    widest = max(window.values(), default=0)
    margin = -(-widest // WINDOW_QUANTUM) * WINDOW_QUANTUM
    return dict.fromkeys(("top", "bottom", "left", "right"), int(margin))
