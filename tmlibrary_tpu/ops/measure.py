"""Per-object feature measurement.

Reference parity: ``jtmodules/measure_intensity.py``,
``measure_morphology.py``, ``measure_texture.py`` (mahotas Haralick),
``measure_zernike.py`` and the extractors in ``jtlib/features/``.

TPU design (SURVEY.md §8 hard parts #3/#4): measurements are ragged per
site (variable object count), so everything is computed into fixed
``(max_objects, ...)`` buffers with ``jax.ops.segment_sum``-family
reductions over the label image — rows past a site's object count are
garbage and must be masked by the caller using the object count.  Haralick
GLCMs accumulate with one scatter-add per direction over
(label, level, level) cells; Zernike moments project per-object patches
(static patch size) onto radial polynomials evaluated at each object's own
scale.  Everything jit/vmap-safe, fp32.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from tmlibrary_tpu.ops.label import shift_with_fill
from tmlibrary_tpu.ops.reduction import (
    capacity_segments,
    resolve_reduction_strategy,
    segmented_max,
    segmented_min,
    segmented_sum,
)
from tmlibrary_tpu.ops import named


def _seg_sum(values: jax.Array, labels: jax.Array, max_objects: int) -> jax.Array:
    """segment_sum over label ids; returns per-object rows 1..max_objects."""
    flat = labels.reshape(-1)
    vals = values.reshape(-1)
    out = jax.ops.segment_sum(
        vals, flat, num_segments=capacity_segments(max_objects)
    )
    return out[1:]


_SUM_CHUNK = 1 << 16  # pixels per one-hot matmul chunk (bounds HBM)


def grouped_sums(
    labels: jax.Array,
    channels: list[jax.Array],
    max_objects: int,
    method: str = "auto",
) -> jax.Array:
    """Per-object sums of several pixel channels via one-hot matmuls.

    TPU scatter-adds serialize; contracting a one-hot of the label image
    against stacked value channels rides the MXU instead — one pass for any
    number of channels.  The pixel axis is processed in fixed-size chunks so
    the (chunk, max_objects+1) one-hot operand stays bounded (a full-image
    one-hot on a large site or 3-D volume would blow out HBM, and the
    site-batch vmap multiplies it).  Returns ``(max_objects, n_channels)``
    float32 (label ids 1..max_objects; background dropped).

    ``method`` names a strategy of ``ops/reduction.py``:
    ``"onehot"`` (alias ``"matmul"``) is the chunked MXU contraction,
    ``"scatter"`` the segment scatter-add, ``"native"`` the
    explicit-opt-in C callback.
    ``"auto"`` resolves through the strategy layer — by default the
    matmul on accelerators and the scatter on CPU, where scatters are
    cheap and the one-hot materialization is the bottleneck (~25x for
    the measurement stack on the test backend).
    """
    flat = labels.reshape(-1)
    stacked = jnp.stack(
        [jnp.asarray(c, jnp.float32).reshape(-1) for c in channels], axis=-1
    )  # (P, S)
    if method == "auto":
        # scatter stays the CPU auto choice: auto-routing the native
        # callback hung XLA-CPU's runtime inside morphology_features'
        # program at batch 128 (np.asarray of the callback operand never
        # returned; minimal reproductions with the same shapes pass, so
        # the interaction is with the surrounding program, not the
        # kernel).  "native" remains an explicit opt-in — the kernel
        # itself is bit-identical and parity-tested — and the strategy
        # resolver never selects it.
        method = resolve_reduction_strategy()
    if method == "onehot":
        method = "matmul"
    if method == "native":
        # one fused C pass over the pixels for ALL channels
        # (tm_site_channel_sums — bit-identical to the segment_sum
        # below), batched like the other measurement callbacks
        from tmlibrary_tpu import native

        n_ch = stacked.shape[-1]
        nd = flat.ndim  # 1 at trace time

        def host(lab, v):
            # align_batch: an operand constant across the vmapped axis
            # arrives with a SIZE-1 lead dim under expand_dims
            lead, (labf, vf) = native.align_batch([(lab, nd), (v, 2)])
            out = native.site_channel_sums_host(
                labf, vf.transpose(0, 2, 1), max_objects
            )  # (n, C, K)
            return out.transpose(0, 2, 1).reshape(
                lead + (max_objects, n_ch)
            )

        return jax.pure_callback(
            host,
            jax.ShapeDtypeStruct((max_objects, n_ch), jnp.float32),
            flat, stacked,
            vmap_method=native.callback_vmap_method(),
        )
    if method == "scatter":
        return segmented_sum(stacked, flat, capacity_segments(max_objects))[1:]
    if method != "matmul":
        raise ValueError(f"unknown grouped_sums method '{method}'")
    p = flat.shape[0]
    pad = (-p) % _SUM_CHUNK
    if pad:
        # padded pixels carry label 0 → they land in the dropped background row
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
        stacked = jnp.concatenate(
            [stacked, jnp.zeros((pad, stacked.shape[1]), stacked.dtype)]
        )
    n_chunks = flat.shape[0] // _SUM_CHUNK
    flat = flat.reshape(n_chunks, _SUM_CHUNK)
    stacked = stacked.reshape(n_chunks, _SUM_CHUNK, -1)

    def body(i, acc):
        oh = jax.nn.one_hot(
            flat[i], capacity_segments(max_objects), dtype=jnp.float32
        )
        return acc + jnp.einsum(
            "ps,pk->ks", stacked[i], oh, precision=jax.lax.Precision.HIGHEST
        )

    init = jnp.zeros(
        (capacity_segments(max_objects), stacked.shape[-1]), jnp.float32
    )
    out = jax.lax.fori_loop(0, n_chunks, body, init)
    return out[1:]


def lookup_by_label(
    labels: jax.Array,
    table: jax.Array,
    method: str = "auto",
) -> jax.Array:
    """Per-pixel lookup of float per-object values: ``out[p] =
    table[labels[p]]`` with ``table`` of shape ``(max_objects + 1, C)``
    (row 0 = background) → ``(*labels.shape, C)`` float32.

    Gathers from a tiny table serialize on TPU (~53 ms/batch-128 net on
    v5e for one 3-column lookup) while a one-hot contraction at
    ``Precision.HIGHEST`` rides the MXU at the fetch floor AND is
    bit-identical to the gather for FINITE table entries (measured: the
    bf16x3 split reconstructs every finite f32 value exactly when each
    dot product has one nonzero term).  Non-finite entries are NOT
    supported: a ±inf/NaN row would poison every pixel's sum through
    ``0 * inf = NaN``, so the matmul path sanitizes them to 0 — callers
    holding sentinel rows (e.g. :func:`grouped_minmax` absent-object
    ±inf) must mask them to finite values first, as
    :func:`quantize_per_object` does.  ``method="auto"``: gather on CPU,
    matmul on accelerators, pixel axis chunked like
    :func:`grouped_sums`."""
    table = jnp.asarray(table, jnp.float32)
    # out-of-range ids clamp into the table on BOTH paths (explicitly —
    # a raw jnp gather would wrap negative ids Python-style while
    # one_hot zeroes them)
    labels = jnp.clip(labels, 0, table.shape[0] - 1)
    if method == "auto":
        method = "gather" if jax.default_backend() == "cpu" else "matmul"
    if method == "gather":
        return table[labels]
    from tmlibrary_tpu.ops.label import _chunked_pixels

    table = jnp.where(jnp.isfinite(table), table, 0.0)
    flat = labels.reshape(-1)
    n = flat.shape[0]
    chunks = _chunked_pixels(flat)

    def body(i, acc):
        oh = jax.nn.one_hot(chunks[i], table.shape[0], dtype=jnp.float32)
        vals = jnp.einsum(
            "pk,kc->pc", oh, table, precision=jax.lax.Precision.HIGHEST
        )
        return acc.at[i].set(vals)

    out = jnp.zeros(
        (chunks.shape[0], chunks.shape[1], table.shape[1]), jnp.float32
    )
    out = jax.lax.fori_loop(0, chunks.shape[0], body, out)
    return out.reshape(-1, table.shape[1])[:n].reshape(
        *labels.shape, table.shape[1]
    )


def grouped_minmax(
    labels: jax.Array,
    values: jax.Array,
    max_objects: int,
    method: str = "auto",
) -> tuple[jax.Array, jax.Array]:
    """Per-object (min, max) of ``values`` via a fused masked reduce
    (streams the (chunk, K) broadcast through one reduction — ~2.4x faster
    than two segment_min/max scatters on TPU).  The pixel axis is chunked
    like :func:`grouped_sums` so the broadcast operand stays bounded on
    large sites / 3-D volumes under the site-batch vmap.  Rows for absent
    labels come back as (+inf, -inf).  ``method="auto"`` resolves through
    the strategy layer: segment_min/max scatters on CPU (see
    :func:`grouped_sums`), the masked reduce elsewhere.  ``"onehot"``
    aliases ``"reduce"`` — min/max have no matmul form, so the dense
    masked broadcast is that strategy's shape here; both strategies agree
    bit-exactly (min/max are accumulation-order-free)."""
    flat_l = labels.reshape(-1)
    flat_v = jnp.asarray(values, jnp.float32).reshape(-1)
    if method == "auto":
        # see grouped_minmax_multi: native is explicit opt-in on CPU
        method = resolve_reduction_strategy()
    if method == "onehot":
        method = "reduce"
    if method == "scatter":
        segs = capacity_segments(max_objects)
        mn = segmented_min(flat_v, flat_l, segs)
        mx = segmented_max(flat_v, flat_l, segs)
        return mn[1:], mx[1:]
    if method != "reduce":
        raise ValueError(f"unknown grouped_minmax method '{method}'")
    p = flat_l.shape[0]
    pad = (-p) % _SUM_CHUNK
    if pad:
        # padded pixels carry label 0 → they match no id in 1..max_objects
        flat_l = jnp.concatenate([flat_l, jnp.zeros((pad,), flat_l.dtype)])
        flat_v = jnp.concatenate([flat_v, jnp.zeros((pad,), flat_v.dtype)])
    n_chunks = flat_l.shape[0] // _SUM_CHUNK
    flat_l = flat_l.reshape(n_chunks, _SUM_CHUNK)
    flat_v = flat_v.reshape(n_chunks, _SUM_CHUNK)
    ids = jnp.arange(1, max_objects + 1, dtype=flat_l.dtype)

    def body(i, carry):
        mn, mx = carry
        sel = flat_l[i][:, None] == ids
        v = flat_v[i][:, None]
        mx = jnp.maximum(mx, jnp.max(jnp.where(sel, v, -jnp.inf), axis=0))
        mn = jnp.minimum(mn, jnp.min(jnp.where(sel, v, jnp.inf), axis=0))
        return mn, mx

    init = (
        jnp.full((max_objects,), jnp.inf, jnp.float32),
        jnp.full((max_objects,), -jnp.inf, jnp.float32),
    )
    return jax.lax.fori_loop(0, n_chunks, body, init)


def grouped_minmax_multi(
    labels: jax.Array,
    values: list[jax.Array],
    max_objects: int,
    method: str = "auto",
) -> tuple[jax.Array, jax.Array]:
    """Per-object (min, max) of SEVERAL pixel value channels in one pass
    over the pixels — (M, K) mins and maxs.  One chunked loop carrying 2K
    accumulators instead of K :func:`grouped_minmax` sweeps (the masked
    broadcast is the dominant cost on TPU).  CPU uses segment scatters."""
    k = len(values)
    flat_l = labels.reshape(-1)
    stacked = jnp.stack(
        [jnp.asarray(v, jnp.float32).reshape(-1) for v in values], axis=-1
    )  # (P, K)
    if method == "auto":
        # scatter stays the CPU auto choice here: routing this through
        # the native callback alongside grouped_sums' callback in ONE
        # jitted program hung XLA-CPU's runtime on mosaic-scale batches
        # (the second callback never returned from materializing its
        # operands); "native" remains an explicit opt-in until that
        # interaction is understood, and the strategy resolver never
        # selects it
        method = resolve_reduction_strategy()
    if method == "onehot":
        method = "reduce"
    if method == "native":
        # fused C pass (tm_site_channel_minmax), bit-identical to the
        # segment scatters below
        from tmlibrary_tpu import native

        nd = flat_l.ndim  # 1 at trace time

        def host(lab, v):
            lead, (labf, vf) = native.align_batch([(lab, nd), (v, 2)])
            mn, mx = native.site_channel_minmax_host(
                labf, vf.transpose(0, 2, 1), max_objects
            )  # (n, C, M) each
            shape = lead + (max_objects, k)
            return (
                mn.transpose(0, 2, 1).reshape(shape),
                mx.transpose(0, 2, 1).reshape(shape),
            )

        return jax.pure_callback(
            host,
            (
                jax.ShapeDtypeStruct((max_objects, k), jnp.float32),
                jax.ShapeDtypeStruct((max_objects, k), jnp.float32),
            ),
            flat_l, stacked,
            vmap_method=native.callback_vmap_method(),
        )
    if method == "scatter":
        segs = capacity_segments(max_objects)
        mn = segmented_min(stacked, flat_l, segs)
        mx = segmented_max(stacked, flat_l, segs)
        return mn[1:], mx[1:]
    if method != "reduce":
        raise ValueError(f"unknown grouped_minmax_multi method '{method}'")
    p = flat_l.shape[0]
    pad = (-p) % _SUM_CHUNK
    if pad:
        flat_l = jnp.concatenate([flat_l, jnp.zeros((pad,), flat_l.dtype)])
        stacked = jnp.concatenate(
            [stacked, jnp.zeros((pad, k), stacked.dtype)]
        )
    n_chunks = flat_l.shape[0] // _SUM_CHUNK
    flat_l = flat_l.reshape(n_chunks, _SUM_CHUNK)
    stacked = stacked.reshape(n_chunks, _SUM_CHUNK, k)
    ids = jnp.arange(1, max_objects + 1, dtype=flat_l.dtype)

    def body(i, carry):
        mn, mx = carry
        sel = flat_l[i][:, None] == ids  # (chunk, M)
        v = stacked[i]  # (chunk, K)
        vm = jnp.where(sel[:, :, None], v[:, None, :], jnp.inf)
        vx = jnp.where(sel[:, :, None], v[:, None, :], -jnp.inf)
        return (
            jnp.minimum(mn, jnp.min(vm, axis=0)),
            jnp.maximum(mx, jnp.max(vx, axis=0)),
        )

    init = (
        jnp.full((max_objects, k), jnp.inf, jnp.float32),
        jnp.full((max_objects, k), -jnp.inf, jnp.float32),
    )
    return jax.lax.fori_loop(0, n_chunks, body, init)


# ------------------------------------------------------------------ intensity
def _native_site_stats(
    labels: jax.Array, img: jax.Array, max_objects: int
) -> tuple[jax.Array, ...]:
    """One fused native pass over the pixels for (count, sum, sq, min,
    max) per label — ``vmap_method="expand_dims"`` (single-device), so a vmapped site
    batch costs ONE host callback total, not one per site (the round-3
    sequential host twin lost to XLA for exactly that reason)."""
    nd = labels.ndim  # site rank at trace time (2-D site or 3-D volume)
    k = max_objects

    def host(lab, im):
        from tmlibrary_tpu import native

        lead, (labf, imf) = native.align_batch([(lab, nd), (im, nd)])
        n = labf.shape[0]
        outs = native.site_stats_host(
            labf.reshape(n, -1), imf.reshape(n, -1), k
        )
        return tuple(o.reshape(lead + (k,)) for o in outs)

    shapes = tuple(
        jax.ShapeDtypeStruct((k,), jnp.float32) for _ in range(5)
    )
    from tmlibrary_tpu import native

    return jax.pure_callback(
        host, shapes, labels, img,
        vmap_method=native.callback_vmap_method(),
    )


@named("measure_intensity")
def intensity_features(
    labels: jax.Array, intensity: jax.Array, max_objects: int,
    method: str = "auto",
) -> dict[str, jax.Array]:
    """Reference feature set of ``jtlib/features/intensity.py``:
    max, mean, min, sum, std per object.

    ``method="auto"``: on the CPU backend one fused native C pass
    computes all five accumulators (XLA-CPU lowers the segment reductions
    to serial element scatters — ~2.3 ms/site at 256², ~5x the C pass;
    the round-3 note that a host twin measured SLOWER was about a
    PER-SITE sequential callback — the batched ``expand_dims`` callback
    pays the graph break once per batch).  Accelerators stay pure-XLA
    (one-hot MXU contractions); the native pass reproduces the XLA
    reductions bit-for-bit (``tm_site_stats``), so dispatch cannot move
    feature values."""
    labels = jnp.asarray(labels, jnp.int32)
    img = jnp.asarray(intensity, jnp.float32)
    if method == "auto":
        from tmlibrary_tpu import native

        method = (
            "native"
            if native.cpu_native_enabled() and native.has_site_stats()
            else "xla"
        )
    if method == "native":
        count, total, sq, mn, mx = _native_site_stats(labels, img, max_objects)
    else:
        sums = grouped_sums(
            labels, [jnp.ones_like(img), img, img * img], max_objects
        )
        count, total, sq = sums[:, 0], sums[:, 1], sums[:, 2]
        mn, mx = grouped_minmax(labels, img, max_objects)
    safe_n = jnp.maximum(count, 1.0)
    mean = total / safe_n
    var = jnp.maximum(sq / safe_n - mean * mean, 0.0)
    present = count > 0
    return {
        "Intensity_max": jnp.where(present, mx, 0.0),
        "Intensity_mean": mean,
        "Intensity_min": jnp.where(present, mn, 0.0),
        "Intensity_sum": total,
        "Intensity_std": jnp.sqrt(var),
    }


@named("measure_intensity")
def intensity_quantiles(
    labels: jax.Array,
    intensity: jax.Array,
    max_objects: int,
    qs: tuple[float, ...] = (0.25, 0.5, 0.75),
    bins: int = 256,
    method: str = "auto",
) -> dict[str, jax.Array]:
    """Per-object intensity quantiles (p25 / median / p75 by default).

    Reference parity: quantile-type per-object intensity statistics
    (round-1 VERDICT weak item #8 — some jtlib versions export them
    alongside mean/std; SURVEY.md §3 jtlibrary row).

    TPU design: a ragged per-object sort is gather-bound, so quantiles are
    read off a per-object histogram instead: each object's gray range is
    stretched into ``bins`` buckets (reusing :func:`quantize_per_object`),
    per-(object, bucket) counts accumulate in one one-hot MXU pass (same
    trick as the GLCM rows), and the quantile is the bucket where the
    object's CDF crosses ``q``, mapped back to gray units.  Exact when an
    object's gray span has ≤ ``bins`` distinct levels (the common case for
    stained cells); otherwise quantized to span/bins granularity.

    ``method`` selects the histogram-accumulation strategy
    (``ops/reduction.py``): ``"onehot"`` the dual one-hot contraction,
    ``"scatter"`` a fused (label*bins + bucket) index into one segmented
    count.  Counts are integers < 2^24 → exact in f32, so both return
    bit-identical quantiles.
    """
    labels = jnp.asarray(labels, jnp.int32)
    img = jnp.asarray(intensity, jnp.float32)
    raw_lo, raw_hi = grouped_minmax(labels, img, max_objects)
    present = raw_hi >= raw_lo
    lo = jnp.where(present, raw_lo, 0.0)
    span = jnp.where(present, raw_hi - lo, 1.0)
    strategy = resolve_reduction_strategy(method)
    q_pix = quantize_per_object(
        labels, img, max_objects, bins, bounds=(raw_lo, raw_hi)
    )
    # per-(object, bucket) counts as ONE contraction: label one-hot
    # (P, M+1) x bucket one-hot (P, bins) -> (M+1, bins) on the MXU, chunked
    # over pixels so both operands stay bounded under the site-batch vmap
    # (a fused (M+1)*bins one-hot would be ~2 GB at M=bins=256).  On CPU a
    # plain fused-index scatter is the fast path (see grouped_sums).
    lab_flat = labels.reshape(-1)
    q_flat = q_pix.reshape(-1)
    if strategy == "scatter":
        idx = lab_flat * bins + q_flat
        segs = capacity_segments(max_objects)
        counts = segmented_sum(
            jnp.ones_like(idx, jnp.float32), idx, segs * bins
        ).reshape(segs, bins)[1:]
        return _quantiles_from_counts(counts, lo, span, present, qs, bins)
    p = lab_flat.shape[0]
    pad = (-p) % _GLCM_CHUNK
    if pad:
        lab_flat = jnp.concatenate([lab_flat, jnp.zeros((pad,), lab_flat.dtype)])
        q_flat = jnp.concatenate([q_flat, jnp.zeros((pad,), q_flat.dtype)])
    n_chunks = lab_flat.shape[0] // _GLCM_CHUNK
    lab_flat = lab_flat.reshape(n_chunks, _GLCM_CHUNK)
    q_flat = q_flat.reshape(n_chunks, _GLCM_CHUNK)

    def body(i, acc):
        oh_l = jax.nn.one_hot(
            lab_flat[i], capacity_segments(max_objects), dtype=jnp.float32
        )
        oh_q = jax.nn.one_hot(q_flat[i], bins, dtype=jnp.float32)
        return acc + jnp.einsum(
            "pm,pb->mb", oh_l, oh_q, precision=jax.lax.Precision.HIGHEST
        )

    counts = jax.lax.fori_loop(
        0, n_chunks, body,
        jnp.zeros((capacity_segments(max_objects), bins), jnp.float32),
    )[1:]
    return _quantiles_from_counts(counts, lo, span, present, qs, bins)


def _quantiles_from_counts(counts, lo, span, present, qs, bins):
    """Nearest-rank quantiles read off per-object histogram counts."""
    cdf = jnp.cumsum(counts, axis=1)  # (M, bins)
    total = jnp.maximum(cdf[:, -1:], 1.0)
    out: dict[str, jax.Array] = {}
    centers = lo[:, None] + (
        jnp.arange(bins, dtype=jnp.float32)[None, :] * span[:, None] / (bins - 1)
    )
    for q in qs:
        # first bucket where CDF >= q * n  (nearest-rank quantile)
        reached = cdf >= q * total
        idx = jnp.argmax(reached, axis=1)
        val = jnp.take_along_axis(centers, idx[:, None], axis=1)[:, 0]
        name = "Intensity_median" if q == 0.5 else f"Intensity_p{int(round(q * 100)):02d}"
        out[name] = jnp.where(present, val, 0.0)
    return out


# ----------------------------------------------------------------- morphology
def _floor_div(num: jax.Array, den: jax.Array) -> jax.Array:
    """``floor(num / den)`` held to floor's definition, ``q * den <= num
    < (q + 1) * den`` (``den`` > 0).  The TPU divides by an approximate
    reciprocal, so a quotient that is a whole number can come out one ulp
    under it and its floor one too low; both products are exact in f32
    while they stay below 2^24, so on integers this is the integer
    quotient.  Where the division is IEEE's it changes nothing."""
    q = jnp.floor(num / den)
    return q + ((q + 1.0) * den <= num) - (q * den > num)


def _centred_coordinates(
    labels: jax.Array,
    yy: jax.Array,
    xx: jax.Array,
    area: jax.Array,
    sum_y: jax.Array,
    sum_x: jax.Array,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """``(cy, cx, dy, dx)``: the per-object centroids and every pixel's
    offset from its own object's centroid.

    A float32 centroid at field coordinates (~2,000) is good to half an
    ulp, 1.2e-4 px, and so is every ``y - cy``.  That is nothing to a
    centroid but not to what is built on the offsets: a nucleus's unit
    disk has a radius of ~5 px, so the radial coordinate is off by 2e-5,
    and ``R_60`` (slope 24 at the rim) moved ``Zernike_6_0`` by 3e-4 on
    the chip at 2160x2160.  ``sum_y`` and ``area`` are exact integers
    (below 2^24), so the centroid is split into its whole part and a
    fraction (:func:`_floor_div`) and a pixel's offset is the exact
    small integer ``y - whole`` less the fraction: good to 1e-7 of
    itself.  One label look-up carries all four columns."""
    safe_a = jnp.maximum(area, 1.0)

    def split(total):
        whole = _floor_div(total, safe_a)
        return whole, (total - whole * safe_a) / safe_a

    with jax.named_scope("centred_coordinates"):
        wy, fy = split(sum_y)
        wx, fx = split(sum_x)
        zero1 = jnp.zeros((1,), jnp.float32)
        pix = lookup_by_label(
            labels,
            jnp.stack(
                [jnp.concatenate([zero1, c]) for c in (wy, fy, wx, fx)],
                axis=-1,
            ),
        )
        dy = (yy - pix[..., 0]) - pix[..., 1]
        dx = (xx - pix[..., 2]) - pix[..., 3]
    return wy + fy, wx + fx, dy, dx


@named("morphology")
def morphology_features(labels: jax.Array, max_objects: int) -> dict[str, jax.Array]:
    """Reference feature set of ``jtlib/features/morphology.py``
    (CellProfiler-style): area, centroids, bounding box/extent, perimeter
    (8-connected boundary pixel count), equivalent diameter, form factor,
    second-moment ellipse (major/minor axis length, eccentricity,
    orientation).  Convex-hull features (solidity) are host-side only and
    live in the polygon pathway.
    """
    labels = jnp.asarray(labels, jnp.int32)
    h, w = labels.shape
    yy, xx = jnp.meshgrid(
        jnp.arange(h, dtype=jnp.float32), jnp.arange(w, dtype=jnp.float32), indexing="ij"
    )
    ones = jnp.ones((h, w), jnp.float32)

    # perimeter mask: pixels with at least one 4-neighbor of a different label
    boundary = jnp.zeros((h, w), bool)
    for dy, dx in ((-1, 0), (1, 0), (0, -1), (0, 1)):
        boundary = boundary | (shift_with_fill(labels, dy, dx, 0) != labels)
    boundary = boundary & (labels > 0)

    chans = [ones, yy, xx, boundary.astype(jnp.float32)]
    # all per-object sums in one MXU pass
    sums = grouped_sums(labels, chans, max_objects)
    # bounding box: both axes' min/max in ONE pass over the pixels
    mins, maxs = grouped_minmax_multi(labels, [yy, xx], max_objects)
    area = sums[:, 0]
    safe_a = jnp.maximum(area, 1.0)
    perimeter = sums[:, 3]

    y_min, x_min = mins[:, 0], mins[:, 1]
    y_max, x_max = maxs[:, 0], maxs[:, 1]
    present = area > 0
    bbox_h = jnp.where(present, y_max - y_min + 1.0, 0.0)
    bbox_w = jnp.where(present, x_max - x_min + 1.0, 0.0)
    extent = area / jnp.maximum(bbox_h * bbox_w, 1.0)

    # central second moments -> ellipse fit (CellProfiler/regionprops
    # math), summed about each pixel's own object's centroid.  As
    # E[y^2] - cy^2 in field coordinates they cancel in float32: at
    # y ~ 2000 both terms are ~4e6, one ulp is 0.5, and a nucleus's
    # variance is ~4 (axes off by several per cent at the far corner of
    # a 2160x2160 field; invisible at 96x96).
    with jax.named_scope("central_moments"):
        cy, cx, dy, dx = _centred_coordinates(
            labels, yy, xx, area, sums[:, 1], sums[:, 2]
        )
        central = grouped_sums(
            labels, [dy * dy, dx * dx, dy * dx], max_objects
        )
    mu_yy = central[:, 0] / safe_a
    mu_xx = central[:, 1] / safe_a
    mu_yx = central[:, 2] / safe_a
    # regionprops adds 1/12 (pixel as unit square) to the diagonal
    mu_yy = mu_yy + 1.0 / 12.0
    mu_xx = mu_xx + 1.0 / 12.0
    common = jnp.sqrt(jnp.maximum((mu_yy - mu_xx) ** 2 + 4.0 * mu_yx**2, 0.0))
    l1 = (mu_yy + mu_xx + common) / 2.0
    l2 = (mu_yy + mu_xx - common) / 2.0
    l2 = jnp.clip(l2, 1e-12, None)
    major = 4.0 * jnp.sqrt(jnp.maximum(l1, 0.0))
    minor = 4.0 * jnp.sqrt(jnp.maximum(l2, 0.0))
    eccentricity = jnp.sqrt(jnp.clip(1.0 - l2 / jnp.maximum(l1, 1e-12), 0.0, 1.0))
    # angle of the major axis measured from the +x (column) axis in
    # (-pi/2, pi/2]; note skimage regionprops measures from the row axis
    orientation = 0.5 * jnp.arctan2(2.0 * mu_yx, mu_xx - mu_yy)

    equivalent_diameter = jnp.sqrt(4.0 * area / jnp.pi)
    form_factor = 4.0 * jnp.pi * area / jnp.maximum(perimeter**2, 1.0)

    z = jnp.zeros_like(area)
    def m(v):
        return jnp.where(present, v, z)

    return {
        "Morphology_area": area,
        "Morphology_centroid_y": m(cy),
        "Morphology_centroid_x": m(cx),
        "Morphology_bbox_height": bbox_h,
        "Morphology_bbox_width": bbox_w,
        "Morphology_extent": m(extent),
        "Morphology_perimeter": perimeter,
        "Morphology_equivalent_diameter": m(equivalent_diameter),
        "Morphology_form_factor": m(form_factor),
        "Morphology_major_axis_length": m(major),
        "Morphology_minor_axis_length": m(minor),
        "Morphology_eccentricity": m(eccentricity),
        "Morphology_orientation": m(orientation),
    }


# -------------------------------------------------------------------- texture
_GLCM_CHUNK = 1 << 13  # pixels per matmul chunk: (chunk, (M+1)*L) one-hot


def _glcm_matmul_all(
    labels: jax.Array,
    quantized: jax.Array,
    max_objects: int,
    levels: int,
    offsets: list[tuple[int, int]],
) -> list[jax.Array]:
    """All directions' GLCMs in ONE chunked contraction.

    The (label, q1) row one-hot is direction-independent once validity is
    moved entirely into the column operand (invalid pairs contribute a
    zero column vector), so the 4 directions share each chunk's expensive
    row one-hot and contract against their column one-hots concatenated
    to (P, 4L) — one wider MXU matmul instead of four, and one pass over
    the pixels instead of four."""
    row = jnp.where(labels > 0, labels * levels + quantized, 0).reshape(-1)
    cols = []
    for dy, dx in offsets:
        lab2 = shift_with_fill(labels, -dy, -dx, 0)
        q2 = shift_with_fill(quantized, -dy, -dx, 0)
        valid = (labels > 0) & (lab2 == labels)
        cols.append(
            (jnp.where(valid, q2, 0).reshape(-1), valid.reshape(-1))
        )

    p = row.shape[0]
    pad = (-p) % _GLCM_CHUNK
    if pad:
        row = jnp.concatenate([row, jnp.zeros((pad,), row.dtype)])
        cols = [
            (
                jnp.concatenate([c, jnp.zeros((pad,), c.dtype)]),
                jnp.concatenate([v, jnp.zeros((pad,), bool)]),
            )
            for c, v in cols
        ]
    n_chunks = row.shape[0] // _GLCM_CHUNK
    row = row.reshape(n_chunks, _GLCM_CHUNK)
    cols = [
        (c.reshape(n_chunks, _GLCM_CHUNK), v.reshape(n_chunks, _GLCM_CHUNK))
        for c, v in cols
    ]
    n_rows = capacity_segments(max_objects) * levels
    k = len(offsets)

    def body(i, acc):
        # bf16 operands are EXACT here (one-hot entries are 0.0/1.0, both
        # representable) and the MXU accumulates into f32 via
        # preferred_element_type, so a single bf16 pass produces the same
        # integer counts as the multi-pass HIGHEST f32 matmul at a
        # fraction of the cost (counts are < 2^24, exact in f32)
        oh_rc = jax.nn.one_hot(row[i], n_rows, dtype=jnp.bfloat16)
        oh_cols = jnp.concatenate(
            [
                jax.nn.one_hot(c[i], levels, dtype=jnp.bfloat16)
                * v[i][:, None].astype(jnp.bfloat16)
                for c, v in cols
            ],
            axis=-1,
        )  # (chunk, k*L)
        return acc + jnp.einsum(
            "pr,pc->rc", oh_rc, oh_cols, preferred_element_type=jnp.float32
        )

    init = jnp.zeros((n_rows, k * levels), jnp.float32)
    counts = jax.lax.fori_loop(0, n_chunks, body, init)
    out = []
    for d in range(k):
        glcm = counts[:, d * levels : (d + 1) * levels].reshape(
            capacity_segments(max_objects), levels, levels
        )[1:]
        out.append(glcm + jnp.swapaxes(glcm, 1, 2))
    return out


def _glcm_scatter(
    labels: jax.Array,
    quantized: jax.Array,
    max_objects: int,
    levels: int,
    offset: tuple[int, int],
) -> jax.Array:
    """GLCM accumulation via one scatter-add per direction over fused
    (label, q1, q2) cell indices: the CPU platform's path (scatters are
    cheap there) and the reference the contraction is compared with."""
    dy, dx = offset
    lab2 = shift_with_fill(labels, -dy, -dx, 0)
    q2 = shift_with_fill(quantized, -dy, -dx, 0)
    valid = (labels > 0) & (lab2 == labels)
    # count into (label, q1, q2) cells
    idx = (
        labels.astype(jnp.int32) * (levels * levels)
        + quantized * levels
        + q2
    )
    idx = jnp.where(valid, idx, 0)
    counts = segmented_sum(
        valid.reshape(-1).astype(jnp.float32),
        idx.reshape(-1),
        capacity_segments(max_objects) * levels * levels,
    )
    glcm = counts.reshape(capacity_segments(max_objects), levels, levels)[1:]
    return glcm + jnp.swapaxes(glcm, 1, 2)


def _resolve_glcm_method(method: str) -> str:
    if method == "onehot":
        return "matmul"
    if method != "auto":
        return method
    # "native" (tm_site_glcm: quantization + all 4 GLCMs in one C pass,
    # bit-identical — counts are exact integers) stays an EXPLICIT opt-in
    # like the channel-sum kernels: auto-routing it stalled XLA-CPU's
    # runtime from batch 16 up regardless of vmap method (batch 8 and the
    # whole existing callback family run fine; the direct C call does the
    # full batch-128 workload in 0.12 s), so the stall is a runtime
    # interaction this release does not ship on by default.
    #
    # Off the CPU the contraction is a constant, not a tuned verdict: on
    # a TPU v5e at one 2160x2160 field and capacity 1024 it takes 81.0 ms
    # against 180 for the scatter (and 161 / 340 for the Pallas kernel
    # and the sorted form that were retired for it) — XLA fuses the
    # one-hot into the dot, 79.9 % of the MXU's peak
    # (scripts/tune_measure_tpu.py, PR 27; tuning/TUNING.json ``glcm_ms``;
    # until PR 29 a ``glcm_matmul_wins`` key there was read back here).
    return "scatter" if jax.default_backend() == "cpu" else "matmul"


def quantize_per_object(
    labels: jax.Array,
    intensity: jax.Array,
    max_objects: int,
    levels: int,
    bounds: tuple[jax.Array, jax.Array] | None = None,
) -> jax.Array:
    """Per-object gray-level stretch to ``[0, levels-1]`` — mahotas
    semantics (``jtlib/features/texture.py`` stretches each object's
    region before ``mahotas.features.haralick``; ``mh.stretch``:
    ``floor((v - min) * (levels-1) / (max - min))``).  Quantizing by the
    *global* image range instead shifts every object's GLCM and breaks
    fidelity (round-1 VERDICT missing item #3)."""
    labels = jnp.asarray(labels, jnp.int32)
    img = jnp.asarray(intensity, jnp.float32)
    # (M,) per-object range; +inf/-inf marks absent.  ``bounds`` lets a
    # caller that already holds grouped_minmax output skip the second full
    # reduction pass over all pixels.
    lo, hi = bounds if bounds is not None else grouped_minmax(
        labels, img, max_objects
    )
    present = hi >= lo
    lo = jnp.where(present, lo, 0.0)
    span = jnp.where(present, hi - lo, 1.0)
    lo_full = jnp.concatenate([jnp.zeros((1,), jnp.float32), lo])
    span_full = jnp.concatenate([jnp.ones((1,), jnp.float32), span])
    per_pix = lookup_by_label(labels, jnp.stack([lo_full, span_full], axis=-1))
    lo_pix = per_pix[..., 0]
    span_pix = jnp.maximum(per_pix[..., 1], 1e-6)
    # a plain TPU division put 6 object pixels in 10,000 a bin too low at
    # 2160x2160 (PERF.md, PR 27), and one such pixel moves an object's 13
    # Haralick features by parts in a thousand; on integer pixels this is
    # floor((v-min)(L-1)/(max-min)) in integer arithmetic
    with jax.named_scope("stretch_exact"):
        q = _floor_div((img - lo_pix) * (levels - 1), span_pix)
    return jnp.clip(q, 0, levels - 1).astype(jnp.int32)


@named("glcm")
def haralick_features(
    labels: jax.Array,
    intensity: jax.Array,
    max_objects: int,
    levels: int = 32,
    distance: int = 1,
    quantization: str = "object",
    glcm_method: str = "auto",
) -> dict[str, jax.Array]:
    """Haralick texture features averaged over the 4 directions
    (reference: mahotas.features.haralick via ``jtlib/features/texture.py``).

    Features: angular second moment, contrast, correlation, sum of squares
    variance, inverse difference moment (homogeneity), sum average, sum
    variance, sum entropy, entropy, difference variance, difference entropy,
    and the two information measures of correlation.

    ``quantization="object"`` (default) stretches each object's own gray
    range into ``levels`` bins, matching the reference's per-object
    ``mh.stretch`` + integer-level GLCM; ``"global"`` keeps the round-1
    whole-image quantization (cheaper: no per-object min/max pass).
    """
    labels = jnp.asarray(labels, jnp.int32)
    img = jnp.asarray(intensity, jnp.float32)
    method = _resolve_glcm_method(glcm_method)
    offsets = [(0, distance), (distance, 0), (distance, distance), (distance, -distance)]
    i_idx = jnp.arange(levels, dtype=jnp.float32)[None, :, None]
    j_idx = jnp.arange(levels, dtype=jnp.float32)[None, None, :]
    eps = 1e-10

    if method == "native" and quantization == "object":
        # quantization + all 4 directions in one C pass (bit-identical:
        # GLCM counts are exact integers, the per-object stretch is the
        # same f32 expression tree) — labels + image are the only
        # operands, both batched under the site vmap
        from tmlibrary_tpu import native

        nd = labels.ndim  # 2 at trace time

        def host(lab, im):
            lead, (labf, imf) = native.align_batch([(lab, nd), (im, nd)])
            out = native.site_glcm_host(
                labf, imf, max_objects, levels, distance
            )
            return out.reshape(lead + out.shape[1:])

        # vmap_method pinned to the SPMD-safe sequential form: the
        # batched expand_dims variant of THIS callback (like
        # morphology's) stalls XLA-CPU's runtime at batch 128 — the
        # callback never returns from materializing its operands, while
        # minimal reproductions with identical shapes/results pass.
        # Sequential still collapses the whole quantize+GLCM chain into
        # one C call per site (~10x the scatter stage).
        packed = jax.pure_callback(
            host,
            jax.ShapeDtypeStruct(
                (4, max_objects, levels, levels), jnp.float32
            ),
            labels, img,
            vmap_method="sequential",
        )
        glcms = [packed[d] for d in range(4)]
    else:
        if method == "native":
            method = "scatter"  # global quantization: no native path
        if quantization == "object":
            q = quantize_per_object(labels, img, max_objects, levels)
        elif quantization == "global":
            lo = jnp.min(img)
            hi = jnp.max(img)
            span = jnp.maximum(hi - lo, 1e-6)
            q = jnp.clip(
                ((img - lo) / span * levels).astype(jnp.int32), 0, levels - 1
            )
        else:
            raise ValueError(f"unknown quantization '{quantization}'")

        if method == "matmul":
            # all 4 directions share each chunk's row one-hot in one pass
            glcms = _glcm_matmul_all(labels, q, max_objects, levels, offsets)
        elif method == "scatter":
            glcms = [
                _glcm_scatter(labels, q, max_objects, levels, off)
                for off in offsets
            ]
        else:
            raise ValueError(f"unknown glcm method '{method}'")

    lv = np.arange(levels)
    sum_sel = jnp.asarray(
        (lv[:, None] + lv[None, :]).reshape(-1, 1)
        == np.arange(2 * levels - 1), jnp.float32)  # (L*L, 2L-1)
    diff_sel = jnp.asarray(
        np.abs(lv[:, None] - lv[None, :]).reshape(-1, 1) == lv,
        jnp.float32)  # (L*L, L)
    acc: dict[str, jax.Array] = {}
    for glcm in glcms:
        total = jnp.maximum(glcm.sum(axis=(1, 2), keepdims=True), eps)
        p = glcm / total  # (M, L, L) normalized

        px = p.sum(axis=2)  # (M, L)
        py = p.sum(axis=1)
        mu_x = (px * i_idx[:, :, 0]).sum(axis=1)
        mu_y = (py * i_idx[:, :, 0]).sum(axis=1)
        sd_x = jnp.sqrt(jnp.maximum((px * (i_idx[:, :, 0] - mu_x[:, None]) ** 2).sum(axis=1), 0.0))
        sd_y = jnp.sqrt(jnp.maximum((py * (i_idx[:, :, 0] - mu_y[:, None]) ** 2).sum(axis=1), 0.0))

        asm = (p**2).sum(axis=(1, 2))
        contrast = (p * (i_idx - j_idx) ** 2).sum(axis=(1, 2))
        corr_num = (p * (i_idx - mu_x[:, None, None]) * (j_idx - mu_y[:, None, None])).sum(axis=(1, 2))
        correlation = corr_num / jnp.maximum(sd_x * sd_y, eps)
        variance = (p * (i_idx - mu_x[:, None, None]) ** 2).sum(axis=(1, 2))
        idm = (p / (1.0 + (i_idx - j_idx) ** 2)).sum(axis=(1, 2))
        entropy = -(p * jnp.log(p + eps)).sum(axis=(1, 2))

        # p_{x+y}(k), k = i+j in [0, 2L-2]; p_{x-y}(k), k = |i-j| in [0, L-1]
        # as contractions with constant 0/1 matrices: a segment_sum under
        # vmap is a scatter-add of capacity*L*L updates a direction, and
        # scatter-adds serialise on a TPU
        k_sum = jnp.arange(2 * levels - 1, dtype=jnp.float32)
        p_flat = p.reshape(max_objects, -1)
        p_sum = jnp.einsum("mc,ck->mk", p_flat, sum_sel,
                           precision=jax.lax.Precision.HIGHEST)
        p_diff = jnp.einsum("mc,ck->mk", p_flat, diff_sel,
                            precision=jax.lax.Precision.HIGHEST)

        sum_avg = (p_sum * k_sum).sum(axis=1)
        sum_entropy = -(p_sum * jnp.log(p_sum + eps)).sum(axis=1)
        sum_var = (p_sum * (k_sum - sum_entropy[:, None]) ** 2).sum(axis=1)  # Haralick's defn
        k_diff = jnp.arange(levels, dtype=jnp.float32)
        diff_avg = (p_diff * k_diff).sum(axis=1)
        diff_var = (p_diff * (k_diff - diff_avg[:, None]) ** 2).sum(axis=1)
        diff_entropy = -(p_diff * jnp.log(p_diff + eps)).sum(axis=1)

        hx = -(px * jnp.log(px + eps)).sum(axis=1)
        hy = -(py * jnp.log(py + eps)).sum(axis=1)
        pxpy = px[:, :, None] * py[:, None, :]
        hxy1 = -(p * jnp.log(pxpy + eps)).sum(axis=(1, 2))
        hxy2 = -(pxpy * jnp.log(pxpy + eps)).sum(axis=(1, 2))
        imc1 = (entropy - hxy1) / jnp.maximum(jnp.maximum(hx, hy), eps)
        imc2 = jnp.sqrt(jnp.clip(1.0 - jnp.exp(-2.0 * (hxy2 - entropy)), 0.0, 1.0))

        feats = {
            "Texture_angular_second_moment": asm,
            "Texture_contrast": contrast,
            "Texture_correlation": correlation,
            "Texture_sum_of_squares_variance": variance,
            "Texture_inverse_difference_moment": idm,
            "Texture_sum_average": sum_avg,
            "Texture_sum_variance": sum_var,
            "Texture_sum_entropy": sum_entropy,
            "Texture_entropy": entropy,
            "Texture_difference_variance": diff_var,
            "Texture_difference_entropy": diff_entropy,
            "Texture_info_measure_corr_1": imc1,
            "Texture_info_measure_corr_2": imc2,
        }
        for k, v in feats.items():
            acc[k] = acc.get(k, 0.0) + v / len(offsets)
    return acc


# -------------------------------------------------------------------- zernike
def _zernike_coeffs(degree: int) -> list[tuple[int, int, np.ndarray]]:
    """Static (n, m, radial-coefficient) table for n<=degree, m>=0,
    (n-m) even.  Coefficient k applies to rho^(n-2k)."""
    out = []
    for n in range(degree + 1):
        for m_ in range(n % 2, n + 1, 2):
            coeffs = np.zeros((n - m_) // 2 + 1)
            for k in range((n - m_) // 2 + 1):
                coeffs[k] = (
                    (-1) ** k
                    * math.factorial(n - k)
                    / (
                        math.factorial(k)
                        * math.factorial((n + m_) // 2 - k)
                        * math.factorial((n - m_) // 2 - k)
                    )
                )
            out.append((n, m_, coeffs))
    return out


def _host_ok() -> bool:
    """Shared gate with the native segmentation path (TMX_NATIVE=0 turns
    every cpu-fallback host routing off at once)."""
    from tmlibrary_tpu.native import tmx_native_env_enabled

    return tmx_native_env_enabled()


def _zernike_host(labels: "np.ndarray", max_objects: int, degree: int) -> "np.ndarray":
    """Host twin of the device Zernike projection, restricted to the
    object pixels (the XLA path evaluates the whole basis over EVERY
    image pixel — fine on TPU where it is fused VPU work, but it
    dominated the CPU-fallback full-feature bench at ~31 ms/site for
    typically ~10% foreground).  Same math, numpy, fg pixels only.
    Returns (max_objects, n_table) float32 magnitudes."""
    labels = np.asarray(labels)
    table = _zernike_coeffs(degree)
    out = np.zeros((max_objects, len(table)), np.float32)
    area = np.bincount(
        labels.ravel(), minlength=max_objects + 1
    )[1:max_objects + 1].astype(np.float64)
    ys, xs = np.nonzero(labels)
    lab = labels[ys, xs]
    keep = lab <= max_objects
    ys, xs, lab = ys[keep], xs[keep], lab[keep]
    if len(lab) == 0:
        return out
    safe_a = np.maximum(area, 1.0)
    cy = np.bincount(lab, weights=ys, minlength=max_objects + 1)[1:] / safe_a
    cx = np.bincount(lab, weights=xs, minlength=max_objects + 1)[1:] / safe_a
    dy = ys - cy[lab - 1]
    dx = xs - cx[lab - 1]
    r2 = dy * dy + dx * dx
    r2_max = np.zeros(max_objects, np.float64)
    np.maximum.at(r2_max, lab - 1, r2)
    r_obj = np.sqrt(np.maximum(np.where(area > 0, r2_max, 1.0), 1.0))
    rho = np.sqrt(r2) / r_obj[lab - 1]
    theta = np.arctan2(dy, dx)
    ok = (rho <= 1.0).astype(np.float64)  # fp-rounding guard, like the XLA path
    rho_pow = [np.ones_like(rho)]
    for _ in range(degree):
        rho_pow.append(rho_pow[-1] * rho)
    cos_m = [np.ones_like(theta)]
    sin_m = [np.zeros_like(theta)]
    for m_ in range(1, degree + 1):
        cos_m.append(np.cos(m_ * theta))
        sin_m.append(np.sin(m_ * theta))
    for idx, (n, m_, coeffs) in enumerate(table):
        radial = np.zeros_like(rho)
        for k, c in enumerate(coeffs):
            radial = radial + float(c) * rho_pow[n - 2 * k]
        base = radial * ok
        re = np.bincount(
            lab, weights=base * cos_m[m_], minlength=max_objects + 1
        )[1:]
        im = np.bincount(
            lab, weights=base * sin_m[m_], minlength=max_objects + 1
        )[1:]
        mag = np.sqrt(re * re + im * im) * (n + 1) / np.pi / safe_a
        out[:, idx] = np.where(area > 0, mag, 0.0)
    return out


def zernike_host_features(
    labels: "np.ndarray", count: int, degree: int = 9, row_block: int = 512
) -> "np.ndarray":
    """PUBLIC ragged host Zernike for dynamic object counts (the spatial
    mosaic path): same math and normalization as :func:`_zernike_host`,
    but processed in row blocks so transient memory stays
    O(row_block * W + count) next to a plate-scale mosaic instead of
    materializing every foreground pixel's polar tables at once.
    Returns ``(count, n_table)`` float32 magnitudes in
    :func:`_zernike_coeffs` order."""
    labels = np.asarray(labels)
    table = _zernike_coeffs(degree)
    out = np.zeros((count, len(table)), np.float32)
    if count == 0:
        return out
    h, w = labels.shape
    colf = np.arange(w, dtype=np.float64)

    # pass 1: area + centroids
    area = np.zeros(count + 1)
    ysum = np.zeros(count + 1)
    xsum = np.zeros(count + 1)
    for y0 in range(0, h, row_block):
        blk = labels[y0:y0 + row_block]
        flat = blk.ravel()
        area += np.bincount(flat, minlength=count + 1)
        rows = np.repeat(
            np.arange(y0, y0 + blk.shape[0], dtype=np.float64), w
        )
        xsum += np.bincount(flat, weights=np.tile(colf, blk.shape[0]),
                            minlength=count + 1)
        ysum += np.bincount(flat, weights=rows, minlength=count + 1)
    safe_a = np.maximum(area[1:], 1.0)
    cy = np.concatenate([[0.0], ysum[1:] / safe_a])
    cx = np.concatenate([[0.0], xsum[1:] / safe_a])

    # pass 2: per-object max radius
    r2_max = np.zeros(count + 1)
    for y0 in range(0, h, row_block):
        blk = labels[y0:y0 + row_block]
        ys, xs = np.nonzero(blk)
        if not len(ys):
            continue
        lab = blk[ys, xs]
        dy = (ys + y0) - cy[lab]
        dx = xs - cx[lab]
        np.maximum.at(r2_max, lab, dy * dy + dx * dx)
    r_obj = np.concatenate([
        [1.0],
        np.sqrt(np.maximum(np.where(area[1:] > 0, r2_max[1:], 1.0), 1.0)),
    ])

    # pass 3: basis projections
    re_acc = np.zeros((len(table), count + 1))
    im_acc = np.zeros((len(table), count + 1))
    for y0 in range(0, h, row_block):
        blk = labels[y0:y0 + row_block]
        ys, xs = np.nonzero(blk)
        if not len(ys):
            continue
        lab = blk[ys, xs]
        dy = (ys + y0) - cy[lab]
        dx = xs - cx[lab]
        r2 = dy * dy + dx * dx
        rho = np.sqrt(r2) / r_obj[lab]
        theta = np.arctan2(dy, dx)
        ok = (rho <= 1.0).astype(np.float64)
        rho_pow = [np.ones_like(rho)]
        for _ in range(degree):
            rho_pow.append(rho_pow[-1] * rho)
        cos_m = [np.ones_like(theta)]
        sin_m = [np.zeros_like(theta)]
        for m_ in range(1, degree + 1):
            cos_m.append(np.cos(m_ * theta))
            sin_m.append(np.sin(m_ * theta))
        for idx, (n, m_, coeffs) in enumerate(table):
            radial = np.zeros_like(rho)
            for k, c in enumerate(coeffs):
                radial = radial + float(c) * rho_pow[n - 2 * k]
            base = radial * ok
            re_acc[idx] += np.bincount(
                lab, weights=base * cos_m[m_], minlength=count + 1
            )
            im_acc[idx] += np.bincount(
                lab, weights=base * sin_m[m_], minlength=count + 1
            )
    for idx, (n, m_, _) in enumerate(table):
        mag = (
            np.sqrt(re_acc[idx, 1:] ** 2 + im_acc[idx, 1:] ** 2)
            * (n + 1) / np.pi / safe_a
        )
        out[:, idx] = np.where(area[1:] > 0, mag, 0.0)
    return out


@named("zernike")
def zernike_features(
    labels: jax.Array,
    max_objects: int,
    degree: int = 9,
    patch: int | None = None,
    method: str = "auto",
) -> dict[str, jax.Array]:
    """Zernike moment magnitudes |Z_nm| per object
    (reference: ``jtlib/features/zernike.py`` via centrosome/mahotas:
    binary mask mapped onto the unit disk at the object's own radius,
    projected on the Zernike basis, mass-normalized, ``*(n+1)/pi``).

    TPU design: patch-free.  Every pixel carries its OWN object's
    unit-disk coordinates via label-indexed centroid/radius lookups, the
    radial polynomials and angular harmonics are evaluated once per pixel
    (pure VPU elementwise work), and all (n, m) projections reduce in a
    single :func:`grouped_sums` MXU pass — ~60 channels at degree 9.
    This removes the round-1 static 64-px patch and its silent cropping
    of over-size objects (VERDICT weak item #5): exact at any object
    size, no dynamic-slice gathers.

    ``patch`` is accepted for backward compatibility and ignored.
    ``method="auto"`` routes to the foreground-only host twin
    (:func:`_zernike_host`) on the cpu backend — same dispatch gate as
    the native segmentation kernels (``TMX_NATIVE=0`` forces xla); the
    host path agrees within float tolerance (it sums per-object in f64,
    the device path in f32), which the golden tests' 2e-3 rtol covers.
    """
    del patch  # patch-free since round 2; kept for YAML/handle compat
    labels = jnp.asarray(labels, jnp.int32)
    h, w = labels.shape

    if method == "auto":
        method = "host" if jax.default_backend() == "cpu" and _host_ok() else "xla"
    if method == "host":
        table = _zernike_coeffs(degree)
        from tmlibrary_tpu import native

        proj = jax.pure_callback(
            native.batch_sites(2)(
                lambda lb: _zernike_host(lb, max_objects, degree)
            ),
            jax.ShapeDtypeStruct((max_objects, len(table)), jnp.float32),
            labels,
            vmap_method=native.callback_vmap_method(),
        )
        return {
            f"Zernike_{n}_{m_}": proj[:, idx]
            for idx, (n, m_, _) in enumerate(table)
        }
    yy, xx = jnp.meshgrid(
        jnp.arange(h, dtype=jnp.float32), jnp.arange(w, dtype=jnp.float32), indexing="ij"
    )
    ones = jnp.ones((h, w), jnp.float32)
    sums = grouped_sums(labels, [ones, yy, xx], max_objects)
    area = sums[:, 0]
    safe_a = jnp.maximum(area, 1.0)
    # per-pixel offset from the centroid of the pixel's own object
    _, _, dy, dx = _centred_coordinates(
        labels, yy, xx, area, sums[:, 1], sums[:, 2]
    )
    r2 = dy * dy + dx * dx
    _, r2_max = grouped_minmax(labels, r2, max_objects)
    r_obj = jnp.sqrt(jnp.maximum(jnp.where(area > 0, r2_max, 1.0), 1.0))
    r_pix = lookup_by_label(
        labels, jnp.concatenate([jnp.ones((1,), jnp.float32), r_obj])[:, None]
    )[..., 0]

    # rho > 1 is impossible by construction (r_pix IS each object's max
    # radius), but TPU lowers x/y to x*(1/y) with a reciprocal approx
    # that can land one ulp above 1.0 at the extremal-radius pixel —
    # dropping it there shifted Zernike_6_0 of a 177-px object by 9%
    # (rim pixels carry R_n0(1)=1, the max radial weight).  Clamp
    # instead of masking so the rim pixel contributes at rho=1 exactly,
    # matching the f64 host twin.
    rho = jnp.minimum(jnp.sqrt(r2) / r_pix, 1.0)
    theta = jnp.arctan2(dy, dx)
    fgf = (labels > 0).astype(jnp.float32)

    # shared power/harmonic tables, evaluated once per pixel
    rho_pow = [jnp.ones_like(rho)]
    for _ in range(degree):
        rho_pow.append(rho_pow[-1] * rho)
    cos_m = [jnp.ones_like(theta)]
    sin_m = [jnp.zeros_like(theta)]
    for m_ in range(1, degree + 1):
        cos_m.append(jnp.cos(m_ * theta))
        sin_m.append(jnp.sin(m_ * theta))

    table = _zernike_coeffs(degree)
    chans: list[jax.Array] = []
    for n, m_, coeffs in table:
        radial = jnp.zeros_like(rho)
        for k, c in enumerate(coeffs):
            radial = radial + float(c) * rho_pow[n - 2 * k]
        chans.append(radial * cos_m[m_] * fgf)
        chans.append(radial * sin_m[m_] * fgf)

    proj = grouped_sums(labels, chans, max_objects)  # (M, 2K)
    out: dict[str, jax.Array] = {}
    for idx, (n, m_, _) in enumerate(table):
        re = proj[:, 2 * idx]
        im = proj[:, 2 * idx + 1]
        mag = jnp.sqrt(re**2 + im**2) * (n + 1) / jnp.pi / safe_a
        out[f"Zernike_{n}_{m_}"] = jnp.where(area > 0, mag, 0.0)
    return out


# -------------------------------------------------------------- point pattern
def point_pattern_features(
    parent_labels: jax.Array,
    point_labels: jax.Array,
    max_parents: int,
    max_points: int,
) -> dict[str, jax.Array]:
    """Spatial point-pattern statistics of child "point" objects (e.g.
    spots/speckles) within parent objects.

    Reference parity: ``jtlib/features/point_pattern.py`` (SURVEY.md §3
    jtlibrary row) — per parent: point count and density, nearest-neighbor
    distance statistics among the parent's points, the Clark–Evans
    aggregation index (observed mean NN distance over the expectation
    ``0.5/sqrt(density)`` for complete spatial randomness), distances from
    points to the parent centroid, and distances to the parent border.

    TPU design: points are reduced to centroids once (one ``grouped_sums``
    MXU pass over the point label image), then every statistic is computed
    on the fixed ``(max_points,)`` axis — the all-pairs distance matrix is
    a dense ``(max_points, max_points)`` op and per-parent aggregation is a
    masked broadcast over ``(max_points, max_parents)``, both tiny and
    tiling-friendly.  Border distance is the exact Euclidean distance from
    each point centroid to the nearest label-boundary pixel: a masked min
    over image pixels, chunked so the ``(max_points, chunk)`` tile stays
    bounded under the site-batch vmap (same metric as the NN/centroid
    distances; no chamfer approximation, no distance cap).  Everything
    jit/vmap-safe; rows for absent parents are zero.
    """
    parents = jnp.asarray(parent_labels, jnp.int32)
    points = jnp.asarray(point_labels, jnp.int32)
    h, w = parents.shape
    yy, xx = jnp.meshgrid(
        jnp.arange(h, dtype=jnp.float32),
        jnp.arange(w, dtype=jnp.float32),
        indexing="ij",
    )
    ones = jnp.ones((h, w), jnp.float32)

    # ---- point centroids + parent centroids/areas (two MXU passes)
    psums = grouped_sums(points, [ones, yy, xx], max_points)  # (P, 3)
    p_n = psums[:, 0]
    p_present = p_n > 0
    safe_pn = jnp.maximum(p_n, 1.0)
    py = psums[:, 1] / safe_pn
    px = psums[:, 2] / safe_pn

    gsums = grouped_sums(parents, [ones, yy, xx], max_parents)  # (M, 3)
    area = gsums[:, 0]
    safe_a = jnp.maximum(area, 1.0)
    g_cy = gsums[:, 1] / safe_a
    g_cx = gsums[:, 2] / safe_a
    parent_present = area > 0

    # ---- assign each point to the parent under its centroid pixel
    iy = jnp.clip(jnp.round(py).astype(jnp.int32), 0, h - 1)
    ix = jnp.clip(jnp.round(px).astype(jnp.int32), 0, w - 1)
    owner = jnp.where(p_present, parents[iy, ix], 0)  # (P,) 0 = unassigned

    # ---- nearest-neighbor distance among same-parent points
    dy = py[:, None] - py[None, :]
    dx = px[:, None] - px[None, :]
    d2 = dy * dy + dx * dx  # (P, P)
    # owner is already 0 for absent points, so owner > 0 implies presence
    pair_ok = (
        (owner[:, None] == owner[None, :])
        & (owner[:, None] > 0)
        & ~jnp.eye(max_points, dtype=bool)
    )
    BIG = jnp.float32(jnp.inf)
    nn = jnp.sqrt(jnp.min(jnp.where(pair_ok, d2, BIG), axis=1))  # (P,)
    has_nn = jnp.isfinite(nn)
    nn = jnp.where(has_nn, nn, 0.0)

    # ---- distance from each point to its parent's centroid
    oc_y = g_cy[jnp.clip(owner - 1, 0, max_parents - 1)]
    oc_x = g_cx[jnp.clip(owner - 1, 0, max_parents - 1)]
    cdist = jnp.sqrt((py - oc_y) ** 2 + (px - oc_x) ** 2)

    # ---- exact Euclidean distance from each point to the nearest
    # label-boundary pixel: masked min over pixels, chunked over the image
    boundary = jnp.zeros((h, w), bool)
    for sy, sx in ((-1, 0), (1, 0), (0, -1), (0, 1)):
        boundary = boundary | (shift_with_fill(parents, sy, sx, -1) != parents)
    b_flat = boundary.reshape(-1)
    y_flat = yy.reshape(-1)
    x_flat = xx.reshape(-1)
    n_pix = h * w
    pad = (-n_pix) % _GLCM_CHUNK
    if pad:  # padded pixels are non-boundary -> masked to +inf below
        b_flat = jnp.concatenate([b_flat, jnp.zeros((pad,), bool)])
        y_flat = jnp.concatenate([y_flat, jnp.zeros((pad,), jnp.float32)])
        x_flat = jnp.concatenate([x_flat, jnp.zeros((pad,), jnp.float32)])
    n_chunks = b_flat.shape[0] // _GLCM_CHUNK
    b_flat = b_flat.reshape(n_chunks, _GLCM_CHUNK)
    y_flat = y_flat.reshape(n_chunks, _GLCM_CHUNK)
    x_flat = x_flat.reshape(n_chunks, _GLCM_CHUNK)

    def bd_body(i, best):
        d2b = (py[:, None] - y_flat[i][None, :]) ** 2 + (
            px[:, None] - x_flat[i][None, :]
        ) ** 2
        d2b = jnp.where(b_flat[i][None, :], d2b, BIG)
        return jnp.minimum(best, jnp.min(d2b, axis=1))

    bdist = jnp.sqrt(
        jax.lax.fori_loop(
            0, n_chunks, bd_body, jnp.full((max_points,), BIG, jnp.float32)
        )
    )

    # ---- per-parent aggregation: masked broadcast over (P, M)
    assign = owner[:, None] == jnp.arange(1, max_parents + 1)[None, :]  # (P, M)

    def _agg(vals, valid):
        sel = assign & valid[:, None]
        n = jnp.sum(sel, axis=0).astype(jnp.float32)
        s = jnp.sum(jnp.where(sel, vals[:, None], 0.0), axis=0)
        sq = jnp.sum(jnp.where(sel, (vals * vals)[:, None], 0.0), axis=0)
        mean = s / jnp.maximum(n, 1.0)
        var = jnp.maximum(sq / jnp.maximum(n, 1.0) - mean * mean, 0.0)
        return n, mean, jnp.sqrt(var)

    n_pts = jnp.sum(assign, axis=0).astype(jnp.float32)
    n_nn, nn_mean, nn_std = _agg(nn, has_nn)
    _, cd_mean, cd_std = _agg(cdist, p_present)
    _, bd_mean, bd_std = _agg(bdist, p_present)

    density = n_pts / safe_a
    # Clark–Evans: observed mean NN distance / E[NN] under CSR
    expected_nn = 0.5 / jnp.sqrt(jnp.maximum(density, 1e-12))
    clark_evans = jnp.where(n_nn > 0, nn_mean / expected_nn, 0.0)

    z = jnp.zeros_like(area)

    def m(v):
        return jnp.where(parent_present, v, z)

    return {
        "PointPattern_count": m(n_pts),
        "PointPattern_density": m(density),
        "PointPattern_nn_dist_mean": m(nn_mean),
        "PointPattern_nn_dist_std": m(nn_std),
        "PointPattern_clark_evans": m(clark_evans),
        "PointPattern_centroid_dist_mean": m(cd_mean),
        "PointPattern_centroid_dist_std": m(cd_std),
        "PointPattern_border_dist_mean": m(bd_mean),
        "PointPattern_border_dist_std": m(bd_std),
    }
