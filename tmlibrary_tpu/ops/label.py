"""Connected-component labeling and binary morphology on TPU.

Reference parity: ``jtmodules/label.py`` (mahotas/scipy connected components),
``jtmodules/fill.py`` (binary hole filling), ``jtmodules/filter.py``
(filter objects by feature) — all native-library calls in the reference.

TPU design (SURVEY.md §8 "hard parts" #1): labeling iterates {diagonal
neighbor min-propagation, row run-scan, column run-scan} inside
``lax.while_loop`` — each pixel carries the minimum linear index seen in
its component, and the segmented run scans (``_run_min_scan``)
move labels across entire straight runs per iteration with **no gathers**
(TPU's slow path).  Convergence is ~O(turns of the most serpentine
component): a handful of iterations for blob-like microscopy objects.
All shapes static; ``vmap``-safe.

Label order is **bit-identical to ``scipy.ndimage.label``**: the converged
label of a component is its minimum linear index (= first pixel in row-major
scan order), and compaction ranks roots by that index — exactly scipy's
assignment order.  This is the acceptance gate from BASELINE.json
("bit-identical object counts").
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from tmlibrary_tpu.ops import named

_BIG = jnp.iinfo(jnp.int32).max


def _neighbor_shifts(connectivity: int) -> list[tuple[int, int]]:
    if connectivity == 4:
        return [(-1, 0), (1, 0), (0, -1), (0, 1)]
    if connectivity == 8:
        return [
            (-1, -1), (-1, 0), (-1, 1),
            (0, -1), (0, 1),
            (1, -1), (1, 0), (1, 1),
        ]
    raise ValueError("connectivity must be 4 or 8")


def shift_with_fill(arr: jax.Array, dy: int, dx: int, fill) -> jax.Array:
    """``out[y, x] = arr[y + dy, x + dx]`` with ``fill`` at exposed borders
    (the neighborhood-access primitive shared by labeling, morphology and
    the GLCM ops)."""
    h, w = arr.shape
    padded = jnp.pad(arr, ((1, 1), (1, 1)), constant_values=fill)
    return lax.dynamic_slice(padded, (1 + dy, 1 + dx), (h, w))


# backward-compat private alias (internal call sites predate the rename)
_shift_with_fill = shift_with_fill


def _propagate_min(labels: jax.Array, mask: jax.Array, shifts) -> jax.Array:
    out = labels
    for dy, dx in shifts:
        neigh = _shift_with_fill(labels, dy, dx, _BIG)
        out = jnp.minimum(out, neigh)
    return jnp.where(mask, out, _BIG)


def _shift_along(x: jax.Array, d: int, axis: int, fill) -> jax.Array:
    """``out[i] = x[i - d]`` along ``axis`` (``d`` of either sign), ``fill``
    where the shift exposes the edge."""
    n = x.shape[axis]
    pad = [(0, 0)] * x.ndim
    pad[axis] = (max(d, 0), max(-d, 0))
    return lax.slice_in_dim(
        jnp.pad(x, pad, constant_values=fill), max(-d, 0), max(-d, 0) + n,
        axis=axis,
    )


def _run_min_scan(labels: jax.Array, mask: jax.Array, axis: int) -> jax.Array:
    """Give every foreground pixel the min label of its contiguous run
    along ``axis``: a segmented min-scan in each direction by shift
    doubling (log2 N whole-array steps of shift + min + select; no
    gathers, no strided slices), then the min of the two.

    ``lax.associative_scan`` computes the same thing in less arithmetic,
    but its even/odd recursion costs the TPU compiler minutes wherever
    the scanned axis is the minor one, and a fixpoint that scans rows AND
    columns always has one such: 15 s at 2160x2160, 112 s for a 540x2160
    shard and no end after 900 s for a 1080x4320 one, against 7-8 s for
    this form at every one of those shapes (described v5e; PERF.md,
    PR 21)."""
    n = labels.shape[axis]
    bg = ~mask
    v = jnp.where(mask, labels, _BIG)
    # a pixel stops taking from behind it once its window holds a run
    # boundary; background is its own segment, so nothing crosses it
    fwd, fwd_stop = v, bg | _shift_along(bg, 1, axis, True)
    bwd, bwd_stop = v, bg | _shift_along(bg, -1, axis, True)
    d = 1
    while d < n:
        fwd = jnp.where(
            fwd_stop, fwd, jnp.minimum(fwd, _shift_along(fwd, d, axis, _BIG)))
        fwd_stop = fwd_stop | _shift_along(fwd_stop, d, axis, True)
        bwd = jnp.where(
            bwd_stop, bwd, jnp.minimum(bwd, _shift_along(bwd, -d, axis, _BIG)))
        bwd_stop = bwd_stop | _shift_along(bwd_stop, -d, axis, True)
        d *= 2
    return jnp.where(mask, jnp.minimum(fwd, bwd), _BIG)


def _row_major_ranks(flags: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Inclusive running count of ``flags`` in row-major order, flat, and
    the total.  Computed as a cumsum along each row plus the exclusive
    cumsum of the row totals: the same integers as one flat cumsum,
    which costs the TPU compiler 24 s per megapixel (PERF.md, PR 21)."""
    flags = flags.astype(jnp.int32)
    in_row = jnp.cumsum(flags, axis=1)
    row_totals = in_row[:, -1]
    before = jnp.cumsum(row_totals) - row_totals
    return (in_row + before[:, None]).reshape(-1), jnp.sum(row_totals)


@named("label")
def connected_components(
    mask: jax.Array, connectivity: int = 8, method: str = "auto",
    chunk: "int | None" = None,
) -> tuple[jax.Array, jax.Array]:
    """Label connected foreground components.

    Returns ``(labels, count)``: int32 label image (0 = background, 1..N in
    scipy scan order) and the scalar component count.

    ``method``: ``"xla"`` iterates {8/4-neighbor min propagation, row
    run-scan, column run-scan} to a fixed point — the run scans move labels
    across entire straight runs per iteration, so convergence is ~O(turns
    of the most serpentine component) with no per-pixel gathers.
    ``"pallas"`` runs the same fixpoint entirely in VMEM
    (:func:`~tmlibrary_tpu.ops.pallas_kernels.cc_min_propagate`) — O(1)
    HBM traffic.  ``"native"`` calls the first-party C++ union-find
    (``native/tmnative.cpp`` ``tm_cc_label``, scipy scan order) via
    ``jax.pure_callback`` — the fast path when the whole pipeline runs on
    the CPU backend, where the while-loop fixpoint is pathological.

    ``"auto"`` resolution order (pinned): native on the cpu backend when
    the library is available and ``TMX_NATIVE`` isn't 0 → pallas on TPU
    per ``pallas_kernels.pallas_enabled("cc")`` (the measured per-kernel
    shootout; on v5e the VMEM fixpoint wins ~2.1x) → xla.  All three
    produce the identical scipy-scan-order labeling.
    """
    mask = jnp.asarray(mask, bool)
    h, w = mask.shape
    if connectivity not in (4, 8):
        raise ValueError("connectivity must be 4 or 8")
    linear = jnp.arange(h * w, dtype=jnp.int32).reshape(h, w)

    if method == "auto":
        from tmlibrary_tpu import native
        from tmlibrary_tpu.ops.pallas_kernels import pallas_enabled

        if native.cpu_native_enabled():
            method = "native"
        else:
            method = (
                "pallas" if pallas_enabled("cc", mask.shape) else "xla"
            )
    if method == "native":
        import numpy as np

        from tmlibrary_tpu import native

        @native.batch_sites(2)
        def _cc_host(m):
            labels, count = native.cc_label_host(np.asarray(m), connectivity)
            return labels, np.int32(count)

        return jax.pure_callback(
            _cc_host,
            (
                jax.ShapeDtypeStruct((h, w), jnp.int32),
                jax.ShapeDtypeStruct((), jnp.int32),
            ),
            mask,
            vmap_method=native.callback_vmap_method(),
        )
    if method == "pallas":
        from tmlibrary_tpu.ops.pallas_kernels import cc_min_propagate

        # interpret mode keeps the pallas path testable off-TPU; chunk
        # (convergence-check interval, output-invariant) defaults to the
        # committed hardware sweep inside cc_min_propagate
        labels = cc_min_propagate(
            mask, connectivity, interpret=jax.default_backend() == "cpu",
            chunk=chunk,
        )
        labels = jnp.where(mask, labels, _BIG)
    else:
        # row+col run scans fully cover 4-neighbor propagation
        shifts = [] if connectivity == 4 else [(-1, -1), (-1, 1), (1, -1), (1, 1)]
        init = jnp.where(mask, linear, _BIG)

        def cond(state):
            labels, prev_changed = state
            return prev_changed

        def body(state):
            labels, _ = state
            new = _propagate_min(labels, mask, shifts) if shifts else labels
            new = _run_min_scan(new, mask, axis=1)
            new = _run_min_scan(new, mask, axis=0)
            changed = jnp.any(new != labels)
            return new, changed

        labels, _ = lax.while_loop(cond, body, (init, jnp.bool_(True)))

    # compact to 1..N in row-major order of component roots (scipy order)
    is_root = mask & (labels == linear)
    ranks, count = _row_major_ranks(is_root)
    root_rank = ranks.reshape(-1)[jnp.clip(labels.reshape(-1), 0, h * w - 1)]
    out = jnp.where(mask, root_rank.reshape(h, w), 0).astype(jnp.int32)
    return out, count


def label(mask: jax.Array, connectivity: int = 8) -> jax.Array:
    """Label image only (reference ``jtmodules/label.main``)."""
    return connected_components(mask, connectivity)[0]


# ------------------------------------------------------------ binary morphology
def binary_dilate(mask: jax.Array, connectivity: int = 8, iterations: int = 1) -> jax.Array:
    mask = jnp.asarray(mask, bool)
    shifts = _neighbor_shifts(connectivity)
    for _ in range(iterations):
        out = mask
        for dy, dx in shifts:
            out = out | _shift_with_fill(mask, dy, dx, False)
        mask = out
    return mask


def binary_erode(mask: jax.Array, connectivity: int = 8, iterations: int = 1) -> jax.Array:
    mask = jnp.asarray(mask, bool)
    shifts = _neighbor_shifts(connectivity)
    for _ in range(iterations):
        out = mask
        for dy, dx in shifts:
            out = out & _shift_with_fill(mask, dy, dx, True)
        mask = out
    return mask


@named("fill_holes")
def fill_holes(
    mask: jax.Array, connectivity: int = 4, method: str = "auto"
) -> jax.Array:
    """Fill background holes (reference ``jtmodules/fill.main``,
    scipy ``binary_fill_holes`` semantics: background connectivity is the
    complement of the foreground's — holes are 4-connected background regions
    not reachable from the border).

    ``method="auto"`` routes to the native border-BFS
    (``tm_fill_holes``) on the cpu backend (see
    :func:`~tmlibrary_tpu.native.cpu_native_enabled`), the VMEM pallas
    flood on TPU when the committed shootout says it wins
    (``pallas_enabled("fill")``), the XLA flood otherwise.
    """
    mask = jnp.asarray(mask, bool)
    h, w = mask.shape
    if method == "auto":
        from tmlibrary_tpu import native

        if native.cpu_native_enabled():
            method = "native"
        else:
            from tmlibrary_tpu.ops.pallas_kernels import pallas_enabled

            method = (
                "pallas" if pallas_enabled("fill", mask.shape) else "xla"
            )
    if method == "pallas":
        from tmlibrary_tpu.ops.pallas_kernels import fill_holes_flood

        return fill_holes_flood(
            mask, connectivity, interpret=jax.default_backend() == "cpu"
        )
    if method == "native":
        import numpy as np

        from tmlibrary_tpu import native

        return jax.pure_callback(
            native.batch_sites(2)(
                lambda m: native.fill_holes_host(np.asarray(m), connectivity)
            ),
            jax.ShapeDtypeStruct((h, w), jnp.bool_),
            mask,
            vmap_method=native.callback_vmap_method(),
        )
    bg = ~mask
    border = jnp.zeros_like(mask).at[0, :].set(True).at[-1, :].set(True)
    border = border.at[:, 0].set(True).at[:, -1].set(True)
    seed = bg & border
    if connectivity not in (4, 8):
        raise ValueError("connectivity must be 4 or 8")

    # diagonal steps are only relevant at 8-connectivity; the run scans
    # below fully cover horizontal/vertical propagation
    diag = [] if connectivity == 4 else [(-1, -1), (-1, 1), (1, -1), (1, 1)]

    # The flood is connected_components' own fixpoint on a two-valued
    # label image (0 = reached from the border, 1 = not yet; run min 0
    # means the whole background run is reached), carried as int32 with
    # the same body.  That is also what the TPU compiler accepts at a
    # full field: the former bool carry with its int32 round trip between
    # the two scans took 94 s to compile at 1024x1024 against 9 s for
    # this form, and did not finish at 2160x2160, where this one takes
    # 11 s (described v5e; PERF.md, PR 21).
    def body(state):
        v, _ = state
        new = _propagate_min(v, bg, diag) if diag else v
        new = _run_min_scan(new, bg, axis=1)
        new = _run_min_scan(new, bg, axis=0)
        return new, jnp.any(new != v)

    init = jnp.where(bg, jnp.where(seed, 0, 1), _BIG).astype(jnp.int32)
    v, _ = lax.while_loop(lambda s: s[1], body, (init, jnp.bool_(True)))
    reach = v == 0
    return mask | (bg & ~reach)


# ------------------------------------------------------------------ filtering
_REDUCE_CHUNK = 1 << 16  # pixels per compare-broadcast chunk (bounds HBM)


def _chunked_pixels(flat: jax.Array) -> jax.Array:
    """Pad ``flat`` with label-0 pixels to a multiple of ``_REDUCE_CHUNK``
    and reshape to (n_chunks, chunk) so broadcast reductions stay bounded
    under the site-batch vmap (matches ``measure.grouped_sums``)."""
    pad = (-flat.shape[0]) % _REDUCE_CHUNK
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    return flat.reshape(-1, _REDUCE_CHUNK)


def areas_by_label(
    labels: jax.Array, max_objects: int, method: str = "auto"
) -> jax.Array:
    """Pixel count per label id 1..max_objects → (max_objects,) int32.

    TPU scatter-adds serialize (the ``segment_sum`` path measured ~3x
    slower than a fused compare+reduce on v5e), so ``method="auto"``
    streams a (chunk, max_objects) equality broadcast through one int32
    sum on accelerators and keeps the scatter on CPU, where scatters are
    cheap and the broadcast is the bottleneck."""
    flat = labels.reshape(-1)
    if method == "auto":
        method = "scatter" if jax.default_backend() == "cpu" else "reduce"
    if method == "scatter":
        ones = jnp.ones_like(flat, dtype=jnp.int32)
        # segment 0 is background; drop it
        sums = jax.ops.segment_sum(ones, flat, num_segments=max_objects + 1)
        return sums[1:]
    chunks = _chunked_pixels(flat)
    ids = jnp.arange(1, max_objects + 1, dtype=flat.dtype)

    def body(i, acc):
        # padded pixels carry label 0 → match no id in 1..max_objects
        return acc + jnp.sum(
            (chunks[i][:, None] == ids).astype(jnp.int32), axis=0
        )

    init = jnp.zeros((max_objects,), jnp.int32)
    return jax.lax.fori_loop(0, chunks.shape[0], body, init)


def remap_labels(
    labels: jax.Array, mapping: jax.Array, method: str = "auto"
) -> jax.Array:
    """Apply a small per-label-id lookup table to a label image:
    ``out[p] = mapping[labels[p]]`` with ``mapping`` of shape
    ``(max_objects + 1,)`` (row 0 = background).

    The obvious ``mapping[labels]`` gather costs ~2.6x more than a one-hot
    contraction against the table on v5e (gathers from a tiny table don't
    tile onto the MXU; the indicator matmul does).  The TPU matmul casts
    f32 operands to bf16, which only represents integers ≤ 256 exactly, so
    the table is split into four bytes — bf16-exact contractions (each
    dot product has exactly one nonzero term, so accumulation order
    cannot round) recombined in int32; exact for every non-negative
    int32 mapped value.  Out-of-range label ids clamp into the table on
    both paths (explicitly — a raw jnp gather would WRAP negative ids
    Python-style while one_hot zeroes them).  ``method="auto"``: gather
    on CPU and for tables past the one-hot sweet spot (> 4096 rows,
    where the chunk×rows indicator work outgrows the gather), matmul on
    accelerators otherwise; pixel axis chunked like
    :func:`areas_by_label`."""
    mapping = jnp.asarray(mapping, jnp.int32)
    labels = jnp.clip(labels, 0, mapping.shape[0] - 1)
    if method == "auto":
        method = (
            "gather"
            if jax.default_backend() == "cpu" or mapping.shape[0] > (1 << 12)
            else "matmul"
        )
    if method == "gather":
        return mapping[labels]
    flat = labels.reshape(-1)
    n = flat.shape[0]
    chunks = _chunked_pixels(flat)
    table = jnp.stack(
        [((mapping >> s) & 0xFF).astype(jnp.float32) for s in (24, 16, 8, 0)],
        axis=-1,
    )  # (K+1, 4) byte planes, each entry ≤ 255 → bf16-exact

    def body(i, acc):
        oh = jax.nn.one_hot(chunks[i], mapping.shape[0], dtype=jnp.float32)
        parts = (oh @ table).astype(jnp.int32)  # (chunk, 4)
        vals = (
            ((parts[:, 0] * 256 + parts[:, 1]) * 256 + parts[:, 2]) * 256
            + parts[:, 3]
        )
        return acc.at[i].set(vals)

    out = jnp.zeros(chunks.shape, jnp.int32)
    out = jax.lax.fori_loop(0, chunks.shape[0], body, out)
    return out.reshape(-1)[:n].reshape(labels.shape)


def relabel_sequential(labels: jax.Array, keep: jax.Array) -> jax.Array:
    """Keep labels where ``keep[label-1]`` is True, renumbering 1..K densely
    in ascending original-label order (scipy-compatible)."""
    keep = jnp.asarray(keep, bool)
    new_ids = jnp.cumsum(keep.astype(jnp.int32))
    mapping = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.where(keep, new_ids, 0)])
    return remap_labels(labels, mapping)


@named("filter_area")
def filter_by_area(
    labels: jax.Array,
    max_objects: int,
    min_area: float = 0,
    max_area: float | None = None,
) -> jax.Array:
    """Remove objects outside [min_area, max_area] (reference
    ``jtmodules/filter.main`` with the 'area' feature).

    Labels beyond ``max_objects`` are dropped first — without this,
    the relabeling gather would clamp them onto object ``max_objects``'s id,
    silently merging distinct objects.
    """
    labels = clip_label_count(labels, max_objects)
    areas = areas_by_label(labels, max_objects)
    keep = areas >= min_area
    if max_area is not None:
        keep = keep & (areas <= max_area)
    keep = keep & (areas > 0)
    return relabel_sequential(labels, keep)


def clip_label_count(labels: jax.Array, max_objects: int) -> jax.Array:
    """Zero out labels beyond ``max_objects`` (static-shape safety valve)."""
    return jnp.where(labels <= max_objects, labels, 0)


def first_pixel_by_label(
    labels: jax.Array, max_labels: int, method: str = "auto"
) -> jax.Array:
    """Min row-major linear pixel index per label id 1..max_labels;
    ``h*w`` for absent labels → (max_labels,) int32.

    Same backend split as :func:`areas_by_label`: ``segment_min`` scatter
    on CPU, fused compare+min broadcast on accelerators (~3x on v5e)."""
    flat = jnp.asarray(labels, jnp.int32).reshape(-1)
    big = jnp.int32(flat.shape[0])
    if method == "auto":
        method = "scatter" if jax.default_backend() == "cpu" else "reduce"
    if method == "scatter":
        linear = jnp.arange(flat.shape[0], dtype=jnp.int32)
        first = jax.ops.segment_min(
            linear, flat, num_segments=max_labels + 1
        )[1:]  # min linear index per label; int32-max-clamped if absent
        return jnp.minimum(first, big)
    chunks = _chunked_pixels(flat)
    ids = jnp.arange(1, max_labels + 1, dtype=jnp.int32)

    def body(i, acc):
        linear = i * _REDUCE_CHUNK + jnp.arange(_REDUCE_CHUNK, dtype=jnp.int32)
        hit = jnp.min(
            jnp.where(chunks[i][:, None] == ids, linear[:, None], big), axis=0
        )
        return jnp.minimum(acc, hit)

    init = jnp.full((max_labels,), big, jnp.int32)
    return jax.lax.fori_loop(0, chunks.shape[0], body, init)


def relabel_by_scan_order(labels: jax.Array, max_labels: int) -> jax.Array:
    """Renumber labels 1..K by each region's first pixel in row-major scan
    order — scipy's assignment order.  Watershed/declump outputs carry seed
    scan order, which deviates from the bit-identical gate
    (``scipy.ndimage.label`` semantics); one compaction pass reconciles
    them.  Absent label ids map to 0.  jit/vmap-safe, static shapes."""
    labels = jnp.asarray(labels, jnp.int32)
    h, w = labels.shape
    big = jnp.int32(h * w)
    first = first_pixel_by_label(labels, max_labels)
    order = jnp.argsort(first)  # label-1 ids sorted by first pixel
    ranks = (
        jnp.zeros((max_labels,), jnp.int32)
        .at[order]
        .set(jnp.arange(1, max_labels + 1, dtype=jnp.int32))
    )
    present = first < big
    mapping = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.where(present, ranks, 0)]
    )
    return remap_labels(jnp.clip(labels, 0, max_labels), mapping)


def filter_by_feature(
    labels: jax.Array,
    feature: str,
    max_objects: int,
    lower: float | None = None,
    upper: float | None = None,
) -> jax.Array:
    """Remove objects whose morphology feature falls outside
    ``[lower, upper]`` (reference ``jtmodules/filter.main`` — the
    reference filters on any measured feature; this covers every
    on-device morphology feature, with ``area`` staying on the cheap
    dedicated path).

    Feature names accept the bare form (``eccentricity``) or the
    exported column name (``Morphology_eccentricity``).
    """
    from tmlibrary_tpu.ops.measure import morphology_features

    if lower is None and upper is None:
        raise ValueError(
            "filter_by_feature needs at least one of lower/upper — with "
            "neither it would be a silent no-op that still renumbers labels"
        )
    labels = clip_label_count(labels, max_objects)
    name = feature if feature.startswith("Morphology_") else f"Morphology_{feature}"
    feats = morphology_features(labels, max_objects)
    if name not in feats:
        raise ValueError(
            f"filter feature '{feature}' is not an on-device morphology "
            f"feature (available: "
            f"{sorted(k.removeprefix('Morphology_') for k in feats)})"
        )
    values = feats[name]
    present = feats["Morphology_area"] > 0
    keep = present
    if lower is not None:
        keep = keep & (values >= lower)
    if upper is not None:
        keep = keep & (values <= upper)
    return relabel_sequential(labels, keep)
