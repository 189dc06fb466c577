"""Fused measure megakernels — the ``"fused"`` reduction strategy.

Roofline motivation (DESIGN.md §16, ROADMAP open item 3): the unfused
measure family makes one pass over the site tile per reduction family —
grouped sums, min/max, the quantile histogram, the GLCM cells — and
every pass re-streams the tile from HBM while its accumulator rows
round-trip HBM through the ``fori_loop`` carry.  ``tmx perf`` attributes
those rungs as bandwidth-bound.  The kernels here keep both sides of
that traffic on chip: the tile streams through VMEM once per kernel and
the per-object accumulators live in VMEM output blocks revisited across
a sequential grid (the canonical TPU accumulation pattern), so HBM sees
one read of the pixels and one write of the ``(segments, ...)`` result.

Three kernels cover the three accumulation shapes of ``ops/measure.py``:

- :func:`grouped_stats` — ONE pass emitting per-object sum, min and max
  for any stack of pixel channels.  ``intensity_features`` gets
  count/sum/sumsq/min/max from a single call (channels ``[1, v, v²]``);
  ``morphology_features`` gets area/centroid/second-moment/perimeter
  sums AND the bounding box from its 7-channel call — one HBM read where
  the unfused path takes two full passes per family.
- :func:`intensity_hist` — the per-(object, bucket) histogram feeding
  ``intensity_quantiles``: the dual one-hot contraction with both
  one-hots built in VMEM from the label and bucket rows.
- :func:`glcm_all` — the second fused pass: all 4 directions' GLCM
  counts in one kernel (bf16 one-hot operands built in VMEM and
  contracted into an f32 VMEM accumulator — the exact-integer-counts
  trick of ``_glcm_matmul_all``).

The per-object gray-level stretch feeding the last two is
``quantize_per_object`` itself, one elementwise XLA pass ahead of the
kernel: the bounds lookup needs the whole table whatever segment tile a
grid step works on, and the TPU lowering accepted the kernels once it
left them (PERF.md, PR 21).

Parity contract (pinned by ``tests/test_reduction.py`` and
``tests/test_fused_measure.py``, interpret mode on CPU): min/max,
counts, histogram and GLCM cells are bit-identical to every reference
strategy (order-free or exact-integer accumulations); fractional f32
sums carry the same 1e-6 relative tolerance as sort/scatter vs the
one-hot reference (different accumulation order).  Bucket assignment
is ``quantize_per_object``'s own, so it cannot drift.

Capacity invariance: the pixel chunk is resolved independently of the
object capacity (:func:`fused_chunk`) and the segment axis is tiled, so
rows ``0..n`` are bit-identical for any capacity ``>= n`` — the bucket
router's contract (``ops/reduction.capacity_segments``).  Interpret mode
is the CPU test path only: ``interpret=None`` resolves to ``True``
off-TPU, and on a ``tpu`` backend a kernel the compiler refuses raises —
nothing catches it and reroutes.  The VMEM chunking knob
follows ``_tuned_chunk`` conventions and shares its memoized
TUNING.json reader (``TMX_FUSED_CHUNK`` env → committed ``fused_chunk``
sweep result → the default).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tmlibrary_tpu.ops.label import shift_with_fill
from tmlibrary_tpu.ops.pallas_kernels import _tuning_results
from tmlibrary_tpu.ops.reduction import capacity_segments

#: pixels per VMEM chunk (stats/histogram kernels).  Purely a cost knob:
#: every per-object row accumulates independently of the chunking, so
#: outputs are bit-identical for any chunk — EXCEPT fractional f32 sums,
#: whose accumulation order follows the chunk walk; the knob is resolved
#: once per program (never from the capacity) so the capacity-invariance
#: contract holds bit-exactly.
FUSED_CHUNK = 2048

#: the GLCM kernel's chunk is clamped here: its (chunk, segments*levels)
#: row one-hot is the largest VMEM operand in the family (DESIGN.md §22)
GLCM_CHUNK_MAX = 512

_LANE = 128  # TPU lane width: lane-dim shapes pad to a multiple of this


def fused_chunk() -> int:
    """Resolution: explicit arg (callers/tests) → ``TMX_FUSED_CHUNK``
    env → committed ``fused_chunk`` sweep result → the default.  Shares
    :func:`pallas_kernels._tuning_results` (memoized per (path, mtime))
    instead of re-reading TUNING.json."""
    import os

    env = os.environ.get("TMX_FUSED_CHUNK")
    if env:
        try:
            return max(_LANE, (int(env) // _LANE) * _LANE)
        except ValueError:
            pass
    tuned = _tuning_results().get("fused_chunk")
    if isinstance(tuned, (int, float)) and tuned >= 1:
        return max(_LANE, (int(tuned) // _LANE) * _LANE)
    return FUSED_CHUNK


def _interpret_default() -> bool:
    """Interpret mode off-TPU (the CPU test path); compiled on ``tpu``,
    where a refusal by the compiler propagates."""
    return jax.default_backend() != "tpu"


def _resolve(interpret: "bool | None", chunk: "int | None") -> tuple[bool, int]:
    if interpret is None:
        interpret = _interpret_default()
    if chunk is None:
        chunk = fused_chunk()
    chunk = max(_LANE, (int(chunk) // _LANE) * _LANE)
    return bool(interpret), chunk


def _pad_lane(n: int) -> int:
    return ((int(n) + _LANE - 1) // _LANE) * _LANE


def _chunked(flat: jax.Array, chunk: int, fill=0) -> jax.Array:
    """(P,) → (n_chunks, chunk); padded pixels carry ``fill`` (label 0
    pads land in the dropped background row, value pads are masked by
    their label-0 one-hot row)."""
    p = flat.shape[0]
    pad = (-p) % chunk
    if pad:
        flat = jnp.concatenate(
            [flat, jnp.full((pad,), fill, flat.dtype)]
        )
    return flat.reshape(-1, chunk)


# Layout shared by the three kernels (what the TPU lowering accepts): a
# pixel operand is ``(n_chunks, rows, chunk)`` and one grid step takes the
# block ``(None, rows, chunk)`` — its last two dimensions are the array's
# own, so ``rows`` may be 1.  Pixels run along the lanes, segments along
# the sublanes: the one-hot is ``(segments, chunk)``, built by comparing a
# sublane iota with the lane-major label row, and every contraction is an
# ``A @ B.T`` over the pixel (lane) axis.  The grid is (segment tile,
# pixel chunk): a tile's accumulator block stays resident while the chunk
# axis walks, and the one-hot stays ``(_SEG_TILE, chunk)`` whatever the
# capacity, so a capacity of thousands fits VMEM and each segment's
# accumulation order is the chunk walk alone (capacity invariance).

#: segments per tile: one lane-width, so it divides every lane-padded
#: segment count
_SEG_TILE = _LANE

_CONTRACT_LANES = (((1,), (1,)), ((), ()))  # A (m, k) x B (n, k) -> (m, n)


def _pixel_spec(rows: int, chunk: int) -> pl.BlockSpec:
    return pl.BlockSpec((None, rows, chunk), lambda j, i: (i, 0, 0))


def _tile_onehot(lab_row, tile_rows: int, chunk: int):
    """(tile_rows, chunk) bool: segment ``j*tile_rows + r`` == label."""
    ids = lax.broadcasted_iota(jnp.int32, (tile_rows, chunk), 0)
    return (ids + pl.program_id(0) * tile_rows) == lab_row


# ------------------------------------------------------------- stats kernel
def _stats_kernel(lab_ref, val_ref, sums_ref, mins_ref, maxs_ref):
    """One chunk's contribution to one segment tile's (sum, min, max) of
    every channel.  The (segments, chunk) one-hot is materialized ONCE
    and shared by the MXU sum contraction and the VPU masked min/max —
    the fusion the separate grouped_sums/grouped_minmax passes cannot
    get."""
    @pl.when(pl.program_id(1) == 0)
    def _init():
        sums_ref[...] = jnp.zeros_like(sums_ref)
        mins_ref[...] = jnp.full_like(mins_ref, jnp.inf)
        maxs_ref[...] = jnp.full_like(maxs_ref, -jnp.inf)

    n_ch, chunk = val_ref.shape
    sel = _tile_onehot(lab_ref[...], sums_ref.shape[1], chunk)
    vals = val_ref[...]  # (n_ch, chunk)
    # HIGHEST keeps f32 operand precision on the MXU — same contract as
    # grouped_sums' einsum, so integral sums stay exact / bit-identical
    sums_ref[...] += lax.dot_general(
        vals, sel.astype(jnp.float32), _CONTRACT_LANES,
        precision=lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )
    for c in range(n_ch):  # static unroll: n_ch is a trace constant
        v = vals[c:c + 1, :]
        mins_ref[c] = jnp.minimum(
            mins_ref[c],
            jnp.min(jnp.where(sel, v, jnp.inf), axis=1, keepdims=True),
        )
        maxs_ref[c] = jnp.maximum(
            maxs_ref[c],
            jnp.max(jnp.where(sel, v, -jnp.inf), axis=1, keepdims=True),
        )


@functools.partial(
    jax.jit, static_argnames=("max_objects", "interpret", "chunk")
)
def _stats_call(flat, stacked, max_objects, interpret, chunk):
    segs = capacity_segments(max_objects)
    segs_p = _pad_lane(segs)
    n_ch = stacked.shape[0]
    lab = _chunked(flat, chunk)[:, None, :]  # (n, 1, chunk)
    vals = jnp.stack(
        [_chunked(v, chunk) for v in stacked], axis=1
    )  # (n, C, chunk)
    minmax_spec = pl.BlockSpec((n_ch, _SEG_TILE, 1), lambda j, i: (0, j, 0))
    sums, mins, maxs = pl.pallas_call(
        _stats_kernel,
        grid=(segs_p // _SEG_TILE, lab.shape[0]),
        in_specs=[_pixel_spec(1, chunk), _pixel_spec(n_ch, chunk)],
        out_specs=[
            pl.BlockSpec((n_ch, _SEG_TILE), lambda j, i: (0, j)),
            minmax_spec,
            minmax_spec,
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_ch, segs_p), jnp.float32),
            jax.ShapeDtypeStruct((n_ch, segs_p, 1), jnp.float32),
            jax.ShapeDtypeStruct((n_ch, segs_p, 1), jnp.float32),
        ],
        name="grouped_stats",
        interpret=interpret,
    )(lab, vals)
    # drop the background row and the lane padding; rows = objects
    return (
        sums[:, 1:segs].T,
        mins[:, 1:segs, 0].T,
        maxs[:, 1:segs, 0].T,
    )


def grouped_stats(
    labels: jax.Array,
    channels: list[jax.Array],
    max_objects: int,
    *,
    interpret: "bool | None" = None,
    chunk: "int | None" = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Per-object (sums, mins, maxs) of several pixel channels in ONE
    fused pass — each ``(max_objects, n_channels)`` f32, label ids
    ``1..max_objects`` (background dropped), absent rows (0, +inf,
    -inf) like the unfused twins."""
    interpret, chunk = _resolve(interpret, chunk)
    flat = jnp.asarray(labels, jnp.int32).reshape(-1)
    stacked = jnp.stack(
        [jnp.asarray(c, jnp.float32).reshape(-1) for c in channels]
    )
    return _stats_call(flat, stacked, max_objects, interpret, chunk)


# --------------------------------------------------------- histogram kernel
def _hist_kernel(lab_ref, q_ref, counts_ref):
    """Per-(object, bucket) counts of one chunk for one segment tile: the
    label one-hot against the bucket one-hot, both built in VMEM."""
    @pl.when(pl.program_id(1) == 0)
    def _init():
        counts_ref[...] = jnp.zeros_like(counts_ref)

    chunk = lab_ref.shape[1]
    seg_t, bins_p = counts_ref.shape
    sel = _tile_onehot(lab_ref[...], seg_t, chunk)
    bin_ids = lax.broadcasted_iota(jnp.int32, (bins_p, chunk), 0)
    oh_q = (bin_ids == q_ref[...]).astype(jnp.bfloat16)
    # bf16 one-hot operands are exact (0.0/1.0) and the MXU accumulates
    # f32 — integer counts < 2^24, the _glcm_matmul_all trick
    counts_ref[...] += lax.dot_general(
        sel.astype(jnp.bfloat16), oh_q, _CONTRACT_LANES,
        preferred_element_type=jnp.float32,
    )


@functools.partial(
    jax.jit, static_argnames=("max_objects", "bins", "interpret", "chunk")
)
def _hist_call(flat, q_flat, max_objects, bins, interpret, chunk):
    segs = capacity_segments(max_objects)
    segs_p = _pad_lane(segs)
    bins_p = _pad_lane(bins)
    lab = _chunked(flat, chunk)[:, None, :]
    q = _chunked(q_flat, chunk)[:, None, :]
    counts = pl.pallas_call(
        _hist_kernel,
        grid=(segs_p // _SEG_TILE, lab.shape[0]),
        in_specs=[_pixel_spec(1, chunk), _pixel_spec(1, chunk)],
        out_specs=pl.BlockSpec((_SEG_TILE, bins_p), lambda j, i: (j, 0)),
        out_shape=jax.ShapeDtypeStruct((segs_p, bins_p), jnp.float32),
        name="intensity_hist",
        interpret=interpret,
    )(lab, q)
    return counts[1:segs, :bins]


def intensity_hist(
    labels: jax.Array,
    intensity: jax.Array,
    max_objects: int,
    bins: int,
    bounds: tuple[jax.Array, jax.Array],
    *,
    interpret: "bool | None" = None,
    chunk: "int | None" = None,
) -> jax.Array:
    """Per-object intensity histogram ``(max_objects, bins)`` for
    ``intensity_quantiles``.  ``bounds`` is the raw ``grouped_minmax``
    output (±inf for absent objects), normally the fused stats kernel's
    min/max.  The per-object stretch is ``quantize_per_object`` itself
    (one elementwise XLA pass), so bucket assignment — and with it every
    count — is bit-identical to the unfused strategies; the kernel
    contracts the two one-hots without either leaving VMEM."""
    from tmlibrary_tpu.ops.measure import quantize_per_object

    interpret, chunk = _resolve(interpret, chunk)
    labels = jnp.asarray(labels, jnp.int32)
    q = quantize_per_object(labels, intensity, max_objects, bins, bounds)
    return _hist_call(
        labels.reshape(-1), q.reshape(-1), max_objects, bins,
        interpret, chunk,
    )


# -------------------------------------------------------------- GLCM kernel
#: GLCM accumulator rows (object x level cells) per tile: the
#: (rows, chunk) row one-hot is the largest VMEM operand in the family
_GLCM_ROW_TILE = 2048


def _glcm_kernel(lab_ref, q_ref, lab2_ref, q2_ref, counts_ref,
                 *, levels, n_dirs):
    """All directions' GLCM counts of one chunk for one tile of (object,
    level) rows: the (label, q1) row one-hot contracted against the
    concatenated per-direction column one-hots — ``_glcm_matmul_all``'s
    factored contraction with both one-hots built in VMEM."""
    @pl.when(pl.program_id(1) == 0)
    def _init():
        counts_ref[...] = jnp.zeros_like(counts_ref)

    chunk = lab_ref.shape[1]
    rows_t, cols_p = counts_ref.shape
    lab = lab_ref[...]  # (1, chunk)
    row = jnp.where(lab > 0, lab * levels + q_ref[...], 0)
    oh_r = _tile_onehot(row, rows_t, chunk).astype(jnp.bfloat16)
    lvl_ids = lax.broadcasted_iota(jnp.int32, (levels, chunk), 0)
    cols = []
    for d in range(n_dirs):  # static unroll
        valid = (lab > 0) & (lab2_ref[d:d + 1, :] == lab)
        cols.append(
            ((lvl_ids == q2_ref[d:d + 1, :]) & valid).astype(jnp.bfloat16)
        )
    if cols_p > n_dirs * levels:
        cols.append(
            jnp.zeros((cols_p - n_dirs * levels, chunk), jnp.bfloat16)
        )
    oh_c = jnp.concatenate(cols, axis=0)  # (cols_p, chunk)
    counts_ref[...] += lax.dot_general(
        oh_r, oh_c, _CONTRACT_LANES, preferred_element_type=jnp.float32,
    )


@functools.partial(
    jax.jit,
    static_argnames=("max_objects", "levels", "offsets", "interpret", "chunk"),
)
def _glcm_call(labels, q, max_objects, levels, offsets, interpret, chunk):
    segs = capacity_segments(max_objects)
    k = len(offsets)
    rows_t = min(_GLCM_ROW_TILE, _pad_lane(segs * levels))
    rows_p = -(-segs * levels // rows_t) * rows_t
    cols_p = _pad_lane(k * levels)
    lab = _chunked(labels.reshape(-1), chunk)[:, None, :]
    q1 = _chunked(q.reshape(-1), chunk)[:, None, :]
    # a shifted pixel was quantized against its OWN object's bounds, so
    # shifting the quantized image equals quantizing the shifted one
    lab2 = jnp.stack([
        _chunked(shift_with_fill(labels, -dy, -dx, 0).reshape(-1), chunk)
        for dy, dx in offsets
    ], axis=1)  # (n, k, chunk)
    q2 = jnp.stack([
        _chunked(shift_with_fill(q, -dy, -dx, 0).reshape(-1), chunk)
        for dy, dx in offsets
    ], axis=1)
    counts = pl.pallas_call(
        functools.partial(_glcm_kernel, levels=levels, n_dirs=k),
        grid=(rows_p // rows_t, lab.shape[0]),
        in_specs=[
            _pixel_spec(1, chunk), _pixel_spec(1, chunk),
            _pixel_spec(k, chunk), _pixel_spec(k, chunk),
        ],
        out_specs=pl.BlockSpec((rows_t, cols_p), lambda j, i: (j, 0)),
        out_shape=jax.ShapeDtypeStruct((rows_p, cols_p), jnp.float32),
        name="glcm_all",
        interpret=interpret,
    )(lab, q1, lab2, q2)
    out = []
    for d in range(k):
        glcm = counts[: segs * levels, d * levels : (d + 1) * levels]
        glcm = glcm.reshape(segs, levels, levels)[1:]
        out.append(glcm + jnp.swapaxes(glcm, 1, 2))
    return out


def glcm_all(
    labels: jax.Array,
    intensity: jax.Array,
    max_objects: int,
    levels: int,
    offsets: list[tuple[int, int]],
    bounds: tuple[jax.Array, jax.Array],
    *,
    interpret: "bool | None" = None,
    chunk: "int | None" = None,
) -> list[jax.Array]:
    """All directions' symmetrized per-object GLCMs
    (``(max_objects, levels, levels)`` each) in one fused pass.
    ``bounds`` is the raw per-object min/max of ``intensity`` (the fused
    stats kernel supplies it); the per-object stretch is
    ``quantize_per_object`` itself.  The chunk is clamped to
    :data:`GLCM_CHUNK_MAX` and the (object, level) rows are tiled by
    :data:`_GLCM_ROW_TILE`: the row one-hot dominates the kernel's VMEM
    budget (DESIGN.md §22)."""
    from tmlibrary_tpu.ops.measure import quantize_per_object

    interpret, chunk = _resolve(interpret, chunk)
    chunk = min(chunk, GLCM_CHUNK_MAX)
    labels = jnp.asarray(labels, jnp.int32)
    q = quantize_per_object(labels, intensity, max_objects, levels, bounds)
    return _glcm_call(
        labels, q, max_objects, levels,
        tuple(tuple(o) for o in offsets), interpret, chunk,
    )


# ------------------------------------------------------------ VMEM budgeting
def vmem_bytes_estimate(
    capacity: int,
    *,
    strategy: str = "fused",
    n_channels: int = 7,
    bins: int = 256,
    levels: int = 32,
    n_directions: int = 4,
    chunk: "int | None" = None,
) -> int:
    """Coarse on-chip working-set estimate (bytes) for one measure pass
    at ``capacity`` — the number bench sweep rows record so a rung's
    VMEM pressure is readable next to its throughput.  For ``"fused"``
    it is the worst kernel's resident bytes per grid step (inputs +
    one-hots + accumulator tile); for the unfused
    strategies, the dominant chunked one-hot / accumulator operand of
    the XLA path (a bound on what XLA must keep live per chunk
    iteration, not a Pallas budget)."""
    segs = capacity_segments(capacity)
    if chunk is None:
        chunk = fused_chunk()
    if strategy == "fused":
        # the segment axis is tiled, so stats and the histogram no longer
        # grow with the capacity; the GLCM grows until its rows fill a tile
        gchunk = min(chunk, GLCM_CHUNK_MAX)
        rows_t = min(_GLCM_ROW_TILE, _pad_lane(segs * levels))
        cols_p = _pad_lane(n_directions * levels)
        stats = (
            chunk * (1 + n_channels) * 4      # label + channel blocks
            + _SEG_TILE * chunk * 4           # shared one-hot / mask
            + 3 * n_channels * _SEG_TILE * 4  # sum/min/max accumulators
        )
        hist = (
            chunk * 2 * 4                     # label + bucket blocks
            + _SEG_TILE * chunk * 2           # label one-hot (bf16)
            + _pad_lane(bins) * chunk * 2     # bucket one-hot (bf16)
            + _SEG_TILE * _pad_lane(bins) * 4  # counts accumulator
        )
        glcm = (
            gchunk * 2 * (1 + n_directions) * 4   # label + level blocks
            + rows_t * gchunk * 2                 # row one-hot (bf16)
            + cols_p * gchunk * 2                 # column one-hots (bf16)
            + rows_t * cols_p * 4                 # counts accumulator
        )
        return max(stats, hist, glcm)
    if strategy == "onehot":
        # grouped_sums' (chunk, segs) f32 one-hot vs the GLCM bf16 pair
        from tmlibrary_tpu.ops.measure import _GLCM_CHUNK, _SUM_CHUNK

        return max(
            _SUM_CHUNK * segs * 4,
            _GLCM_CHUNK * (segs * levels + n_directions * levels) * 2,
        )
    # sort/scatter: flat operands plus the largest segmented accumulator
    # (the (segs*levels*levels) GLCM cells); no chunked one-hots
    return segs * levels * levels * 4 + segs * bins * 4
