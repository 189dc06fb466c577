"""Pallas TPU kernels for the iterative label-propagation hot loops.

Reference parity: the pixel math these kernels accelerate is the
reference's mahotas/scipy connected-components labeling
(``jtmodules/label.py``, ``segment_primary``) and CellProfiler-style
watershed propagation (``jtmodules/segment_secondary.py``).

Why Pallas (SURVEY.md §8 hard part #1): the XLA implementations in
:mod:`tmlibrary_tpu.ops.label` / :mod:`~tmlibrary_tpu.ops.segment_secondary`
run a ``lax.while_loop`` whose carried label image round-trips HBM every
iteration (plus the run-scan passes).  A site image is tiny relative to
VMEM (256×256 int32 = 256 KB vs ~16 MB), so these kernels load the image
ONCE, iterate the neighbor-propagation fixpoint entirely in VMEM on the
VPU, and write the converged result — O(1) HBM traffic instead of
O(iterations).

Semantics are bit-identical to the XLA twins (asserted by
``tests/test_pallas_kernels.py``):

- :func:`cc_min_propagate`: every foreground pixel converges to the
  minimum linear index of its 8/4-connected component (the same fixpoint
  ``ops.label.connected_components`` reaches; compaction to scipy label
  order stays in XLA).
- :func:`watershed_flood`: level-ordered flooding of seed labels through a
  mask with 8-neighbor max-label adoption — the same schedule as
  ``ops.segment_secondary.watershed_from_seeds``.
- :func:`cc3d_min_propagate` / :func:`watershed3d_flood`: the (Z, H, W)
  volume twins of the two above (``ops.volume`` fixpoints; a z-stack is
  ~2 MB — comfortably VMEM-resident).

Convergence checks run every ``CHUNK`` propagation steps so the scalar
reduction doesn't serialize each cheap VPU pass.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: sentinel for "no label yet" in min-propagation; small enough that
#: int32 arithmetic can never overflow around it (plain int so kernels
#: don't close over a traced constant)
BIG = 2**30

#: propagation steps between convergence checks (default; the measured
#: per-hardware value from the tune_tpu chunk sweep overrides via
#: TUNING.json ``pallas_chunk`` — purely a performance knob: the
#: fixpoint is idempotent, so extra steps after convergence cannot
#: change a label and outputs are bit-identical for any chunk ≥ 1)
CHUNK = 8


#: A whole-site kernel keeps the site and every temporary of one
#: propagation step resident in VMEM.  The compiler's own scoped limit
#: (16 MiB) holds a 256x256 site but not a 16x128x128 volume, whose
#: 26-neighbour step was refused at 17.9 MB (CC) and 21.1 MB (watershed)
#: on a described v5e — about 20 int32 copies of the block.  So each
#: kernel asks for that many copies, with headroom.
_VMEM_COPIES = 24
#: largest block (int32 bytes) ``method="auto"`` gives a whole-site
#: kernel: 512x512 or 16x128x128.  Mosaic unrolls the step over every
#: vreg of the block, so compile time grows faster than the block: CC
#: compiled in 1.5 s at 256x256, 19 s at 512x512 and 226 s at 1024x1024
#: on a described v5e, and a 2160x2160 plane (18.7 MB) neither fits nor
#: finishes compiling (PERF.md, PR 21).  Larger sites need the tiled
#: kernel ROADMAP A4/C6 describe; until then they take the XLA twin.
_MAX_BLOCK_BYTES = 1 << 20


def _block_bytes(shape) -> int:
    n = 4
    for d in shape:
        n *= int(d)
    return n


def _vmem_limit(shape) -> int:
    """Scoped-VMEM request (bytes) of a whole-site kernel on ``shape``."""
    return max(16 * 1024 * 1024, _VMEM_COPIES * _block_bytes(shape))


def fits_vmem(shape) -> bool:
    """Whether ``method="auto"`` may route a ``shape`` site to a
    whole-site kernel at all (see :data:`_MAX_BLOCK_BYTES`)."""
    return _block_bytes(shape) <= _MAX_BLOCK_BYTES


def _compiler_params(shape) -> "pltpu.CompilerParams":
    return pltpu.CompilerParams(vmem_limit_bytes=_vmem_limit(shape))


#: the 3-D twins' default interval.  A 26-neighbour step is 3.25x a 2-D
#: one and the loop body unrolls ``chunk`` of them: on a described v5e
#: the 16x128x128 kernels compiled in 79 s (CC) and 161 s (watershed) at
#: chunk 8 against 8 s and 16 s at chunk 2 (PERF.md, PR 21); labels are
#: the same for any chunk.
CHUNK_3D = 2


def _tuned_chunk(default: int = 0) -> int:
    """Resolution: explicit arg (callers/tuner) → TMX_PALLAS_CHUNK env →
    committed ``pallas_chunk`` sweep result → ``default`` (the 2-D
    :data:`CHUNK` unless the kernel names its own)."""
    import os

    env = os.environ.get("TMX_PALLAS_CHUNK")
    if env:
        try:
            return max(1, int(env))
        except ValueError:
            pass
    tuned = _tuning_results().get("pallas_chunk")
    if isinstance(tuned, (int, float)) and tuned >= 1:
        return int(tuned)
    return default or CHUNK


def _shift_fill(a: jax.Array, dy: int, dx: int, fill, h: int, w: int) -> jax.Array:
    """``out[y, x] = a[y + dy, x + dx]`` with ``fill`` at exposed borders,
    built from circular rolls + iota border masks (pallas-friendly: no
    pads, no gathers)."""
    out = a
    if dy:
        # pltpu.roll wants non-negative shifts: roll by (-dy) mod h
        out = pltpu.roll(out, shift=(-dy) % h, axis=0)
        rows = lax.broadcasted_iota(jnp.int32, (h, w), 0)
        border = rows == (h - 1 if dy > 0 else 0)
        out = jnp.where(border, fill, out)
    if dx:
        out = pltpu.roll(out, shift=(-dx) % w, axis=1)
        cols = lax.broadcasted_iota(jnp.int32, (h, w), 1)
        border = cols == (w - 1 if dx > 0 else 0)
        out = jnp.where(border, fill, out)
    return out


def _shifts_for(connectivity: int) -> list[tuple[int, int]]:
    if connectivity == 4:
        return [(-1, 0), (1, 0), (0, -1), (0, 1)]
    if connectivity == 8:
        return [
            (-1, -1), (-1, 0), (-1, 1),
            (0, -1), (0, 1),
            (1, -1), (1, 0), (1, 1),
        ]
    raise ValueError("connectivity must be 4 or 8")


# ----------------------------------------------------------- CC min-propagate
def _cc_kernel(mask_ref, out_ref, *, connectivity: int, chunk: int):
    h, w = out_ref.shape
    mask = mask_ref[:] != 0
    # plain synchronous stepping, all shifts reading the same input vector.
    # Two alternatives MEASURED SLOWER on v5e (interleaved A/B,
    # scripts/cc_kernel_shootout.py): log-doubling segmented run-scans
    # (~2.2x slower — large-distance lane rolls cost more than the
    # convergence iterations they save) and the separable 3x3 window-min
    # decomposition (~2x slower — the row->col roll dependency chain
    # beats the VPU's appetite for 8 independent rolls)
    shifts = _shifts_for(connectivity)

    rows = lax.broadcasted_iota(jnp.int32, (h, w), 0)
    cols = lax.broadcasted_iota(jnp.int32, (h, w), 1)
    linear = rows * w + cols
    labels = jnp.where(mask, linear, BIG)

    def step(lab):
        new = lab
        for dy, dx in shifts:
            new = jnp.minimum(new, _shift_fill(lab, dy, dx, BIG, h, w))
        return jnp.where(mask, new, BIG)

    def body(state):
        lab, _ = state
        new = lab
        for _ in range(chunk):
            new = step(new)
        return new, jnp.any(new != lab)

    def cond(state):
        return state[1]

    labels, _ = lax.while_loop(cond, body, (labels, jnp.bool_(True)))
    out_ref[:] = labels


def _resolve_chunk(chunk: "int | None", default: int = 0) -> int:
    """Explicit value (validated ≥ 1), else the tuned one with the
    kernel's ``default`` as the last resort — resolved OUTSIDE jit so a
    changed TMX_PALLAS_CHUNK / re-written TUNING.json is picked up per
    call instead of being baked into the first trace."""
    if chunk is None:
        return _tuned_chunk(default)
    if not isinstance(chunk, int) or chunk < 1:
        raise ValueError(f"chunk must be an int >= 1, got {chunk!r}")
    return chunk


@functools.partial(
    jax.jit, static_argnames=("connectivity", "interpret", "chunk")
)
def _cc_min_propagate_jit(
    mask: jax.Array, connectivity: int, interpret: bool, chunk: int
) -> jax.Array:
    h, w = mask.shape
    return pl.pallas_call(
        functools.partial(
            _cc_kernel, connectivity=connectivity, chunk=chunk,
        ),
        out_shape=jax.ShapeDtypeStruct((h, w), jnp.int32),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        compiler_params=_compiler_params((h, w)),
        name="cc_min_propagate",
        interpret=interpret,
    )(jnp.asarray(mask, jnp.int32))


def cc_min_propagate(
    mask: jax.Array, connectivity: int = 8, interpret: bool = False,
    chunk: "int | None" = None,
) -> jax.Array:
    """Converged min-linear-index labels for one (H, W) bool mask.

    Background pixels hold ``BIG``.  Identical fixpoint to the XLA path in
    ``ops.label.connected_components`` (which then compacts to scipy
    order).  ``chunk`` (propagation steps per convergence check) is a
    pure performance knob — same labels for any value ≥ 1.
    """
    return _cc_min_propagate_jit(
        mask, connectivity, interpret, _resolve_chunk(chunk)
    )


# -------------------------------------------------------------- watershed
def _watershed_kernel(intensity_ref, seeds_ref, mask_ref, out_ref,
                      *, n_levels: int, connectivity: int, chunk: int):
    h, w = out_ref.shape
    intensity = intensity_ref[:]
    seeds = seeds_ref[:]
    mask = (mask_ref[:] != 0) | (seeds > 0)
    shifts = _shifts_for(connectivity)

    neg_inf = jnp.float32(-3.4e38)
    pos_inf = jnp.float32(3.4e38)
    lo = jnp.min(jnp.where(mask, intensity, pos_inf))
    hi = jnp.max(jnp.where(mask, intensity, neg_inf))
    span = jnp.maximum(hi - lo, 1e-6)

    def adopt(lab, allowed):
        neigh_max = jnp.zeros_like(lab)
        for dy, dx in shifts:
            neigh_max = jnp.maximum(neigh_max, _shift_fill(lab, dy, dx, 0, h, w))
        return jnp.where((lab == 0) & allowed, neigh_max, lab)

    def flood(labels, allowed):
        def body(state):
            lab, _ = state
            new = lab
            for _ in range(chunk):
                new = adopt(new, allowed)
            return new, jnp.any(new != lab)

        out, _ = lax.while_loop(lambda s: s[1], body, (labels, jnp.bool_(True)))
        return out

    def level_body(i, labels):
        level = hi - span * (i + 1).astype(jnp.float32) / n_levels
        allowed = mask & (intensity >= level)
        return flood(labels, allowed)

    labels = lax.fori_loop(0, n_levels, level_body, seeds)
    labels = flood(labels, mask)  # mop up below the lowest level
    out_ref[:] = jnp.where(mask, labels, 0)


@functools.partial(
    jax.jit, static_argnames=("n_levels", "connectivity", "interpret", "chunk")
)
def _watershed_flood_jit(
    intensity: jax.Array,
    seeds: jax.Array,
    mask: jax.Array,
    n_levels: int,
    connectivity: int,
    interpret: bool,
    chunk: int,
) -> jax.Array:
    h, w = intensity.shape
    return pl.pallas_call(
        functools.partial(
            _watershed_kernel, n_levels=n_levels, connectivity=connectivity,
            chunk=chunk,
        ),
        out_shape=jax.ShapeDtypeStruct((h, w), jnp.int32),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        compiler_params=_compiler_params((h, w)),
        name="watershed_flood",
        interpret=interpret,
    )(
        jnp.asarray(intensity, jnp.float32),
        jnp.asarray(seeds, jnp.int32),
        jnp.asarray(mask, jnp.int32),
    )


def watershed_flood(
    intensity: jax.Array,
    seeds: jax.Array,
    mask: jax.Array,
    n_levels: int = 32,
    connectivity: int = 8,
    interpret: bool = False,
    chunk: "int | None" = None,
) -> jax.Array:
    """Level-ordered watershed flooding of one (H, W) site, all in VMEM.

    Same schedule and tie-breaking as
    ``ops.segment_secondary.watershed_from_seeds``.  ``chunk`` is the
    convergence-check interval — bit-identical output for any value ≥ 1.
    """
    return _watershed_flood_jit(
        intensity, seeds, mask, n_levels, connectivity, interpret,
        _resolve_chunk(chunk),
    )


# -------------------------------------------------------------- fill holes
def _fill_kernel(mask_ref, out_ref, *, connectivity: int, chunk: int):
    h, w = out_ref.shape
    mask = mask_ref[:] != 0
    bg = ~mask
    rows = lax.broadcasted_iota(jnp.int32, (h, w), 0)
    cols = lax.broadcasted_iota(jnp.int32, (h, w), 1)
    border = (rows == 0) | (rows == h - 1) | (cols == 0) | (cols == w - 1)
    # reached-from-border flood through background; carried as int32 0/1
    # (Mosaic cannot legalize vector<i1> while_loop carries — see the
    # distance kernel) and OR over {0,1} is exactly max
    reach = (bg & border).astype(jnp.int32)
    shifts = _shifts_for(connectivity)

    def step(r):
        new = r
        for dy, dx in shifts:
            new = jnp.maximum(new, _shift_fill(r, dy, dx, 0, h, w))
        return jnp.where(bg, new, 0)

    def body(state):
        r, _ = state
        new = r
        for _ in range(chunk):
            new = step(new)
        return new, jnp.any(new != r)

    reach, _ = lax.while_loop(lambda s: s[1], body, (reach, jnp.bool_(True)))
    out_ref[:] = (mask | (bg & (reach == 0))).astype(jnp.int32)


@functools.partial(
    jax.jit, static_argnames=("connectivity", "interpret", "chunk")
)
def _fill_holes_jit(
    mask: jax.Array, connectivity: int, interpret: bool, chunk: int
) -> jax.Array:
    h, w = mask.shape
    return pl.pallas_call(
        functools.partial(
            _fill_kernel, connectivity=connectivity, chunk=chunk,
        ),
        out_shape=jax.ShapeDtypeStruct((h, w), jnp.int32),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        compiler_params=_compiler_params((h, w)),
        name="fill_holes_flood",
        interpret=interpret,
    )(jnp.asarray(mask, jnp.int32))


def fill_holes_flood(
    mask: jax.Array, connectivity: int = 4, interpret: bool = False,
    chunk: "int | None" = None,
) -> jax.Array:
    """VMEM hole filling: flood "reached" from the border through the
    background, fill what the flood never touched — identical fixpoint
    to the XLA path in ``ops.label.fill_holes`` (scipy
    ``binary_fill_holes`` semantics; ``connectivity`` is the BACKGROUND
    connectivity, 4 = complement of 8-connected foreground)."""
    return _fill_holes_jit(
        mask, connectivity, interpret, _resolve_chunk(chunk)
    ) != 0


# ------------------------------------------------------------- 3-D twins
def _shift_fill_3d(a: jax.Array, dz: int, dy: int, dx: int, fill,
                   z: int, h: int, w: int) -> jax.Array:
    """3-D ``_shift_fill``: rolls + iota border masks on every axis."""
    out = a
    if dz:
        out = pltpu.roll(out, shift=(-dz) % z, axis=0)
        planes = lax.broadcasted_iota(jnp.int32, (z, h, w), 0)
        border = planes == (z - 1 if dz > 0 else 0)
        out = jnp.where(border, fill, out)
    if dy:
        out = pltpu.roll(out, shift=(-dy) % h, axis=1)
        rows = lax.broadcasted_iota(jnp.int32, (z, h, w), 1)
        border = rows == (h - 1 if dy > 0 else 0)
        out = jnp.where(border, fill, out)
    if dx:
        out = pltpu.roll(out, shift=(-dx) % w, axis=2)
        cols = lax.broadcasted_iota(jnp.int32, (z, h, w), 2)
        border = cols == (w - 1 if dx > 0 else 0)
        out = jnp.where(border, fill, out)
    return out


def _shifts3d_for(connectivity: int) -> list[tuple[int, int, int]]:
    if connectivity not in (6, 18, 26):
        raise ValueError("3-D connectivity must be 6, 18 or 26")
    out = []
    for dz in (-1, 0, 1):
        for dy in (-1, 0, 1):
            for dx in (-1, 0, 1):
                nonzero = (dz != 0) + (dy != 0) + (dx != 0)
                if nonzero == 0:
                    continue
                if connectivity == 6 and nonzero > 1:
                    continue
                if connectivity == 18 and nonzero == 3:
                    continue
                out.append((dz, dy, dx))
    return out


def _cc3d_kernel(mask_ref, out_ref, *, connectivity: int, chunk: int):
    z, h, w = out_ref.shape
    mask = mask_ref[:] != 0
    shifts = _shifts3d_for(connectivity)

    planes = lax.broadcasted_iota(jnp.int32, (z, h, w), 0)
    rows = lax.broadcasted_iota(jnp.int32, (z, h, w), 1)
    cols = lax.broadcasted_iota(jnp.int32, (z, h, w), 2)
    linear = (planes * h + rows) * w + cols
    labels = jnp.where(mask, linear, BIG)

    def step(lab):
        new = lab
        for s in shifts:
            new = jnp.minimum(new, _shift_fill_3d(lab, *s, BIG, z, h, w))
        return jnp.where(mask, new, BIG)

    def body(state):
        lab, _ = state
        new = lab
        for _ in range(chunk):
            new = step(new)
        return new, jnp.any(new != lab)

    labels, _ = lax.while_loop(lambda s: s[1], body, (labels, jnp.bool_(True)))
    out_ref[:] = labels


@functools.partial(
    jax.jit, static_argnames=("connectivity", "interpret", "chunk")
)
def _cc3d_min_propagate_jit(
    mask: jax.Array, connectivity: int, interpret: bool, chunk: int
) -> jax.Array:
    z, h, w = mask.shape
    return pl.pallas_call(
        functools.partial(
            _cc3d_kernel, connectivity=connectivity, chunk=chunk,
        ),
        out_shape=jax.ShapeDtypeStruct((z, h, w), jnp.int32),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        compiler_params=_compiler_params((z, h, w)),
        name="cc3d_min_propagate",
        interpret=interpret,
    )(jnp.asarray(mask, jnp.int32))


def cc3d_min_propagate(
    mask: jax.Array, connectivity: int = 26, interpret: bool = False,
    chunk: "int | None" = None,
) -> jax.Array:
    """3-D :func:`cc_min_propagate`: converged min-linear-index labels
    for one (Z, H, W) bool volume, entirely in VMEM (a 32x128x128 int32
    volume is 2 MB vs ~16 MB VMEM).  Identical fixpoint to the XLA path
    in ``ops.volume.connected_components_3d`` (which then compacts to
    scipy order)."""
    return _cc3d_min_propagate_jit(
        mask, connectivity, interpret, _resolve_chunk(chunk, CHUNK_3D)
    )


def _watershed3d_kernel(intensity_ref, seeds_ref, mask_ref, out_ref,
                        *, n_levels: int, chunk: int):
    z, h, w = out_ref.shape
    intensity = intensity_ref[:]
    seeds = seeds_ref[:]
    mask = (mask_ref[:] != 0) | (seeds > 0)
    shifts = _shifts3d_for(26)  # _adopt_step_3d uses the full neighborhood

    neg_inf = jnp.float32(-3.4e38)
    pos_inf = jnp.float32(3.4e38)
    lo = jnp.min(jnp.where(mask, intensity, pos_inf))
    hi = jnp.max(jnp.where(mask, intensity, neg_inf))
    span = jnp.maximum(hi - lo, 1e-6)

    def adopt(lab, allowed):
        neigh_max = jnp.zeros_like(lab)
        for s in shifts:
            neigh_max = jnp.maximum(
                neigh_max, _shift_fill_3d(lab, *s, 0, z, h, w)
            )
        return jnp.where((lab == 0) & allowed, neigh_max, lab)

    def flood(labels, allowed):
        def body(state):
            lab, _ = state
            new = lab
            for _ in range(chunk):
                new = adopt(new, allowed)
            return new, jnp.any(new != lab)

        out, _ = lax.while_loop(lambda s: s[1], body, (labels, jnp.bool_(True)))
        return out

    def level_body(i, labels):
        # the same left-associative expression as the XLA twin's
        # level_body, so band membership is decided bit-identically
        level = hi - span * (i + 1).astype(jnp.float32) / n_levels
        allowed = mask & (intensity >= level)
        return flood(labels, allowed)

    labels = lax.fori_loop(0, n_levels, level_body, seeds)
    labels = flood(labels, mask)
    out_ref[:] = jnp.where(mask, labels, 0)


@functools.partial(
    jax.jit, static_argnames=("n_levels", "interpret", "chunk")
)
def _watershed3d_flood_jit(
    intensity: jax.Array,
    seeds: jax.Array,
    mask: jax.Array,
    n_levels: int,
    interpret: bool,
    chunk: int,
) -> jax.Array:
    z, h, w = intensity.shape
    return pl.pallas_call(
        functools.partial(
            _watershed3d_kernel, n_levels=n_levels, chunk=chunk,
        ),
        out_shape=jax.ShapeDtypeStruct((z, h, w), jnp.int32),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        compiler_params=_compiler_params((z, h, w)),
        name="watershed3d_flood",
        interpret=interpret,
    )(
        jnp.asarray(intensity, jnp.float32),
        jnp.asarray(seeds, jnp.int32),
        jnp.asarray(mask, jnp.int32),
    )


def watershed3d_flood(
    intensity: jax.Array,
    seeds: jax.Array,
    mask: jax.Array,
    n_levels: int = 16,
    interpret: bool = False,
    chunk: "int | None" = None,
) -> jax.Array:
    """3-D :func:`watershed_flood`: level-ordered flooding of one
    (Z, H, W) volume in VMEM — same schedule and tie-breaking as
    ``ops.volume.watershed_from_seeds_3d``'s XLA path."""
    return _watershed3d_flood_jit(
        intensity, seeds, mask, n_levels, interpret,
        _resolve_chunk(chunk, CHUNK_3D),
    )


# ----------------------------------------------------------- distance xform
def _distance_kernel(mask_ref, out_ref, *, max_distance: int):
    h, w = out_ref.shape
    # the eroding mask is carried as int32 0/1, not bool: Mosaic cannot
    # legalize vector<i1> while_loop carries (scf.yield legalization error
    # seen on v5e), and min over {0,1} is exactly boolean AND
    mask = (mask_ref[:] != 0).astype(jnp.int32)

    def erode(cur):
        # out-of-image neighbors count as foreground (fill=1) to match the
        # XLA golden ``binary_erode``'s border=True convention — masks that
        # touch the image edge must not erode from the edge side
        out = cur
        for dy, dx in _shifts_for(8):
            out = jnp.minimum(out, _shift_fill(cur, dy, dx, 1, h, w))
        return out

    def cond(state):
        _, cur, i = state
        return (jnp.max(cur) > 0) & (i < max_distance)

    def body(state):
        dist, cur, i = state
        nxt = erode(cur)
        return dist + nxt.astype(jnp.float32), nxt, i + 1

    dist, _, _ = lax.while_loop(
        cond, body, (mask.astype(jnp.float32), mask, jnp.int32(0))
    )
    out_ref[:] = dist


@functools.partial(jax.jit, static_argnames=("max_distance", "interpret"))
def distance_transform(
    mask: jax.Array, max_distance: int = 64, interpret: bool = False
) -> jax.Array:
    """Chessboard distance-to-background by VMEM-resident erosion counting
    — identical fixpoint to the XLA path in
    ``ops.segment_primary.distance_transform_approx``."""
    h, w = mask.shape
    return pl.pallas_call(
        functools.partial(_distance_kernel, max_distance=max_distance),
        out_shape=jax.ShapeDtypeStruct((h, w), jnp.float32),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        compiler_params=_compiler_params((h, w)),
        name="distance_transform",
        interpret=interpret,
    )(jnp.asarray(mask, jnp.int32))


# ------------------------------------------------------------------ dispatch
#: (path, mtime_ns, size) -> parsed tuning dict.  Keyed on the stat
#: signature like ``RunLedger.events()``: a sweep rewriting TUNING.json
#: in place is picked up on the next call (the old lru_cache keyed on
#: path alone served stale verdicts for the life of the process), while
#: repeat calls from hot dispatch paths (``_tuned_chunk``, every GLCM
#: method resolution) cost one ``os.stat`` instead of a JSON parse.
_TUNING_CACHE: dict = {}


def _tuning_results_at(path: str) -> dict:
    import json
    import os

    try:
        st = os.stat(path)
        key = (path, st.st_mtime_ns, st.st_size)
    except OSError:
        key = (path, None, None)
    hit = _TUNING_CACHE.get(key)
    if hit is not None:
        return hit
    try:
        with open(path) as f:
            tuning = json.load(f)
    except (OSError, ValueError):
        tuning = {}
    # a dry-run (smoke-scale) sweep must never drive production dispatch
    if "SMOKE(" in str(tuning.get("timing_methodology", "")):
        tuning = {}
    if len(_TUNING_CACHE) > 8:  # stale (path, mtime) keys never re-hit
        _TUNING_CACHE.clear()
    _TUNING_CACHE[key] = tuning
    return tuning


def _tuning_results() -> dict:
    """Hardware-tuning measurements (``tuning/TUNING.json``, written by
    ``scripts/tune_tpu.py`` on a real chip); {} if absent.  Resolves the
    file through :func:`tmlibrary_tpu.tuning.tuning_json_path` so the
    ``TMX_TUNING_JSON`` rehearsal redirect applies to kernel dispatch the
    same way it does to the tuned engine defaults (the cache is keyed on
    the resolved path + stat signature, so in-place rewrites are seen)."""
    from tmlibrary_tpu.tuning import tuning_json_path

    return _tuning_results_at(tuning_json_path())


_tuning_results.cache_clear = _TUNING_CACHE.clear


def pallas_enabled(kernel: str | None = None, shape=None) -> bool:
    """Whether ``method="auto"`` dispatches to the pallas kernels.

    ``shape`` is the site (or volume) the caller is about to dispatch: a
    block larger than a whole-site kernel can hold (:func:`fits_vmem`)
    takes the XLA twin whatever the verdicts below say.

    Resolution order on TPU-class backends: the ``TMX_PALLAS`` env var
    (explicit global override) → the committed per-kernel shootout
    (``tuning/TUNING.json`` ``kernels_ms``: ``{kernel}_pallas`` vs
    ``{kernel}_xla``, when ``kernel`` is one of ``"cc"`` /
    ``"watershed"`` / ``"distance"`` / ``"fill"`` / ``"cc3d"`` /
    ``"watershed3d"`` and both timings are present) → for the original
    trio only (cc/watershed/distance — the kernels the aggregate verdict
    was computed FROM), the aggregate ``pallas_wins`` verdict → off.
    Kernels added after a committed tune run (fill, the 3-D twins) are
    NEVER auto-dispatched without their own measured win: a stale
    aggregate must not route production through a kernel that has never
    compiled on the deployment's hardware.  The per-kernel gate matters
    because the hardware verdict is split: on TPU v5e the CC fixpoint is
    ~2.1x faster in VMEM while the watershed/distance fixpoints measured
    slightly faster as XLA loops — a single global flag would pick wrong
    for one side or the other.  CPU/GPU always use the XLA twins (the
    portable path and the golden reference).
    """
    import os

    if jax.default_backend() in ("cpu", "gpu"):
        return False
    if shape is not None and not fits_vmem(shape):
        return False
    env = os.environ.get("TMX_PALLAS")
    if env is not None:
        return env not in ("0", "false", "no")
    tuning = _tuning_results()
    if kernel is not None:
        ms = tuning.get("kernels_ms") or {}
        t_pallas = ms.get(f"{kernel}_pallas")
        t_xla = ms.get(f"{kernel}_xla")
        if isinstance(t_pallas, (int, float)) and isinstance(t_xla, (int, float)):
            return t_pallas < t_xla
        # a kernel that failed on hardware during the shootout is recorded
        # as null — never auto-dispatch to it, even if the aggregate
        # verdict says pallas wins overall
        if t_pallas is None and f"{kernel}_pallas" in ms:
            return False
        # unmeasured kernel: only the original trio may ride the
        # aggregate verdict (it was computed from exactly them)
        if kernel not in ("cc", "watershed", "distance"):
            return False
    return bool(tuning.get("pallas_wins", False))
