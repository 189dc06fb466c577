"""Cross-device corilla: sharded Welford with deterministic tree merge.

Reference parity: ``corilla``'s collect phase — the reference runs one job
per channel and folds sites sequentially in that job
(``tmlib/workflow/corilla/api.py``); at pod scale we shard the site axis
over the mesh, ``lax.scan`` locally, and merge shard states with the
parallel-variance combination (``ops/stats.welford_merge``) via
``all_gather`` + an in-order fold, which is bitwise-deterministic for a
given mesh size (ordinary ``psum`` would not be order-stable for the
variance combination).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec

from tmlibrary_tpu.parallel.compat import pcast_varying, shard_map

from tmlibrary_tpu.ops.stats import (
    WelfordState,
    welford_finalize,
    welford_merge,
    welford_scan,
)


def _scan_and_merge(stack_shard: jax.Array, axis: str) -> WelfordState:
    """Per-shard body: local scan, then deterministic cross-shard fold."""
    from tmlibrary_tpu.ops.stats import welford_init

    # the scan carry must be marked device-varying to satisfy shard_map's
    # varying-axis check (each shard accumulates different values)
    init = jax.tree.map(
        lambda x: pcast_varying(x, (axis,)),
        welford_init(stack_shard.shape[1:]),
    )
    local = welford_scan(stack_shard, init)
    # gather every shard's state to every device; fold in shard order
    gathered = jax.tree.map(
        lambda x: lax.all_gather(x, axis_name=axis), local
    )
    n_shards = gathered.n.shape[0]

    def fold(i, acc):
        piece = jax.tree.map(lambda x: x[i], gathered)
        return welford_merge(acc, piece)

    first = jax.tree.map(lambda x: x[0], gathered)
    return lax.fori_loop(1, n_shards, fold, first)


def _mesh_axis_size(mesh: Mesh, axis: "str | tuple[str, ...]") -> int:
    if isinstance(axis, str):
        return mesh.shape[axis]
    out = 1
    for name in axis:
        out *= mesh.shape[name]
    return out


@functools.lru_cache(maxsize=16)
def _cached_scan_and_merge(mesh: Mesh, axis):
    """The compiled scan-and-fold, built and jitted once per (mesh,
    axis): a fresh ``jax.jit(shard_map(...))`` per call re-traced,
    re-lowered and read the compile cache once per channel of every well
    (``halo._cached_gaussian_halo_2d`` says what that cost)."""
    return jax.jit(shard_map(
        functools.partial(_scan_and_merge, axis=axis),
        mesh=mesh,
        in_specs=PartitionSpec(axis),
        out_specs=PartitionSpec(),  # merged state identical on all shards
        # the all_gather + in-order fold makes outputs replicated, but the
        # varying-axis checker can't prove it statically
        check_vma=False,
    ))


def sharded_welford(stack: jax.Array, mesh: Mesh, axis: str = "sites") -> WelfordState:
    """Merged :class:`WelfordState` over a (B, H, W) stack sharded on the
    leading axis.

    The workflow layer plans batches divisible by the mesh size, but the
    LAST batch of a plate is whatever is left over — so a ragged ``B`` is
    handled here rather than trusted away: the divisible head goes
    through the sharded scan+fold, the tail is scanned locally
    (replicated — one shard's worth of extra work at most, once per
    plate), and the two states combine with the same parallel-variance
    merge the shards use.  Bit-identical to padding with mask bookkeeping
    and cheaper than it; a pad+mask path would also poison ``n`` unless
    every downstream consumer threads the mask."""
    stack = jnp.asarray(stack)
    size = _mesh_axis_size(mesh, axis)
    b = stack.shape[0]
    head = (b // size) * size
    fn = _cached_scan_and_merge(mesh, axis)
    if head == b:
        return fn(stack)
    if head == 0:
        # fewer sites than devices: plain local scan (no shard has a
        # full row to work on)
        return welford_scan(stack)
    # tail scan + merge stay un-jitted: once per ragged batch, and eager
    # op-by-op execution keeps them bit-reproducible against the same
    # composition written by hand (jit refuses nothing but fuses
    # differently)
    head_state = fn(stack[:head])
    tail_state = welford_scan(stack[head:])
    return welford_merge(head_state, tail_state)


def sharded_channel_stats(
    stack: jax.Array, mesh: Mesh, axis: str = "sites"
) -> dict[str, jax.Array]:
    """One channel's finalized illumination statistics over a sharded
    (B, H, W) stack; outputs are replicated."""
    return welford_finalize(sharded_welford(stack, mesh, axis))
