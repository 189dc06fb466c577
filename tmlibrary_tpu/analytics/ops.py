"""Core analytics ops: tiled kNN, randomized PCA, spectral embedding.

All three are pure XLA programs shaped for the MXU:

kNN
    Brute force via the k-means-style matmul expansion
    ``d2 = |q|^2 - 2 q @ x.T + |x|^2`` followed by ``lax.top_k`` on the
    negated distances.  The (tile, N) distance block is the only O(N)
    intermediate, so the query axis is tiled: with the default 256 MiB
    block budget a N=10**6 x F=256 store runs at tile=65536 — the full
    (N, N) matrix (4 TB) never exists.  Every tile reuses ONE jitted
    program (fixed shapes; the last tile is padded), so a store-sized
    sweep costs one compile.
PCA
    Randomized range-finder SVD (Halko et al.): Y = X @ G for a
    Gaussian test matrix G (F, k+oversample), a few QR-stabilized power
    iterations Y <- X @ (X.T @ Y) to sharpen the spectrum, then the
    small (k+p, F) projected SVD.  Everything is tall-matmul + tiny-QR:
    MXU-friendly, deterministic given the PRNG key.
Spectral embedding
    A UMAP-style 2-D layout from the kNN graph without materializing
    the N x N adjacency: the symmetrized, degree-normalized adjacency
    acts as an implicit matvec (gather + segment_sum for the transpose
    half), and orthogonal (subspace) iteration with per-step QR pulls
    the top non-trivial eigenvectors.  Deterministic: fixed key, fixed
    iteration count, no data-dependent branches.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

#: HBM budget for one (tile, N) distance block — bounds the kNN tile so
#: N=10**6 x F=256 stores fit comfortably alongside the feature matrix
KNN_TILE_BLOCK_BYTES = 256 * 1024 * 1024


def knn_tile_rows(n: int, block_bytes: int = KNN_TILE_BLOCK_BYTES) -> int:
    """Rows per query tile such that the (tile, n) float32 distance
    block stays under ``block_bytes`` (at least 8 rows)."""
    return max(8, min(n, block_bytes // max(1, 4 * n)))


@functools.partial(jax.jit, static_argnums=(3, 4))
def _knn_tile(q: jax.Array, x: jax.Array, base: jax.Array, k: int,
              exclude_self: bool) -> tuple[jax.Array, jax.Array]:
    """Top-k neighbors of the query tile ``q`` against the full matrix
    ``x``.  ``base`` (traced, so every tile shares one compiled program)
    is the tile's starting row in ``x``; with ``exclude_self`` the
    diagonal is masked out (self-kNN)."""
    # HIGHEST: the TPU's default matmul rounds f32 operands to bf16, and
    # the cancellation in |q|^2 - 2 q.x + |x|^2 then misranks neighbours
    # against the exact float32 answer; the CPU computes f32 either way
    cross = jnp.matmul(q, x.T, precision=jax.lax.Precision.HIGHEST)
    d2 = (
        jnp.sum(q * q, axis=1, keepdims=True)
        - 2.0 * cross
        + jnp.sum(x * x, axis=1)[None]
    )
    if exclude_self:
        rows = base + jnp.arange(q.shape[0])
        d2 = d2 + jnp.where(
            jnp.arange(x.shape[0])[None, :] == rows[:, None], jnp.inf, 0.0
        )
    neg, idx = jax.lax.top_k(-d2, k)
    return idx, jnp.sqrt(jnp.maximum(-neg, 0.0))


def knn(x: np.ndarray, k: int, queries: np.ndarray | None = None,
        tile: int | None = None) -> tuple[np.ndarray, np.ndarray]:
    """k nearest neighbors by brute force, tiled over the query axis.

    Returns ``(indices (Q, k) int32, distances (Q, k) float32)``; rows
    are sorted nearest-first.  With ``queries=None`` the store queries
    itself and each object's own row is excluded.  The tile size only
    partitions the query axis — each row's distances are computed from
    the same expansion regardless of which tile carries it — and it is
    derived from N alone, so repeated queries are deterministic.
    """
    x = jnp.asarray(x, jnp.float32)
    n = int(x.shape[0])
    self_query = queries is None
    q_all = x if self_query else jnp.asarray(queries, jnp.float32)
    nq = int(q_all.shape[0])
    k = min(int(k), n - 1 if self_query else n)
    if k <= 0:
        return (np.zeros((nq, 0), np.int32), np.zeros((nq, 0), np.float32))
    tile = int(tile) if tile else knn_tile_rows(n)
    idx_out = np.empty((nq, k), np.int32)
    dist_out = np.empty((nq, k), np.float32)
    for start in range(0, nq, tile):
        stop = min(start + tile, nq)
        q = q_all[start:stop]
        pad = tile - (stop - start)
        if pad:  # fixed tile shape -> one compiled program for the sweep
            q = jnp.pad(q, ((0, pad), (0, 0)))
        idx, dist = _knn_tile(q, x, jnp.int32(start), k, self_query)
        idx_out[start:stop] = np.asarray(idx)[: stop - start]
        dist_out[start:stop] = np.asarray(dist)[: stop - start]
    return idx_out, dist_out


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _pca(x: jax.Array, n_components: int, n_iter: int, seed: int):
    n, f = x.shape
    mu = jnp.mean(x, axis=0, keepdims=True)
    xc = x - mu
    rank = min(n, f)
    n_components = min(n_components, rank)
    sketch = min(n_components + 8, rank)
    g = jax.random.normal(jax.random.PRNGKey(seed), (f, sketch), jnp.float32)
    y = xc @ g
    for _ in range(n_iter):  # QR per step keeps the power iteration stable
        y, _ = jnp.linalg.qr(xc @ (xc.T @ y))
    q, _ = jnp.linalg.qr(y)
    b = q.T @ xc  # (sketch, f): the small projected problem
    u_b, s, vt = jnp.linalg.svd(b, full_matrices=False)
    comps = vt[:n_components]
    # sign convention: largest-|loading| coordinate positive, so the
    # decomposition is deterministic across backends/repeats
    flip = jnp.sign(comps[jnp.arange(n_components),
                          jnp.argmax(jnp.abs(comps), axis=1)])
    comps = comps * flip[:, None]
    scores = xc @ comps.T
    var = jnp.sum(xc * xc) / jnp.maximum(n - 1, 1)
    explained = (s[:n_components] ** 2) / jnp.maximum(n - 1, 1)
    return scores, comps, explained / jnp.maximum(var, 1e-12)


def pca(x: np.ndarray, n_components: int = 2, n_iter: int = 8,
        seed: int = 0) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Randomized-SVD PCA: ``(scores (N, k), components (k, F),
    explained_variance_ratio (k,))``, deterministic given ``seed``."""
    x = jnp.asarray(x, jnp.float32)
    scores, comps, ratio = _pca(x, int(n_components), int(n_iter), int(seed))
    return np.asarray(scores), np.asarray(comps), np.asarray(ratio)


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _spectral(neighbors: jax.Array, weights: jax.Array, n: int,
              n_components: int, n_iter: int):
    k = neighbors.shape[1]
    rows = jnp.repeat(jnp.arange(n), k)
    cols = neighbors.reshape(-1)
    vals = weights.reshape(-1)
    # symmetrized degree: deg[i] = sum_j (w_ij + w_ji)
    deg = (jax.ops.segment_sum(vals, rows, num_segments=n)
           + jax.ops.segment_sum(vals, cols, num_segments=n))
    inv_sqrt = 1.0 / jnp.sqrt(jnp.maximum(deg, 1e-12))

    def matvec(v):
        # M = D^-1/2 (W + W.T) D^-1/2 without materializing W
        u = v * inv_sqrt
        fwd = jax.ops.segment_sum(vals * u[cols], rows, num_segments=n)
        bwd = jax.ops.segment_sum(vals * u[rows], cols, num_segments=n)
        return (fwd + bwd) * inv_sqrt

    # the trivial top eigenvector of M is known analytically: D^1/2 1.
    # Deflate it and run orthogonal iteration for the next ones.
    triv = jnp.sqrt(jnp.maximum(deg, 1e-12))
    triv = triv / jnp.linalg.norm(triv)
    v = jax.random.normal(jax.random.PRNGKey(7), (n, n_components),
                          jnp.float32)

    def step(v, _):
        w = jax.vmap(matvec, in_axes=1, out_axes=1)(v)
        w = w - triv[:, None] * (triv @ w)[None, :]
        q, _ = jnp.linalg.qr(w)
        return q, None

    v, _ = jax.lax.scan(step, v, None, length=n_iter)
    # deterministic orientation: largest-|coordinate| entry positive
    flip = jnp.sign(v[jnp.argmax(jnp.abs(v), axis=0),
                      jnp.arange(n_components)])
    return v * flip[None, :]


def spectral_embedding(x: np.ndarray, n_components: int = 2, k: int = 15,
                       n_iter: int = 60, tile: int | None = None,
                       graph: tuple[np.ndarray, np.ndarray] | None = None
                       ) -> np.ndarray:
    """UMAP-style 2-D layout: kNN graph -> Gaussian edge weights ->
    top eigenvectors of the normalized adjacency (trivial vector
    deflated).  Returns (N, n_components) float32, deterministic.

    ``graph`` supplies a precomputed self-kNN ``(neighbors, dists)``
    pair — the embedding tool passes an index-backed graph here
    (``analytics/index.knn_search``) so the layout goes sublinear with
    the store; without it the exact brute-force sweep runs."""
    n = int(np.asarray(x).shape[0])
    k = max(1, min(int(k), n - 1))
    if graph is not None:
        neighbors, dists = graph
    else:
        neighbors, dists = knn(x, k, tile=tile)
    # adaptive Gaussian kernel: each row's bandwidth is its median
    # neighbor distance (umap's local connectivity, simplified)
    sigma = np.maximum(np.median(dists, axis=1, keepdims=True), 1e-6)
    weights = np.exp(-((dists / sigma) ** 2)).astype(np.float32)
    out = _spectral(jnp.asarray(neighbors), jnp.asarray(weights), n,
                    int(n_components), int(n_iter))
    return np.asarray(out)
