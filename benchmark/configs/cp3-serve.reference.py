"""The plain reference of ``cp3-serve``: k nearest neighbours by float64
brute force in numpy over the feature matrix the query named, read from
the experiment's raw feature shards and z-scored here in float64
(``feature_matrix``), after ``chip_smoke.py``'s ``phase_serve``.  It takes
nothing the program prepared: a wrong mean or scale, a wrong column or a
wrong object order in the program's standardization reads as a wrong
answer.

What "equal" can mean.  The system evaluates ``|q|^2 - 2 q.x + |x|^2`` in
float32, so a squared distance carries an error of a few ulps of the
operands' squared norms, whatever the distance itself is: two far-out
objects that lie close together have a distance float32 cannot resolve
to 1e-4 (measured on the chip, PR 23: worst error 6.6e-4 on 4,687
objects with 3 features, where ten features in PR 21 gave 2.6e-5).  So
the tolerance is stated where the arithmetic puts it: ``RESOLUTION``
float32 ulps of ``|q|^2 + |x|^2`` on the squared distance.  A matrix
product in bfloat16 (ulp 2^-8 against 2^-23) misses it by three orders
of magnitude.

* every returned distance equals the float64 distance to the neighbour
  that was returned, to that resolution;
* every returned neighbour is the float64 brute-force neighbour of its
  slot, or one whose float64 squared distance differs from that one's by
  less than the resolution of the two (a tie float32 cannot decide)."""

from pathlib import Path

import numpy as np
import pandas as pd

#: float32 ulps of |q|^2 + |x|^2 allowed on a squared distance
RESOLUTION = 16.0

#: the limit on both numbers compared, in units of that resolution: the
#: configuration states it (``guarantees.knn``), so it is 1.  Readings it
#: stands between (PERF.md section 2): the program's worst over its runs
#: on the chip, and the least the control below reads
LIMIT_UNITS = 1.0


def feature_matrix(experiment, objects: str, features: list) -> np.ndarray:
    """The matrix a query over ``features`` of ``objects`` runs on, float64:
    the experiment's feature shards (``features/<objects>/*.parquet``) in
    name order, the object order the store states, each value as the store
    keeps it (float32), z-scored here (``standardize``)."""
    shards = sorted((Path(experiment) / "features" / objects).glob("*.parquet"))
    if not shards:
        raise FileNotFoundError(f"no feature shards of {objects!r}")
    raw = pd.concat([pd.read_parquet(p, columns=list(features))
                     for p in shards], ignore_index=True)
    return standardize(raw[list(features)].to_numpy(np.float32))


def standardize(raw: np.ndarray) -> np.ndarray:
    """z-scores of each column in float64: a cell that is not finite takes
    the mean of its column's finite cells (0 where there is none) before
    the mean and the population deviation are taken; a column whose
    deviation is under 1e-9 is only centred."""
    x = np.asarray(raw, np.float64)
    finite = np.isfinite(x)
    if not finite.all():
        counts = finite.sum(0)
        sums = np.where(finite, x, 0.0).sum(0)
        fill = np.divide(sums, counts, out=np.zeros(x.shape[1]),
                         where=counts > 0)
        x = np.where(finite, x, fill[None, :])
    sd = x.std(0)
    return (x - x.mean(0)) / np.where(sd > 1e-9, sd, 1.0)


def brute_knn(x: np.ndarray, k: int) -> tuple:
    """``(d2, idx)``: all squared distances (diagonal infinite) and the
    ``k`` nearest rows of each row, float64, nearest first, ties by row
    order among the ``k`` taken."""
    sq = (x * x).sum(1)
    d2 = sq[:, None] - 2.0 * x @ x.T + sq[None, :]
    np.fill_diagonal(d2, np.inf)
    # the k smallest of each row, then those k in order: a full sort of
    # every row costs ten times as much and the check runs in every run
    nearest = np.sort(np.argpartition(d2, k - 1, axis=1)[:, :k], axis=1)
    order = np.argsort(np.take_along_axis(d2, nearest, 1), axis=1,
                       kind="stable")
    return d2, np.take_along_axis(nearest, order, 1)


def _bf16_parts(a: np.ndarray) -> tuple:
    """``a`` as the sum of two bfloat16 numbers, the way the TPU splits a
    float32 operand for ``Precision.HIGH``."""
    from ml_dtypes import bfloat16

    hi = a.astype(bfloat16).astype(np.float32)
    return hi, (a - hi).astype(bfloat16).astype(np.float32)


def control_answer(x: np.ndarray, k: int, cross=None) -> dict:
    """The control: this reference put in the program's place, computed
    one precision below what the configuration states.  The program
    evaluates ``q.x`` in float32 at ``Precision.HIGHEST``; the step below
    is ``HIGH``, three bfloat16 passes (``hi.hi + hi.lo + lo.hi``, float32
    accumulation), written out here so that it is the same arithmetic on
    any platform (``cross``: that product from elsewhere, as a TPU's
    own ``HIGH`` gives it).  Returns ``{"nn<j>", "nnd<j>"}`` columns as
    ``check_answer`` takes them; it has to come out as not equal."""
    x32 = np.asarray(x, np.float32)
    if cross is None:
        hi, lo = _bf16_parts(x32)
        cross = hi @ hi.T + hi @ lo.T + lo @ hi.T
    sq = (x32 * x32).sum(1)
    d2 = sq[:, None] - np.float32(2.0) * cross + sq[None, :]
    np.fill_diagonal(d2, np.inf)
    idx = np.argsort(d2, axis=1, kind="stable")[:, :k]
    dist = np.sqrt(np.maximum(np.take_along_axis(d2, idx, 1), 0.0))
    out = {f"nn{j}": idx[:, j] for j in range(k)}
    out.update({f"nnd{j}": dist[:, j] for j in range(k)})
    return out


def check_answer(values, x: np.ndarray, k: int) -> dict:
    """One kNN answer (the ``ToolResult.values`` frame) against brute
    force on ``x``, the matrix ``feature_matrix`` reads."""
    d2, ref_idx = brute_knn(x, k)
    got_idx = np.stack([np.asarray(values[f"nn{j}"]) for j in range(k)], 1)
    got_dist = np.stack([np.asarray(values[f"nnd{j}"]) for j in range(k)], 1)
    sq = (x * x).sum(1)
    ulp = RESOLUTION * float(np.finfo(np.float32).eps)
    res_got = ulp * (sq[:, None] + sq[got_idx])
    res_ref = ulp * (sq[:, None] + sq[ref_idx])
    true_got = np.take_along_axis(d2, got_idx, 1)
    true_ref = np.take_along_axis(d2, ref_idx, 1)
    distance_units = np.abs(got_dist.astype(np.float64) ** 2
                            - true_got) / res_got
    differs = got_idx != ref_idx
    index_units = np.where(
        differs, np.abs(true_got - true_ref) / (res_got + res_ref), 0.0)
    return {
        "objects": int(x.shape[0]), "k": k,
        "slots_differing": int(differs.sum()),
        "max_abs_distance_error": float(np.abs(
            got_dist - np.sqrt(np.maximum(true_ref, 0.0))).max()),
        # in units of the tolerance: 1.0 is the limit
        "worst_distance_units": float(distance_units.max()),
        "worst_index_units": float(index_units.max()),
        "equal": bool(distance_units.max() <= LIMIT_UNITS
                      and index_units.max() <= LIMIT_UNITS),
    }
