"""The plain reference of ``cp3-serve``: k nearest neighbours by float64
brute force in numpy over the standardized feature matrix the query named
(the store's own matrix: "the same matrix"), after ``chip_smoke.py``'s
``phase_serve``.

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

import numpy as np

#: float32 ulps of |q|^2 + |x|^2 allowed on a squared distance
RESOLUTION = 16.0


def brute_knn(x: np.ndarray, k: int) -> tuple:
    """``(d2, idx)``: all squared distances (diagonal infinite) and the
    ``k`` nearest rows of each row, float64, ties by row order."""
    sq = (x * x).sum(1)
    d2 = sq[:, None] - 2.0 * x @ x.T + sq[None, :]
    np.fill_diagonal(d2, np.inf)
    return d2, np.argsort(d2, axis=1, kind="stable")[:, :k]


def check_answer(values, x: np.ndarray, k: int) -> dict:
    """One kNN answer (the ``ToolResult.values`` frame) against brute
    force on ``x``, the matrix in the store's canonical object order."""
    d2, ref_idx = brute_knn(x, k)
    got_idx = np.stack([values[f"nn{j}"].to_numpy() for j in range(k)], 1)
    got_dist = np.stack([values[f"nnd{j}"].to_numpy() for j in range(k)], 1)
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
        "equal": bool(distance_units.max() <= 1.0
                      and index_units.max() <= 1.0),
    }
