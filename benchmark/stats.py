"""The percentile rule and the spread the bounds are set from."""

import statistics


def percentile(values, q: float) -> float:
    """The ``q``-th percentile (0-100) by linear interpolation between
    order statistics — numpy's default, written out so the yardstick
    needs no library's definition."""
    xs = sorted(values)
    if not xs:
        raise ValueError("no samples")
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def highest_supported_percentile(n: int, beyond: int = 10) -> float:
    """The highest percentile that still has ``beyond`` samples above it
    among ``n`` (choosing-metrics section 1): 95 needs 200 samples."""
    if n <= beyond:
        return 50.0
    return 100.0 * (n - beyond) / n


def tail(values, q: float = 95.0) -> tuple:
    """``(value, percentile reported)``: the ``q``-th percentile of ALL
    the samples — the end-to-end metric keeps its meaning whatever the
    count — and beside it the highest percentile the count supports,
    which the harness prints on an earlier line."""
    return percentile(values, q), min(q, highest_supported_percentile(
        len(values)))


def iqr_share(values) -> float:
    """Distance between the first and third quartile as a share of the
    median, as the driver computes a spread."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
