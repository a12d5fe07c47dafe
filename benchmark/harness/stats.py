"""Percentile and mean arithmetic of the benchmark (pure Python)."""

import math


def percentile(xs, q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between the
    closest ranks (numpy's default): rank = q/100 * (n - 1)."""
    s = sorted(xs)
    if not s:
        raise ValueError("percentile of no samples")
    rank = q / 100.0 * (len(s) - 1)
    lo = int(math.floor(rank))
    hi = min(lo + 1, len(s) - 1)
    return s[lo] + (s[hi] - s[lo]) * (rank - lo)


def median(xs) -> float:
    return percentile(xs, 50.0)


def geomean(xs) -> float:
    xs = list(xs)
    if not xs or any(x <= 0 for x in xs):
        raise ValueError("geomean needs positive samples")
    return math.exp(sum(math.log(x) for x in xs) / len(xs))
