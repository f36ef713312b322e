"""Order statistics the benchmark reports (copied arithmetic: the
yardstick does not move with the program's `bench.py` `_pctl`)."""

from __future__ import annotations

import math


def quantile(values, q: float) -> float:
    """Linear-interpolated quantile, q in [0, 1]; NaN for no samples."""
    xs = sorted(values)
    if not xs:
        return math.nan
    pos = q * (len(xs) - 1)
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def median(values) -> float:
    return quantile(values, 0.5)


def quartiles(values) -> tuple[float, float, float]:
    return quantile(values, 0.25), quantile(values, 0.5), quantile(values, 0.75)


def highest_percentile(n: int) -> float | None:
    """The highest of p50/p90/p95/p99/p99.9 with at least ten samples
    beyond it; None for fewer than twenty samples."""
    best = None
    for p in (50.0, 90.0, 95.0, 99.0, 99.9):
        if n * (1 - p / 100.0) >= 10:
            best = p
    return best


def summary(values) -> str:
    """`n=.. p25/p50/p75 [pXX]`, for the lines before the result."""
    xs = list(values)
    if not xs:
        return "n=0"
    q1, q2, q3 = quartiles(xs)
    out = f"n={len(xs)} p25={q1:.3f} p50={q2:.3f} p75={q3:.3f}"
    p = highest_percentile(len(xs))
    if p and p > 50:
        out += f" p{p:g}={quantile(xs, p / 100.0):.3f}"
    return out
