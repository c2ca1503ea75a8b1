"""Percentiles, with the rule that a tail needs ten samples beyond it."""

from __future__ import annotations

import math

CANDIDATE_PERCENTILES = (50.0, 90.0, 95.0, 99.0, 99.9)


def highest_percentile(n: int, beyond: int = 10) -> float:
    """The highest candidate percentile that leaves at least ``beyond``
    of ``n`` samples above it (50 when even the median does not)."""
    best = CANDIDATE_PERCENTILES[0]
    for p in CANDIDATE_PERCENTILES:
        if n * (100.0 - p) >= 100.0 * beyond - 1e-9:
            best = p
    return best


def percentile(values, p: float) -> float:
    """Linear-interpolated percentile of a non-empty sequence (numpy's
    default rule, written out so the yardstick needs only the stdlib).
    ``math.inf`` entries stand for requests that were never answered."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no samples")
    k = (len(xs) - 1) * p / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    if lo == hi or xs[hi] == math.inf:
        return float(xs[hi])
    return float(xs[lo] + (xs[hi] - xs[lo]) * (k - lo))


def median(values) -> float:
    return percentile(values, 50.0)
