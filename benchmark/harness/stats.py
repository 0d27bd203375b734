"""Medians and percentiles as the benchmark reports them."""

from __future__ import annotations

import math


def median(values):
    xs = sorted(values)
    if not xs:
        return None
    mid = len(xs) // 2
    return xs[mid] if len(xs) % 2 else 0.5 * (xs[mid - 1] + xs[mid])


def median_rate(stamps, amount):
    """The rate of a loop that does `amount` of work between each two
    consecutive `stamps` (seconds on one clock), at the median gap. A
    stall lengthens one gap and leaves the median where it was; the mean
    over the whole window would carry it. None with fewer than two
    stamps."""
    gaps = [b - a for a, b in zip(stamps, stamps[1:])]
    return amount / median(gaps) if gaps else None


def percentile(values, q):
    """Nearest-rank percentile (q in 0..100): the smallest value with at
    least q% of the samples at or below it."""
    xs = sorted(values)
    if not xs:
        return None
    rank = max(1, math.ceil(q / 100.0 * len(xs)))
    return xs[rank - 1]


def samples_beyond(n, q):
    """How many of n samples lie beyond the q-th percentile; a percentile
    is reported only with at least ten."""
    return n - max(1, math.ceil(q / 100.0 * n))
