"""Rates, tails and spreads as the benchmark defines them.

A rate is all work completed over the whole window: whole forwards, steps
or requests until the window's time has passed, divided by the elapsed time
up to the end of the last one.  A tail is taken over every sample of the
window (every request, every inter-token gap), never from medians of
pieces.  Latencies of an open loop are timed from when each request was
*due*, not from when the generator got round to submitting it.
"""

from __future__ import annotations

import math
import statistics


def rate(work: float, t_start: float, t_last_end: float) -> float:
    """Work per second over ``[t_start, t_last_end]``."""
    elapsed = t_last_end - t_start
    if elapsed <= 0:
        raise ValueError(f"empty window: {t_start} .. {t_last_end}")
    return work / elapsed


def percentile(values, q: float) -> float:
    """The ``q``-th percentile (0..100) by linear interpolation between
    closest ranks, over all values."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def ttfts_from_due(due, first_token_at) -> list:
    """Time to first token of each request, measured from its due time."""
    return [f - d for d, f in zip(due, first_token_at)]


def gaps(token_times) -> list:
    """Inter-token gaps of one request (times of its output tokens)."""
    return [b - a for a, b in zip(token_times, token_times[1:])]


def spread(values) -> float:
    """Interquartile distance as a share of the median, with Python's
    ``statistics.quantiles(values, n=4)`` quartiles."""
    q1, med, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / med
