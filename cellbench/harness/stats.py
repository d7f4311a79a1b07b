"""Percentile arithmetic of the benchmark (the yardstick: later PRs cannot
change it). A tail is reported only where the sample supports it: a
percentile p needs at least ten samples beyond it (choosing-metrics guide,
section 1), so a p95 needs 200 and a median needs 20."""

from __future__ import annotations

import math


def min_samples(p: float) -> int:
    """Samples needed so that ten lie beyond the p-th percentile."""
    tail = min(p, 100.0 - p) / 100.0
    return math.ceil(10.0 / tail)


def percentile(values, p: float, *, enforce: bool = True) -> float:
    """p-th percentile by linear interpolation between order statistics
    (numpy's default). Raises where fewer than `min_samples(p)` values are
    given, unless enforce=False (printing on an earlier line only)."""
    xs = sorted(float(v) for v in values)
    if not xs:
        raise ValueError("percentile of no samples")
    if enforce and len(xs) < min_samples(p):
        raise ValueError(
            f"p{p:g} over {len(xs)} samples: needs {min_samples(p)} "
            f"(ten beyond the percentile)"
        )
    k = (len(xs) - 1) * p / 100.0
    lo = math.floor(k)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def tokens_in_window(events, w0: float, w1: float) -> float:
    """Output tokens of one request delivered inside [w0, w1). `events` are
    its stream events [(t, tokens_so_far)] in time order. A delivery counts
    for the time since the request's previous delivery, and one that
    straddles an edge of the window counts by the share of that time inside
    it; the first delivery has no earlier one and counts at its instant.
    (The fleet's rows are delivered together, a 16-step chunk at a time:
    counted whole by arrival, a 50 s window holds 57 or 58 such deliveries
    according to the phase, and the rate reads 2% apart by the seed alone.)"""
    total, prev_t, prev_n = 0.0, None, 0
    for t, n in events:
        new = n - prev_n
        if new > 0:
            if prev_t is None or t <= prev_t:
                total += new if w0 <= t < w1 else 0.0
            else:
                total += new * max(0.0, min(t, w1) - max(prev_t, w0)) / (t - prev_t)
            prev_n = n
        prev_t = t
    return total


def interval_union(intervals) -> float:
    """Total length covered by [(start, end), ...]."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def interval_gaps(intervals, lo: float, hi: float) -> list:
    """[(start, end)] of the parts of [lo, hi] no interval covers."""
    gaps, cur = [], lo
    for s, e in sorted(intervals):
        if e <= lo or s >= hi:
            continue
        if s > cur:
            gaps.append((cur, min(s, hi)))
        cur = max(cur, e)
    if cur < hi:
        gaps.append((cur, hi))
    return gaps
