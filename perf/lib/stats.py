"""The statistics every cell reads its numbers with."""

from __future__ import annotations

import math
from typing import Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """Linear interpolation between closest ranks (numpy's default), on a
    copy; q in [0, 100]. One value is its own every percentile."""
    xs = sorted(float(v) for v in values)
    if not xs:
        raise ValueError("percentile of nothing")
    k = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def median(values: Sequence[float]) -> float:
    return percentile(values, 50.0)


def segment_rates(seg_seconds: Sequence[float], items_per_segment: float,
                  window_seconds: float) -> dict:
    """A train window of `window_seconds` cut into segments of equal work.

    window_rate   all items over ALL the window's time, the gaps between
                  segments included: what a user pays for (the cell's
                  end-to-end rate; a stall anywhere lowers it)
    median_rate   items/s at the median segment time: what the steady
                  phases run at (a per-layer reading beside it)
    slowest_pct   (slowest / median - 1) * 100
    """
    if not seg_seconds:
        raise ValueError("no segments")
    if window_seconds < sum(seg_seconds) * (1.0 - 1e-9):
        raise ValueError("a window shorter than its segments")
    med = median(seg_seconds)
    return {
        "segments": len(seg_seconds),
        "median_s": med,
        "median_rate": items_per_segment / med,
        "window_rate": items_per_segment * len(seg_seconds)
        / window_seconds,
        "slowest_pct": (max(seg_seconds) / med - 1.0) * 100.0,
    }


def union_seconds(intervals) -> float:
    """Total length of the union of (start, end) intervals."""
    busy, end = 0.0, -math.inf
    for s, e in sorted(intervals):
        if e <= end:
            continue
        busy += e - max(s, end)
        end = e
    return busy


def gaps(intervals, t0: float, t1: float) -> list:
    """The (start, end) stretches of [t0, t1] no interval covers."""
    out, at = [], t0
    for s, e in sorted(intervals):
        if e <= at:
            continue
        if s >= t1:
            break
        if s > at:
            out.append((at, min(s, t1)))
        at = max(at, e)
    if at < t1:
        out.append((at, t1))
    return out
