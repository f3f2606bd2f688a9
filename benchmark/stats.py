"""Arithmetic on samples and intervals; plain Python, no JAX."""

from __future__ import annotations

import math
import statistics
from typing import Iterable, List, Sequence, Tuple

Interval = Tuple[float, float]


def percentile(values: Sequence[float], q: float) -> float:
    """The `q`-th percentile (0..100) by linear interpolation between
    order statistics (numpy's default).  Raises on an empty sample: a
    tail over nothing is not 0."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of an empty sample")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def median(values: Sequence[float]) -> float:
    return percentile(values, 50.0)


def iqr_spread(values: Sequence[float]) -> float:
    """Distance between the first and third quartile as a share of the
    median, quartiles as `statistics.quantiles(values, n=4)` gives them
    (the contract's definition of a spread)."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merge overlapping or touching (start, end) intervals."""
    out: List[Interval] = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def union_length(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in union(intervals))


def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def gaps(busy: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    """The idle intervals of [lo, hi] that a merged `busy` list leaves."""
    out, at = [], lo
    for s, e in clip(union(busy), lo, hi):
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


def idle_share(busy: Sequence[Interval], lo: float, hi: float) -> float:
    """1 - (union of busy intervals inside [lo, hi]) / (hi - lo)."""
    if hi <= lo:
        raise ValueError("empty window")
    return 1.0 - union_length(clip(busy, lo, hi)) / (hi - lo)


def roofline_share(ops: float, nbytes: float, seconds: float,
                   peak_flops: float, peak_bytes: float) -> float:
    """Least time the chip could take (the larger of ops over peak
    FLOP/s and bytes over peak bytes/s) over the time taken, in %.
    Not clamped: above 100 means ops or bytes are over-counted or the
    time leaves out part of the work."""
    if seconds <= 0:
        raise ValueError("non-positive time")
    return 100.0 * max(ops / peak_flops, nbytes / peak_bytes) / seconds
