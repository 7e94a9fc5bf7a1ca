"""Percentile and per-request pace arithmetic (no numpy: fixed, readable)."""

from __future__ import annotations

import math
from typing import Optional, Sequence


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """q in [0, 100]; linear interpolation between closest ranks (numpy's
    default). None for an empty sample: a metric with no sample is absent,
    never 0."""
    if not values:
        return None
    xs = sorted(values)
    if len(xs) == 1:
        return float(xs[0])
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return float(xs[lo] + (xs[hi] - xs[lo]) * (pos - lo))


def tpot_ms(t_first: float, t_last: float, n_out: int) -> Optional[float]:
    """Time per output token of ONE request after its first token:
    (t_last - t_first) / (n_out - 1), in ms. Per request and not per gap: a
    fused horizon delivers tokens in groups, so single gaps are 0 or a whole
    dispatch; the pace a stream is read at is the mean over the stream."""
    if n_out < 2:
        return None
    return (t_last - t_first) * 1e3 / (n_out - 1)


def tokens_in_window(chunks, t0: float, t1: float) -> int:
    """chunks: iterable of (arrival time, token count)."""
    return sum(n for t, n in chunks if t0 <= t < t1)


def iqr_share(values: Sequence[float]) -> Optional[float]:
    """(Q3 - Q1) / median with statistics.quantiles(n=4): the spread the
    bounds are set from."""
    import statistics

    if len(values) < 2:
        return None
    q1, _, q3 = statistics.quantiles(values, n=4)
    med = statistics.median(values)
    return (q3 - q1) / med if med else None
