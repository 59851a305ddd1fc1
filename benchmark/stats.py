"""Percentile, rate and interval arithmetic of the benchmark (plain
Python; nothing here reads a clock or the program)."""
from __future__ import annotations

import math
from typing import Dict, Iterable, List, Sequence


def percentile(values: Sequence[float], p: float) -> float:
    """Nearest-rank percentile, p in (0, 1]: the smallest sample with at
    least p of the samples at or below it.  Every sample counts; none is
    interpolated away."""
    if not values:
        raise ValueError("percentile of no samples")
    vals = sorted(values)
    k = max(1, math.ceil(p * len(vals)))
    return vals[k - 1]


def latency_summary_ms(latencies_s: Iterable[float]) -> Dict[str, float]:
    ms = [1000.0 * v for v in latencies_s]
    return {"p50": percentile(ms, 0.50), "p95": percentile(ms, 0.95),
            "max": max(ms), "n": len(ms)}


def rate_per_s(count: float, seconds: float) -> float:
    """All the work over all the time of the window."""
    if seconds <= 0:
        raise ValueError("a rate needs a window longer than 0 s")
    return count / seconds


def union_length(intervals: List[tuple]) -> float:
    """Length of the union of (start, end) intervals."""
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
