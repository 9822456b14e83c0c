"""Percentiles and counts, the same arithmetic for every cell."""
from __future__ import annotations

import math
from typing import Iterable, List, Optional, Sequence, Tuple


def percentile(values: Sequence[float], q: float) -> float:
    """The q-th percentile (0-100) by linear interpolation between order
    statistics (numpy's default), written out so that the yardstick does not
    move with a library."""
    xs = sorted(float(v) for v in values)
    if not xs:
        raise ValueError("percentile of nothing")
    if len(xs) == 1:
        return xs[0]
    pos = (len(xs) - 1) * q / 100.0
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(xs) - 1)
    if pos == lo or xs[lo] == xs[hi]:
        return xs[lo]  # also keeps an infinite tail from turning into nan
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def median(values: Sequence[float]) -> float:
    return percentile(values, 50.0)


def gaps(times: Sequence[float]) -> List[float]:
    """Gaps between successive tokens of one request."""
    return [b - a for a, b in zip(times, times[1:])]


def request_latencies(requests: Iterable[Tuple[float, Optional[float], Sequence[float]]],
                      deadline: float) -> Tuple[List[float], List[float], List[float]]:
    """From (due, submitted, token times) of every request attempted:
    time to first token from when the request was *due* (a request with no
    token by the drain deadline missed every limit and counts with the
    deadline's latency), every gap between successive tokens, and how late
    after its due time each submit was called."""
    ttft, itl, late = [], [], []
    for due, submitted, times in requests:
        ttft.append((times[0] if times else deadline) - due)
        itl.extend(gaps(times))
        if submitted is not None:
            late.append(submitted - due)
    return ttft, itl, late
