"""Percentiles in which a request that never finished counts as a miss."""
from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """Nearest-rank percentile (q in (0, 100]) of ``values``; a value of
    ``math.inf`` is a miss and sorts last.  Empty input is ``inf``."""
    vals = sorted(values)
    if not vals:
        return math.inf
    rank = max(1, math.ceil(q / 100.0 * len(vals)))
    return vals[rank - 1]


def ttfts(requests, t_stop: float) -> list:
    """Time from due to first token of every request, in seconds; a
    request refused, failed or without a first token by ``t_stop`` is
    ``inf``."""
    out = []
    for r in requests:
        first = r.get("first")
        ok = r.get("accepted", True) and first is not None and first <= t_stop
        out.append(first - r["due"] if ok else math.inf)
    return out


def gaps(requests) -> list:
    """Every gap between consecutive output tokens of ``requests``."""
    out = []
    for r in requests:
        times = r.get("times", [])
        out.extend(b - a for a, b in zip(times, times[1:]))
    return out
