"""Window arithmetic: the end-to-end numbers from a run's record.

All times are seconds on the window's clock, whose zero is the window's
start; the window is ``[0, seconds)``.
"""
from __future__ import annotations

import math
from typing import List, Sequence


def nearest_rank(values: Sequence[float], q: float) -> float:
    """The q-th percentile (0 < q <= 100) by nearest rank."""
    if not values:
        raise ValueError("no samples")
    v = sorted(values)
    return v[max(0, math.ceil(q / 100.0 * len(v)) - 1)]


def itl_samples(rec) -> List[float]:
    """Every gap between successive tokens of a request, both inside the
    window, over every request."""
    out = []
    for times in rec.times.values():
        t = [x for x in times if 0.0 <= x < rec.seconds]
        out.extend(b - a for a, b in zip(t, t[1:]))
    return out


def tokens_in_window(rec) -> int:
    return sum(1 for times in rec.times.values() for x in times
               if 0.0 <= x < rec.seconds)
