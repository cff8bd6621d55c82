"""Metric arithmetic shared by the traffic loops and the per-layer readers."""
from __future__ import annotations

import math
from typing import Iterable, Optional


def rate(count: int, window_s: float) -> float:
    """Events per second over a window."""
    if window_s <= 0:
        raise ValueError(f"window of {window_s} s")
    return count / window_s


def percentile(values: Iterable[float], q: float) -> float:
    """Nearest-rank percentile (``q`` in (0, 100]): the smallest value with
    at least q% of the values at or below it. ``inf`` (a refused, failed or
    unanswered request) sorts above every latency."""
    v = sorted(values)
    if not v:
        raise ValueError("percentile of no values")
    rank = max(1, math.ceil(q / 100.0 * len(v)))
    return v[rank - 1]


def occupancy(walks_executed: int, waves: int, max_walks: int
              ) -> Optional[float]:
    """Share of the walk slots of the waves run that carried a walk."""
    if waves <= 0:
        return None
    return walks_executed / (waves * max_walks)
