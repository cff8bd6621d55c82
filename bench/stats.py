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


def span_ms(run: dict, span: str, per: str = "waves_run") -> Optional[float]:
    """Milliseconds of the program's stage span ``span`` over the window
    (its totals differenced), per unit of the window counter ``per``
    (``waves_run``, or ``<span>.n`` for each call). None where the spans
    were off, as in an untraced run, or nothing ran."""
    c = run.get("counters") or {}
    if f"{span}.s" not in c or not c.get(per):
        return None
    return 1e3 * c[f"{span}.s"] / c[per]


def setup_span_s(run: dict, span: str) -> Optional[float]:
    """Seconds of the program's stage span ``span`` in set-up, up to the
    end of ``Gateway.open``; None where the spans were off."""
    totals = (run.get("setup_spans") or {}).get(span)
    return None if totals is None else totals["s"]
