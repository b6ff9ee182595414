"""The arithmetic of the end-to-end metrics, and of a spread."""

from __future__ import annotations

import math
import statistics


def percentile(values, q: float) -> float:
    """Nearest-rank percentile (q in (0, 100]) of all values: the smallest
    value with at least q% of the sample at or under it."""
    if not values:
        raise ValueError("percentile of nothing")
    ordered = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(ordered)))
    return ordered[rank - 1]


def rate_in_window(event_times, t_open: float, t_close: float) -> float:
    """Events whose time fell in [t_open, t_close) over the window's
    seconds: all the work over all the time."""
    if t_close <= t_open:
        raise ValueError("empty window")
    n = sum(1 for t in event_times if t_open <= t < t_close)
    return n / (t_close - t_open)


def max_gap(event_times, t_open: float, t_close: float) -> float:
    """Longest time with no event inside [t_open, t_close), the edges
    counting as events."""
    inside = sorted(t for t in event_times if t_open <= t < t_close)
    edges = [t_open, *inside, t_close]
    return max(b - a for a, b in zip(edges, edges[1:]))


def spread(values) -> float:
    """Distance between the first and third quartile as a share of the
    median (``statistics.quantiles(values, n=4)``), the driver's measure."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
