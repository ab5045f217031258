"""Percentiles and spreads, as the benchmark counts them."""

from __future__ import annotations

import math


def percentile(values: list[float], q: float) -> float | None:
    """The ``q``-th percentile (0-100) with linear interpolation between
    order statistics; ``None`` for no values. A request that failed is
    passed in as ``math.inf``: it misses every limit, and a percentile that
    lands on it is infinite."""
    if not values:
        return None
    xs = sorted(values)
    rank = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(rank), math.ceil(rank)
    if math.isinf(xs[hi]):
        return math.inf
    return xs[lo] + (xs[hi] - xs[lo]) * (rank - lo)


def median(values: list[float]) -> float | None:
    return percentile(values, 50.0)


def spread(values: list[float]) -> float | None:
    """Distance between the quartiles over the median: the driver's measure
    of how far repeated runs of one cell disagree."""
    med = median(values)
    if not med:
        return None
    return (percentile(values, 75.0) - percentile(values, 25.0)) / med


def served_in_window(requests: list[tuple[float, float]], seconds: float) -> float:
    """How many requests the window ``[0, seconds)`` served, given each
    answered request's ``(due, done)`` on the window's clock. A request that
    lies inside counts 1; one that straddles an edge counts by the share of
    its time that lies inside. In a steady state this has the mean of the
    count of replies that land in the window, without its all-or-nothing
    edges: with some ten requests of several seconds in flight at either
    edge, whole counts differ by a request or two between runs (1-2 % of the
    hundred a window serves) for no reason but where the edges fell. The
    callers start no turn after the window, so the requests at its closing
    edge are answered a little sooner than in the steady state and their
    shares are a little large: under 1 % of the rate, alike in every run."""
    served = 0.0
    for due, done in requests:
        inside = min(done, seconds) - max(due, 0.0)
        if inside > 0.0 and done > due:
            served += inside / (done - due)
    return served
