"""The arithmetic of the end-to-end metrics, kept with the benchmark."""
from __future__ import annotations

import math
from typing import Sequence, Tuple


def percentile(values: Sequence[float], p: float) -> float:
    """The ``p``-th percentile (0..100) by linear interpolation between
    closest ranks, over every value given."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    pos = (len(xs) - 1) * p / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def window_rate(work: Sequence[Tuple[float, float, float]], t0: float,
                t_end: float) -> float:
    """Work per second over a window that opens at ``t0`` and closes
    for new work at ``t_end``.

    ``work`` holds ``(issued, completed, amount)`` of each request. Every
    request issued before the close counts in full, and the time runs
    until the last of them completed, so work that straddles the close
    stretches the time rather than being cut off."""
    counted = [(c, a) for i, c, a in work if t0 <= i < t_end]
    if not counted:
        raise ValueError("no work issued in the window")
    last = max(c for c, _ in counted)
    return sum(a for _, a in counted) / (last - t0)


def percentile_of_attempts(done: Sequence[float], failed: int,
                           p: float) -> float:
    """`percentile` over every attempt, where each of the ``failed``
    requests counts as slower than any that completed: infinite when the
    percentile touches one of them."""
    xs = sorted(done)
    n = len(xs) + int(failed)
    if n == 0:
        raise ValueError("percentile of no values")
    pos = (n - 1) * p / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, n - 1)
    if lo >= len(xs) or (pos > lo and hi >= len(xs)):
        return math.inf
    if pos == lo:
        return xs[lo]
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)
