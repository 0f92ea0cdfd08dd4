"""Window statistics: percentiles of ask latency and the whole-window rate."""
from __future__ import annotations

import math
from typing import Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between the
    closest ranks: rank q/100·(n − 1) of the sorted values."""
    if not values:
        raise ValueError("percentile of no values")
    xs = sorted(values)
    rank = q / 100.0 * (len(xs) - 1)
    lo = math.floor(rank)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (rank - lo)


def window_rate(n_completed: int, t_start: float, t_end: float) -> float:
    """Completions over the whole window, stalls included: the window
    runs from its start to its last completion."""
    if t_end <= t_start:
        raise ValueError(f"empty window [{t_start}, {t_end}]")
    return n_completed / (t_end - t_start)
