"""Rate and percentile arithmetic of the end-to-end metrics."""
from __future__ import annotations

import math

import numpy as np


def percentile(values, q: float) -> float:
    """The q-th percentile (linear interpolation between order statistics).
    A failed request enters as ``inf``, so it misses every limit."""
    v = np.sort(np.asarray(values, np.float64))
    if v.size == 0:
        raise ValueError("percentile of no values")
    pos = (v.size - 1) * q / 100.0
    lo, hi = math.floor(pos), math.ceil(pos)
    if v[hi] == math.inf:
        return math.inf if pos > lo or v[lo] == math.inf else float(v[lo])
    return float(v[lo] + (v[hi] - v[lo]) * (pos - lo))


def closed_loop_rate(batches) -> float:
    """Converged systems per second over a closed-loop window.

    ``batches`` are ``(t_start, t_end, converged)`` of back-to-back batches
    from the window's start; the rate is every converged system over the
    time from the first start to the last end, so a stall between batches
    counts against it."""
    if not batches:
        return 0.0
    span = batches[-1][1] - batches[0][0]
    return sum(b[2] for b in batches) / span
