"""Arithmetic of the end-to-end metrics (host only, no JAX)."""
from __future__ import annotations

import math
from typing import Sequence

import numpy as np


def crossing_time(stamps: Sequence[float], gaps: Sequence[float],
                  target: float) -> float | None:
    """Wall time at which the duality gap first reaches ``target``.

    ``stamps[k]`` is the wall time (from the start of the training) at
    which the gap ``gaps[k]`` was known.  Between the two rows that
    straddle the target the crossing is interpolated linearly in
    ``log(gap)``; where a gap is not positive, linearly in the gap.  A
    first row already at or below the target crosses at its own stamp.
    Returns ``None`` when no row reaches the target.
    """
    for k, g in enumerate(gaps):
        if g > target:
            continue
        if k == 0:
            return float(stamps[0])
        g0, t0, t1 = gaps[k - 1], stamps[k - 1], stamps[k]
        if g > 0 and g0 > 0:
            frac = (math.log(g0) - math.log(target)) / (math.log(g0)
                                                         - math.log(g))
        else:
            frac = (g0 - target) / (g0 - g)
        return float(t0 + (t1 - t0) * frac)
    return None


def p95(values: Sequence[float]) -> float:
    """95th percentile, linear between order statistics."""
    return float(np.percentile(np.asarray(values, np.float64), 95))


def open_loop_latencies(due: np.ndarray, done: np.ndarray,
                        end: float) -> np.ndarray:
    """Latency of every request due in a window that closed at ``end``.

    ``done`` holds each request's completion time, NaN where it was not
    answered by ``end``; such a request counts with ``end - due``.
    """
    done = np.where(np.isnan(done) | (done > end), end, done)
    return done - due


def labels_per_s(lengths: np.ndarray, done: np.ndarray, end: float,
                 seconds: float) -> float:
    """Real positions of the requests answered by ``end``, per second."""
    answered = ~np.isnan(done) & (done <= end)
    return float(np.sum(lengths[answered]) / seconds)
