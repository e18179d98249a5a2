"""Percentile, arrival and window arithmetic, kept with the benchmark.

The arrivals are the Poisson arithmetic of the program's
``repro.fleet.workloads.PoissonWorkload``, copied so that the yardstick
cannot move with the program. A run draws a fixed number of arrivals,
``round(rate * seconds)``, at times spread uniformly over the window and
sorted: that is a Poisson process conditioned on its count, so every seed
offers the same amount of work in another order.
"""

from __future__ import annotations

import math

import numpy as np

#: a request that failed, or never answered, is slower than every answer
MISSING = math.inf


def percentile(values, p: float) -> float:
    """Nearest-rank p-th percentile (0 < p <= 100): the ceil(p/100 * n)-th
    smallest value. Works with ``MISSING`` entries, which sort last."""
    if not 0 < p <= 100:
        raise ValueError(f"percentile {p} outside (0, 100]")
    s = sorted(values)
    if not s:
        raise ValueError("percentile of no values")
    return s[max(0, math.ceil(p / 100.0 * len(s)) - 1)]


def mean(values) -> float:
    vals = list(values)
    if not vals:
        raise ValueError("mean of no values")
    return math.fsum(vals) / len(vals)


def poisson_arrivals(rng: np.random.Generator, rate_per_s: float, seconds: float) -> np.ndarray:
    """Due times (seconds from the window's start) of a Poisson(rate) stream
    conditioned on ``round(rate * seconds)`` arrivals in [0, seconds)."""
    if rate_per_s <= 0 or seconds <= 0:
        raise ValueError("rate and seconds must be positive")
    count = int(round(rate_per_s * seconds))
    return np.sort(rng.uniform(0.0, seconds, size=count))


def count_before(times, t_end: float) -> int:
    """How many of ``times`` fall before ``t_end`` (the window's close)."""
    return int(sum(1 for t in times if t < t_end))
