"""Arrival schedules for open-loop traffic, from a mix's parameters and a seed.

A mix file (bench/traffic/<name>.json) with `"loop": "open"` gives

  rate_per_s     mean offered load, requests per second
  pool           distinct input images; request i serves image idx[i]

Every seed gets the same set of gaps, in its own order: the gaps are the
quantiles (i + 1/2)/n of an exponential distribution, shuffled by the seed.
The count of requests and the span they cover are therefore the same on
every seed; only which image comes when, and the order of the gaps,
change. That keeps a Poisson-like open loop while keeping the work of a
run fixed.
"""
from __future__ import annotations

from typing import Dict, Tuple

import numpy as np


def arrivals(mix: Dict, seed: int, seconds: float,
             rate_per_s: float = None) -> Tuple[np.ndarray, np.ndarray]:
    """(offsets in seconds from the window's start, ascending; pool index
    of each request)."""
    rate = float(rate_per_s if rate_per_s is not None else mix["rate_per_s"])
    n = max(1, int(round(rate * seconds)))
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q) / rate
    g = np.random.default_rng(int(seed))
    g.shuffle(gaps)
    idx = g.permutation(n) % int(mix["pool"])
    return np.cumsum(gaps), idx
