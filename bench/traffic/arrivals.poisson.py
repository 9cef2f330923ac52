"""Poisson arrivals at the cell's rate, conditioned on their count.

`rate_qps * seconds` arrivals spread uniformly at random over the window:
a Poisson process given its number of events, so every seed offers the
same load.  No parameters.
"""

import numpy as np


def times(params: dict, rate_qps: float, seconds: float, rng) -> np.ndarray:
    return np.sort(rng.uniform(0.0, seconds, int(round(rate_qps * seconds))))
