"""Bursts: Poisson arrivals during `on_s` seconds, none for `off_s`.

Parameters `on_s` and `off_s`; the pattern starts with a burst and
repeats over the window.  The cell's rate is the mean over the window,
so a burst offers `rate_qps * (on_s + off_s) / on_s`.  The count is
fixed, as for plain Poisson arrivals, and the times are uniform over the
union of the bursts.
"""

import numpy as np


def times(params: dict, rate_qps: float, seconds: float, rng) -> np.ndarray:
    on, off = float(params["on_s"]), float(params["off_s"])
    n = int(round(rate_qps * seconds))
    starts = np.arange(0.0, seconds, on + off)
    lengths = np.minimum(on, seconds - starts)
    ends = np.cumsum(lengths)                    # burst time, concatenated
    u = rng.uniform(0.0, ends[-1], n)
    burst = np.searchsorted(ends, u, side="right")
    return np.sort(starts[burst] + u - (ends[burst] - lengths[burst]))
