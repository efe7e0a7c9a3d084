"""Poisson arrivals at a fixed rate: independent users, such as analysts
sending one-off queries (the ad-hoc pattern of ``core/workload.py``,
whose work-hours bursts are compressed here to one steady rate)."""
from __future__ import annotations

import numpy as np


def schedule(params: dict, seconds: float, seed: int) -> np.ndarray:
    """Sorted arrival offsets in [0, seconds)."""
    rate = float(params["rate_qps"])
    rng = np.random.default_rng(seed)
    n = int(rate * seconds * 2) + 16
    times = np.cumsum(rng.exponential(1.0 / rate, n))
    while times[-1] < seconds:  # never short of the window's end
        times = np.concatenate(
            [times, times[-1] + np.cumsum(rng.exponential(1.0 / rate, n))]
        )
    return times[times < seconds]


def rate_qps(params: dict) -> float:
    return float(params["rate_qps"])


def tiny(params: dict) -> dict:
    """The same process at the CPU tests' size (a window of 1.5 s)."""
    return {**params, "rate_qps": 10.0}
