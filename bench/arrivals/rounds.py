"""Synchronized refresh rounds: the dashboard pattern.

After ``core/workload.py`` (timing "periodic"): dashboards refresh
together, so arrivals come in rounds. Here a round starts every
``period_s`` seconds of the window, and its ``per_round`` arrivals fall
uniformly over the first ``spread_s`` seconds of it.
"""
from __future__ import annotations

import numpy as np


def schedule(params: dict, seconds: float, seed: int) -> np.ndarray:
    """Sorted arrival offsets in [0, seconds)."""
    period = float(params["period_s"])
    spread = float(params["spread_s"])
    per = int(params["per_round"])
    rng = np.random.default_rng(seed)
    starts = np.arange(0.0, seconds, period)
    times = (starts[:, None] + rng.uniform(0.0, spread, (len(starts), per)))
    times = np.sort(times.ravel())
    return times[times < seconds]


def rate_qps(params: dict) -> float:
    return int(params["per_round"]) / float(params["period_s"])


def tiny(params: dict) -> dict:
    """The same rounds at the CPU tests' size (a window of 1.5 s): short
    rounds whose bursts still queue and fuse."""
    return {**params, "period_s": 0.5, "spread_s": 0.05, "per_round": 12}
