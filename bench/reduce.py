"""Arithmetic the metric readers share: percentiles over the window's
queries, and device time and work from the trace."""
from __future__ import annotations

import math

from . import trace as tr
from .harness import DRAIN_S, percentile


def tail(run, level: str, p: float, since_due) -> float | None:
    """p-th percentile over every query of ``level`` due in the window of
    ``since_due(record)`` seconds; a query that failed or never finished
    sorts above every finished one (and reads as the time waited for it)."""
    vals = []
    for r in run.queries:
        if r.level != level:
            continue
        v = since_due(r) if r.state == "done" else None
        vals.append(math.inf if v is None else v)
    if not vals:
        return None
    x = percentile(vals, p)
    if math.isinf(x):
        return run.seconds + DRAIN_S
    return x


def kernel_roofline(run, kernel: str) -> float | None:
    """Least time the chip could take for the kernel's calls (the larger
    of FLOPs over peak and bytes over bandwidth), over the kernel's device
    time, in %. Calls come from the window's executed stages; when the
    trace holds a different number of the kernel's events, the per-call
    mean is scaled to the events it holds."""
    if run.trace is None or not run.trace.ops:
        return None
    from .manifest import load_module

    k = load_module("kernels", kernel, run.bench_dir)
    calls = k.calls(run)
    n, secs = tr.op_time(run.trace, k.NAMES)
    if not calls or n == 0 or secs <= 0:
        return None
    pf, pb = run.peaks["bf16_flops_per_s"], run.peaks["hbm_bytes_per_s"]
    need = sum(max(f / pf, b / pb) for f, b in calls)
    return 100.0 * need / len(calls) * n / secs


def module_time(run, prefix: str):
    if run.trace is None:
        return 0, 0.0
    return tr.module_time(run.trace, prefix)
