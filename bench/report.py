"""The run's result line, its earlier lines, and its summary file."""
from __future__ import annotations

import json
from collections import Counter

from . import check, manifest
from . import trace as tr


def metrics(entries: list, run) -> tuple[dict, list]:
    """The metrics that read a number, and the names of those that read
    nothing: a reader that finds nothing returns None, and the metric is
    left out of the line."""
    out, missing = {}, []
    for m in entries:
        v = manifest.load_module("metrics", m["name"], run.bench_dir).read(run)
        if v is None:
            missing.append(m["name"])
        else:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out, missing


def result(cell, run, device: dict, traced: bool) -> dict:
    """The result line. Every entry the cell is given names it (by its
    ``workloads`` or by having none), so a metric that reads nothing here
    is a fault: ``metrics_missing`` counts them, limit 0."""
    failed = sum(1 for r in run.queries if r.state != "done")
    dev = dict(device, memory_peak_bytes=run.memory_peak_bytes)
    found, missing = metrics(cell.per_layer if traced else cell.end_to_end, run)
    run.checks["metrics_missing"] = {"value": len(missing), "limit": 0}
    run.metrics_missing = missing
    res = {
        "correct": check.correct(run.checks),
        "attempted": len(run.queries),
        "failed": failed,
        "metrics": found,
        "device": dev,
    }
    if traced and run.trace is not None:
        dev["busy_s"] = tr.busy_s(run.trace)
        dev["window_s"] = tr.window_s(run.trace)
        res["breakdown"] = {"device_ops": tr.top_ops(run.trace),
                            "idle_gaps": tr.idle_gaps(run.trace)}
    res["checks"] = run.checks
    return res


def earlier_lines(run) -> list:
    levels = Counter(r.level for r in run.queries)
    done = Counter(r.level for r in run.queries if r.state == "done")
    fused = Counter(len(e.members) for e in run.executions.values())
    return [
        f"window: {run.seconds} s, queries due {dict(levels)}, done {dict(done)}",
        f"generator: latest submit {run.lateness_max_s * 1000:.3f} ms after its due time",
        f"compiles in window: {len(run.compiles_in_window)} served programs"
        f" {run.compiles_in_window}; {run.backend_compiles_in_window} XLA compiles",
        f"executed programs by batch size: {dict(sorted(fused.items()))}",
        f"gc in window: {len(run.gc_pauses)} collections, longest"
        f" {max((t for _, t in run.gc_pauses), default=0.0) * 1000:.3f} ms,"
        f" {sum(g == 2 for g, _ in run.gc_pauses)} of generation 2",
        "setup: " + ", ".join(f"{n} {t:.3f} s" for n, t in run.setup_phases),
        f"device memory (peak, in use): end of set-up {run.memory_setup},"
        f" end of window {run.memory_window}",
        f"metrics that read nothing: {run.metrics_missing}",
    ]


def write_summary(path, run, result: dict) -> None:
    """Per-query records and the result, for reading after a chip run."""
    summary = {
        "result": result,
        "window": run.window,
        "setup_s": run.setup_s,
        "queries": [r.__dict__ for r in run.queries],
        "executions": [[e.qid, e.members] for e in run.executions.values()],
    }
    if run.trace is not None:
        ev = run.trace
        mods = ev.modules[0] if ev.modules else tr.Series()
        summary["trace"] = {
            "n_ops": [len(o) for o in ev.ops],
            "n_modules": [len(m) for m in ev.modules],
            "modules": sorted(Counter(mods.names[i] for i in mods.idx).items()),
            "top_ops": tr.top_ops(ev, 40),
            "spans": Counter(s[0] for s in ev.spans),
            "read_s": run.trace_read_s,
        }
    path.write_text(json.dumps(summary, default=str))
