"""The live engine's own spans, read beside the device's operations.

The program writes ``repro.*`` host spans into the profiler's trace
(``src/repro/core/tracing.py``; the span tree and every argument are in
docs/live.md, "Tracing"). ``bench/trace.py`` reads the device and the
harness's ``bench.*`` spans of the same ``.xplane.pb``; this module
reads the program's spans from it and puts the two together:

- ``idle_by_cause``: every instant of device-0 idle time in the traced
  window, put down to one cause of five (``CAUSES``), in seconds;
- ``idle_starved_share``, ``idle_boundary_share``: two of those causes
  over the window, in %;
- ``stage_launch_ms_p50``: median time from the start of a stage to its
  first served program on the device;
- ``poll_busy_share``: the scheduler's polls, which hold the engine
  lock, over the window, in %;
- ``engine_offset``: the engine clock (``LiveEngine.now()``, on which
  every ``Query`` time is stamped) placed on the trace's clock.

Each program span is ``[name, start_ns, end_ns, line, args]``: ``line``
numbers the host thread it came from, ``args`` holds its arguments.

    python3 bench/program_spans.py <trace dir> [--save events.json.gz]

reads the trace that ``bench/run.py --trace 1 --keep-trace`` keeps in
``bench_out/<cell>.<seed>.1/trace`` and prints these numbers, and the
longest idle gaps named by what each host thread was doing, as JSON.
``--save`` writes the device events, the harness's spans and the
program's spans to one file that ``load`` reads; ``trace.Events.from_json``
reads the same file and ignores the program's spans.
"""
from __future__ import annotations

import argparse
import gzip
import json
import statistics
import sys
from pathlib import Path

import numpy as np

if __package__ in (None, ""):
    sys.path[0] = str(Path(__file__).resolve().parent.parent)

from bench import trace as tr  # noqa: E402

PREFIX = "repro."
#: idle causes, in the order they are tried: the first that fits an
#: instant takes it
CAUSES = ("compile", "stage", "starved", "empty", "boundary")
#: served programs whose start ends a stage's launch
SERVED = ("jit_prefill", "jit_decode")


def read_program(path: Path) -> list:
    """The ``repro.*`` spans of every host thread in an ``.xplane.pb``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    out, line_no = [], 0
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX):
                    out.append([e.name, e.start_ns, e.end_ns, line_no,
                                {k: v for k, v in e.stats}])
            line_no += 1
    return out


def save(path: Path, ev: tr.Events, program: list) -> None:
    with gzip.open(path, "wt") as f:
        json.dump({"ops": [s.to_dict() for s in ev.ops],
                   "modules": [s.to_dict() for s in ev.modules],
                   "spans": ev.spans, "program": program}, f)


def load(path: Path) -> tuple:
    """(Events, program spans) of a file ``save`` wrote."""
    with gzip.open(path, "rt") as f:
        program = json.load(f).get("program", [])
    return tr.Events.from_json(path), program


def excerpt(ev: tr.Events, program: list, lo: float, hi: float) -> tuple:
    """The events inside [lo, hi], with the program spans that overlap
    it kept whole (a stage or a poll cut at the edge would read short)."""
    return ev.excerpt(lo, hi), [s for s in program if s[2] > lo and s[1] < hi]


# --- interval arithmetic over the window -------------------------------------

def intervals(program: list, name: str, lo: float, hi: float) -> np.ndarray:
    """Merged intervals of the spans named ``name``, clipped to [lo, hi]."""
    iv = [(max(s, lo), min(e, hi)) for n, s, e, _, _ in program
          if n == name and min(e, hi) > max(s, lo)]
    if not iv:
        return np.zeros((0, 2))
    return tr.union([a for a, _ in iv], [b for _, b in iv])


def _inside(merged: np.ndarray, x: np.ndarray) -> np.ndarray:
    """Per point of x: whether a merged interval holds it."""
    if not len(merged):
        return np.zeros(len(x), bool)
    i = np.searchsorted(merged[:, 0], x, side="right") - 1
    return (i >= 0) & (x < merged[np.maximum(i, 0), 1])


def idle(ev: tr.Events) -> np.ndarray:
    """Merged intervals of the window in which device 0 ran nothing."""
    lo, hi = tr.window(ev)
    b = tr.busy(ev)[0]
    edges = np.r_[lo, b.ravel(), hi]
    gs, ge = edges[0::2], edges[1::2]
    keep = ge > gs
    return np.stack([gs[keep], ge[keep]], axis=1)


def _cause_masks(ev: tr.Events, program: list):
    """Elementary pieces of the window (cut at every edge of an idle
    interval, a cause's span and a poll's end): per piece its middle,
    its length, whether the device idles, and per cause whether the
    cause takes it."""
    lo, hi = tr.window(ev)
    idle_iv = idle(ev)
    spans = {k: intervals(program, PREFIX + k, lo, hi)
             for k in ("model.compile", "executor.stage", "executor.wait")}
    polls = sorted((s[2], s[4].get("left", 0)) for s in program
                   if s[0] == PREFIX + "service.poll")
    poll_end = np.array([e for e, _ in polls], float)
    poll_left = np.array([n for _, n in polls], float)
    cuts = [idle_iv.ravel()] + [m.ravel() for m in spans.values()]
    cuts.append(poll_end[(poll_end > lo) & (poll_end < hi)])
    pts = np.unique(np.clip(np.concatenate([[lo, hi], *cuts]), lo, hi))
    mid = (pts[:-1] + pts[1:]) / 2
    length = np.diff(pts)
    pending = np.zeros(len(mid), bool)
    if len(polls):  # the latest poll to end before each middle
        k = np.searchsorted(poll_end, mid, side="right") - 1
        pending = (k >= 0) & (poll_left[np.maximum(k, 0)] > 0)
    waiting = _inside(spans["executor.wait"], mid)
    fits = {
        "compile": _inside(spans["model.compile"], mid),
        "stage": _inside(spans["executor.stage"], mid),
        "starved": waiting & pending,
        "empty": waiting & ~pending,
        "boundary": np.ones(len(mid), bool),
    }
    taken = np.zeros(len(mid), bool)
    out = {}
    for c in CAUSES:
        out[c] = fits[c] & ~taken
        taken |= fits[c]
    return mid, length, _inside(idle_iv, mid), out


def idle_by_cause(ev: tr.Events, program: list) -> dict | None:
    """Seconds of device-0 idle time in the window, by cause:

    - ``compile``: inside a ``repro.model.compile``;
    - ``stage``: a worker inside ``repro.executor.stage``;
    - ``starved``: a worker in ``repro.executor.wait`` while the latest
      ``repro.service.poll`` to end before the instant left queries
      pending;
    - ``empty``: a worker waiting, and that poll left nothing pending
      (or no poll had ended yet);
    - ``boundary``: anything else: a worker between stages or between
      placements.

    The first cause that fits takes the instant, so the five sum to the
    device's idle time."""
    if not ev.ops or not program:
        return None
    _, length, is_idle, masks = _cause_masks(ev, program)
    return {c: float(np.sum(length[is_idle & m])) / 1e9
            for c, m in masks.items()}


def _share_of_window(ev: tr.Events, program: list, cause: str):
    got = idle_by_cause(ev, program)
    return None if got is None else 100.0 * got[cause] / tr.window_s(ev)


def idle_starved_share(ev: tr.Events, program: list) -> float | None:
    """Device idle while a query waits for a poll to release it, over
    the window, in %."""
    return _share_of_window(ev, program, "starved")


def idle_boundary_share(ev: tr.Events, program: list) -> float | None:
    """Device idle with the worker between stages or placements, over
    the window, in %."""
    return _share_of_window(ev, program, "boundary")


def stage_launches(ev: tr.Events, program: list) -> list:
    """Per ``repro.executor.stage`` span wholly in the window, the ns
    from its start to the start of the first served program
    (``jit_prefill*``/``jit_decode*``) that starts inside it on device
    0; stages with none are left out."""
    if not ev.modules:
        return []
    lo, hi = tr.window(ev)
    m = ev.modules[0]
    served = np.array([n.startswith(SERVED) for n in m.names], bool)
    starts = np.sort(m.start[served[m.idx]]) if len(m.idx) else np.zeros(0)
    out = []
    for n, s, e, _, _ in program:
        if n != PREFIX + "executor.stage" or s < lo or e > hi:
            continue
        i = np.searchsorted(starts, s, side="left")
        if i < len(starts) and starts[i] <= e:
            out.append(float(starts[i] - s))
    return out


def stage_launch_ms_p50(ev: tr.Events, program: list) -> float | None:
    got = stage_launches(ev, program)
    return statistics.median(got) / 1e6 if got else None


def device_lags(ev: tr.Events, program: list) -> list:
    """Per ``repro.executor.stage`` span wholly in the window, the ns by
    which the last device-0 program to start in it ends after the span
    does. A stage waits for its programs, so in true time this is never
    above 0: a positive reading is how far the device's timestamps run
    late against the host's in the trace, and bounds how finely a host
    span and a device event can be compared."""
    if not ev.modules:
        return []
    lo, hi = tr.window(ev)
    m = ev.modules[0]
    order = np.argsort(m.start)
    starts, ends = m.start[order], m.end[order]
    out = []
    for n, s, e, _, _ in program:
        if n != PREFIX + "executor.stage" or s < lo or e > hi:
            continue
        i = np.searchsorted(starts, e) - 1
        if i >= 0 and starts[i] >= s:
            out.append(float(ends[i] - e))
    return out


def poll_busy_share(ev: tr.Events, program: list) -> float | None:
    """Union of the scheduler's polls over the window, in %: the time
    the engine lock that every submit waits on is held by a poll."""
    lo, hi = tr.window(ev)
    polls = intervals(program, PREFIX + "service.poll", lo, hi)
    if not len(polls):
        return None
    return 100.0 * tr.covered(polls, lo, hi) / (hi - lo)


def engine_offsets(program: list) -> np.ndarray:
    """Per span that carries an engine-clock ``t``: start_ns - t * 1e9."""
    return np.array([s - a["t"] * 1e9 for _, s, _, _, a in program
                     if "t" in a], float)


def engine_offset(program: list) -> float | None:
    """Trace ns at engine time 0: the median of ``engine_offsets``.
    ``t * 1e9 + engine_offset`` places an engine time on the trace."""
    off = engine_offsets(program)
    return float(np.median(off)) if len(off) else None


def doing(program: list, at: float) -> dict:
    """Per host line, the program spans that hold the instant ``at``,
    outermost first, joined by ">"."""
    held: dict = {}
    for n, s, e, line, _ in sorted(program, key=lambda x: (x[1], -x[2])):
        if s <= at < e:
            held.setdefault(line, []).append(n[len(PREFIX):])
    return {line: ">".join(v) for line, v in held.items()}


def gaps(ev: tr.Events, program: list, k: int = 12) -> list:
    """The k longest device-0 idle gaps: seconds, the cause that takes
    most of each, and what every host line was doing at its middle."""
    if not ev.ops:
        return []
    lo, _ = tr.window(ev)
    iv = idle(ev)
    mid, length, is_idle, masks = _cause_masks(ev, program)
    out = []
    for s, e in iv[np.argsort(iv[:, 0] - iv[:, 1])[:k]]:
        inside = is_idle & (mid >= s) & (mid < e)
        by = {c: float(np.sum(length[inside & m])) / 1e9
              for c, m in masks.items()}
        out.append({"s": float(e - s) / 1e9, "at_s": float(s - lo) / 1e9,
                    "cause": max(by, key=by.get),
                    "doing": doing(program, (s + e) / 2)})
    return out


def report(ev: tr.Events, program: list) -> dict:
    causes = idle_by_cause(ev, program)
    off = engine_offsets(program)
    q = np.percentile(off, [25, 75]) if len(off) else [np.nan, np.nan]
    lag = device_lags(ev, program)
    return {
        "window_s": tr.window_s(ev),
        "busy_s": tr.busy_s(ev),
        "device_idle_share": 100.0 * (1 - tr.busy_s(ev) / tr.window_s(ev)),
        "idle_by_cause_s": causes,
        "metrics": {
            "idle_starved_share": idle_starved_share(ev, program),
            "idle_boundary_share": idle_boundary_share(ev, program),
            "stage_launch_ms_p50": stage_launch_ms_p50(ev, program),
            "poll_busy_share": poll_busy_share(ev, program),
        },
        "engine_offset_ns": engine_offset(program),
        "engine_offset_iqr_ns": float(q[1] - q[0]),
        "device_lag_ms_p5_p50_p95": (np.percentile(lag, [5, 50, 95]) / 1e6
                                     ).tolist() if lag else None,
        "program_spans": len(program),
        "gaps": gaps(ev, program),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace_dir", type=Path)
    ap.add_argument("--save", type=Path,
                    help="write the device events and both kinds of span here")
    args = ap.parse_args(argv)
    path = tr.find_xplane(args.trace_dir)
    ev, program = tr.read_xplane(path), read_program(path)
    if args.save:
        save(args.save, ev, program)
    print(json.dumps(report(ev, program)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
