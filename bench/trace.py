"""From a profiler trace to the numbers the per-layer metrics read.

``read_xplane`` turns the ``.xplane.pb`` that ``jax.profiler`` writes
into plain arrays (``Events``); the functions below reduce them. Every
time is in nanoseconds on the trace's own clock, on which the harness's
host spans (``bench.*`` annotations) and the device's operations lie
together.

On a TPU, the "XLA Ops" line of a device plane holds every operation
that ran, named by its HLO text (``%fusion.82 = bf16[...] fusion(...)``).
A loop (``%while``) is an operation too, and the operations of its body
lie inside it. Device busy time is the union of all of them; time per
operation and per kernel counts only the operations that hold no others.
The traced window is the harness's ``bench.window`` span.
"""
from __future__ import annotations

import gzip
import json
import re
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

#: host spans the harness records: window, submit, stage
SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: operations that hold the operations of a nested computation
CONTAINERS = ("%while", "%conditional", "%call")
#: characters of an operation's HLO text kept as its name
NAME_CHARS = 200


@dataclass
class Series:
    """Events of one trace line: interned names, and per event the name's
    index, start and end (ns)."""

    names: list = field(default_factory=list)
    idx: np.ndarray = field(default_factory=lambda: np.zeros(0, np.int32))
    start: np.ndarray = field(default_factory=lambda: np.zeros(0))
    end: np.ndarray = field(default_factory=lambda: np.zeros(0))

    def __len__(self) -> int:
        return len(self.idx)

    def to_dict(self) -> dict:
        return {"names": self.names, "idx": self.idx.tolist(),
                "start": self.start.tolist(), "end": self.end.tolist()}

    @classmethod
    def from_dict(cls, d: dict) -> "Series":
        return cls(d["names"], np.asarray(d["idx"], np.int32),
                   np.asarray(d["start"], float), np.asarray(d["end"], float))

    def clip_to(self, lo: float, hi: float) -> "Series":
        keep = (self.start >= lo) & (self.end <= hi)
        return Series(self.names, self.idx[keep], self.start[keep],
                      self.end[keep])


def _series(events) -> Series:
    names: list = []
    index: dict = {}
    idx, start, end = [], [], []
    for e in events:
        n = e.name[:NAME_CHARS]
        i = index.get(n)
        if i is None:
            i = index[n] = len(names)
            names.append(n)
        idx.append(i)
        start.append(e.start_ns)
        end.append(e.end_ns)
    return Series(names, np.asarray(idx, np.int32), np.asarray(start, float),
                  np.asarray(end, float))


@dataclass
class Events:
    """What the reduction needs of one trace: per device, its operations
    and its programs (XLA modules); and the harness's host spans as
    [name, start_ns, end_ns]."""

    ops: list = field(default_factory=list)
    modules: list = field(default_factory=list)
    spans: list = field(default_factory=list)

    def to_json(self, path: Path) -> None:
        with gzip.open(path, "wt") as f:
            json.dump({"ops": [s.to_dict() for s in self.ops],
                       "modules": [s.to_dict() for s in self.modules],
                       "spans": self.spans}, f)

    @classmethod
    def from_json(cls, path: Path) -> "Events":
        with gzip.open(path, "rt") as f:
            d = json.load(f)
        return cls([Series.from_dict(s) for s in d["ops"]],
                   [Series.from_dict(s) for s in d["modules"]], d["spans"])

    def excerpt(self, lo: float, hi: float) -> "Events":
        """The events inside [lo, hi], with a window span over it."""
        spans = [[n, max(s, lo), min(e, hi)] for n, s, e in self.spans
                 if n != SPAN_PREFIX + "window" and min(e, hi) > max(s, lo)]
        return Events([s.clip_to(lo, hi) for s in self.ops],
                      [s.clip_to(lo, hi) for s in self.modules],
                      [[SPAN_PREFIX + "window", lo, hi]] + spans)


def _is_device_plane(name: str) -> bool:
    return (name.startswith("/device:") and "CPU" not in name
            and "NON_CORE" not in name)


def read_xplane(path: Path) -> Events:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    ev = Events()
    for plane in pd.planes:
        if _is_device_plane(plane.name):
            lines = {line.name: line for line in plane.lines}
            if OPS_LINE in lines or MODULES_LINE in lines:
                ev.ops.append(_series(lines[OPS_LINE].events)
                              if OPS_LINE in lines else Series())
                ev.modules.append(_series(lines[MODULES_LINE].events)
                                  if MODULES_LINE in lines else Series())
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    n = e.name
                    if n.startswith(SPAN_PREFIX):
                        ev.spans.append([n, e.start_ns, e.end_ns])
    return ev


def find_xplane(trace_dir: Path) -> Path:
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


# --- interval arithmetic ----------------------------------------------------

def union(starts, ends) -> np.ndarray:
    """Merged, sorted intervals as an (n, 2) array."""
    s = np.asarray(starts, float)
    e = np.asarray(ends, float)
    keep = e > s
    s, e = s[keep], e[keep]
    if not len(s):
        return np.zeros((0, 2))
    order = np.argsort(s, kind="stable")
    s, e = s[order], np.maximum.accumulate(e[order])
    new = np.ones(len(s), bool)
    new[1:] = s[1:] > e[:-1]
    first = np.flatnonzero(new)
    last = np.r_[first[1:] - 1, len(s) - 1]
    return np.stack([s[first], e[last]], axis=1)


def covered(merged: np.ndarray, lo: float, hi: float) -> float:
    """Length of [lo, hi] that the merged intervals cover."""
    if not len(merged):
        return 0.0
    s = np.clip(merged[:, 0], lo, hi)
    e = np.clip(merged[:, 1], lo, hi)
    return float(np.sum(e - s))


def window(ev: Events) -> tuple:
    w = [s for s in ev.spans if s[0] == SPAN_PREFIX + "window"]
    if not w:
        raise ValueError("trace holds no bench.window span")
    return float(w[0][1]), float(w[0][2])


def busy(ev: Events) -> list:
    """Per device: merged intervals with an operation running, clipped to
    the traced window."""
    lo, hi = window(ev)
    out = []
    for s in ev.ops:
        m = union(np.clip(s.start, lo, hi), np.clip(s.end, lo, hi))
        out.append(m)
    return out


def busy_s(ev: Events) -> float:
    """Seconds with an operation on the device, averaged over devices."""
    lo, hi = window(ev)
    per = [covered(b, lo, hi) for b in busy(ev)]
    return sum(per) / max(1, len(per)) / 1e9


def window_s(ev: Events) -> float:
    lo, hi = window(ev)
    return (hi - lo) / 1e9


def spans(ev: Events, name: str) -> np.ndarray:
    lo, hi = window(ev)
    iv = [(max(s, lo), min(e, hi)) for n, s, e in ev.spans
          if n == SPAN_PREFIX + name and min(e, hi) > max(s, lo)]
    if not iv:
        return np.zeros((0, 2))
    return union([a for a, _ in iv], [b for _, b in iv])


def idle_inside(ev: Events, name: str):
    """(idle seconds, span seconds) of device 0 inside the named spans."""
    sp = spans(ev, name)
    if not len(sp) or not ev.ops:
        return None
    b = busy(ev)[0]
    total = float(np.sum(sp[:, 1] - sp[:, 0]))
    busy_in = sum(covered(b, s, e) for s, e in sp)
    return (total - busy_in) / 1e9, total / 1e9


def short_name(text: str) -> str:
    """``%flash_attention.5`` of ``%flash_attention.5 = bf16[...] ...``."""
    return text.split(" = ", 1)[0]


def _leaf_totals(ev: Events) -> tuple:
    """Per op name of device 0, in the window: (count, seconds), leaves only."""
    if not ev.ops:
        return [], np.zeros(0), np.zeros(0)
    lo, hi = window(ev)
    s = ev.ops[0].clip_to(lo, hi)
    names = s.names
    leaf = np.array([not short_name(n).startswith(CONTAINERS) for n in names],
                    bool)
    n = np.bincount(s.idx, minlength=len(names)).astype(float)
    t = np.bincount(s.idx, weights=s.end - s.start, minlength=len(names))
    return names, np.where(leaf, n, 0.0), np.where(leaf, t, 0.0)


def op_time(ev: Events, patterns):
    """(count, seconds) of the device-0 operations whose own name (the
    part before " = ") holds one of the patterns, in the traced window."""
    names, n, t = _leaf_totals(ev)
    hit = [i for i, nm in enumerate(names)
           if any(p in short_name(nm) for p in patterns)]
    return int(sum(n[i] for i in hit)), float(sum(t[i] for i in hit)) / 1e9


def module_time(ev: Events, prefix: str):
    """(count, seconds) of the device-0 programs whose name starts with
    ``prefix`` (e.g. "jit_decode"), in the traced window."""
    if not ev.modules:
        return 0, 0.0
    lo, hi = window(ev)
    s = ev.modules[0].clip_to(lo, hi)
    hit = np.array([nm.startswith(prefix) for nm in s.names], bool)
    if not len(hit):
        return 0, 0.0
    m = hit[s.idx]
    return int(m.sum()), float(np.sum((s.end - s.start)[m])) / 1e9


def readable(text: str, chars: int = 120) -> str:
    """An operation's HLO text without its layouts, cut to ``chars``."""
    return re.sub(r"\{[^{}]*\}", "", text)[:chars]


def top_ops(ev: Events, k: int = 10) -> list:
    """[name, seconds] of the device-0 operations that took most time."""
    names, _, t = _leaf_totals(ev)
    order = np.argsort(-t)[:k]
    return [[readable(names[i]), float(t[i]) / 1e9] for i in order if t[i] > 0]


def idle_gaps(ev: Events, k: int = 10) -> list:
    """[host span, seconds] of the longest device-0 idle gaps in the
    window, each named by what the host did for most of it: inside a
    harness span ("submit", "stage") or in none of them ("none")."""
    if not ev.ops:
        return []
    lo, hi = window(ev)
    b = busy(ev)[0]
    edges = np.r_[lo, b.ravel(), hi]
    gs, ge = edges[0::2], edges[1::2]
    keep = ge > gs
    gs, ge = gs[keep], ge[keep]
    order = np.argsort(gs - ge)[:k]
    named = {n: spans(ev, n) for n in ("stage", "submit")}
    inside = union(*np.concatenate(list(named.values())).T)
    outside = union(np.r_[lo, inside[:, 1]], np.r_[inside[:, 0], hi])
    out = []
    for i in order:
        cover = {n: covered(iv, gs[i], ge[i]) for n, iv in named.items()}
        cover["none"] = covered(outside, gs[i], ge[i])
        out.append([max(cover, key=cover.get), float(ge[i] - gs[i]) / 1e9])
    return out
