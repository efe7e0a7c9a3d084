"""One run of one cell: set-up, an open-loop window, the drain, the
check against the plain reference, and the metrics.

The system under test is ``LiveEngine`` as the program builds it; the
harness only submits queries (``LiveEngine.submit``), waits for them,
and reads what they record. To see what the served path produced, it
wraps the executor's stage method with a call-through (as
``core/chaos.py`` wraps the same method): the wrapper hands the stage a
copy of the compiled model whose ``prefill``/``decode`` are the same
executables, recording their input prompts and output tokens as device
arrays. Nothing is copied to the host inside the window.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import itertools
import math
import sys
import threading
import time
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

from . import manifest
from . import trace as tr

#: how long past the window's close the harness waits for queries due
#: in the window; a query unfinished then never came
DRAIN_S = 60.0


@dataclass
class QueryRecord:
    qid: int
    level: str
    due: float  # scheduled arrival, engine clock
    lateness: float  # submit - due, seconds
    batch: int
    output_tokens: int
    state: str = "pending"
    dequeue: float | None = None
    start: float | None = None
    finish: float | None = None
    chip_s: float = 0.0
    fused_with: int = 0
    done_count: int = 0  # times the engine reported it finished


@dataclass
class Execution:
    """One executed program run: a query, or a fused batch of them."""

    qid: int
    members: list  # member qids in row order, each the traffic's batch rows
    prompt: object = None  # (B, S) device array fed to prefill
    tokens: list = field(default_factory=list)  # (B, 1) device arrays


@dataclass
class Run:
    """Everything a metric reader may read of one run."""

    cell: str
    seed: int
    seconds: float
    config: dict
    traffic: dict
    dims: dict
    peaks: dict
    prompt_tokens: int
    #: where the cell's families, metrics and kernels are loaded from
    bench_dir: Path
    setup_s: float = 0.0
    window: tuple = (0.0, 0.0)  # engine clock
    queries: list = field(default_factory=list)
    executions: dict = field(default_factory=dict)
    #: batch size of every executed prefill in the window
    prefill_batches: list = field(default_factory=list)
    #: (batch, position) of every executed decode step in the window
    decode_steps: list = field(default_factory=list)
    compiles_in_window: list = field(default_factory=list)
    backend_compiles_in_window: int = 0
    lateness_max_s: float = 0.0
    #: (generation, seconds) of each collection of Python's cyclic
    #: garbage collector inside the window
    gc_pauses: list = field(default_factory=list)
    memory_peak_bytes: int = 0
    #: device memory at the end of set-up and of the window:
    #: (peak, in use) in bytes
    memory_setup: tuple = (0, 0)
    memory_window: tuple = (0, 0)
    #: (phase, seconds) of set-up, in order
    setup_phases: list = field(default_factory=list)
    trace: tr.Events | None = None
    trace_read_s: float = 0.0
    checks: dict = field(default_factory=dict)
    #: the same checks with the fp8 control in the program's place
    control_checks: dict = field(default_factory=dict)
    #: names of the cell's metrics that read nothing in this run
    metrics_missing: list = field(default_factory=list)

    def finished_in_window(self) -> list:
        w1 = self.window[1]
        return [q for q in self.queries
                if q.state == "done" and q.finish is not None and q.finish <= w1]


class _Recorder:
    """The call-through wrapper around ``_run_stage_work``."""

    def __init__(self, annotate: bool):
        self.lock = threading.Lock()
        self.executions: dict[int, Execution] = {}
        self.prefills: list = []  # (exec qid, batch)
        self.decodes: list = []  # (exec qid, batch, position)
        self.annotate = annotate
        self.recording = True

    def install(self, pool) -> None:
        orig = pool._run_stage_work

        def wrapped(lm, q, _orig=orig):
            ex = self._execution(q)
            first = q.stage_cursor == 0

            def prefill(params, toks, kw, _f=lm.prefill):
                out = _f(params, toks, kw)
                ex.prompt = toks
                ex.tokens.append(out[0])
                return out

            def decode(params, cache, tok, _f=lm.decode):
                out = _f(params, cache, tok)
                ex.tokens.append(out[0])
                return out

            proxy = dataclasses.replace(lm, prefill=prefill, decode=decode)
            n0 = len(ex.tokens)
            span = (jax_annotation("bench.stage") if self.annotate
                    else contextlib.nullcontext())
            with span:
                _orig(proxy, q)
            if self.recording:
                b = max(1, q.work.batch)
                with self.lock:
                    if first:
                        self.prefills.append((q.qid, b))
                    for i in range(n0, len(ex.tokens)):
                        if i > 0:  # token i-1 fed at position S + i - 1
                            self.decodes.append(
                                (q.qid, b, q.work.prompt_tokens + i - 1))

        pool._run_stage_work = wrapped

    def _execution(self, q) -> Execution:
        with self.lock:
            ex = self.executions.get(q.qid)
            if ex is None:
                members = [m.qid for m in q.members] if q.members else [q.qid]
                ex = self.executions[q.qid] = Execution(q.qid, members)
            return ex

    def clear(self) -> None:
        with self.lock:
            self.executions.clear()
            self.prefills.clear()
            self.decodes.clear()


class _Tracer(threading.Thread):
    """Profiles a part of the window, from its own thread: starts the
    profiler at ``start`` (monotonic), marks ``bench.window`` for
    ``seconds``, then stops it. A whole window of serving holds more
    device events than the profiler keeps, so only a part is traced."""

    def __init__(self, trace_dir: Path, start: float, seconds: float):
        super().__init__(name="bench-tracer", daemon=True)
        self.trace_dir, self.t0, self.seconds = trace_dir, start, seconds
        self.error = None
        self.start()

    def run(self) -> None:
        import jax

        try:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1  # the harness's spans, little else
            wait = self.t0 - 0.5 - time.monotonic()
            if wait > 0:
                time.sleep(wait)
            jax.profiler.start_trace(str(self.trace_dir), profiler_options=opts)
            wait = self.t0 - time.monotonic()
            if wait > 0:
                time.sleep(wait)
            with jax_annotation("bench.window"):
                time.sleep(max(0.0, self.t0 + self.seconds - time.monotonic()))
            jax.profiler.stop_trace()
        except Exception as err:  # noqa: BLE001 — surfaced by join()
            self.error = err

    def join(self, timeout=None) -> None:
        super().join(timeout)
        if self.error is not None:
            raise self.error


class _GcPauses:
    """Times every collection of Python's cyclic garbage collector while
    installed: one stops every thread, the executor's among them."""

    def __init__(self):
        self.pauses: list = []
        self._t0 = 0.0
        gc.callbacks.append(self._cb)

    def _cb(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            self.pauses.append((info["generation"], time.perf_counter() - self._t0))

    def remove(self) -> list:
        gc.callbacks.remove(self._cb)
        return self.pauses


def jax_annotation(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


def warm_batches(cell_cfg: dict, traffic: dict) -> list:
    """Every batch size the cell's traffic can make the executor run:
    with fusion on, queries that wait in a pending queue (RELAXED,
    BEST_EFFORT) fuse up to ``fuse_max`` of them."""
    dep = cell_cfg["deployment"]
    b = int(traffic["batch"])
    waits = any(lv != "IMMEDIATE" for lv in traffic["levels"])
    if dep.get("fuse_queries") and waits:
        return [b * k for k in range(1, int(dep["fuse_max"]) + 1)]
    return [b]


def build_engine(config: dict, traffic: dict):
    from repro.core.live import LiveConfig, LiveEngine
    from repro.core.pools import PoolSpec
    from repro.core.sla import Policy, SLAConfig

    dep, model = config["deployment"], config["model"]
    cfg = LiveConfig(
        policy=Policy[dep["policy"]],
        pools=[PoolSpec(**p) for p in dep["pools"]],
        sla=SLAConfig(**dep["sla"]),
        fuse_queries=bool(dep["fuse_queries"]),
        fuse_max=int(dep["fuse_max"]),
        decode_chunk_tokens=int(dep["decode_chunk_tokens"]),
        prompt_tokens=int(traffic["prompt_tokens"]),
        decode_tokens=int(traffic["output_tokens"]),
        published_widths=bool(model["published_widths"]),
        impl=model["impl"],
    )
    return LiveEngine(cfg)


def check_program_config(prog_cfg, config: dict, family) -> list:
    """The program as built against the configuration file, its cut
    applied: every field of the family's ``PROGRAM_FIELDS``."""
    bad = []
    for f, key in family.PROGRAM_FIELDS.items():
        want, got = manifest.file_value(config, key), getattr(prog_cfg, f)
        if got != want:
            bad.append(f"{f}: program {got!r} != config {key} {want!r}")
    return bad


def program_overrides(config: dict, family) -> dict:
    """The ``ModelConfig`` fields that the configuration's cut changes,
    with the values kept on this chip; empty where there is no cut."""
    cut = config.get("cut") or {}
    field_of = {key: f for f, key in family.PROGRAM_FIELDS.items()}
    unknown = sorted(set(cut) - set(field_of))
    if unknown:
        raise RuntimeError(f"the family's PROGRAM_FIELDS sets no field from"
                           f" the cut's {unknown}")
    return {field_of[k]: v for k, v in cut.items()}


@contextlib.contextmanager
def _program_cut(arch: str, overrides: dict):
    """While open, the program's ``_ModelPool._build`` builds ``arch`` with
    ``overrides`` applied to its config (``ModelConfig.replace``), and
    nothing else changes. The engine takes no cut of its own yet."""
    if not overrides:
        yield
        return
    from repro.core import live

    published = live.get_config

    def get_config(name, reduced=False):
        cfg = published(name, reduced)
        return cfg.replace(**overrides) if name == arch else cfg

    live.get_config = get_config
    try:
        yield
    finally:
        live.get_config = published


def install_weights(eng, config: dict, family, seed: int) -> None:
    """Build the arch's served programs, cut as the configuration says,
    then serve the benchmark's weights (made from the seed) in place of
    the program's own."""
    import jax

    arch = config["model"]["arch"]
    models = eng.models
    with _program_cut(arch, program_overrides(config, family)):
        prog_cfg, prog_params, prefill, decode = models._build(arch)
    bad = check_program_config(prog_cfg, config, family)
    if bad:
        raise RuntimeError("program config differs from the file: " + "; ".join(bad))
    want = jax.tree.map(lambda a: (a.shape, a.dtype), prog_params)
    # the program's own weights go before the seed's are made, so that
    # set-up never holds two sets
    del prog_params
    params = family.make_weights(config, seed, program=True)
    got = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    if jax.tree.structure(want) != jax.tree.structure(got) or want != got:
        raise RuntimeError(f"weight layout differs from the program's: {got} vs {want}")
    with models._lock:
        models._archs[arch] = (prog_cfg, params, prefill, decode)


_COMPILES = {"n": 0, "listening": False}


def _count_backend_compiles() -> dict:
    """XLA compilations so far in this process, from JAX's monitoring
    events (the listener is registered once)."""
    import jax

    if not _COMPILES["listening"]:
        def listener(event, duration, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                _COMPILES["n"] += 1

        jax.monitoring.register_event_duration_secs_listener(listener)
        _COMPILES["listening"] = True
    return _COMPILES


def _memory(dev) -> tuple:
    stats = dev.memory_stats() or {}
    return (int(stats.get("peak_bytes_in_use", 0)), int(stats.get("bytes_in_use", 0)))


def _qid_base(seed: int) -> int:
    rng = np.random.default_rng([int(seed) % (1 << 63), 7])
    return int(rng.integers(1 << 20, 1 << 29))


def run_cell(cell: manifest.Cell, seed: int, seconds: float, trace: bool,
             t_process: float, out_dir: Path, require_chip: bool = True,
             control: bool = False, fault=None, keep_trace: bool = False,
             log=None) -> Run:
    """One run. ``fault`` (tests only) is called with the engine after
    set-up and may break the served path underneath."""
    import jax

    from repro.core import query as query_mod
    from repro.core.query import Query, QueryWork
    from repro.core.sla import ServiceLevel

    log = log or (lambda s: print(s, file=sys.stderr, flush=True))
    config, traffic, bench_dir = cell.config, cell.traffic, cell.bench_dir
    family = manifest.load_module("families", config["model"]["family"], bench_dir)
    arrivals = manifest.load_module("arrivals", traffic["arrivals"], bench_dir)
    dev = jax.devices()[0]
    peaks = (manifest.load_peaks(dev.device_kind, bench_dir) if require_chip
             else {"bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 1.0})
    run = Run(cell=cell.name, seed=seed, seconds=seconds, config=config,
              traffic=traffic, dims=family.dims(config), peaks=peaks,
              prompt_tokens=int(traffic["prompt_tokens"]), bench_dir=bench_dir)
    compiles = _count_backend_compiles()
    t_phase = [time.monotonic()]

    def phase(name: str) -> None:
        now = time.monotonic()
        run.setup_phases.append((name, now - t_phase[0]))
        t_phase[0] = now

    run.setup_phases.append(("start and device", t_phase[0] - t_process))

    # --- set-up: engine, weights from the seed, every shape warmed --------
    base = _qid_base(seed)
    query_mod._qid = itertools.count(base + 10_000_000)  # fused batches
    eng = build_engine(config, traffic)
    try:
        install_weights(eng, config, family, seed)
        phase("engine and weights")
        arch = config["model"]["arch"]
        batches = warm_batches(config, traffic)
        for b in batches:
            eng.models.ensure(arch, b)
        phase("served programs")
        rec = _Recorder(annotate=trace)
        for pool in eng.pools:
            rec.install(pool)
        if fault is not None:
            fault(eng)
        work = QueryWork(arch=arch, kind="serve", batch=int(traffic["batch"]),
                         prompt_tokens=int(traffic["prompt_tokens"]),
                         output_tokens=int(traffic["output_tokens"]))
        # one query per level through the whole path, outside the window
        warm = [Query(work=work, sla=ServiceLevel[lv], submit_time=0.0,
                      qid=base - 1 - i)
                for i, lv in enumerate(sorted(set(traffic["levels"])))]
        for q in warm:
            eng.submit(q)
        _wait(warm, time.monotonic() + 600.0)
        phase("one query per level")
        rec.clear()
        compiled_before = set(eng.models.compile_s)
        n_compiles_before = compiles["n"]
        sched = arrivals.schedule(traffic["params"], seconds,
                                  int(traffic["schedule_seed"]))
        levels = traffic["levels"]
        trace_dir = out_dir / "trace"
        if trace:
            import shutil

            shutil.rmtree(trace_dir, ignore_errors=True)
        # Set-up (tracing, compiling, loading) leaves millions of Python
        # objects behind; the first full collection that scans them stops
        # the executor for seconds. Collect them here and exempt the
        # survivors from later collections.
        gc.collect()
        gc.freeze()
        phase("garbage collection")
        run.memory_setup = _memory(dev)
        gc_pauses = _GcPauses()
        run.setup_s = time.monotonic() - t_process

        # --- the window: open loop, timed from each scheduled arrival ------
        t_origin = time.monotonic() + 0.01
        w0 = t_origin - eng._t0
        run.window = (w0, w0 + seconds)
        qs = []
        tracer = None
        if trace:
            t_lo, t_len = (float(x) for x in traffic["trace_window"])
            t_len = min(t_len, seconds)
            t_lo = min(t_lo, seconds - t_len)
            tracer = _Tracer(trace_dir, t_origin + t_lo, t_len)
        for i, off in enumerate(sched):
            due = t_origin + float(off)
            wait = due - time.monotonic()
            if wait > 0:
                time.sleep(wait)
            q = Query(work=work, sla=ServiceLevel[levels[i % len(levels)]],
                      submit_time=0.0, qid=base + i)
            late = time.monotonic() - due
            if trace:
                with jax_annotation("bench.submit"):
                    eng.submit(q)
            else:
                eng.submit(q)
            qs.append(q)
            run.queries.append(QueryRecord(
                qid=q.qid, level=q.sla.name, due=due - eng._t0, lateness=late,
                batch=q.work.batch, output_tokens=q.work.output_tokens))
        close = t_origin + seconds
        if close > time.monotonic():
            time.sleep(close - time.monotonic())
        _wait(qs, close + DRAIN_S)
        if tracer is not None:
            tracer.join()
        rec.recording = False
        run.gc_pauses = gc_pauses.remove()
        gc.unfreeze()
        run.compiles_in_window = sorted(
            set(eng.models.compile_s) - compiled_before)
        run.backend_compiles_in_window = compiles["n"] - n_compiles_before
        run.lateness_max_s = max((r.lateness for r in run.queries), default=0.0)
        run.memory_window = _memory(dev)
        run.memory_peak_bytes = run.memory_window[0]
    finally:
        eng.shutdown()

    # --- what the window produced --------------------------------------
    with eng._lock:
        reported = list(eng.done) + list(eng.failed)
    counts: dict = {}
    for q in reported:
        counts[q.qid] = counts.get(q.qid, 0) + 1
    for r, q in zip(run.queries, qs):
        r.state, r.dequeue, r.start, r.finish = (
            q.state, q.dequeue_time, q.start_time, q.finish_time)
        r.chip_s, r.fused_with = q.chip_seconds, q.fused_with
        r.done_count = counts.get(q.qid, 0)
    in_window = {r.qid for r in run.queries}
    run.executions = {k: v for k, v in rec.executions.items()
                      if set(v.members) <= in_window}
    run.prefill_batches = [b for k, b in rec.prefills if k in run.executions]
    run.decode_steps = [(b, p) for k, b, p in rec.decodes if k in run.executions]

    from . import check

    run.checks = check.structural(run, qs, eng, require_chip)
    rows = check.sample_rows(run, seed)
    rows = [(tok, check.row_tokens(run.executions[e], j)) for e, j, tok in rows]
    del eng, qs, rec
    run.executions = {k: dataclasses.replace(v, prompt=None, tokens=[])
                      for k, v in run.executions.items()}
    gc.collect()
    if trace:
        t_read = time.monotonic()
        xplane = tr.find_xplane(trace_dir)
        ev = tr.read_xplane(xplane)
        run.trace = ev
        run.trace_read_s = time.monotonic() - t_read
        log(f"trace: {xplane.stat().st_size} bytes, {sum(map(len, ev.ops))}"
            f" device ops, read in {run.trace_read_s:.1f} s")
        if keep_trace:
            st = [s for s in ev.spans if s[0] == "bench.stage"]
            if len(st) > 3:
                ev.excerpt(st[2][1] - 1e6, st[2][1] + 4e7).to_json(
                    out_dir / "events_excerpt.json.gz")
        else:
            import shutil

            shutil.rmtree(trace_dir, ignore_errors=True)
    t_ref = time.monotonic()
    run.checks |= check.against_reference(run, family, seed, rows)
    log(f"reference: {len(rows)} rows in {time.monotonic() - t_ref:.1f} s")
    if control:
        run.control_checks = run.checks | check.against_reference(
            run, family, seed, rows, quant="fp8")
    return run


def _wait(qs, deadline: float) -> None:
    while time.monotonic() < deadline:
        if all(q.state in ("done", "failed") for q in qs):
            return
        time.sleep(0.005)


def percentile(values, p: float) -> float:
    """Nearest-rank percentile; ``math.inf`` sorts above every number."""
    v = sorted(values)
    if not v:
        return math.nan
    k = max(0, math.ceil(p / 100.0 * len(v)) - 1)
    return v[k]
