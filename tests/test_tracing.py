"""The live engine's spans (core/tracing.py) in a profiler trace, read
back with the benchmark's readers (bench/trace.py for the harness's
spans, bench/program_spans.py for the program's): every span of the
tree is written, stages nest in their placement on one thread with the
query's qid, the harness's stage span lies inside the program's, and
the engine clock carried in ``t`` places every Query time on the
trace's clock."""
import statistics
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402
from bench import program_spans as ps  # noqa: E402
from bench import trace as tr  # noqa: E402
from repro.core.live import LiveConfig, LiveEngine  # noqa: E402
from repro.core.pools import PoolSpec  # noqa: E402
from repro.core.query import Query, QueryWork  # noqa: E402
from repro.core.sla import ServiceLevel, SLAConfig  # noqa: E402

#: every span the program writes (docs/live.md, "Tracing")
SPANS = ("service.submit", "service.poll", "coordinator.route",
         "executor.wait", "executor.query", "executor.stage",
         "stage.inputs", "stage.dispatch", "stage.sync", "stage.checkpoint",
         "executor.boundary", "model.compile")
MS = 1e6  # ns


def _q(sla):
    return Query(work=QueryWork(arch="paper-default", batch=1), sla=sla,
                 submit_time=0.0)


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """A one-worker engine traced from start to drain: an IMMEDIATE
    query alone, then three RELAXED queries that fuse (fuse_max 2) and
    one more IMMEDIATE query. Both batch sizes compile inside the trace.
    The harness's stage recorder is installed, as in a benchmark run."""
    import jax

    out = tmp_path_factory.mktemp("trace")
    eng = LiveEngine(LiveConfig(
        pools=[PoolSpec(name="vm", kind="reserved", chips=1)],
        sla=SLAConfig(relaxed_deadline_s=10.0, poll_period_s=0.01,
                      vm_overload_threshold=1),
        fuse_queries=True, fuse_max=2, decode_tokens=4, decode_chunk_tokens=2,
    ))
    rec = harness._Recorder(annotate=True)
    rec.install(eng.pools[0])
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(out), profiler_options=opts)
    try:
        first = _q(ServiceLevel.IMMEDIATE)
        eng.submit(first)
        qs = [first]
        deadline = time.monotonic() + 120
        while first.state != "done" and time.monotonic() < deadline:
            time.sleep(0.005)
        for sla in (ServiceLevel.RELAXED,) * 3 + (ServiceLevel.IMMEDIATE,):
            q = _q(sla)
            eng.submit(q)
            qs.append(q)
        done = eng.drain(len(qs), timeout=120)
    finally:
        jax.profiler.stop_trace()
    assert len(done) == len(qs) and all(q.state == "done" for q in qs)
    path = tr.find_xplane(out)
    return qs, rec, tr.read_xplane(path), ps.read_program(path)


def _named(program, name):
    return [s for s in program if s[0] == "repro." + name]


def test_every_span_of_the_tree_is_written_with_its_arguments(traced):
    qs, _, _, program = traced
    names = {s[0] for s in program}
    assert names == {"repro." + n for n in SPANS}
    args = {"service.submit": {"qid", "level"},
            "service.poll": {"t", "pending", "released", "left"},
            "coordinator.route": {"qid", "members", "pool"},
            "executor.wait": {"pool"},
            "executor.query": {"qid", "batch", "level", "cursor"},
            "executor.stage": {"qid", "stage", "t"},
            "executor.boundary": {"qid"},
            "model.compile": {"arch", "batch"}}
    for name, keys in args.items():
        for s in _named(program, name):
            assert set(s[4]) == keys, (name, s[4])
    assert sorted(s[4]["batch"] for s in _named(program, "model.compile")) == [1, 2]
    submitted = {q.qid for q in qs}
    assert {s[4]["qid"] for s in _named(program, "service.submit")} == submitted
    # three RELAXED queries, fuse_max 2: one batch of two, one alone
    members = sorted(s[4]["members"] for s in _named(program, "coordinator.route"))
    assert members == [1, 1, 1, 2]
    polls = _named(program, "service.poll")
    assert sum(s[4]["released"] for s in polls) == 2
    assert all(s[4]["left"] <= s[4]["pending"] for s in polls)


def test_stages_nest_in_their_placement_with_one_qid(traced):
    _, _, _, program = traced
    routed = {s[4]["qid"] for s in _named(program, "coordinator.route")}
    queries = _named(program, "executor.query")
    stages = _named(program, "executor.stage")
    assert {s[4]["qid"] for s in queries} == routed
    for st in stages:
        holders = [p for p in queries if p[3] == st[3] and p[1] <= st[1]
                   and st[2] <= p[2]]
        assert len(holders) == 1 and holders[0][4]["qid"] == st[4]["qid"]
        inner = [s for s in program if s[0].startswith("repro.stage.")
                 and st[1] <= s[1] and s[2] <= st[2]]
        assert inner and all(s[3] == st[3] for s in inner)
    # one prefill and two decode chunks per placement
    assert len(stages) == 3 * len(queries)


def test_harness_stage_span_lies_inside_the_program_stage(traced):
    _, _, ev, program = traced
    assert {s[0] for s in ev.spans} == {"bench.stage"}  # repro.* kept apart
    stages = _named(program, "executor.stage")
    bench = [s for s in ev.spans if s[0] == "bench.stage"]
    assert len(bench) == len(stages)
    for _, s, e in bench:
        assert any(p[1] <= s and e <= p[2] for p in stages)


def test_engine_clock_places_query_times_on_the_trace(traced):
    qs, rec, _, program = traced
    off = ps.engine_offsets(program)
    mid = ps.engine_offset(program)
    assert len(off) >= 10
    # t is stamped a microsecond before its span opens: the middle half
    # of the spans agree to well under 1 ms (a thread preempted between
    # the two would read one span late, not move the median)
    q1, _, q3 = statistics.quantiles(off, n=4)
    assert q3 - q1 < 1 * MS
    assert sum(abs(o - mid) < 1 * MS for o in off) >= 0.9 * len(off)
    # each stage's t is its StageEvent's billed start, exactly
    stage_t = {(s[4]["qid"], s[4]["stage"]): s[4]["t"]
               for s in _named(program, "executor.stage")}
    for q in qs:
        if q.fused_with <= 1:
            for e in q.stage_trace:
                assert stage_t[(q.qid, e.stage)] == e.start
    # every query's start_time lands on the start of its first stage
    exec_of = {m: k for k, ex in rec.executions.items() for m in ex.members}
    first = {}
    for s in _named(program, "executor.stage"):
        first[s[4]["qid"]] = min(first.get(s[4]["qid"], s[1]), s[1])
    gaps = [first[exec_of[q.qid]] - (q.start_time * 1e9 + mid) for q in qs]
    # never after its first stage; a thread the OS sets aside may start
    # one late, but not the typical query
    assert all(g > -1 * MS for g in gaps), gaps
    assert statistics.median(gaps) < 1 * MS
