"""The program's spans read beside the device (bench/program_spans.py):
idle time by cause, stage launch and poll share on hand-made events with
hand counts, and the readers the benchmark already has left unchanged
by a file that carries program spans."""
from pathlib import Path

import numpy as np
import pytest

from bench import program_spans as ps
from bench import trace as tr

DATA = Path(__file__).parent / "data"
OFFSET = -5000.0  # trace ns at engine time 0 in the hand-made events


def _span(name, s, e, line=0, **args):
    if "t" in args:
        args["t"] = (s - OFFSET) / 1e9 if args["t"] is None else args["t"]
    return ["repro." + name, float(s), float(e), line, args]


def _events():
    """Window [0, 1000] ns. Device busy [100, 200], [300, 400],
    [600, 700], [900, 950]: 650 ns idle. Line 0 is the worker, line 1
    the scheduler."""
    names = ["%fusion.1 = bf16[8] fusion(%a)"]
    ops = tr.Series(names, np.zeros(4, np.int32),
                    np.array([100, 300, 600, 900.]),
                    np.array([200, 400, 700, 950.]))
    mods = tr.Series(["jit_prefill(1)", "jit_randint(2)", "jit_decode(3)",
                      "jit_other(4)"],
                     np.array([0, 1, 2, 3, 2], np.int32),
                     np.array([110, 305, 320, 600, 990.]),
                     np.array([200, 310, 400, 700, 1050.]))
    ev = tr.Events([ops], [mods], [["bench.window", 0.0, 1000.0]])
    program = [
        _span("model.compile", 0, 50, arch="a", batch=1),
        _span("service.poll", 40, 45, 1, t=None, pending=0, released=0, left=0),
        _span("executor.wait", 50, 80, pool="vm"),
        _span("executor.query", 90, 460, qid=7, batch=1, level="RELAXED", cursor=0),
        _span("executor.stage", 100, 250, qid=7, stage="prefill", t=None),
        _span("executor.wait", 250, 300, pool="vm"),
        _span("service.poll", 255, 260, 1, t=None, pending=3, released=1, left=2),
        _span("executor.stage", 300, 420, qid=7, stage="decode_0", t=None),
        _span("executor.wait", 450, 600, pool="vm"),
        _span("service.poll", 500, 510, 1, t=None, pending=2, released=1, left=0),
        _span("model.compile", 700, 750, arch="a", batch=2),
        # a span whose t was stamped 1000 ns early (a preempted thread)
        _span("executor.stage", 740, 800, qid=8, stage="prefill",
              t=(740 - OFFSET - 1000) / 1e9),
        _span("executor.wait", 800, 900, pool="vm"),
        _span("executor.stage", 980, 1100, qid=9, stage="prefill", t=None),
    ]
    return ev, program


def test_idle_by_cause_on_hand_counts():
    ev, program = _events()
    got = ps.idle_by_cause(ev, program)
    # compile [0, 50] and [700, 750] (compile before stage); stage
    # [200, 250], [400, 420], [750, 800], [980, 1000] (the stage that
    # runs past the window counts inside it); starved [260, 300] (the
    # poll that ends at 260 left 2) and [450, 510]; empty [50, 80],
    # [250, 260], [510, 600], [800, 900]; boundary [80, 100], [420, 450],
    # [950, 980]
    want = {"compile": 100, "stage": 140, "starved": 100, "empty": 230,
            "boundary": 80}
    assert got == {k: pytest.approx(v * 1e-9) for k, v in want.items()}
    idle_s = tr.window_s(ev) - tr.busy_s(ev)
    assert sum(got.values()) == pytest.approx(idle_s, rel=1e-12)
    assert ps.idle_starved_share(ev, program) == pytest.approx(10.0)
    assert ps.idle_boundary_share(ev, program) == pytest.approx(8.0)
    assert ps.idle_by_cause(ev, []) is None


def test_stage_launch_and_poll_share_on_hand_counts():
    ev, program = _events()
    # prefill [100, 250] first runs jit_prefill at 110; decode_0
    # [300, 420] runs jit_randint at 305 (not a served program), then
    # jit_decode at 320; the stage at 740 starts no served program, and
    # the one at 980 runs past the window
    assert ps.stage_launches(ev, program) == [10.0, 20.0]
    assert ps.stage_launch_ms_p50(ev, program) == pytest.approx(15e-6)
    # polls [40, 45], [255, 260], [500, 510] over 1000 ns
    assert ps.poll_busy_share(ev, program) == pytest.approx(2.0)
    no_polls = [s for s in program if s[0] != "repro.service.poll"]
    assert ps.poll_busy_share(ev, no_polls) is None


def test_engine_offset_and_gaps_on_hand_counts():
    ev, program = _events()
    # seven spans carry t; one was stamped 1000 ns early
    off = ps.engine_offsets(program)
    assert sorted(off.tolist()) == [OFFSET] * 6 + [OFFSET + 1000]
    assert ps.engine_offset(program) == pytest.approx(OFFSET)
    gaps = ps.gaps(ev, program, k=2)
    assert [g["s"] for g in gaps] == [pytest.approx(200e-9)] * 2
    by_start = sorted(gaps, key=lambda g: g["at_s"])
    # [400, 600]: starved 60, empty 90, boundary 30, stage 20; at 500
    # the scheduler has begun a poll
    assert by_start[0]["cause"] == "empty"
    assert by_start[0]["doing"] == {0: "executor.wait", 1: "service.poll"}
    # [700, 900]: empty 100; at 800 the worker has begun to wait
    assert by_start[1]["cause"] == "empty"
    assert by_start[1]["doing"] == {0: "executor.wait"}
    assert ps.doing(program, 105) == {0: "executor.query>executor.stage"}


def test_a_file_with_program_spans_reads_the_same_to_the_old_readers(tmp_path):
    """The recorded excerpt that the benchmark's tests read, saved with
    program spans beside it, gives every existing reduction the same
    number; and its own reader finds the spans again."""
    old = tr.Events.from_json(DATA / "dashboard_trace_excerpt.json.gz")
    _, program = _events()
    ps.save(tmp_path / "e.json.gz", old, program)
    new = tr.Events.from_json(tmp_path / "e.json.gz")
    for f in (tr.busy_s, tr.window_s, tr.top_ops, tr.idle_gaps):
        assert f(new) == f(old)
    assert tr.idle_inside(new, "stage") == tr.idle_inside(old, "stage")
    for kernel in ("flash_attention", "decode_attention"):
        assert tr.op_time(new, (kernel,)) == tr.op_time(old, (kernel,))
    for prog in ("jit_prefill", "jit_decode"):
        assert tr.module_time(new, prog) == tr.module_time(old, prog)
    ev, back = ps.load(tmp_path / "e.json.gz")
    assert back == program and tr.busy_s(ev) == tr.busy_s(old)
    assert ps.load(DATA / "dashboard_trace_excerpt.json.gz")[1] == []


def test_excerpt_keeps_overlapping_program_spans_whole():
    ev, program = _events()
    cut, kept = ps.excerpt(ev, program, 260.0, 460.0)
    assert tr.window_s(cut) == pytest.approx(200e-9)
    assert [s[1:3] for s in kept if s[0] == "repro.executor.stage"] == \
        [[300.0, 420.0]]
    # the query span [90, 460] and the wait [250, 300] overlap: whole
    assert [90.0, 460.0] in [s[1:3] for s in kept]
    assert [250.0, 300.0] in [s[1:3] for s in kept]


def test_recorded_chip_excerpt_with_program_spans():
    """50 ms of the dashboard's traced round on a TPU v5e: the worker
    ends an IMMEDIATE query's last stage, waits for the next poll with
    148 RELAXED queries pending, and runs the batch of 8 it releases."""
    ev, program = ps.load(DATA / "dashboard_program_excerpt.json.gz")
    lo, hi = tr.window(ev)
    got = ps.idle_by_cause(ev, program)
    idle_s = tr.window_s(ev) - tr.busy_s(ev)
    assert sum(got.values()) == pytest.approx(idle_s, rel=1e-9)
    assert got["compile"] == 0 and got["empty"] == 0
    # the poll before the one wait left 148 pending: all its idle starves
    idle = ps.idle(ev)
    (ws, we), = ps.intervals(program, "repro.executor.wait", lo, hi)
    assert got["starved"] == pytest.approx(tr.covered(idle, ws, we) / 1e9)
    assert got["starved"] > 0.005
    polls = [s[4] for s in program if s[0] == "repro.service.poll"]
    assert [p["released"] for p in polls] == [0, 1, 0, 0, 0]
    assert polls[0]["left"] == 148 and polls[1]["left"] == 140
    route, = [s[4] for s in program if s[0] == "repro.coordinator.route"]
    assert route["members"] == 8
    prefill, = [s for s in program if s[0] == "repro.executor.stage"
                and s[4]["stage"] == "prefill"]
    assert prefill[4]["qid"] == route["qid"]
    # the engine clock: every span with a t agrees to 10 microseconds
    off = ps.engine_offsets(program)
    assert len(off) == 8 and np.ptp(off) < 10_000
    # the harness's stage spans lie inside the program's
    stages = ps.intervals(program, "repro.executor.stage", lo, hi)
    for _, s, e in (x for x in ev.spans if x[0] == "bench.stage"):
        assert tr.covered(stages, s, e) == pytest.approx(e - s)
    # only the prefill lies wholly in the window; its first served
    # program is jit_prefill, after the three programs of its inputs
    assert len(ps.stage_launches(ev, program)) == 1
    assert 0 < ps.stage_launch_ms_p50(ev, program) < 2.0
    assert ps.poll_busy_share(ev, program) > 0
