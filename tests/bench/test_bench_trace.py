"""The trace reduction, on hand-made events and on a recorded excerpt of
a chip trace (41 ms of the dashboard cell on a TPU v5e: one batch-8
prefill and decode steps)."""
from pathlib import Path

import numpy as np
import pytest

from bench import trace as tr

DATA = Path(__file__).parent / "data"


def _events():
    names = ["%while.1 = (s32[]) while(%t)",
             "%fusion.1 = bf16[8] fusion(%flash_attention.5)",
             "%flash_attention.5 = bf16[1,2,7,512,64] custom-call(%a)",
             "%decode_attention.2 = bf16[1,2,7,64] custom-call(%b)"]
    ops = tr.Series(names, np.array([0, 1, 2, 3, 1], np.int32),
                    np.array([0, 10, 30, 60, 80.]),
                    np.array([100, 20, 50, 70, 120.]))
    mods = tr.Series(["jit_decode(1)", "jit_prefill(2)"], np.array([0, 1]),
                     np.array([0, 60.]), np.array([55, 125.]))
    spans = [["bench.window", 0, 200], ["bench.stage", 0, 50],
             ["bench.submit", 150, 160], ["bench.stage", 100, 130]]
    return tr.Events([ops], [mods], spans)


def test_hand_made_events_reduce_to_hand_counts():
    ev = _events()
    assert tr.window_s(ev) == pytest.approx(200e-9)
    # the loop [0, 100] holds three ops; the last op runs to 120
    assert tr.busy(ev)[0].tolist() == [[0.0, 120.0]]
    assert tr.busy_s(ev) == pytest.approx(120e-9)
    # a kernel is matched by its own name, not by an operand's
    assert tr.op_time(ev, ("flash_attention",)) == (1, pytest.approx(20e-9))
    assert tr.op_time(ev, ("decode_attention",)) == (1, pytest.approx(10e-9))
    assert tr.module_time(ev, "jit_decode") == (1, pytest.approx(55e-9))
    # containers are left out of the per-op list; layouts are dropped
    assert [n for n, _ in tr.top_ops(ev)][0].startswith("%fusion.1")
    assert tr.readable("%f.1 = bf16[8]{0:T(1024)} fusion(%a)") == \
        "%f.1 = bf16[8] fusion(%a)"
    assert all(not n.startswith("%while") for n, _ in tr.top_ops(ev))
    # stage spans cover [0, 50] and [100, 130]: idle only in [120, 130]
    idle, total = tr.idle_inside(ev, "stage")
    assert (idle, total) == (pytest.approx(10e-9), pytest.approx(80e-9))
    # the one gap, [120, 200]: 10 in a stage, 10 in a submit, 60 in none
    assert tr.idle_gaps(ev) == [["none", pytest.approx(80e-9)]]
    ev.spans.append(["bench.stage", 125, 190])
    assert tr.idle_gaps(ev) == [["stage", pytest.approx(80e-9)]]


def _naive_busy(starts, ends, lo, hi):
    pts = sorted((max(s, lo), min(e, hi)) for s, e in zip(starts, ends)
                 if min(e, hi) > max(s, lo))
    total, cur_s, cur_e = 0.0, None, None
    for s, e in pts:
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def test_recorded_excerpt_reduces_as_a_plain_sweep_does():
    ev = tr.Events.from_json(DATA / "dashboard_trace_excerpt.json.gz")
    lo, hi = tr.window(ev)
    ops = ev.ops[0]
    want = _naive_busy(ops.start, ops.end, lo, hi) / 1e9
    assert tr.busy_s(ev) == pytest.approx(want, rel=1e-12)
    assert 0 < tr.busy_s(ev) < tr.window_s(ev)
    # one batch-8 prefill of 24 layers: one flash call per layer
    n, secs = tr.op_time(ev, ("flash_attention",))
    assert n == 24 and secs > 0
    n_dec, _ = tr.op_time(ev, ("decode_attention",))
    n_steps, _ = tr.module_time(ev, "jit_decode")
    assert n_steps >= 10 and n_dec >= 24 * n_steps
    by_hand = sum(
        e - s for i, s, e in zip(ops.idx, ops.start, ops.end)
        if tr.short_name(ops.names[i]).startswith("%flash_attention")
        and s >= lo and e <= hi)
    assert secs == pytest.approx(by_hand / 1e9)
    gaps = tr.idle_gaps(ev)
    assert gaps and all(k in ("stage", "submit", "none") for k, _ in gaps)
    assert sum(s for _, s in gaps) <= tr.window_s(ev) - tr.busy_s(ev) + 1e-12


def test_excerpt_round_trips_through_json(tmp_path):
    ev = _events()
    ev.to_json(tmp_path / "e.json.gz")
    back = tr.Events.from_json(tmp_path / "e.json.gz")
    assert tr.busy_s(back) == tr.busy_s(ev)
    cut = ev.excerpt(0.0, 60.0)
    assert tr.window_s(cut) == pytest.approx(60e-9)
    assert len(cut.ops[0]) == 2  # the ops wholly inside [0, 60]


def test_read_xplane_finds_the_harness_spans(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.stage"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    ev = tr.read_xplane(tr.find_xplane(tmp_path))
    names = sorted(s[0] for s in ev.spans)
    assert names == ["bench.stage", "bench.window"]
    assert tr.window_s(ev) > 0
