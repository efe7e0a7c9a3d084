"""A sound run is correct; the control (the reference in fp8, one
precision below the configuration's, put in the program's place) is
not, by the same ``check.correct``. At CPU size the program runs
float32, so its gap is rounding alone."""
from types import SimpleNamespace

import pytest

from bench import check, manifest


@pytest.mark.parametrize(
    "workload", [w["name"] for w in manifest.load_manifest()["workloads"]])
def test_sound_run_passes_and_control_fails(workload, tiny_cell, run_tiny):
    cell = tiny_cell(workload)
    run = run_tiny(cell, control=True)
    c = run.checks
    assert check.correct(c), c
    assert c["unaccounted"]["value"] == 0 and c["trace_faults"]["value"] == 0
    assert c["tokens_missing"]["value"] == 0
    assert c["logit_gap"]["value"] <= 1e-3
    ctrl = run.control_checks
    assert not check.correct(ctrl), ctrl
    assert ctrl["logit_gap"]["value"] > ctrl["logit_gap"]["limit"]
    assert {k: v for k, v in ctrl.items() if k != "logit_gap"} == {
        k: v for k, v in c.items() if k != "logit_gap"}
    done = [r for r in run.queries if r.state == "done"]
    assert len(done) == len(run.queries) > 0


def _ex(qid, n):
    return SimpleNamespace(qid=qid, members=[qid * 100 + j for j in range(n)])


@pytest.mark.parametrize("seed", [1, 2**31 + 5, 7_000_000_123])
def test_sample_holds_the_last_row_of_the_largest_batch(seed):
    execs = {1: _ex(1, 1), 2: _ex(2, 3), 3: _ex(3, 8), 4: _ex(4, 1), 5: _ex(5, 2)}
    done = [m for e in execs.values() for m in e.members]
    run = SimpleNamespace(
        executions=execs, traffic={"check_rows": 4, "batch": 1},
        queries=[SimpleNamespace(qid=m, state="done") for m in done])
    rows = check.sample_rows(run, seed)
    assert (3, 7, 307) in rows
    assert len(rows) == 4 and len(set(rows)) == 4
    assert any(len(execs[e].members) == 1 for e, _, _ in rows)


def test_sample_reaches_every_row_of_queries_of_several_rows():
    """A query of the traffic's ``batch`` rows holds that many rows of its
    program, fused or alone; the sample reaches each, and the last row of
    the largest batch."""
    execs = {1: _ex(1, 1), 2: _ex(2, 2)}
    done = [m for e in execs.values() for m in e.members]
    run = SimpleNamespace(
        executions=execs, traffic={"check_rows": 12, "batch": 4},
        queries=[SimpleNamespace(qid=m, state="done") for m in done])
    rows = check.sample_rows(run, 3)
    assert rows[0] == (2, 7, 201)
    assert sorted(rows) == [(1, i, 100) for i in range(4)] + [
        (2, i, 200 + i // 4) for i in range(8)]
