"""The timed path broken underneath: each fault a served cell can have
must make ``correct`` come out false. (One chip: there is no exchange
between chips to leave out.)"""
import pytest

from bench import check
from bench.faults import FAULTS, half_batch, state_unchanged, token_altered


@pytest.mark.parametrize("fault", [state_unchanged, half_batch, token_altered],
                         ids=lambda f: f.__name__)
def test_fault_makes_the_run_incorrect(fault, tiny_cell, run_tiny):
    run = run_tiny(tiny_cell("qwen2-0.5b.dashboard"), fault=fault)
    assert any(len(e.members) > 1 for e in run.executions.values())
    assert not check.correct(run.checks), run.checks
    assert run.checks["logit_gap"]["value"] > run.checks["logit_gap"]["limit"]


def test_every_fault_is_named():
    assert set(FAULTS) == {"state_unchanged", "half_batch", "token_altered"}
