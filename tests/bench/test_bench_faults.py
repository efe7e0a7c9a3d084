"""The timed path broken underneath: each fault a served cell can have
must make ``correct`` come out false, in every cell of the manifest.
(One chip: there is no exchange between chips to leave out.)"""
import pytest

from bench import check, manifest
from bench.faults import FAULTS, half_batch, state_unchanged, token_altered


@pytest.mark.parametrize(
    "workload", [w["name"] for w in manifest.load_manifest()["workloads"]])
@pytest.mark.parametrize("fault", [state_unchanged, half_batch, token_altered],
                         ids=lambda f: f.__name__)
def test_fault_makes_the_run_incorrect(fault, workload, tiny_cell, run_tiny):
    cell = tiny_cell(workload)
    run = run_tiny(cell, fault=fault)
    b = cell.traffic["batch"]
    assert any(len(e.members) * b > 1 for e in run.executions.values())
    assert not check.correct(run.checks), run.checks
    assert run.checks["logit_gap"]["value"] > run.checks["logit_gap"]["limit"]


def test_every_fault_is_named():
    assert set(FAULTS) == {"state_unchanged", "half_batch", "token_altered"}
