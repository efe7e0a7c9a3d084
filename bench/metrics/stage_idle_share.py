"""Executor: share of the time inside the executor's stage calls
(the harness's ``bench.stage`` spans) in which the device ran nothing:
host overhead inside billed time, in %."""
from bench import trace as tr


def read(run):
    if run.trace is None:
        return None
    got = tr.idle_inside(run.trace, "stage")
    if got is None or got[1] <= 0:
        return None
    return 100.0 * got[0] / got[1]
