"""Device: share of the traced window in which no operation ran on the
device, in %."""
from bench import trace as tr


def read(run):
    if run.trace is None or not run.trace.ops:
        return None
    return 100.0 * (1.0 - tr.busy_s(run.trace) / tr.window_s(run.trace))
