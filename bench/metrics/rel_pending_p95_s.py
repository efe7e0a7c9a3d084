"""95th percentile of RELAXED pending time: scheduled arrival to the
start of execution, over every RELAXED query due in the window."""
from bench.reduce import tail


def read(run):
    return tail(run, "RELAXED", 95,
                lambda r: None if r.start is None else r.start - r.due)
