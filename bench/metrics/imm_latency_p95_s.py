"""95th percentile of IMMEDIATE latency: scheduled arrival to the
query's last token, over every IMMEDIATE query due in the window."""
from bench.reduce import tail


def read(run):
    return tail(run, "IMMEDIATE", 95, lambda r: r.finish - r.due)
