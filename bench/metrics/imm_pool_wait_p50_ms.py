"""Executor: median wait of an IMMEDIATE query in the pool, from its
dequeue to the start of execution (head-of-line wait behind a running
stage), in ms."""
from bench.harness import percentile


def read(run):
    w = [r.start - r.dequeue for r in run.queries
         if r.level == "IMMEDIATE" and r.state == "done"
         and r.start is not None and r.dequeue is not None]
    return 1000.0 * percentile(w, 50) if w else None
