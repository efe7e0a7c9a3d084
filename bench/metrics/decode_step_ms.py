"""Model step: device time of the served decode program (``jit_decode``)
per call, in ms."""
from bench.reduce import module_time


def read(run):
    n, secs = module_time(run, "jit_decode")
    return 1000.0 * secs / n if n else None
