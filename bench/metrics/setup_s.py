"""Process start to the end of warm-up: engine, weights, compilation or
loading from the compile cache, and one query of each level served."""


def read(run):
    return run.setup_s
