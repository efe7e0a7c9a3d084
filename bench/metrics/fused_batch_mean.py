"""Service layer: queries per executed program, over the queries
finished in the window (a query that ran alone counts as a batch of 1)."""


def read(run):
    done = [r for r in run.queries if r.state == "done"]
    if not done:
        return None
    programs = sum(1.0 / max(1, r.fused_with) for r in done)
    return len(done) / programs
