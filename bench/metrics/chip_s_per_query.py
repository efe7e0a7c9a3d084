"""Billed chip-seconds of the queries finished in the window, over their
count: the price users pay. Fused members carry their own split."""


def read(run):
    done = run.finished_in_window()
    if not done:
        return None
    return sum(r.chip_s for r in done) / len(done)
