"""Output tokens of the queries finished in the window, over the
window's seconds. Each fused member counts its own."""


def read(run):
    done = run.finished_in_window()
    return sum(r.output_tokens * r.batch for r in done) / run.seconds
