"""Model step: model FLOPs of the served prefill and decode programs over
their device time times the chip's bf16 peak, in %. FLOPs come from the
window's executed stages; where the trace holds a different number of
programs, each kind's per-call mean is scaled to the programs it holds."""
from bench.manifest import load_module
from bench.reduce import module_time


def read(run):
    fam = load_module("families", run.config["model"]["family"], run.bench_dir)
    c = run.config
    work = {
        "jit_prefill": [fam.prefill_flops(c, b, run.prompt_tokens)
                        for b in run.prefill_batches],
        "jit_decode": [fam.decode_flops(c, b, p) for b, p in run.decode_steps],
    }
    flops, secs = 0.0, 0.0
    for prefix, f in work.items():
        n, t = module_time(run, prefix)
        if n and f:
            flops += sum(f) / len(f) * n
            secs += t
    if secs <= 0:
        return None
    return 100.0 * flops / (secs * run.peaks["bf16_flops_per_s"])
