import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


import copy  # noqa: E402

import pytest  # noqa: E402


@pytest.fixture
def tiny_cell():
    """A cell of the manifest cut to a size the CPU runs in seconds: the
    program's reduced float32 widths, the jnp attention path, short
    prompts, a short window with bursts that fuse, and fuse_max 2.

    The logit-gap limit is this size's own: the float32 program reads
    at most 1e-3 here and the fp8 control about 0.26, so 0.1 lies
    between them (the chip limits are set from readings at the cells'
    own widths, in the configuration files)."""
    from bench import manifest
    from repro.configs import get_config

    def make(workload: str) -> manifest.Cell:
        cell = manifest.load_cell(workload)
        c = copy.deepcopy(cell.config)
        r = get_config(c["model"]["arch"], reduced=True)
        c.update(hidden_size=r.d_model, intermediate_size=r.d_ff,
                 num_attention_heads=r.num_heads, num_key_value_heads=r.num_kv_heads,
                 num_hidden_layers=r.num_layers, vocab_size=r.vocab_size,
                 rms_norm_eps=r.norm_eps)
        c["model"].update(head_dim=r.head_dim, published_widths=False,
                          dtype="float32", impl="jnp")
        c["deployment"]["fuse_max"] = 2
        c["check"]["logit_gap_limit"] = 0.1
        t = copy.deepcopy(cell.traffic)
        t.update(prompt_tokens=32, output_tokens=4, check_rows=6,
                 trace_window=[0.0, 1.0])
        if t["arrivals"] == "rounds":
            t["params"].update(period_s=0.5, spread_s=0.05, per_round=12)
        else:
            t["params"].update(rate_qps=10.0)
        cell.config, cell.traffic = c, t
        return cell

    return make


@pytest.fixture
def run_tiny(tmp_path):
    """Run a tiny cell on the CPU: the harness with its look for a chip
    skipped, everything else as on the chip."""
    import time

    from bench import harness

    def run(cell, seed=2**31 + 77, **kw):
        return harness.run_cell(cell, seed, 1.5, False, time.monotonic(),
                                tmp_path, require_chip=False,
                                log=lambda s: None, **kw)

    return run
