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
    program's reduced float32 widths written into its configuration
    through the family's ``PROGRAM_FIELDS``, the jnp attention path,
    short prompts, the arrival process's own ``tiny`` parameters (a
    short window with bursts that fuse), and fuse_max 2. A ``cut`` is
    for the published sizes and goes, unless ``keep_cut``: then it is
    applied to the reduced program as it is to the published one.

    The logit-gap limit is this size's own: the float32 program reads
    at most 1e-3 here and the fp8 control about 0.26, so 0.1 lies
    between them (the chip limits are set from readings at the cells'
    own widths, in the configuration files)."""
    from bench import manifest
    from repro.configs import get_config

    def make(workload: str, bench_json: dict | None = None,
             bench_dir=manifest.BENCH_DIR, keep_cut: bool = False) -> manifest.Cell:
        cell = manifest.load_cell(workload, bench_json, bench_dir)
        c = copy.deepcopy(cell.config)
        cut = c.pop("cut", None)
        c.pop("published", None)
        family = manifest.load_module("families", c["model"]["family"], bench_dir)
        r = get_config(c["model"]["arch"], reduced=True)
        for field, key in family.PROGRAM_FIELDS.items():
            *groups, leaf = key.split(".")
            node = c
            for g in groups:
                node = node[g]
            node[leaf] = getattr(r, field)
        if keep_cut and cut:
            c = manifest.apply_cut(dict(c, cut=cut))
        c["model"].update(published_widths=False, dtype="float32", impl="jnp")
        c["deployment"]["fuse_max"] = 2
        c["check"]["logit_gap_limit"] = 0.1
        t = copy.deepcopy(cell.traffic)
        t.update(prompt_tokens=32, output_tokens=4, check_rows=6,
                 trace_window=[0.0, 1.0])
        arrivals = manifest.load_module("arrivals", t["arrivals"], bench_dir)
        t["params"] = arrivals.tiny(t["params"])
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
