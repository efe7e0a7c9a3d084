"""Find a cell's knee once: run its traffic at several fixed rates.

    python3 bench/sweep.py --workload <cell> --seconds <s> --seed <n> \
        --param per_round --values 60 100 150

Each value replaces one arrival parameter of the cell's traffic for one
run, in one process. Prints, per value, the offered rate, how many
queries due in the window were unfinished when it closed, and the
end-to-end metrics. The knee is the highest rate at which the
unfinished count does not grow with the window.
"""
import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import copy  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[0] = str(ROOT)
sys.path.insert(1, str(ROOT / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--param", required=True)
    ap.add_argument("--values", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)

    from bench import cli, harness, manifest, report

    cell = manifest.load_cell(args.workload)
    cli.prepare(cell.chips)
    base = cell.traffic
    arrivals = manifest.load_module("arrivals", base["arrivals"], cell.bench_dir)
    t0 = T_PROCESS
    for v in args.values:
        cell.traffic = copy.deepcopy(base)
        p = cell.traffic["params"]
        p[args.param] = type(p[args.param])(v)
        run = harness.run_cell(cell, args.seed, args.seconds, False, t0,
                               cli.OUT_DIR / f"sweep.{v}", log=cli.log)
        w1 = run.window[1]
        open_at_close = sum(1 for r in run.queries
                            if r.finish is None or r.finish > w1)
        # the backlog at the close against the one a quarter before it
        w_q = run.window[0] + 0.75 * args.seconds
        open_at_q = sum(1 for r in run.queries if r.due <= w_q
                        and (r.finish is None or r.finish > w_q))
        print(json.dumps({
            "value": v, "rate_qps": arrivals.rate_qps(p),
            "attempted": len(run.queries), "open_at_close": open_at_close,
            "open_at_three_quarters": open_at_q,
            "metrics": report.metrics(cell.end_to_end, run)[0],
            "checks": {k: c["value"] for k, c in run.checks.items()},
        }), flush=True)
        t0 = time.monotonic()
    return 0


if __name__ == "__main__":
    sys.exit(main())
