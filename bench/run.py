"""Run one cell of the benchmark once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Needs an accelerator with as many chips as the cell asks for; with none
it exits non-zero and prints no result. The last line of stdout is one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics, or with ``--trace 1`` its per-layer ones),
``device``, with ``--trace 1`` a ``breakdown``, and last ``checks``:
every number compared for ``correct`` beside its limit. The same
numbers are the last lines of stderr.

JAX's persistent compilation cache is kept in ``.jax_cache`` at the root
of the checkout, so only a cell's first run there compiles.
"""
import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[0] = str(ROOT)
sys.path.insert(1, str(ROOT / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", action="store_true",
                    help="keep the profiler's files, and an excerpt, beside the summary")
    args = ap.parse_args(argv)

    from bench import cli, manifest

    cell = manifest.load_cell(args.workload)
    device = cli.prepare(cell.chips)

    from bench import harness, report

    out = cli.OUT_DIR / f"{args.workload}.{args.seed}.{args.trace}"
    out.mkdir(parents=True, exist_ok=True)
    run = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                           T_PROCESS, out, keep_trace=args.keep_trace,
                           log=cli.log)
    result = report.result(cell, run, device, bool(args.trace))
    report.write_summary(out / "summary.json", run, result)
    for line in report.earlier_lines(run):
        cli.log(line)
    for name, c in run.checks.items():
        cli.log(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
