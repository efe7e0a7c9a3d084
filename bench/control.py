"""Readings that set the limit of ``logit_gap``, on the chip.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds 1 2 3 ...
        [--faults state_unchanged half_batch token_altered]

For each seed, in one process: one run of the cell's timed path at the
cell's own load and sizes (a shorter window), then, over the same sampled
rows, the program's checks and the control's. The control puts the
reference computed with every matmul's operands in float8 e4m3, one
precision below the bf16 the configuration serves, in the program's
place: its first choice at each position stands for the served token,
and ``check.correct`` has to find it not correct. Then, for each fault
of ``bench/faults.py`` named, one more run (on the next seed) with that
fault planted under the timed path. The benchmark's own runs never
compute the control. Prints one line per run and a JSON summary last;
exits 1 where a sound run is not correct or the control or a fault is.
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


def _values(checks: dict) -> dict:
    return {k: v["value"] for k, v in checks.items()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--faults", nargs="*", default=[])
    args = ap.parse_args(argv)

    from bench import check, cli, faults, harness, manifest

    cell = manifest.load_cell(args.workload)
    cli.prepare(cell.chips)
    planted = [faults.FAULTS[f] for f in args.faults]
    rows, fault_rows = [], []
    t0 = T_PROCESS
    for seed in args.seeds:
        run = harness.run_cell(cell, seed, args.seconds, False, t0,
                               cli.OUT_DIR / f"control.{seed}", control=True,
                               log=cli.log)
        row = {"seed": seed, "correct": check.correct(run.checks),
               "control_correct": check.correct(run.control_checks),
               "logit_gap": run.checks["logit_gap"]["value"],
               "control_gap": run.control_checks["logit_gap"]["value"],
               "setup_s": run.setup_s, "setup_phases": run.setup_phases,
               "memory_setup": run.memory_setup, "memory_window": run.memory_window,
               "checks": _values(run.checks)}
        rows.append(row)
        print(json.dumps(row), flush=True)
        t0 = time.monotonic()
    for i, fault in enumerate(planted):
        seed = args.seeds[-1] + 1 + i
        run = harness.run_cell(cell, seed, args.seconds, False, t0,
                               cli.OUT_DIR / f"fault.{fault.__name__}", fault=fault,
                               log=cli.log)
        row = {"fault": fault.__name__, "seed": seed,
               "correct": check.correct(run.checks), "checks": _values(run.checks)}
        fault_rows.append(row)
        print(json.dumps(row), flush=True)
        t0 = time.monotonic()
    print(json.dumps({
        "workload": args.workload,
        "program_max": max(r["logit_gap"] for r in rows),
        "control_min": min(r["control_gap"] for r in rows),
        "seeds": len(rows),
        "sound_runs_correct": all(r["correct"] for r in rows),
        "control_ever_correct": any(r["control_correct"] for r in rows),
        "faults_ever_correct": [r["fault"] for r in fault_rows if r["correct"]],
    }), flush=True)
    ok = (all(r["correct"] for r in rows)
          and not any(r["control_correct"] for r in rows)
          and not any(r["correct"] for r in fault_rows))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
