#!/usr/bin/env python3
"""Readings that set a cell's correctness limit: the program's own
numbers over many seeds, and the control's over a few.

    python bench/control.py --workload granite-long-decode \
        --seeds 11 12 13 --control-seeds 11 12 13 --seconds 14

For every seed the cell's driver runs a short window at the cell's own
size and load, then the number its check compares is read for the
program and, on the control seeds, for the control: the reference
computed one precision below the configuration's (float8 for a
bfloat16 model, bfloat16 for float32 data).  The lower reading is the
largest the program gives, the upper the smallest the control gives;
a limit lies between them.  Prints one JSON line per seed and a last
line with both readings.  The benchmark's own runs never run this.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness  # noqa: E402


def readings(workload, seeds, control_seeds, seconds, *,
             root=harness.ROOT, bench_dir=harness.BENCH,
             require_tpu=True, out=sys.stdout):
    harness.configure_caches(root)
    cell = harness.resolve(workload, root, bench_dir)
    if require_tpu:
        why = harness.check_chips(cell.chips)
        if why:
            raise SystemExit(f"control: {why}")
    program, control = {}, {}
    for seed in sorted(set(seeds) | set(control_seeds)):
        run = cell.driver.run(cell, seed=seed, seconds=seconds, trace=None,
                              t_start=time.perf_counter())
        row = {"seed": seed, "attempted": run.attempted}
        if seed in seeds:
            row["program"] = {k: v for k, (v, _) in run.check().items()}
            for k, v in row["program"].items():
                program[k] = max(program.get(k, 0.0), v)
        if seed in control_seeds:
            row["control"] = run.control()
            for k, v in row["control"].items():
                control[k] = min(control.get(k, float("inf")), v)
        print(json.dumps(row), file=out, flush=True)
        del run
    summary = {"workload": workload, "lower": program, "upper": control,
               "program_seeds": len(seeds),
               "control_seeds": len(control_seeds)}
    print(json.dumps(summary), file=out, flush=True)
    return summary


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    readings(args.workload, args.seeds, args.control_seeds, args.seconds)
    return 0


if __name__ == "__main__":
    sys.exit(main())
