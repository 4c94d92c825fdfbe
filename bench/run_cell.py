#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine.

    python bench/run_cell.py --workload q6-scan --seed 7 --seconds 40 --trace 0

Loads and warms up the cell (set-up), measures for ``--seconds``,
checks what the timed path produced against the plain reference, and
prints one JSON object as the last line of standard output: the cell's
end-to-end metrics with ``--trace 0``, its per-layer metrics (read from
a profiler trace of a slice of the window) with ``--trace 1``.  Exits
non-zero, printing no result, where JAX finds no TPU or fewer chips
than the cell asks for.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    result = harness.run_cell(args.workload, args.seed, args.seconds,
                              bool(args.trace), t_start=T_START)
    harness.report(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
