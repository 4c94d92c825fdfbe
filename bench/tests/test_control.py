"""The control -- the reference computed one precision below the
configuration's, in the program's place -- fails each limit that the
program passes, at a size the CPU holds.  On the chip, at the cells'
own sizes, ``bench/control.py`` gives the readings in PERF.md."""
from __future__ import annotations

import io

import pytest

import control
import harness


@pytest.mark.parametrize("workload", ["q6-small", "q6-parts-small",
                                      "granite-control-small"])
def test_control_fails_where_the_program_passes(small, workload):
    root, bench = small
    seeds = [1, 2, 3]
    got = control.readings(workload, seeds, seeds, 0.5, root=root,
                           bench_dir=bench, require_tpu=False,
                           out=io.StringIO())
    limits = harness.resolve(workload, root, bench).reference.LIMITS
    for name, limit in limits.items():
        assert got["upper"][name] > limit         # every control seed
        assert got["lower"][name] <= limit / 3    # every program seed
