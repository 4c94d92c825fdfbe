#!/usr/bin/env python3
"""Record the small trace the program-span readers' test reads: four
Q6 queries at 2**22 rows through the program's pipeline call on a TPU,
traced the way the harness traces a window, with telemetry off: the
program's ``pipeline.*`` spans reach the trace as profiler annotations.
Writes ``q6_program_2p22.xplane.pb`` and ``q6_program_2p22.json``
(device kind, rows, queries and the host times of each query) beside
this file.

    python bench/tests/data/record_program_trace.py
"""
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
BENCH = HERE.parents[1]
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402

NAME = "q6_program_2p22"


def main() -> int:
    harness.configure_caches(harness.ROOT)
    why = harness.check_chips(1)
    if why:
        raise SystemExit(f"record_program_trace: {why}")
    import jax
    import numpy as np

    from repro.core import pipeline as plmod
    from repro.patterns.analytics import tpchq6_pipeline

    ref = harness.load_module(BENCH / "reference" / "tpchq6_2p29.py")
    rows = 1 << 22
    cols = ref.make_columns({}, 5, 0, rows)
    call = plmod.lower_pipeline(tpchq6_pipeline(rows)[0], fused=True)
    for _ in range(3):
        float(np.asarray(call(**cols)))
    trace = harness.TraceSlice(harness.ROOT / ".bench_traces" / "record")
    trace.start()
    queries = []
    for _ in range(4):
        tb = time.perf_counter()
        out = call(**cols)
        td = time.perf_counter()
        float(np.asarray(out))
        queries.append((tb, td, time.perf_counter()))
    trace.stop()
    # the trace names source files by path: keep the checkout's out of
    # the recording (same length, so the protobuf stays valid)
    data = trace.xplane().read_bytes()
    root = str(harness.ROOT).encode()
    (HERE / f"{NAME}.xplane.pb").write_bytes(
        data.replace(root, b"/" + b"_" * (len(root) - 1)))
    (HERE / f"{NAME}.json").write_text(json.dumps({
        "device_kind": jax.devices()[0].device_kind, "rows": rows,
        "queries": len(queries), "query_times": queries}, indent=1))
    trace.remove()
    return 0


if __name__ == "__main__":
    sys.exit(main())
