"""Driver for table scans through the program's fused pattern pipeline.

The table lives on the device as ``partitions`` resident partitions,
made from the seed by the configuration's reference module.  One
client runs a closed loop: it picks a partition (from the seed), calls
the program's ``lower_pipeline(pipe, fused=True)`` result on it and
reads the scalar back to the host before the next query.  Set-up makes
the data, lowers the pipeline and runs a few queries, which compile it;
the window then runs queries until ``seconds`` have passed.
"""
from __future__ import annotations

import time

import numpy as np

from harness import Run


def _reduce(trace, cell, rows: int, queries):
    import counts
    import reduce_trace

    cols = len(cell.config["columns"])
    spans = []
    for tb, td, te in queries:
        spans.append(("query.dispatch", tb, td))
        spans.append(("query.readback", td, te))
    red = reduce_trace.reduce(
        trace.xplane(), sync_pc=trace.sync_pc,
        window=(trace.started, trace.stopped), host_spans=spans)
    red["kernel_bytes"] = red["kernel_calls"] * counts.scan_bytes(rows, cols)
    red["kernel_flops"] = red["kernel_calls"] * counts.scan_flops(rows)
    return red


def run(cell, *, seed: int, seconds: float, trace, t_start: float) -> Run:
    import jax

    from repro.core import pipeline as plmod
    from repro.patterns.analytics import PIPELINES

    cfg, ref = cell.config, cell.reference
    parts = int(cell.traffic["partitions"])
    rows = int(cfg["lineitem_rows"]) // parts
    data = [ref.make_columns(cfg, seed, p, rows) for p in range(parts)]
    jax.block_until_ready(data)
    pipe, _, _ = PIPELINES[cfg["pipeline"]](rows)
    call = plmod.lower_pipeline(pipe, fused=True)
    rng = np.random.default_rng(seed)
    for p in range(min(parts, 3)):                    # compiles here
        float(np.asarray(call(**data[p])))

    lead = min(1.0, seconds / 4)
    trace_s = min(float(cell.traffic["trace_s"]), seconds / 2)
    times, picks, answers = [], [], []
    traced = []
    t0 = time.perf_counter()
    t_end = t0 + seconds
    while True:
        if trace is not None:
            now = time.perf_counter()
            if trace.started is None and now >= t0 + lead:
                trace.start()
            elif trace.stopped is None and trace.started is not None \
                    and now >= trace.started + trace_s:
                trace.stop()
        p = int(rng.integers(parts))
        tb = time.perf_counter()
        out = call(**data[p])
        td = time.perf_counter()
        answers.append(float(np.asarray(out)))
        te = time.perf_counter()
        times.append((tb, td, te))
        picks.append(p)
        if trace is not None and trace.started is not None \
                and trace.stopped is None:
            traced.append((tb, td, te))
        if te >= t_end:
            break
    t1 = times[-1][2]
    if trace is not None and trace.stopped is None:
        trace.stop()

    used = sorted(set(picks))

    def rel_err(got, want):
        exp = np.asarray([want[p] for p in picks], np.float64)
        return float(np.max(np.abs(np.asarray(got, np.float64) - exp)
                            / np.abs(exp)))

    def check():
        want = {p: ref.answer(data[p], cfg) for p in used}
        return {"rel_err": (rel_err(answers, want), ref.LIMITS["rel_err"])}

    def control():
        want = {p: ref.answer(data[p], cfg) for p in used}
        low = {p: ref.control_answer(data[p], cfg) for p in used}
        return {"rel_err": rel_err([low[p] for p in picks], want)}

    red = _reduce(trace, cell, rows, traced) if trace is not None else None
    del call
    return Run(setup_end=t0, window=(t0, t1), attempted=len(answers),
               failed=0, check=check, control=control, trace=red,
               data={"queries": np.asarray(times), "rows": rows})
