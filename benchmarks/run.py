"""Benchmark harness -- one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV:

  fig7/*      the six benchmarks (Table 5) in base / tiled /
              tiled+metapipeline configurations.  us_per_call = CPU
              wall-time of the jnp-lowered program; derived = modeled
              speedup from the analytic cost model (HBM traffic +
              metapipeline overlap -- the quantity Fig. 7 measures on
              the FPGA; see EXPERIMENTS.md §Perf for the comparison).
  fig5c/*     k-means traffic table entries (reads reduction factors).
  table2/*    strip-mining rule structural checks (PASS/FAIL).
  table3/*    gemm interchange + generated-Pallas-kernel equivalence.
  kernels/*   Pallas kernel interpret-mode sanity timings vs oracle.
  roofline/*  per-(arch x shape) dominant-term summary from the latest
              dry-run results, if present.
  autotile/*  (--autotile) per-benchmark comparison of hand-picked vs
              DSE-tuned tile sizes: wall time of the lowered program and
              the cost model's traffic/modeled-seconds accounting, plus
              a depth row (the searched metapipeline buffer depth and
              the depth-2-vs-best modeled delta at the winning sizes).
  fused/*     pipeline fusion (tpchq6 / gda chains, the kmeans and
              gda_moments fan-out DAGs, the normalize Map-terminal
              pipeline): the single-megakernel lowering vs the
              per-pattern DAG -- interpret-mode wall time plus modeled
              HBM traffic (the intermediate round-trips fusion deletes;
              paper Fig. 5/6), and a depth row per pipeline (chosen
              per-group buffer depths + depth-2-vs-best modeled delta).
              These rows feed the CI perf-regression gate
              (``benchmarks/check_regression.py``).
  measured/*  (--measure) hybrid analytic->measured DSE
              (``core.measure`` / ``core.calibrate``): for all five
              Pallas kernels' proxy programs and all five PIPELINES,
              the analytic shortlist's top-k candidates are lowered and
              timed, and the row reports the Spearman rank correlation
              of the analytic and the calibrated model's candidate
              ranking against the measured one, plus the calibration
              profile the samples refreshed.
  serving/*   cold-shape tail latency through the shape-bucket
              warm-start layer (``core.buckets``): per cold shape, the
              first-request latency of a full foreground exploration
              vs the bucketed warm-start resolve, plus the bucket hit
              rate and how many background re-tunes promoted a
              certified winner.  Feeds the serving notes the
              regression gate prints.
  resilience/* degradation accounting for the whole run
              (``core.resilience.LOG``): one row per action taken --
              candidates quarantined, transient retries, analytic
              fallbacks, stores rebuilt from corruption.  Zero rows on
              a clean run; the CI chaos-smoke step injects faults
              (``REPRO_FAULTS``) and asserts these counts are nonzero.

All wall times go through ``core.measure.measure``: warmup runs
(compilation) excluded, median of ``--repeat`` (default 3) fenced
calls.  ``--warmup``/``--repeat`` are recorded in the BENCH json so the
regression gate can flag noisy configurations.

``--only fig5c,table2`` restricts to the named sections (CI smoke).
``--json OUT`` additionally writes the rows as machine-readable
``BENCH_<rev>.json`` (section, name, us, derived, traffic fields) so CI
can archive the perf trajectory per commit; the file is written even
when no rows were produced or a section crashed (empty-but-valid doc).
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import backend, ir
from repro.core import measure as measure_mod
from repro.core import resilience, telemetry
from repro.core.codegen_jax import execute
from repro.core.cost import traffic
from repro.core.scheduling import build_schedule, model_speedup
from repro.core.strip_mine import insert_tile_copies, strip_mine, tile
from repro.patterns.analytics import PIPELINES, SUITE

ROWS = []
JSON_ROWS = []

# timing configuration (overridden by --repeat/--warmup in main);
# repeat=None means "each call site's historical default", and the
# repeats _time actually used are tracked so the BENCH json reports
# what really happened, not the configured wish
TIMING = {"repeat": None, "warmup": 1, "topk": None,
          "used_min": None, "used_max": None}


def emit(name: str, us: float, derived, **extra) -> None:
    ROWS.append(f"{name},{us:.1f},{derived}")
    JSON_ROWS.append({"section": name.split("/", 1)[0], "name": name,
                      "us": round(float(us), 1), "derived": str(derived),
                      **extra})
    print(ROWS[-1], flush=True)


def _git_rev() -> str:
    try:
        return subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=10,
            cwd=os.path.dirname(os.path.abspath(__file__))
        ).stdout.strip() or "unknown"
    except (OSError, subprocess.SubprocessError):
        return "unknown"


def write_json(out: str, error: str = "") -> str:
    """Write rows as BENCH_<rev>.json; ``out`` is a directory (file named
    by rev) or an explicit ``.json`` path.

    Always emits a valid JSON document -- ``rows`` may be empty (e.g.
    ``--only`` selected a section that produced nothing, or a section
    died before its first row; ``error`` records the latter) so the CI
    artifact upload and the regression gate never face a missing file.
    """
    rev = _git_rev()
    path = out if out.endswith(".json") else os.path.join(
        out, f"BENCH_{rev}.json")
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    doc = {"rev": rev, "rows": JSON_ROWS,
           # repeat = the SMALLEST repeat any timed row actually used
           # (sections default to 1-3 when --repeat is unset), so the
           # regression gate's noise note fires on what really ran
           "timing": {"repeat": TIMING["used_min"]
                      or TIMING["repeat"] or 3,
                      "repeat_max": TIMING["used_max"]
                      or TIMING["repeat"] or 3,
                      "warmup": TIMING["warmup"],
                      "device": measure_mod.device_kind(),
                      "interpret": measure_mod.interpret_mode()},
           # degradation accounting for the run: how many candidates
           # were quarantined / retried / fell back (the chaos-smoke CI
           # step asserts these are nonzero under injected faults)
           "resilience": {
               "counts": resilience.LOG.counts(),
               "faults": os.environ.get("REPRO_FAULTS", ""),
               "events": [e.to_json()
                          for e in resilience.LOG.events()[:200]]},
           # unified metrics registry: counters (bucket/cache hits),
           # gauges (model drift / Spearman per family), histograms
           # (serving latency) -- the regression gate prints the
           # model-accuracy gauges next to its verdicts
           "telemetry": telemetry.metrics_snapshot()}
    if error:
        doc["error"] = error
    with open(path, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
    print(f"wrote {len(JSON_ROWS)} rows to {path}")
    if telemetry.enabled():
        tpath = os.path.join(os.path.dirname(path) or ".",
                             f"TRACE_{rev}.json")
        telemetry.export_trace(tpath)
        print(f"wrote trace ({len(telemetry.span_log())} spans) to "
              f"{tpath} -- load in https://ui.perfetto.dev")
    return path


def _time(fn, reps=3):
    """Steady-state µs of ``fn()`` via ``core.measure``: warmup runs
    (compilation) excluded, median of the repeats, every call fenced.
    ``--repeat``/``--warmup`` override every call site's default."""
    repeat = TIMING["repeat"] or reps
    TIMING["used_min"] = min(TIMING["used_min"] or repeat, repeat)
    TIMING["used_max"] = max(TIMING["used_max"] or repeat, repeat)
    m = measure_mod.measure(fn, warmup=TIMING["warmup"], repeat=repeat)
    return m.median_s * 1e6


def _modeled_seconds(prog, metapipelined: bool) -> float:
    """HBM-stream time of the program's main-memory reads; with
    metapipelining, overlapped per the schedule (max of stages)."""
    tr = traffic(prog)
    stream_s = tr.total_reads * 4 / 819e9
    if not metapipelined:
        return stream_s
    mp = build_schedule(prog)
    if mp is None:
        return stream_s
    body_words = sum(s.words for s in mp.stages if s.kind == "body")
    _, _, overlap = model_speedup(mp, flops_per_body=body_words * 100.0)
    return stream_s / max(overlap, 1.0)


def fig7():
    for name, builder in SUITE.items():
        p, sizes, make_inputs, reference = builder()
        inputs = {k: jnp.asarray(v) for k, v in make_inputs().items()}
        ref = np.asarray(reference(inputs))

        tiled_ir = insert_tile_copies(strip_mine(p, sizes))
        full_ir = tile(p, sizes)
        base_s = _modeled_seconds(p, metapipelined=False)
        variants = (("base", p, base_s),
                    ("tiled", tiled_ir,
                     _modeled_seconds(tiled_ir, metapipelined=False)),
                    ("tiled_meta", full_ir,
                     _modeled_seconds(full_ir, metapipelined=True)))
        for label, prog, model_s in variants:
            f = jax.jit(lambda **kw: execute(prog, kw))
            out = f(**inputs)
            if isinstance(out, tuple):
                out = out[0]
            np.testing.assert_allclose(np.asarray(out), ref,
                                       rtol=2e-3, atol=2e-3)
            us = _time(lambda: f(**inputs))
            emit(f"fig7/{name}/{label}", us,
                 f"model_speedup={base_s / max(model_s, 1e-12):.1f}x")


def fig5c():
    from repro.patterns.analytics import kmeans
    n, k, d, b0, b1 = 256, 8, 16, 32, 4
    p, sizes, _, _ = kmeans(n, k, d, b0, b1)
    fused = traffic(p)
    sm = traffic(insert_tile_copies(strip_mine(p, sizes)))
    ic = traffic(tile(p, sizes))
    emit("fig5c/fused/centroids_reads", 0, fused.reads["centroids"])
    emit("fig5c/stripmined/centroids_reads", 0, sm.reads["centroids"])
    emit("fig5c/interchanged/centroids_reads", 0, ic.reads["centroids"])
    ok = ic.reads["centroids"] == (n // b0) * k * d
    factor = fused.reads["centroids"] / ic.reads["centroids"]
    emit("fig5c/interchange_reduction_matches_paper", 0,
         f"{'PASS' if ok else 'FAIL'}(factor={factor:.0f}=b0)")


def table2():
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "tests"))
    from test_core_transforms import (mk_filter, mk_hist, mk_map_2x,
                                      mk_sumrows)
    checks = {
        "map": (mk_map_2x(32), {"m": (8,)}, ir.MultiFold),
        "multifold": (mk_sumrows(12, 16), {"sr": (4, 8)}, ir.MultiFold),
        "flatmap": (mk_filter(40), {"f": (8,)}, ir.FlatMap),
        "groupbyfold": (mk_hist(64, 8), {"h": (16,)}, ir.GroupByFold),
    }
    for name, (p, sizes, want) in checks.items():
        t = strip_mine(p, sizes)
        ok = isinstance(t, want) and t.strided and t.inner is not None
        emit(f"table2/{name}", 0, "PASS" if ok else "FAIL")


def table3():
    from repro.core.codegen_pallas import lower, match_tiled_gemm
    p, sizes, make_inputs, reference = SUITE["gemm"]()
    t = tile(p, sizes)
    inputs = make_inputs()
    ok = match_tiled_gemm(t)
    kern = lower(t)
    out = kern(**inputs)
    np.testing.assert_allclose(np.asarray(out), reference(inputs),
                               rtol=2e-3, atol=2e-3)
    us = _time(lambda: kern(**inputs), reps=1)
    emit("table3/gemm_interchanged_kernel", us,
         "PASS" if ok else "FAIL")


def kernels():
    from repro.kernels import ref
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.matmul import matmul
    from repro.kernels.ssd_scan import ssd_scan

    x = jax.random.normal(jax.random.PRNGKey(0), (256, 256))
    y = jax.random.normal(jax.random.PRNGKey(1), (256, 256))
    us = _time(lambda: matmul(x, y, block_m=128, block_n=128,
                              block_k=128), reps=1)
    err = float(jnp.max(jnp.abs(matmul(x, y) - ref.matmul(x, y))))
    emit("kernels/matmul_256", us, f"max_err={err:.1e}")

    q = jax.random.normal(jax.random.PRNGKey(2), (1, 4, 256, 64))
    k = jax.random.normal(jax.random.PRNGKey(3), (1, 2, 256, 64))
    v = jax.random.normal(jax.random.PRNGKey(4), (1, 2, 256, 64))
    us = _time(lambda: flash_attention(q, k, v, block_q=128,
                                       block_k=128), reps=1)
    err = float(jnp.max(jnp.abs(
        flash_attention(q, k, v) - ref.attention(q, k, v))))
    emit("kernels/flash_attention_gqa", us, f"max_err={err:.1e}")

    xs = jax.random.normal(jax.random.PRNGKey(5), (1, 128, 4, 32))
    dt = jax.nn.softplus(jax.random.normal(jax.random.PRNGKey(6),
                                           (1, 128, 4))) * 0.1
    A = -jnp.ones((4,)) * 0.5
    B = jax.random.normal(jax.random.PRNGKey(7), (1, 128, 16))
    C = jax.random.normal(jax.random.PRNGKey(8), (1, 128, 16))
    us = _time(lambda: ssd_scan(xs, dt, A, B, C, chunk=32), reps=1)
    err = float(jnp.max(jnp.abs(ssd_scan(xs, dt, A, B, C, chunk=32)
                                - ref.ssd_scan(xs, dt, A, B, C))))
    emit("kernels/ssd_scan_chunked", us, f"max_err={err:.1e}")


def roofline():
    path = os.path.join(os.path.dirname(__file__), "..",
                        "results_single.jsonl")
    if not os.path.exists(path):
        emit("roofline/skipped", 0, "no results_single.jsonl")
        return
    from benchmarks.roofline import analyze_record
    with open(path) as f:
        for line in f:
            r = json.loads(line)
            if "skipped" in r or "error" in r:
                continue
            a = analyze_record(r)
            emit(f"roofline/{r['arch']}/{r['shape']}", 0,
                 f"bottleneck={a['dominant']}"
                 f";frac={a['roofline_fraction']:.3f}")


def _depth_delta_row(section: str, p, plan) -> None:
    """One row per workload: the depth the DSE chose and the modeled
    depth-2-vs-best delta at the winning tile sizes (0% everywhere the
    exposed-DMA-latency term is already saturated at depth 2)."""
    from repro.core.dse import price

    best = price(p, plan.sizes, depth=plan.depth)
    d2 = price(p, plan.sizes, depth=2)
    if best is None or d2 is None:  # depth-2 over budget: report why
        emit(f"{section}/depth", 0,
             f"chosen={plan.depth};depth2=over-vmem", depth=plan.depth)
        return
    delta = (d2.modeled_seconds - best.modeled_seconds) \
        / max(d2.modeled_seconds, 1e-30)
    emit(f"{section}/depth", 0,
         f"chosen={plan.depth};model_d2_vs_best={delta * 100:+.1f}%",
         depth=int(plan.depth), model_d2_vs_best=round(delta, 4))


def autotile():
    """Tuned-vs-hand-picked tile sizes for every suite benchmark: wall
    time of the lowered program plus the cost model's accounting (the
    quantity the DSE argmin optimizes), and the searched metapipeline
    buffer depth with its depth-2-vs-best modeled delta."""
    from repro.core.dse import explore, price

    for name, builder in SUITE.items():
        p, hand_sizes, make_inputs, reference = builder()
        inputs = {k: jnp.asarray(v) for k, v in make_inputs().items()}
        ref = np.asarray(reference(inputs))
        plan = explore(p)
        hand = price(p, hand_sizes)
        variants = (("hand", hand_sizes,
                     hand.traffic_words if hand else "over-vmem"),
                    ("tuned", plan.sizes, plan.traffic_words))
        for label, sizes, words in variants:
            prog = tile(p, sizes)
            f = jax.jit(lambda **kw: execute(prog, kw))
            out = f(**inputs)
            if isinstance(out, tuple):
                out = out[0]
            np.testing.assert_allclose(np.asarray(out), ref,
                                       rtol=2e-3, atol=2e-3)
            us = _time(lambda: f(**inputs))
            emit(f"autotile/{name}/{label}", us,
                 f"traffic_words={words};sizes={dict(sizes)}")
        _depth_delta_row(f"autotile/{name}", p, plan)
        ok = hand is None or plan.traffic_words <= hand.traffic_words
        emit(f"autotile/{name}/tuned_le_hand", 0,
             "PASS" if ok else "FAIL")


def _pipeline_depth_row(section: str, pipe, plan) -> None:
    """Chosen per-group buffer depths + the modeled depth-2-vs-best
    delta, repricing the winning (groups, blocks) with every group
    forced to depth 2 (uncalibrated pricing both ways, so the delta
    isolates the exposed-DMA-latency term deeper buffering buys down).
    """
    from repro.core import dse
    from repro.core import pipeline as plmod
    from repro.core.cost import VMEM_BYTES

    counters = {"explored": 0, "pruned": 0}

    def total_seconds(depths):
        s = 0.0
        for (i0, i1), b, d in zip(plan.groups, plan.group_blocks,
                                  depths):
            pr = dse._price_pipeline_group(
                plmod.sub_pipeline(pipe, i0, i1), b,
                vmem_budget=VMEM_BYTES, profile=None,
                counters=counters, depth=d)
            if pr is None:
                return None
            s += pr[3]
        return s

    chosen = plan.depths or (2,) * len(plan.groups)
    best_s = total_seconds(chosen)
    d2_s = total_seconds((2,) * len(plan.groups))
    if best_s is None or d2_s is None:
        emit(f"{section}/depth", 0,
             f"chosen={list(chosen)};depth2=over-vmem",
             depths=list(map(int, chosen)))
        return
    delta = (d2_s - best_s) / max(d2_s, 1e-30)
    emit(f"{section}/depth", 0,
         f"chosen={list(chosen)};model_d2_vs_best={delta * 100:+.1f}%",
         depths=list(map(int, chosen)),
         model_d2_vs_best=round(delta, 4))


def _check_outputs(pipe, got, ref):
    """Compare a pipeline execution (array or name -> array dict)
    against its reference, output by output."""
    from repro.core.pipeline import output_names

    if not isinstance(ref, dict):
        ref = {output_names(pipe)[0]: np.asarray(ref)}
    if not isinstance(got, dict):
        got = {output_names(pipe)[0]: got}
    for k, want in ref.items():
        np.testing.assert_allclose(np.asarray(got[k]), np.asarray(want),
                                   rtol=2e-3, atol=2e-3)


def fused():
    """Pipeline fusion: fused megakernel vs per-pattern DAG for every
    pipeline in ``PIPELINES`` (chains and fan-out DAGs alike; kmeans
    and gda_moments are multi-output, normalize ends in a write-once
    Map terminal).  Reports interpret-mode wall time and the cost
    model's HBM traffic both ways; the traffic ratio is the fusion win
    the paper's Fig. 5/6 metapipelines bank on, and these rows are the
    perf surface ``benchmarks/check_regression.py`` gates in CI.  Each
    pipeline also reports its searched metapipeline buffer depths and
    the depth-2-vs-best modeled delta at the winning blocks."""
    from repro.core.dse import explore_pipeline
    from repro.core.pipeline import lower_pipeline

    wins = 0
    strict = 0
    for name, builder in PIPELINES.items():
        pipe, make_inputs, reference = builder()
        inputs = {k: jnp.asarray(v) for k, v in make_inputs().items()}
        ref = reference(make_inputs())
        plan = explore_pipeline(pipe)

        fused_f = lower_pipeline(pipe, fused=True, plan=plan)
        unfused_f = lower_pipeline(pipe, fused=False)
        for label, f, words in (
                ("fused", fused_f, plan.traffic_words),
                ("unfused", unfused_f, plan.unfused_traffic_words)):
            _check_outputs(pipe, f(**inputs), ref)
            us = _time(lambda: f(**inputs), reps=1)
            emit(f"fused/{name}/{label}", us,
                 f"traffic_words={words};block={plan.block}",
                 traffic_words=int(words), block=int(plan.block))
        _pipeline_depth_row(f"fused/{name}", pipe, plan)
        ratio = plan.traffic_ratio
        if ratio >= 1.5:
            wins += 1
        if plan.traffic_words < plan.unfused_traffic_words:
            strict += 1
        emit(f"fused/{name}/traffic_ratio", 0, f"{ratio:.2f}x"
             + (";groups=" + str(list(plan.groups)) if not plan.fused
                else ""),
             traffic_ratio=round(ratio, 2))
    emit("fused/ge_1.5x_on_most", 0,
         "PASS" if wins >= len(PIPELINES) - 1 else "FAIL", wins=wins)
    emit("fused/strictly_below_unfused_all", 0,
         "PASS" if strict == len(PIPELINES) else "FAIL", strict=strict)


def _kernel_proxy_programs():
    """The five Pallas kernels' DSE proxy programs at the suite's
    interpret-friendly shapes (one entry per ``auto_tile=True`` kernel)."""
    from repro.core import dse

    return {
        "matmul": dse.gemm_program(256, 256, 256),
        "flash_attention": dse.attention_program(256, 256, 64),
        "ssd_scan": dse.scan_program(256, 16, 32),
        "filter_reduce": dse.filter_reduce_program(4096),
        "groupby_fold": dse.groupby_program(256, 8, 16),
    }


def measured():
    """Hybrid analytic->measured DSE over every kernel proxy and every
    pipeline: lower + time the analytic top-k, fold the samples into
    the device calibration profile, then table the Spearman rank
    correlation of the analytic and the *final* calibrated ranking
    against the measured one.  The gate row checks the calibrated model
    ranks candidates at least as well as the uncalibrated one."""
    from repro.core import calibrate, dse
    from repro.core.cost import HBM_BYTES_PER_S
    from repro.core.measure import spearman

    top_k = TIMING["topk"] or dse.TOP_K
    warmup = TIMING["warmup"]
    repeat = TIMING["repeat"] or dse.MEASURE_REPEAT
    TIMING["used_min"] = min(TIMING["used_min"] or repeat, repeat)
    TIMING["used_max"] = max(TIMING["used_max"] or repeat, repeat)
    # (row name, pattern kind, [(analytic_s, steps, measured_s, label)],
    #  extra json fields)
    tables = []

    for name, p in _kernel_proxy_programs().items():
        # cache=None: the default on-disk tuning cache supplies the
        # persistent candidate quarantine (crashing candidates are
        # skipped on re-runs instead of re-attempted)
        ts = dse.measured_shortlist(p, top_k=top_k, warmup=warmup,
                                    repeat=repeat, cache=None)
        tables.append((f"kernel/{name}", type(p).__name__,
                       [(t.analytic_seconds, t.steps,
                         t.measurement.median_s, str(dict(t.plan.sizes)))
                        for t in ts], {}))
    for name, builder in PIPELINES.items():
        pipe, _, _ = builder()
        ts = dse.measured_pipeline_shortlist(pipe, top_k=top_k,
                                             warmup=warmup, repeat=repeat,
                                             cache=None)
        # measured depth-2-vs-best: the timed (block, depth) variants
        # execute depth-deep rotating scratch, so when both the winner
        # and a depth-2 variant were timed the delta is real, not
        # modeled
        extra = {}
        if ts:
            best_t = min(ts, key=lambda t: t.measurement.median_s)
            d2 = [t for t in ts if t.plan.depth == 2]
            if d2 and best_t.plan.depth != 2:
                d2_s = min(t.measurement.median_s for t in d2)
                extra["measured_d2_vs_best"] = round(
                    (d2_s - best_t.measurement.median_s)
                    / max(d2_s, 1e-30), 4)
        tables.append((f"pipeline/{name}", "Pipeline",
                       [(t.analytic_seconds, t.steps,
                         t.measurement.median_s,
                         f"block={t.plan.block},depth={t.plan.depth}")
                        for t in ts], extra))

    # rank correlations against the FINAL profile (fitted on exactly
    # these samples): its rank guard makes the calibrated mean >= the
    # analytic mean in-sample, the property the gate row asserts
    prof = calibrate.load_profile()
    rhos_a, rhos_c = [], []
    for name, kind, rows, extra in tables:
        if not rows:
            emit(f"measured/{name}", 0, "no-candidates-timed")
            continue
        meas = [r[2] for r in rows]
        ana = [r[0] for r in rows]
        cal = [r[0] if prof is None
               else prof.seconds(kind, r[0] * HBM_BYTES_PER_S, r[1])
               for r in rows]
        rho_a = spearman(ana, meas)
        rho_c = spearman(cal, meas)
        rhos_a.append(rho_a)
        rhos_c.append(rho_c)
        best = min(range(len(rows)), key=lambda i: rows[i][2])
        derived = (f"rho_analytic={rho_a:+.2f};"
                   f"rho_calibrated={rho_c:+.2f};"
                   f"timed={len(rows)};best={rows[best][3]}")
        if "measured_d2_vs_best" in extra:
            derived += (";measured_d2_vs_best="
                        f"{extra['measured_d2_vs_best'] * 100:+.1f}%")
        emit(f"measured/{name}", rows[best][2] * 1e6, derived,
             rho_analytic=round(rho_a, 3), rho_calibrated=round(rho_c, 3),
             timed=len(rows), **extra)

    if prof is not None:
        emit("measured/calibration_profile", 0,
             f"device={prof.device};mode={prof.mode};"
             f"eff_bw={prof.bandwidth_bytes_per_s:.3e}B/s;"
             f"n_samples={prof.n_samples};hash={prof.hash}",
             device=prof.device, mode=prof.mode,
             n_samples=prof.n_samples, profile_hash=prof.hash)
    if not rhos_a:
        # zero timed candidates means zero evidence: a broken
        # lower-for-timing path must not show up as a green gate
        emit("measured/calibrated_ge_analytic", 0,
             "FAIL(no candidates were timed)", timed_workloads=0)
        return
    mean_a = sum(rhos_a) / len(rhos_a)
    mean_c = sum(rhos_c) / len(rhos_c)
    ok = mean_c >= mean_a - 0.05
    emit("measured/calibrated_ge_analytic", 0,
         ("PASS" if ok else "FAIL")
         + f"(mean_rho_calibrated={mean_c:+.2f},"
           f"mean_rho_analytic={mean_a:+.2f})",
         mean_rho_analytic=round(mean_a, 3),
         mean_rho_calibrated=round(mean_c, 3),
         timed_workloads=len(rhos_a))


def serving():
    """Cold-shape tail latency through the shape-bucket warm-start
    layer (``core.buckets``).  One donor shape per kernel family is
    tuned into a scratch cache, then each *cold* shape in the same
    bucket family is explored twice: once cold (fresh cache, full
    foreground exploration -- the first-request latency a bucketless
    server pays) and once bucketed (warm-start plan adapted from the
    donor, background re-tune).  Rows report both latencies, the warm
    plan's provenance, the bucket hit rate, and how many background
    re-tunes promoted a certified winner."""
    import tempfile
    import time as time_mod

    from repro.core import buckets, dse
    from repro.core.options import Options

    tmp = tempfile.mkdtemp(prefix="repro-serving-")
    cache_path = os.path.join(tmp, "dse_cache.json")
    buckets.reset_stats()

    # (family label, program builder, donor shape, cold shapes): cold
    # shapes share the donor's bucket family (same signature/dtype/rank)
    # but were never explored at their exact extents
    cases = [
        ("attention", dse.attention_program,
         (256, 256, 64), [(192, 256, 64), (224, 256, 64)]),
        ("gemm", dse.gemm_program,
         (256, 256, 256), [(250, 250, 250)]),
    ]

    warm_opts = Options(cache=cache_path, bucketing=True)
    for label, build, donor, colds in cases:
        dse.explore(build(*donor), options=warm_opts)  # tune the donor
        for shape in colds:
            p = build(*shape)
            t0 = time_mod.perf_counter()
            dse.explore(p, options=Options(cache=False))
            before_s = time_mod.perf_counter() - t0

            t0 = time_mod.perf_counter()
            plan = dse.explore(p, options=warm_opts)
            after_s = time_mod.perf_counter() - t0
            name = f"serving/{label}/" + "x".join(map(str, shape))
            emit(name, after_s * 1e6,
                 f"cold_explore={before_s * 1e6:.0f}us;"
                 f"warm_start={plan.warm_start};bucket={plan.bucket}",
                 cold_us=round(before_s * 1e6, 1),
                 warm_us=round(after_s * 1e6, 1),
                 warm_start=bool(plan.warm_start),
                 bucket=plan.bucket)

    buckets.drain()
    st = buckets.stats()
    emit("serving/bucket_hit_rate", 0,
         f"{buckets.hit_rate():.2f}"
         f"(exact={st['exact_hits']},warm={st['warm_hits']},"
         f"miss={st['misses']})",
         hit_rate=round(buckets.hit_rate(), 3), **st)
    emit("serving/background_promotions", 0,
         f"{st['promotions']}/{st['retunes']} re-tunes certified "
         "and promoted",
         promotions=st["promotions"], retunes=st["retunes"],
         retune_failures=st["retune_failures"])
    serving_decode()


def serving_decode():
    """Decode ms/token plain (grouped dense caches) vs paged
    (continuous batching over the paged pool, fused Pallas decode),
    plus the *modeled* decode HBM traffic of the same mixed-length
    trace under both cache disciplines and the continuous-batching
    slot occupancy.  Traffic rows are deterministic (analytic model
    over the trace) and gated by check_regression.py; ms/token rows
    are reported for visibility."""
    from repro.launch import serve as serve_mod

    lens = (3, 5, 9, 4, 6)
    gen, slots = 5, 3
    plain_stats = {}
    serve_mod.serve("granite-3-2b", True, len(lens), 8, gen,
                    prompt_lens=lens, stats_out=plain_stats)
    _, cont = serve_mod.serve_continuous("granite-3-2b", True, slots,
                                         gen, prompt_lens=lens)

    emit("serving/decode_ms_per_token/plain",
         plain_stats["ms_per_token"] * 1e3,
         f"{plain_stats['ms_per_token']:.1f}ms/token "
         f"(grouped dense caches)",
         ms_per_token=round(plain_stats["ms_per_token"], 3))
    emit("serving/decode_ms_per_token/paged",
         cont["ms_per_token"] * 1e3,
         f"{cont['ms_per_token']:.1f}ms/token "
         f"(layout={cont['layout']},page={cont['page_size']},"
         f"pallas={cont['use_pallas']},certified={cont['certified']})",
         ms_per_token=round(cont["ms_per_token"], 3),
         layout=cont["layout"], page_size=cont["page_size"],
         use_pallas=cont["use_pallas"], certified=cont["certified"])
    dense_w = cont["modeled_dense_traffic_words"]
    paged_w = cont["modeled_paged_traffic_words"]
    emit("serving/decode_traffic/plain", 0, f"{dense_w} words "
         "(dense lanes at max context)", traffic_words=dense_w)
    emit("serving/decode_traffic/paged", 0,
         f"{paged_w} words ({dense_w / max(paged_w, 1):.2f}x fewer: "
         "live pages only)", traffic_words=paged_w,
         traffic_ratio=round(dense_w / max(paged_w, 1), 3))
    emit("serving/continuous_occupancy", 0,
         f"{cont['occupancy']:.2f} "
         f"({cont['requests']} requests over {cont['slots']} slots, "
         f"{cont['steps']} steps)",
         occupancy=round(cont["occupancy"], 3),
         requests=cont["requests"], slots=cont["slots"],
         steps=cont["steps"])


def resilience_rows() -> None:
    """One row per degradation action the run took (quarantined /
    retried / fallback / rebuilt / skipped), plus a total.  Zero rows
    on a clean run; the chaos-smoke CI step asserts they are NONZERO
    under injected faults -- proving the tuning runtime degraded
    instead of dying."""
    counts = resilience.LOG.counts()
    for action in sorted(counts):
        emit(f"resilience/{action}", 0, counts[action],
             count=counts[action])
    if counts:
        emit("resilience/total", 0, sum(counts.values()),
             count=sum(counts.values()))


SECTIONS = {
    "fig7": fig7,
    "fig5c": fig5c,
    "table2": table2,
    "table3": table3,
    "kernels": kernels,
    "roofline": roofline,
    "autotile": autotile,
    "fused": fused,
    "measured": measured,
    "serving": serving,
}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--autotile", action="store_true",
                    help="also run the autotile section (DSE-tuned vs "
                         "hand-picked tile sizes)")
    ap.add_argument("--measure", action="store_true",
                    help="also run the measured section (hybrid "
                         "analytic->measured DSE + calibration, rank-"
                         "correlation table)")
    ap.add_argument("--repeat", type=int, default=None, metavar="N",
                    help="timed repeats per row (median reported; "
                         "default: per-section, 1-3)")
    ap.add_argument("--warmup", type=int, default=1, metavar="N",
                    help="warmup (compile) runs excluded from every "
                         "timing (default 1)")
    ap.add_argument("--topk", type=int, default=None, metavar="K",
                    help="candidates lowered+timed per workload in the "
                         "measured section (default core.dse.TOP_K)")
    ap.add_argument("--only", default=None, metavar="SECTIONS",
                    help="comma-separated subset of sections to run: "
                         + ",".join(SECTIONS))
    ap.add_argument("--json", default=None, metavar="OUT",
                    help="write rows as BENCH_<rev>.json (OUT = dir or "
                         ".json path)")
    args = ap.parse_args(argv)
    backend.enable_compile_cache()
    TIMING["repeat"] = args.repeat
    TIMING["warmup"] = args.warmup
    TIMING["topk"] = args.topk

    if args.only:
        names = [s.strip() for s in args.only.split(",") if s.strip()]
        unknown = [s for s in names if s not in SECTIONS]
        if unknown:
            ap.error(f"unknown sections {unknown}; choose from "
                     f"{list(SECTIONS)}")
    else:
        names = [s for s in SECTIONS if s not in ("autotile", "measured")]
    if args.autotile and "autotile" not in names:
        names.append("autotile")
    if args.measure and "measured" not in names:
        names.append("measured")

    error = ""
    try:
        for s in names:
            SECTIONS[s]()
    except BaseException as e:
        error = f"{type(e).__name__}: {e}"
        raise
    finally:
        # degradation summary rows come last so every section's
        # quarantine/fallback/retry activity is already accounted
        resilience_rows()
        print(f"\n{len(ROWS)} benchmark rows emitted")
        if args.json:
            # written even on zero rows or a mid-section crash: the CI
            # artifact / regression gate must always find the file
            write_json(args.json, error=error)


if __name__ == "__main__":
    main()
