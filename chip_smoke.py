#!/usr/bin/env python3
"""Smoke run of the system's two main paths on one TPU.

    python chip_smoke.py

* Patterns: the five ``patterns.analytics.PIPELINES`` through
  ``lower_pipeline(fused=True)`` -- ``tpchq6`` at 2**26 rows (three f32
  columns, about the ``lineitem`` of TPC-H SF 11), the others at 2**20
  -- each of which must lower as one Mosaic megakernel and agree with
  the unfused oracle (``pipeline.run_unfused``), evaluated in float64 on
  the host CPU.
* Serve: granite-3-2b at its full published config (40 layers, d_model
  2048, random weights from seed 0) behind ``serve_continuous``: six
  requests over four slots of the paged KV pool, decoded by the fused
  paged-attention kernel, whose first-step logits are certified
  against the reference paged path.

Everything runs in this one process.  Without a TPU it exits non-zero
and prints no result; on success the last line of stdout is one JSON
object naming the device.  Times printed are of a single run, the
first call of each program in this process, compilation included; the
last line before the JSON says how many programs JAX's persistent
compile cache supplied (all zero when the cache started empty).
"""
import json
import os
import sys
import time
from pathlib import Path

import numpy as np

SIZES = {"tpchq6": 2 ** 26, "gda": 2 ** 20, "kmeans": 2 ** 20,
         "gda_moments": 2 ** 20, "normalize": 2 ** 20}
# fused f32 kernel vs the float64 oracle, relative to the largest
# reference value: the kernel folds at most 2**26 f32 terms, one vector
# sum per chunk and one accumulator add per chunk, so the rounding
# errors (2**-24 each, random in sign) add to ~1e-6 of the total; the
# CAM matmul runs at HIGHEST precision.  kmeans may also move a point
# whose two nearest centroids tie within f32 rounding (a count of 1 in
# ~1e5 per cluster).  1e-4 covers both with margin.
PATTERN_RTOL = 1e-4
SERVE = dict(slots=4, gen=32, prompt_lens=(128, 512, 2000, 64, 1024, 300))


class Compiles:
    """Seconds JAX spends in backend compiles, summed, and how many of
    the programs the persistent cache supplied (their seconds are the
    cache read, not a compile)."""

    EVENT = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.count = 0
        self.cache_hits = 0

        def on_duration(event, duration, **_):
            if event == self.EVENT:
                self.seconds += duration
                self.count += 1

        def on_event(event, **_):
            if event == self.HIT:
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


def patterns_phase(compiles: Compiles) -> None:
    import jax

    from repro.core import pipeline as plmod
    from repro.patterns.analytics import PIPELINES

    cpu = jax.devices("cpu")[0]
    for name, build in PIPELINES.items():
        pipe, make_inputs, _ = build(SIZES[name])
        host = make_inputs()
        t0, c0 = time.perf_counter(), compiles.seconds
        call = plmod.lower_pipeline(pipe, fused=True)
        hows = [how for _, how in call.group_lowerings]
        if any(how != "megakernel" for how in hows):
            raise RuntimeError(f"{name}: lowered as {hows}, not one "
                               "megakernel per group")
        out = call(**{k: jax.device_put(v) for k, v in host.items()})
        out = jax.block_until_ready(out)
        wall, comp = time.perf_counter() - t0, compiles.seconds - c0
        with jax.enable_x64(True), jax.default_device(cpu):
            ref = plmod.unfused_runner(pipe)(
                **{k: np.asarray(v, np.float64) for k, v in host.items()})
        outs = out if isinstance(out, dict) else {pipe.name: out}
        refs = ref if isinstance(ref, dict) else {pipe.name: ref}
        for key in refs:
            got = np.asarray(outs[key], np.float64)
            want = np.asarray(refs[key], np.float64)
            if got.shape != want.shape or not np.all(np.isfinite(got)):
                raise RuntimeError(f"{name}/{key}: shape {got.shape} "
                                   f"(want {want.shape}) or non-finite")
            err = float(np.max(np.abs(got - want))
                        / max(float(np.max(np.abs(want))), 1e-30))
            print(f"patterns {name}/{key}: n={SIZES[name]} "
                  f"block={call.pipeline_plan.block} lowering={hows} "
                  f"rel_err={err:.3e} (tol {PATTERN_RTOL})")
            if not err <= PATTERN_RTOL:
                raise RuntimeError(f"{name}/{key}: fused kernel differs "
                                   f"from the oracle by {err:.3e}")
        print(f"patterns {name}: single run {wall:.2f} s wall, "
              f"{comp:.2f} s of it compiling")


def serve_phase() -> None:
    from repro.launch import serve
    from repro.launch.serve import CERTIFY_RTOL

    t0 = time.perf_counter()
    toks, stats = serve.serve_continuous("granite-3-2b", smoke=False,
                                         **SERVE)
    wall = time.perf_counter() - t0
    want = (len(SERVE["prompt_lens"]), SERVE["gen"])
    if toks.shape != want or toks.min() < 0:
        raise RuntimeError(f"serve: tokens {toks.shape}, want {want}")
    if not (stats["use_pallas"] and stats["certified"]):
        raise RuntimeError(f"serve: use_pallas={stats['use_pallas']} "
                           f"certified={stats['certified']}")
    print(f"serve granite-3-2b: use_pallas={stats['use_pallas']} "
          f"certified={stats['certified']} layout={stats['layout']} "
          f"page_size={stats['page_size']} first-step logits rel_err="
          f"{stats['certify_err']:.3e} (tol {CERTIFY_RTOL}); "
          f"{stats['requests']} requests, {stats['steps']} steps")
    print(f"serve granite-3-2b: single run {wall:.2f} s wall "
          f"(prefill {stats['prefill_s']:.2f} s, decode "
          f"{stats['decode_s']:.2f} s, compiles included)")


def main() -> int:
    # the oracle runs on the host CPU next to the chip
    platforms = os.environ.get("JAX_PLATFORMS")
    if platforms and "cpu" not in platforms.split(","):
        os.environ["JAX_PLATFORMS"] = platforms + ",cpu"
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX's first device is "
              f"{dev.platform}: {dev.device_kind})", file=sys.stderr)
        return 1
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.core import backend, cost

    cache_dir = backend.enable_compile_cache()
    peaks = cost.chip()
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}"
          f" (peaks {peaks.peak_flops:.3g} FLOP/s, "
          f"{peaks.hbm_bytes_per_s:.3g} B/s HBM)")
    print(f"compile cache: {cache_dir}")
    compiles = Compiles()
    t0 = time.perf_counter()
    patterns_phase(compiles)
    c0 = compiles.seconds
    serve_phase()
    print(f"compile: {compiles.seconds:.2f} s over {compiles.count} "
          f"programs, {compiles.cache_hits} of them read from the "
          f"persistent cache (serve {compiles.seconds - c0:.2f} s); "
          f"total {time.perf_counter() - t0:.2f} s wall, single run")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
