"""The benchmark harness: finds a cell's parts by name, runs the cell,
checks what it produced and prints the result line.

Everything that belongs to one configuration, traffic mix or metric
sits in a file of its own, found by the name ``BENCHMARK.json`` gives:

* ``configs/<config>.json``      sizes of the configuration as it runs
* ``reference/<config>.py``      its plain reference (``-`` read as ``_``)
* ``traffic/<traffic>.json``     parameters of a traffic mix; its
  ``driver`` key names the general driver that reads it
* ``drivers/<driver>.py``        generator and driver for a kind of system
* ``metrics/<metric>.py``        one reader per metric

So a later cell, configuration, mix or metric is new files plus new
entries in ``BENCHMARK.json``, and no edit to a file that is here.
"""
from __future__ import annotations

import importlib.util
import json
import os
import shutil
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional, Tuple

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_module(path: Path):
    """Import one file of the benchmark by its path (names may hold
    ``.`` and ``-``, which ``import`` cannot)."""
    if not path.is_file():
        raise FileNotFoundError(f"benchmark part missing: {path}")
    mod_name = "bench_part_" + "".join(
        c if c.isalnum() else "_" for c in str(path.relative_to(path.parents[1])))
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with its parts resolved."""
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    driver: Any
    reference: Any
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    bench_dir: Path
    root: Path

    def metric_reader(self, name: str):
        return load_module(self.bench_dir / "metrics" / f"{name}.py")


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(workload: str, root: Path = ROOT,
            bench_dir: Path = BENCH) -> Cell:
    spec = json.loads((root / "BENCHMARK.json").read_text())
    by_name = {w["name"]: w for w in spec["workloads"]}
    if workload not in by_name:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"known: {sorted(by_name)}")
    w = by_name[workload]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[w["config"]]
    config = json.loads((root / cfg_entry["file"]).read_text())
    traffic = json.loads(
        (bench_dir / "traffic" / f"{w['traffic']}.json").read_text())
    driver = load_module(bench_dir / "drivers" / f"{traffic['driver']}.py")
    reference = load_module(
        bench_dir / "reference" / f"{w['config'].replace('-', '_')}.py")
    e2e = [m for m in spec["end_to_end"] if _applies(m, workload)]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if (workload in m["workloads"] if "workloads" in m
                     else m["moves"] in e2e_names)]
    return Cell(workload, int(w["chips"]), config, traffic, driver,
                reference, e2e, per_layer, bench_dir, root)


# ----------------------------------------------------------- compiles
class Compiles:
    """Backend compiles (and persistent-cache reads, which JAX reports
    as the same event), each with the host time it ended."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.ends: List[float] = []

        def on_duration(event, duration, **_):
            if event == self.EVENT:
                self.ends.append(time.perf_counter())

        jax.monitoring.register_event_duration_secs_listener(on_duration)

    def between(self, t0: float, t1: float) -> int:
        return sum(t0 < t <= t1 for t in self.ends)


# -------------------------------------------------------------- trace
class TraceSlice:
    """A profiler trace of a short steady slice of the window.  The
    ``bench.sync`` annotation, taken at a known host time, puts the
    host clock and the trace's clock side by side."""

    def __init__(self, out_dir: Path):
        self.out_dir = out_dir
        self.started = self.stopped = None
        self.sync_pc = None

    def start(self):
        import jax

        shutil.rmtree(self.out_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # no per-call Python events
        opts.host_tracer_level = 1        # annotations and runtime only
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(str(self.out_dir), profiler_options=opts)
        self.started = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.sync"):
            self.sync_pc = time.perf_counter()

    def stop(self):
        import jax

        self.stopped = time.perf_counter()
        jax.profiler.stop_trace()

    def xplane(self) -> Path:
        found = sorted(self.out_dir.rglob("*.xplane.pb"))
        if len(found) != 1:
            raise RuntimeError(f"expected one trace under {self.out_dir}, "
                               f"found {found}")
        return found[0]

    def remove(self):
        shutil.rmtree(self.out_dir, ignore_errors=True)


# ---------------------------------------------------------------- run
@dataclass
class Run:
    """What a driver hands back: the measured window and what the
    metric readers and the correctness check read."""
    setup_end: float                    # host time the window opened
    window: Tuple[float, float]
    attempted: int
    failed: int
    data: Dict[str, Any] = field(default_factory=dict)
    check: Optional[Callable[[], Dict[str, Tuple[float, float]]]] = None
    # the same numbers from the control (the reference one precision
    # down): read by ``bench/control.py``, never by a benchmark run
    control: Optional[Callable[[], Dict[str, float]]] = None
    trace: Optional[Dict[str, Any]] = None


def device_info(chips: int) -> Dict[str, Any]:
    import jax

    devs = jax.devices()[:chips]
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def check_chips(chips: int) -> Optional[str]:
    """Why this machine cannot run the cell, or None."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        return (f"no TPU: JAX's first device is {devs[0].platform} "
                f"({devs[0].device_kind})")
    if len(devs) < chips:
        return f"the cell needs {chips} chips, JAX sees {len(devs)}"
    return None


def configure_caches(root: Path) -> None:
    """Compile cache and DSE tuning cache at fixed paths inside the
    checkout, so only a cell's first run in a checkout compiles or
    searches; the program's sources on the path.  Runs before JAX or
    the program is imported."""
    cache = root / ".jax_cache"
    cache.mkdir(parents=True, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(cache)
    os.environ["REPRO_DSE_CACHE"] = str(root / ".bench_cache" /
                                        "dse_cache.json")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    for path in (str(ROOT / "src"), str(BENCH)):
        if path not in sys.path:
            sys.path.insert(0, path)
    import jax

    jax.config.update("jax_compilation_cache_dir", str(cache))
    # every program into the persistent cache, however fast it compiled
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, root: Path = ROOT, bench_dir: Path = BENCH,
             require_tpu: bool = True) -> Dict[str, Any]:
    """Run one cell and return its result object (the last line).
    ``require_tpu=False`` lets the tests drive everything but the
    look for a chip, on the CPU at small sizes."""
    configure_caches(root)
    cell = resolve(workload, root, bench_dir)
    if require_tpu:
        why = check_chips(cell.chips)
        if why:
            raise SystemExit(f"run_cell: {why}")
    compiles = Compiles()
    trace_dir = root / ".bench_traces" / f"{workload}-{seed}"
    run: Run = cell.driver.run(cell, seed=seed, seconds=seconds,
                               trace=TraceSlice(trace_dir) if trace else None,
                               t_start=t_start)
    t0, t1 = run.window
    n_comp = compiles.between(t0, t1)
    print(f"compiles in the window: {n_comp}")
    device = device_info(cell.chips)
    checks = run.check() if run.check else {}
    correct = bool(checks) and all(
        v == v and v <= lim for v, lim in checks.values())
    run.data.update(setup_s=run.setup_end - t_start, device=device)
    metrics = {}
    wanted = cell.per_layer if trace else cell.end_to_end
    for m in wanted:
        value = cell.metric_reader(m["name"]).read(run, cell)
        if value is None:
            if not trace:
                raise RuntimeError(f"metric {m['name']} read nothing")
            continue        # a per-layer reader that found nothing
        metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    result: Dict[str, Any] = {
        "correct": correct, "attempted": run.attempted,
        "failed": run.failed, "metrics": metrics, "device": device}
    if trace and run.trace:
        device.update(busy_s=run.trace["busy_s"],
                      window_s=run.trace["window_s"])
        result["breakdown"] = {
            "device_ops": run.trace["top_ops"][:10],
            "idle_gaps": run.trace["idle_gaps"][:10]}
        shutil.rmtree(trace_dir, ignore_errors=True)
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    return result


def report(result: Dict[str, Any]) -> None:
    """The compared numbers as the last lines of standard error, and
    the result as the last line of standard output."""
    sys.stdout.flush()
    for k, c in result["checks"].items():
        ok = "ok" if c["value"] <= c["limit"] else "FAILS"
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r}) {ok}",
              file=sys.stderr)
    if not result["checks"]:
        print("check: nothing was compared", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result))
    sys.stdout.flush()
