"""The program's own spans and the device programs they launched, on
one clock, from a cell's profiler trace; and the program's span log
on the host clock.  The per-layer readers of the pipeline call and the
serving loop share it.

The program opens a profiler annotation for each of its telemetry
spans (``repro.core.telemetry``), so the trace's host plane holds them
on the same clock as the runtime's ``DoEnqueueProgram`` events.  Each
enqueue and each device ``XLA Modules`` event carries the program
run's ``run_id``.  A run cannot start on the device before the host
enqueued it, so ``enqueue start - device start`` over every run bounds
the device clock's offset from below; the largest of them is the
offset used, and its slack is the shortest launch latency of the
trace.

A reading that finds nothing to read (no trace, or a program that
opens no such spans) returns ``None``.
"""
from __future__ import annotations

import re
from dataclasses import dataclass
from functools import lru_cache
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

Interval = Tuple[float, float]

DEVICE_PREFIX = "/device:TPU:"
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
ENQUEUE = "DoEnqueueProgram"
PROGRAM_SPANS = ("pipeline.", "serve.")


@dataclass
class ProgramTrace:
    """Times in nanoseconds of the trace's host clock."""
    spans: Dict[str, List[Interval]]     # program spans by name
    modules: List[Tuple[str, int, Interval]]   # name, run_id, on host
    enqueues: Dict[int, float]           # run_id -> host enqueue start
    busy: List[Interval]                 # device ops, merged, on host
    offset_ns: float                     # device clock -> host clock

    def runs_in(self, span: Interval) -> List[int]:
        """``run_id`` of the device programs enqueued inside ``span``."""
        ran = {rid for _, rid, _ in self.modules}
        return [rid for rid, t in self.enqueues.items()
                if span[0] <= t <= span[1] and rid in ran]

    def idle_in(self, span: Interval) -> float:
        """Nanoseconds of ``span`` in which no device op ran."""
        a, b = span
        busy = sum(min(e, b) - max(s, a) for s, e in self.busy
                   if e > a and s < b)
        return (b - a) - busy


def newest_trace(root: Path, cell: str) -> Optional[Path]:
    """The newest ``*.xplane.pb`` of the cell's runs, under
    ``<root>/.bench_traces/<cell>-<seed>``."""
    base = Path(root) / ".bench_traces"
    if not base.is_dir():
        return None
    own = re.compile(re.escape(cell) + r"-(-?\d+)")
    found = [p for d in base.iterdir() if own.fullmatch(d.name)
             for p in d.rglob("*.xplane.pb")]
    return max(found, key=lambda p: p.stat().st_mtime) if found else None


def _union(intervals: Sequence[Interval]) -> List[Interval]:
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


@lru_cache(maxsize=2)
def _load(path: str, mtime: float) -> Optional[ProgramTrace]:
    from jax.profiler import ProfileData

    spans: Dict[str, List[Interval]] = {}
    modules, ops, enqueues = [], [], {}
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith(DEVICE_PREFIX)
        for line in plane.lines:
            if device and line.name not in (MODULES_LINE, OPS_LINE):
                continue
            for ev in line.events:
                t = (float(ev.start_ns),
                     float(ev.start_ns + ev.duration_ns))
                if device and line.name == OPS_LINE:
                    ops.append(t)
                elif device:
                    rid = dict(ev.stats).get("run_id")
                    if rid is not None:
                        modules.append((ev.name.split("(")[0], int(rid), t))
                elif ev.name == ENQUEUE:
                    rid = dict(ev.stats).get("run_id")
                    if rid is not None:
                        enqueues[int(rid)] = t[0]
                elif ev.name.startswith(PROGRAM_SPANS):
                    spans.setdefault(ev.name, []).append(t)
    lags = [enqueues[rid] - t[0] for _, rid, t in modules
            if rid in enqueues]
    if not lags:
        return None
    off = max(lags)
    return ProgramTrace(
        spans={k: sorted(v) for k, v in spans.items()},
        modules=[(n, rid, (a + off, b + off)) for n, rid, (a, b) in modules],
        enqueues=enqueues,
        busy=_union([(a + off, b + off) for a, b in ops]),
        offset_ns=off)


def load(cell) -> Optional[ProgramTrace]:
    """The program's spans and device programs in the cell's newest
    trace, or ``None`` where there is no trace or no program run in
    it."""
    path = newest_trace(cell.root, cell.name)
    if path is None:
        return None
    return _load(str(path), path.stat().st_mtime)


# ------------------------------------------------------- host span log
def span_log(run) -> Optional[Dict[str, np.ndarray]]:
    """The program's recorded spans by name, each an ``(n, 2)`` array
    of ``time.perf_counter`` starts and ends, the clock of
    ``run.window``; ``None`` where the program records none or gives
    no clock origin."""
    from repro.core import telemetry

    origin = getattr(telemetry, "clock_origin", None)
    log = telemetry.span_log()
    if origin is None or not log:
        return None
    t0 = origin()
    out: Dict[str, list] = {}
    for s in log:
        start = t0 + s["ts"] * 1e-6
        out.setdefault(s["name"], []).append((start, start + s["dur"] * 1e-6))
    return {k: np.asarray(sorted(v)) for k, v in out.items()}


def ending_in(run, intervals: np.ndarray) -> np.ndarray:
    """The intervals that end inside the run's window."""
    t0, t1 = run.window
    return intervals[(intervals[:, 1] > t0) & (intervals[:, 1] <= t1)]
