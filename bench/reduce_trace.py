"""Reduction of a profiler trace (``.xplane.pb``) to the benchmark's
device numbers: busy and idle time over a window, the time of the
Pallas kernels, the device operations that took the most time, and the
longest idle gaps labelled with what the host was doing in them.

The trace's times are relative to its own start.  The harness puts a
``bench.sync`` annotation into the trace at a host time it knows
(``time.perf_counter``), which maps the host clock onto the trace's.
On a TPU v5e the device's timestamps sit 1-2 ms off the host's, so a
label on an idle gap shorter than that is approximate.
Only the ``XLA Ops`` line of each device plane is read: the operations
the device ran, one interval each.
"""
from __future__ import annotations

from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
SYNC = "bench.sync"


def is_pallas_kernel(name: str, stats: Dict[str, object]) -> bool:
    """A Mosaic kernel: every ``pallas_call`` lowers to a TPU custom
    call.  The program gives its kernels no names of their own yet, so
    a cell that runs one kind of kernel attributes all of them to it."""
    text = " ".join([name] + [str(v) for v in stats.values()])
    return "tpu_custom_call" in text or "custom-call" in text \
        or "custom_call" in text


def op_name(name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``: the
    trace names a device operation by its whole HLO instruction."""
    return name.split(" = ", 1)[0].lstrip("%")


def load(path):
    from jax.profiler import ProfileData

    return ProfileData.from_file(str(path))


def device_ops(pd) -> Dict[str, List[Tuple[float, float, str, dict]]]:
    """``plane name -> [(start_ns, end_ns, op name, stats)]`` for every
    device plane of the trace."""
    out = {}
    for plane in pd.planes:
        if not plane.name.startswith(DEVICE_PREFIX):
            continue
        ops = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                ops.append((float(ev.start_ns),
                            float(ev.start_ns + ev.duration_ns),
                            ev.name, dict(ev.stats)))
        out[plane.name] = sorted(ops)
    return out


def host_event_ns(pd, name: str) -> Optional[float]:
    """Start of the first host event called ``name``."""
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == name:
                    return float(ev.start_ns)
    return None


def union(intervals: Iterable[Interval]) -> List[Interval]:
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """Idle intervals of ``[lo, hi]`` between merged busy intervals."""
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def label(gap: Interval, host: Sequence[Tuple[str, float, float]]) -> str:
    """The shortest host span that covers the gap's middle, or
    ``untracked``."""
    mid = (gap[0] + gap[1]) / 2
    best = None
    for name, a, b in host:
        if a <= mid <= b and (best is None or b - a < best[2] - best[1]):
            best = (name, a, b)
    return best[0] if best else "untracked"


def reduce(path, *, sync_pc: float, window: Tuple[float, float],
           host_spans: Sequence[Tuple[str, float, float]] = ()
           ) -> Dict[str, object]:
    """Device numbers of the trace at ``path`` over ``window``, given
    in host seconds (``perf_counter``) like ``sync_pc`` and the host
    spans ``(name, start, end)``.  Times out are in seconds."""
    pd = load(path)
    sync_ns = host_event_ns(pd, SYNC)
    if sync_ns is None:
        raise RuntimeError(f"{path}: no {SYNC} annotation in the trace")

    def to_ns(t: float) -> float:
        return sync_ns + (t - sync_pc) * 1e9

    lo, hi = to_ns(window[0]), to_ns(window[1])
    host = [(n, to_ns(a), to_ns(b)) for n, a, b in host_spans]
    planes = device_ops(pd)
    if not planes:
        raise RuntimeError(f"{path}: no {DEVICE_PREFIX}* plane")
    busy_ns, k_ns, k_calls = 0.0, 0.0, 0
    per_op: Dict[str, float] = defaultdict(float)
    idle: List[Tuple[str, float]] = []
    for ops in planes.values():
        inside = [(a, b, n, s) for a, b, n, s in ops if b > lo and a < hi]
        merged = union(clip(((a, b) for a, b, _, _ in inside), lo, hi))
        busy_ns += sum(b - a for a, b in merged)
        for a, b, n, s in inside:
            per_op[op_name(n)] += b - a
            if is_pallas_kernel(n, s):
                k_ns += b - a
                k_calls += 1
        idle += [(label(g, host), (g[1] - g[0]) * 1e-9)
                 for g in gaps(merged, lo, hi)]
    n = len(planes)
    top = sorted(per_op.items(), key=lambda kv: -kv[1])[:10]
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy_ns * 1e-9 / n,
        "kernel_s": k_ns * 1e-9 / n,
        "kernel_calls": k_calls // n,
        "top_ops": [[name, t * 1e-9 / n] for name, t in top],
        "idle_gaps": [list(g) for g in
                      sorted(idle, key=lambda g: -g[1])[:10]],
        "chips": n,
    }
