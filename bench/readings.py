"""Arithmetic the metric readers share: window sums over the serving
schedule, and shares of the roofline and of the device's time from a
reduced trace.  A reading that has nothing to read returns ``None``,
never 0."""
from __future__ import annotations

from typing import Optional

import numpy as np

import peaks


def in_window(run, end: float) -> bool:
    t0, t1 = run.window
    return t0 < end <= t1


def roofline_share(run) -> Optional[float]:
    """Per cent of the least time the chip could take for the kernel
    calls in the trace, over the time they took."""
    tr = run.trace
    if not tr or not tr.get("kernel_calls") or "kernel_bytes" not in tr:
        return None
    p = peaks.peaks(run.data["device"]["kind"])
    least, _ = peaks.roofline_s(tr["kernel_flops"], tr["kernel_bytes"], p)
    return 100.0 * least / tr["kernel_s"]


def idle_share(run) -> Optional[float]:
    tr = run.trace
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])


def window_steps(run) -> np.ndarray:
    """Indices of the decode steps that end inside the window (all but
    the two whose ends open it)."""
    steps = run.data["steps"]
    return np.asarray([k for k in range(len(steps))
                       if in_window(run, steps[k, 1])], int)
