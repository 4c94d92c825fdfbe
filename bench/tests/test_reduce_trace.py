"""The trace reducer: interval arithmetic by hand, and a small trace
recorded on a TPU v5e (four Q6 queries at 2**22 rows through the
fused kernel, committed under ``data/``)."""
from __future__ import annotations

import json
from pathlib import Path

import pytest

import reduce_trace as rt

DATA = Path(__file__).resolve().parent / "data"


def test_union_clip_and_gaps():
    busy = rt.union([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert busy == [(0, 3), (5, 8)]
    assert rt.clip(busy, 1, 6) == [(1, 3), (5, 6)]
    assert rt.gaps(rt.clip(busy, -1, 10), -1, 10) == [(-1, 0), (3, 5),
                                                      (8, 10)]
    assert rt.gaps([], 0, 4) == [(0, 4)]


def test_gap_takes_the_innermost_host_span():
    host = [("loop", 0, 100), ("query.readback", 40, 60)]
    assert rt.label((45, 55), host) == "query.readback"
    assert rt.label((10, 20), host) == "loop"
    assert rt.label((200, 210), host) == "untracked"


def test_kernel_predicate():
    assert rt.is_pallas_kernel("custom-call.3", {})
    assert rt.is_pallas_kernel("fusion", {"long_name": "tpu_custom_call"})
    assert not rt.is_pallas_kernel("fusion.12", {"long_name": "add"})


def test_recorded_q6_trace():
    meta = json.loads((DATA / "q6_2p22.json").read_text())
    # the device's timestamps sit 1-2 ms off the host's in this trace:
    # widen the window so the first kernel is inside it
    w0, w1 = meta["window"]
    window = (w0 - 0.005, w1 + 0.005)
    red = rt.reduce(DATA / "q6_2p22.xplane.pb", sync_pc=meta["sync_pc"],
                    window=window, host_spans=[
                        tuple(s) for s in meta["host_spans"]])
    assert red["chips"] == 1
    assert red["kernel_calls"] == meta["queries"]
    assert 0 < red["kernel_s"] <= red["busy_s"] <= red["window_s"]
    assert red["window_s"] == pytest.approx(window[1] - window[0])
    # four kernels of 0.58 ms each (50 MB at ~86 GB/s)
    assert red["kernel_s"] == pytest.approx(4 * 0.583e-3, rel=0.01)
    assert red["top_ops"][0][0] == "tpu_custom_call.1"
    assert red["top_ops"][0][1] == max(t for _, t in red["top_ops"])
    assert all(s > 0 for _, s in red["idle_gaps"])
    labels = {name for name, _ in red["idle_gaps"]}
    assert labels <= {"query.dispatch", "query.readback", "untracked"}
