"""The readers of the program's own spans: the shared helper and the
pipeline-call readers on a small trace recorded on a TPU v5e (four Q6
queries at 2**22 rows through the program's pipeline call, its
``pipeline.*`` spans in the trace; ``data/record_program_trace.py``),
the serving readers on synthetic span logs, and every reader where the
program records nothing for it to read."""
from __future__ import annotations

import json
import os
import shutil
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from conftest import BENCH

import harness
import program_trace

from repro.core import telemetry

DATA = Path(__file__).resolve().parent / "data"
RECORDED = DATA / "q6_program_2p22.xplane.pb"
NO_SPANS = DATA / "q6_2p22.xplane.pb"       # recorded before the spans


def _reader(metric):
    return harness.load_module(BENCH / "metrics" / f"{metric}.py").read


def _cell(root: Path, trace: Path, name="q6-partition", seed=7):
    d = root / ".bench_traces" / f"{name}-{seed}" / "plugins" / "profile" / "t"
    d.mkdir(parents=True)
    shutil.copy(trace, d / "host.xplane.pb")
    return SimpleNamespace(root=root, name=name)


def test_newest_trace_of_the_cell_and_no_other(tmp_path):
    _cell(tmp_path, NO_SPANS, seed=1)
    _cell(tmp_path, RECORDED, seed=-2)
    _cell(tmp_path, NO_SPANS, name="q6-partition-x", seed=3)
    first = next((tmp_path / ".bench_traces" / "q6-partition-1").rglob("*.pb"))
    os.utime(first, (1, 1))
    got = program_trace.newest_trace(tmp_path, "q6-partition")
    assert got.parents[3].name == "q6-partition--2"
    assert program_trace.newest_trace(tmp_path, "q6-scan") is None
    assert program_trace.newest_trace(tmp_path / "none", "q6-scan") is None


def test_recorded_trace_spans_programs_and_offset(tmp_path):
    tr = program_trace.load(_cell(tmp_path, RECORDED))
    meta = json.loads((DATA / "q6_program_2p22.json").read_text())
    n = meta["queries"]
    calls = tr.spans["pipeline.call"]
    assert len(calls) == n
    assert len(tr.spans["pipeline.launch"]) == len(
        tr.spans["pipeline.finish"]) == n
    for a, b in tr.spans["pipeline.launch"] + tr.spans["pipeline.finish"]:
        assert any(c0 <= a and b <= c1 for c0, c1 in calls)
    names = {name for name, _, _ in tr.modules}
    assert "jit_fused_dag_tpchq6" in names
    assert not names & {"jit_wrapped", "jit__lambda_"}
    # on the aligned clock no program starts before its enqueue, and
    # the tightest one starts with it
    lags = [a - tr.enqueues[rid] for _, rid, (a, _) in tr.modules
            if rid in tr.enqueues]
    assert min(lags) == pytest.approx(0, abs=1.0)
    assert tr.offset_ns > 0
    assert tr.busy == sorted(tr.busy)


def test_pipeline_readers_on_the_recorded_trace(tmp_path):
    cell = _cell(tmp_path, RECORDED)
    meta = json.loads((DATA / "q6_program_2p22.json").read_text())
    dispatch_ms = np.median([(td - tb) * 1e3
                             for tb, td, _ in meta["query_times"]])
    launch = _reader("launch_ms.query")(None, cell)
    assert 0 < launch < dispatch_ms
    assert _reader("programs_per_query")(None, cell) == 2.0
    tr = program_trace.load(cell)
    call_ms = np.median([b - a for a, b in tr.spans["pipeline.call"]]) * 1e-6
    idle = _reader("call_idle_ms.query")(None, cell)
    assert 0 < idle < call_ms


@pytest.mark.parametrize("metric", ["launch_ms.query", "programs_per_query",
                                    "call_idle_ms.query"])
def test_pipeline_readers_read_nothing_without_program_spans(tmp_path,
                                                             metric):
    assert _reader(metric)(None, _cell(tmp_path, NO_SPANS)) is None
    assert _reader(metric)(None, SimpleNamespace(
        root=tmp_path / "empty", name="q6-scan")) is None


def test_idle_in_counts_the_span_outside_device_ops():
    tr = program_trace.ProgramTrace(spans={}, modules=[], enqueues={},
                                    busy=[(2.0, 4.0), (6.0, 7.0)],
                                    offset_ns=0.0)
    assert tr.idle_in((0.0, 10.0)) == 7.0
    assert tr.idle_in((3.0, 6.5)) == 2.0
    assert tr.idle_in((4.0, 6.0)) == 2.0


# ------------------------------------------------------------ serving
ORIGIN = 100.0


def _log(spans):
    """A span log as the program's telemetry records it, from
    ``(name, start_s, end_s)`` after ``ORIGIN``."""
    return [{"name": n, "ph": "X", "ts": a * 1e6, "dur": (b - a) * 1e6}
            for n, a, b in spans]


def _serving_log(steps=6, step_s=0.1):
    """Steps of 80 ms (2 ms launch, 78 ms wait), 1 ms of host work after
    each and 0.5 ms before the next; two admissions (40 ms prefill,
    10 ms scatter) between steps 2 and 3."""
    spans, t = [], 0.0
    for k in range(steps):
        if k == 3:
            for _ in range(2):
                spans += [("serve.admit", t, t + 0.05),
                          ("serve.admit.prefill", t, t + 0.04),
                          ("serve.admit.scatter", t + 0.04, t + 0.05)]
                t += 0.05
        spans += [("serve.step.host", t, t + 0.0005),
                  ("serve.decode_step", t + 0.0005, t + 0.0805),
                  ("serve.step.launch", t + 0.0005, t + 0.0025),
                  ("serve.step.wait", t + 0.0025, t + 0.0805),
                  ("serve.step.host", t + 0.0805, t + 0.0815)]
        t += step_s
    return _log(spans)


@pytest.fixture
def serving_log(monkeypatch):
    monkeypatch.setattr(telemetry, "clock_origin", lambda: ORIGIN)
    monkeypatch.setattr(telemetry, "span_log", _serving_log)


def test_step_host_ms_sums_launch_and_the_host_work_after(serving_log):
    run = SimpleNamespace(window=(ORIGIN, ORIGIN + 10))
    # launch 2 ms + 1 ms after + 0.5 ms before the next step; the last
    # step has no next one: 3 ms
    assert _reader("step_host_ms")(run, None) == pytest.approx(3.5)


def test_step_host_ms_reads_only_steps_ending_in_the_window(serving_log):
    run = SimpleNamespace(window=(ORIGIN + 0.5, ORIGIN + 10))
    assert _reader("step_host_ms")(run, None) == pytest.approx(3.25)
    run = SimpleNamespace(window=(ORIGIN + 20, ORIGIN + 30))
    assert _reader("step_host_ms")(run, None) is None


def test_admit_scatter_share_is_the_window_in_scatter(serving_log):
    run = SimpleNamespace(window=(ORIGIN, ORIGIN + 1.0))
    assert _reader("admit_scatter_share")(run, None) \
        == pytest.approx(100 * 0.02 / 1.0)
    run = SimpleNamespace(window=(ORIGIN + 0.5, ORIGIN + 1.0))
    assert _reader("admit_scatter_share")(run, None) is None


@pytest.mark.parametrize("metric", ["step_host_ms", "admit_scatter_share"])
def test_serving_readers_read_nothing_without_spans_or_clock(monkeypatch,
                                                             metric):
    run = SimpleNamespace(window=(0.0, 1e9))
    monkeypatch.setattr(telemetry, "span_log", lambda: [])
    assert _reader(metric)(run, None) is None
    monkeypatch.setattr(telemetry, "span_log", _serving_log)
    monkeypatch.delattr(telemetry, "clock_origin")
    assert _reader(metric)(run, None) is None
