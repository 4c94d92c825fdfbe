"""Unified telemetry layer (``core.telemetry``): span semantics,
disabled-mode zero-cost guarantees, metrics registry determinism,
thread safety under the background re-tune daemons, Chrome-trace
export, and ``dse.explain`` plan provenance."""
import importlib.util
import json
import os
import threading

import pytest

from repro.core import buckets, dse, resilience, telemetry
from repro.core.options import Options


# ------------------------------------------------------------------ spans


def test_span_nesting_and_attribute_capture():
    telemetry.enable()
    with telemetry.span("outer", a=1) as sp:
        sp.set(b=2)
        with telemetry.span("inner", c=3):
            pass
    log = telemetry.span_log()
    assert [e["name"] for e in log] == ["inner", "outer"]  # exit order
    inner, outer = log
    assert inner["parent"] == "outer"
    assert "parent" not in outer
    assert outer["args"] == {"a": 1, "b": 2}
    assert inner["args"] == {"c": 3}
    # the child's interval nests inside the parent's
    assert inner["ts"] >= outer["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]


def test_span_records_exception():
    telemetry.enable()
    with pytest.raises(ValueError):
        with telemetry.span("boom", stage="x"):
            raise ValueError("nope")
    [e] = telemetry.span_log()
    assert e["args"]["error"] == "ValueError"
    assert e["args"]["stage"] == "x"


def test_disabled_mode_is_a_shared_noop():
    telemetry.disable()
    s1 = telemetry.span("a", x=1)
    s2 = telemetry.span("b")
    # same singleton back every time: zero allocation per site
    assert s1 is s2 is telemetry.NULL_SPAN
    with s1 as sp:
        sp.set(y=2)
    assert telemetry.span_log() == []
    # gated surfaces add zero registry growth when disabled
    telemetry.observe("lat", 0.5)
    telemetry.put_record("plan", "k", {"x": 1})
    snap = telemetry.metrics_snapshot()
    assert snap["histograms"] == {}
    assert snap["spans"] == 0
    assert telemetry.get_record("plan", "k") is None
    # counters/gauges/events stay on: they back always-on stat sinks
    telemetry.count("c")
    telemetry.gauge("g", 2.0)
    telemetry.emit("s", "k", a=1)
    snap = telemetry.metrics_snapshot()
    assert snap["counters"]["c"] == 1
    assert snap["gauges"]["g"] == 2.0
    assert snap["events"] == {"s": 1}


def test_env_enablement_via_options(monkeypatch):
    monkeypatch.setenv("REPRO_TRACE", "1")
    telemetry.reset()
    assert telemetry.enabled()
    monkeypatch.delenv("REPRO_TRACE")
    telemetry.reset()
    assert not telemetry.enabled()
    assert Options(trace=True).resolved().trace is True


def _profiled_events(log_dir, run):
    """``(name, duration_ns)`` of every host event of a CPU profiler
    trace taken around ``run()``."""
    import glob

    import jax
    from jax.profiler import ProfileData

    with jax.profiler.trace(str(log_dir)):
        run()
    [path] = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    pd = ProfileData.from_file(path)
    return [(ev.name, ev.duration_ns) for plane in pd.planes
            if not plane.name.startswith("/device:")
            for line in plane.lines for ev in line.events]


def test_span_lands_in_profiler_trace_with_telemetry_off(tmp_path):
    """A profiler session sees the program's spans though telemetry is
    off, and the registry records none of them."""
    import time

    telemetry.disable()

    def run():
        with telemetry.span("probe.outer", x=1) as sp:
            sp.set(y=2)
            with telemetry.span("probe.inner"):
                time.sleep(0.002)

    events = dict(_profiled_events(tmp_path, run))
    assert events["probe.inner"] >= 2e6
    assert events["probe.outer"] >= events["probe.inner"]
    assert telemetry.span_log() == []


def test_span_lands_in_profiler_trace_and_registry_with_telemetry_on(
        tmp_path):
    telemetry.enable()

    def run():
        with telemetry.span("probe.both"):
            pass

    assert "probe.both" in dict(_profiled_events(tmp_path, run))
    assert [e["name"] for e in telemetry.span_log()] == ["probe.both"]


def test_no_telemetry_and_no_profiler_is_the_shared_noop():
    from jaxlib._profiler import TraceMe

    telemetry.disable()
    assert not TraceMe.is_enabled()
    assert telemetry.span("a", x=1) is telemetry.NULL_SPAN
    telemetry.enable()
    assert telemetry.span("a") is not telemetry.NULL_SPAN


def test_clock_origin_maps_span_log_onto_perf_counter():
    import time

    telemetry.enable()
    before = time.perf_counter()
    with telemetry.span("timed"):
        inside = time.perf_counter()
    after = time.perf_counter()
    [e] = telemetry.span_log()
    start = telemetry.clock_origin() + e["ts"] * 1e-6
    end = start + e["dur"] * 1e-6
    assert before <= start <= inside <= end <= after


# ---------------------------------------------------------------- metrics


def test_log_bounds_deterministic():
    b1 = telemetry.log_bounds(1e-6, 1e2, per_decade=4)
    b2 = telemetry.log_bounds(1e-6, 1e2, per_decade=4)
    assert b1 == b2 == telemetry.LATENCY_BOUNDS_S
    assert b1[0] == pytest.approx(1e-6)
    assert b1[-1] >= 1e2
    assert all(lo < hi for lo, hi in zip(b1, b1[1:]))
    # 4 edges per decade over 8 decades, inclusive endpoints
    assert len(b1) == 33


def test_histogram_bucketing_and_tails():
    telemetry.enable()
    telemetry.observe("h", 1e-9)   # below the lowest edge
    telemetry.observe("h", 1e3)    # above the highest edge
    telemetry.observe("h", 2e-6)
    h = telemetry.metrics_snapshot()["histograms"]["h"]
    assert h["count"] == 3 and sum(h["counts"]) == 3
    assert h["counts"][0] == 1 and h["counts"][-1] == 1
    assert len(h["counts"]) == len(h["bounds"]) + 1
    assert h["sum"] == pytest.approx(1e-9 + 1e3 + 2e-6)


def test_event_stream_filtering():
    telemetry.emit("resilience", "retry", key="a")
    telemetry.emit("resilience", "fallback", key="b")
    telemetry.emit("recovery", "retry", key="c")
    assert len(telemetry.events("resilience")) == 2
    assert telemetry.events("resilience", kind="retry")[0]["key"] == "a"
    telemetry.clear_events("resilience")
    assert telemetry.events("resilience") == []
    assert len(telemetry.events("recovery")) == 1


# ----------------------------------------------------------- thread safety


def test_thread_safety_under_retune_daemons():
    telemetry.enable()
    n = 6

    def _retune():
        with telemetry.span("work"):
            for _ in range(50):
                telemetry.count("t.work")
        return "plan"

    threads = []
    for i in range(n):
        t = buckets.schedule_retune(
            f"tag-{i}", _retune, certify=lambda pl: (True, "ok"),
            promote=lambda pl: None,
            policy=resilience.Policy(timeout_s=0))
        assert t is not None
        threads.append(t)
    # the main thread traces concurrently with the daemons
    for _ in range(50):
        with telemetry.span("main.tick"):
            telemetry.count("t.main")
    buckets.drain()

    snap = telemetry.metrics_snapshot()
    assert snap["counters"]["t.work"] == n * 50
    assert snap["counters"]["t.main"] == 50
    assert snap["counters"]["bucket.promotions"] == n
    log = telemetry.span_log()
    retunes = [e for e in log if e["name"] == "buckets.retune"]
    assert len(retunes) == n
    assert all(e["args"]["outcome"] == "promoted" for e in retunes)
    assert all(e["thread"].startswith("repro-retune-") for e in retunes)
    # nesting is per-thread: each daemon's work span parents correctly
    works = [e for e in log if e["name"] == "work"]
    assert len(works) == n
    assert all(e["parent"] == "buckets.retune" for e in works)
    assert all(e["parent"] != "main.tick" for e in works)


# ---------------------------------------------------------------- export


def _load_check_trace():
    path = os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                        "check_trace.py")
    spec = importlib.util.spec_from_file_location("check_trace", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_export_trace_roundtrip(tmp_path):
    telemetry.enable()
    with telemetry.span("dse.explore", pattern="p"):
        with telemetry.span("dse.shortlist"):
            pass
    telemetry.emit("resilience", "retry", key="k")
    out = str(tmp_path / "trace.json")
    telemetry.export_trace(out)
    with open(out) as f:
        doc = json.load(f)
    evs = doc["traceEvents"]
    spans = [e for e in evs if e.get("ph") == "X"]
    assert {e["name"] for e in spans} == {"dse.explore", "dse.shortlist"}
    for e in spans:
        assert e["ts"] >= 0 and e["dur"] >= 0 and e["pid"] == 1
    child = next(e for e in spans if e["name"] == "dse.shortlist")
    assert child["args"]["parent"] == "dse.explore"
    marks = [e for e in evs if e.get("ph") == "i"]
    assert [m["name"] for m in marks] == ["resilience.retry"]
    assert marks[0]["args"]["key"] == "k"
    # timestamps are monotone over the timed events
    ts = [e["ts"] for e in evs if e.get("ph") != "M"]
    assert ts == sorted(ts)
    # and the CI validator agrees
    assert _load_check_trace().validate(doc) == []


def test_check_trace_rejects_bad_traces():
    ct = _load_check_trace()
    assert ct.validate({}) != []
    assert ct.validate({"traceEvents": []}) != []
    # a trace with spans but no dse.explore fails the smoke contract
    doc = {"traceEvents": [{"name": "x", "ph": "X", "ts": 0, "dur": 1}]}
    assert any("dse.explore" in p for p in ct.validate(doc))
    doc = {"traceEvents": [
        {"name": "dse.explore", "ph": "X", "ts": 5.0, "dur": 1.0},
        {"name": "late", "ph": "i", "ts": 1.0}]}
    assert any("monotone" in p for p in ct.validate(doc))


# ------------------------------------------------------------ dse.explain


def test_explain_freshly_explored(tmp_path):
    telemetry.enable()
    plan = dse.explore(dse.filter_reduce_program(1024),
                       options=Options(cache=str(tmp_path / "c.json")))
    d = dse.explain_dict(plan)
    assert d["source"] == "explored"
    prov = d["provenance"]
    assert prov["enumerated"] > 0
    assert set(prov["pruned"]) == {"vmem", "dominated",
                                   "measure_failures"}
    assert prov["analytic_ranks"]
    text = dse.explain(plan)
    assert "source: explored" in text
    assert "pruned by reason" in text
    assert "analytic ranks" in text


def test_explain_cached(tmp_path):
    telemetry.enable()
    opts = Options(cache=str(tmp_path / "c.json"))
    p = dse.filter_reduce_program(1024)
    dse.explore(p, options=opts)
    plan = dse.explore(p, options=opts)
    assert plan.cached
    d = dse.explain_dict(plan)
    assert d["source"] == "cache"
    assert "source: cache" in dse.explain(plan)


def test_explain_warm_started(tmp_path):
    telemetry.enable()
    opts = Options(cache=str(tmp_path / "c.json"), bucketing=True)
    dse.explore(dse.attention_program(256, 256, 64), options=opts)
    plan = dse.explore(dse.attention_program(192, 256, 64), options=opts)
    buckets.drain()
    assert plan.warm_start
    d = dse.explain_dict(plan)
    assert d["source"] == "warm_start"
    assert d["provenance"]["retune_tag"].startswith("tile|")
    assert f"(bucket {plan.bucket})" in dse.explain(plan)


def test_explain_without_tracing(tmp_path):
    telemetry.disable()
    plan = dse.explore(dse.filter_reduce_program(512),
                       options=Options(cache=False))
    d = dse.explain_dict(plan)
    assert d["source"] == "explored"
    assert "provenance" not in d
    assert "REPRO_TRACE=1" in dse.explain(plan)
