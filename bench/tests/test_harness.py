"""The harness end to end on the CPU at small sizes, the benchmark's
file against its contract, and a cell added as new files only."""
from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from conftest import BENCH, CELLS, build, run_small

import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.mark.parametrize("workload", [c[0] for c in CELLS])
def test_small_cell_runs_correct(small, workload):
    root, bench = small
    cell = harness.resolve(workload, root, bench)
    r = run_small(small, workload)
    assert r["correct"] is True
    assert r["attempted"] > 0 and r["failed"] == 0
    assert set(r["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(m["value"] > 0 for m in r["metrics"].values())
    assert list(r)[-1] == "checks"
    # the system and the reference agree on the CPU: served tokens are
    # the reference's best or within rounding of it
    for c in r["checks"].values():
        assert c["value"] <= c["limit"] / 10


def test_no_tpu_exits_nonzero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(BENCH / "run_cell.py"), "--workload",
         "q6-scan", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_bench_alone_exits_nonzero(tmp_path):
    """A directory holding only BENCHMARK.json and the benchmark's
    files has no program to run."""
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run_cell.py", "--workload", "q6-scan",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=tmp_path, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_benchmark_json_follows_the_contract():
    spec = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    assert list(spec) == ["command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"]
    assert spec["paths"] == ["bench"]
    assert spec["command"][1].startswith("bench/")
    assert 1 <= spec["run_seconds"] <= 51
    names = set()
    for c in spec["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert 1 <= len(c["why"]) <= 200 and "\n" not in c["why"]
        assert NAME.match(c["name"]) and c["file"].startswith("bench/")
        assert (harness.ROOT / c["file"]).is_file()
        assert all(NAME.match(k) for k in c["reduced"])
        names.add(c["name"])
    cells = {}
    for w in spec["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and w["config"] in names
        assert w["chips"] == 1 and len(w["why"]) <= 200
        cells[w["name"]] = w
    assert len({(w["config"], w["traffic"]) for w in cells.values()}) \
        == len(cells)
    metrics = spec["end_to_end"] + spec["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in spec["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    assert e2e["setup_s"]["bound"] == 0.25
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()
        assert set(m.get("workloads", cells)) <= set(cells)
    for m in spec["per_layer"]:
        assert m["moves"] in e2e
        assert set(m["workloads"]) <= set(e2e[m["moves"]].get(
            "workloads", cells))
    for name in cells:
        cell = harness.resolve(name)
        got = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in got and len(got) >= 2 and cell.per_layer


def test_cell_mix_and_metric_are_added_as_new_files(tmp_path):
    bench = build(tmp_path)
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    (bench / "traffic" / "parts-throwaway.json").write_text(json.dumps(
        {"driver": "scan_queries", "partitions": 2, "trace_s": 0.5}))
    (bench / "metrics" / "queries_done.py").write_text(
        "def read(run, cell):\n"
        "    return float(len(run.data['queries']))\n")
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": "q6-throwaway",
                              "config": "tpchq6-small",
                              "traffic": "parts-throwaway", "chips": 1,
                              "why": "throwaway"})
    for m in spec["end_to_end"]:
        if m["name"] == "query_ms":
            m["workloads"].append("q6-throwaway")
    spec["end_to_end"].append({"name": "queries_done", "unit": "queries",
                               "better": "higher", "bound": 0.05,
                               "source": "host_clock",
                               "workloads": ["q6-throwaway"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    r = run_small((tmp_path, bench), "q6-throwaway")
    assert r["correct"]
    assert set(r["metrics"]) == {"query_ms", "queries_done", "setup_s"}
    assert r["metrics"]["queries_done"]["value"] == r["attempted"]
    for p, data in before.items():
        assert p.read_bytes() == data, f"{p} was edited"
