"""Small copies of the benchmark for the CPU: the same harness, drivers,
references and readers, on configurations and mixes cut to a size the
Pallas interpreter runs in seconds."""
from __future__ import annotations

import json
import shutil
import sys
import time
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import harness  # noqa: E402

Q6_SMALL = dict(json.loads((BENCH / "configs" / "tpchq6-2p29.json")
                           .read_text()), lineitem_rows=1 << 14)
GRANITE_SMALL = dict(
    json.loads((BENCH / "configs" / "granite-3-2b.json").read_text()),
    smoke=True, hidden_size=64, intermediate_size=128, num_hidden_layers=2,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    vocab_size=251, vocab_pad=0, attention_multiplier=0.25)
TRAFFIC = {
    "scan-small": {"driver": "scan_queries", "partitions": 1,
                   "trace_s": 0.5},
    "parts-small": {"driver": "scan_queries", "partitions": 4,
                    "trace_s": 0.5},
    "chat-small": {"driver": "serve_waves", "slots": 2,
                   "prompt_lens": [8, 24], "shares": [1, 1], "gen": 4,
                   "wave_s": 1.0, "sample_requests": 2, "trace_s": 0.5},
    # long enough that the float8 control's widest gap shows
    "chat-control": {"driver": "serve_waves", "slots": 2,
                     "prompt_lens": [8, 24], "shares": [1, 1], "gen": 48,
                     "wave_s": 1.0, "sample_requests": 2, "trace_s": 0.5},
}
CELLS = [("q6-small", "tpchq6-small", "scan-small"),
         ("q6-parts-small", "tpchq6-small", "parts-small"),
         ("granite-small", "granite-small", "chat-small"),
         ("granite-control-small", "granite-small", "chat-control")]


def build(root: Path, cells=CELLS) -> Path:
    """A checkout-shaped tree under ``root``: a BENCHMARK.json of the
    small cells and a copy of ``bench`` with their files added.
    Returns the copy's directory."""
    bench = root / "bench"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "tests", "__pycache__"))
    (bench / "configs" / "tpchq6-small.json").write_text(json.dumps(Q6_SMALL))
    (bench / "configs" / "granite-small.json").write_text(
        json.dumps(GRANITE_SMALL))
    shutil.copy(bench / "reference" / "tpchq6_2p29.py",
                bench / "reference" / "tpchq6_small.py")
    shutil.copy(bench / "reference" / "granite_3_2b.py",
                bench / "reference" / "granite_small.py")
    for name, doc in TRAFFIC.items():
        (bench / "traffic" / f"{name}.json").write_text(json.dumps(doc))
    spec = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    spec["configs"] = [
        {"name": n, "source": "test", "file": f"bench/configs/{n}.json",
         "reduced": [], "why": "test"} for n in ("tpchq6-small", "granite-small")]
    spec["workloads"] = [{"name": n, "config": c, "traffic": t, "chips": 1,
                          "why": "test"} for n, c, t in cells]
    scan = [n for n, c, _ in cells if c.startswith("tpchq6")]
    serve = [n for n, c, _ in cells if c.startswith("granite")]
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] = scan if m["workloads"][0].startswith("q6") \
                else serve
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return bench


@pytest.fixture(scope="module")
def small(tmp_path_factory):
    root = tmp_path_factory.mktemp("checkout")
    return root, build(root)


def run_small(small, workload: str, seed: int = 3, seconds: float = 0.5,
              trace: bool = False):
    root, bench = small
    return harness.run_cell(workload, seed, seconds, trace,
                            t_start=time.perf_counter(), root=root,
                            bench_dir=bench, require_tpu=False)
