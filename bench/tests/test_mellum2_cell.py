"""The mellum2 cell end to end on the CPU at SMOKE size (its driver,
reference and readers as they are), and the driver's split of a
trace's kernel time by name."""
from __future__ import annotations

import json
import shutil
import time

import numpy as np
import pytest

import harness
from conftest import BENCH

CELL = "mellum2-small"
TRAFFIC = {"driver": "serve_waves_moe", "slots": 3,
           "prompt_lens": [9, 14, 21], "shares": [1, 1, 1], "gen": 12,
           "wave_s": 1.0, "sample_requests": 2, "trace_s": 0.5}


def small_config() -> dict:
    """The configuration file at the program's SMOKE sizes."""
    from repro.configs import get_config

    m = get_config("mellum2-12b", smoke=True)
    cfg = json.loads((BENCH / "configs" / "mellum2-12b-ep4.json").read_text())
    cfg.update(
        smoke=True, hidden_size=m.d_model, moe_intermediate_size=m.d_ff,
        num_hidden_layers=m.n_layers, num_attention_heads=m.n_heads,
        num_key_value_heads=m.n_kv_heads, head_dim=m.head_dim,
        vocab_size=m.vocab, num_experts=m.n_experts_held,
        router_experts=m.n_experts, num_experts_per_tok=m.top_k,
        sliding_window=m.sliding_window,
        layer_types=cfg["layer_types"][:m.n_layers])
    cfg["rope_parameters"]["full_attention"][
        "original_max_position_embeddings"] = m.yarn[1]
    return cfg


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    root = tmp_path_factory.mktemp("checkout")
    bench = root / "bench"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "tests", "__pycache__"))
    (bench / "configs" / "mellum2-small.json").write_text(
        json.dumps(small_config()))
    shutil.copy(bench / "reference" / "mellum2_12b_ep4.py",
                bench / "reference" / "mellum2_small.py")
    (bench / "traffic" / "moe-small.json").write_text(json.dumps(TRAFFIC))
    spec = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    spec["configs"] = [{"name": "mellum2-small", "source": "test",
                        "file": "bench/configs/mellum2-small.json",
                        "reduced": [], "why": "test"}]
    spec["workloads"] = [{"name": CELL, "config": "mellum2-small",
                          "traffic": "moe-small", "chips": 1,
                          "why": "test"}]
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [CELL] if "mellum2-swa-moe-decode" in \
                m["workloads"] else []
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root, bench


def test_small_cell_runs_correct(checkout):
    root, bench = checkout
    cell = harness.resolve(CELL, root, bench)
    assert {m["name"] for m in cell.end_to_end} == {
        "decode_tokens_per_s", "setup_s"}
    assert {m["name"] for m in cell.per_layer} == {
        "moe_ffn_roofline", "swa_attn_roofline", "decode_mfu.moe",
        "idle_share.serve"}
    r = harness.run_cell(CELL, 3, 0.5, False, t_start=time.perf_counter(),
                         root=root, bench_dir=bench, require_tpu=False)
    assert r["correct"] is True and r["failed"] == 0
    assert r["attempted"] == TRAFFIC["slots"]
    assert all(m["value"] > 0 for m in r["metrics"].values())
    # the program and the reference agree on the CPU: served tokens are
    # the reference's best or within bf16 rounding of it (0.015 here,
    # where the float8 control reads ~0.4)
    assert r["checks"]["logit_gap"]["value"] <= \
        r["checks"]["logit_gap"]["limit"] / 4


def test_driver_reads_the_routing_counters_and_the_control_fails(checkout):
    root, bench = checkout
    cell = harness.resolve(CELL, root, bench)
    run = cell.driver.run(cell, seed=5, seconds=0.5, trace=None,
                          t_start=time.perf_counter())
    n = len(run.data["steps"])
    assert run.data["moe_pairs"].shape == run.data["moe_touched"].shape \
        == (n,)
    # every live token routes top_k experts over all the layers; a
    # quarter of the 16 are held here
    active = run.data["active"]
    cfg = cell.config
    assert np.all(run.data["moe_pairs"] <= active * cfg["num_hidden_layers"]
                  * cfg["num_experts_per_tok"])
    assert np.all(run.data["moe_touched"]
                  <= cfg["num_hidden_layers"] * cfg["num_experts"])
    assert run.data["moe_pairs"].sum() > 0
    mfu = cell.metric_reader("decode_mfu.moe")
    run.data["device"] = {"kind": "TPU v5 lite"}
    assert mfu.read(run, cell) > 0
    limit = cell.reference.LIMITS["logit_gap"]
    assert run.control()["logit_gap"] > limit


def test_kernel_split_by_name():
    """Operations named by kernel, on two device planes, inside and
    across the slice's edges: time and calls per plane."""
    drv = harness.load_module(BENCH / "drivers" / "serve_waves_moe.py")
    ops = [(0.0, 10.0, "%paged_decode.3 = (bf16[8]) custom-call()", {}),
           (10.0, 40.0, "%moe_gmm.1 = bf16[4] custom-call()", {}),
           (40.0, 45.0, "%fusion.7 = f32[2] fusion()", {}),
           (45.0, 60.0, "%paged_decode.4 = (bf16[8]) custom-call()", {}),
           (95.0, 130.0, "%moe_gmm.2 = bf16[4] custom-call()", {}),
           (130.0, 140.0, "%moe_gmm.2 = bf16[4] custom-call()", {})]
    planes = {"/device:TPU:0": ops, "/device:TPU:1": ops}
    got = drv.split_kernels(planes, 5.0, 120.0)
    assert got["paged_decode"] == (pytest.approx(25e-9), 2)
    assert got["moe_gmm"] == (pytest.approx(65e-9), 2)
    assert drv.split_kernels(planes, 200.0, 300.0) == {
        "paged_decode": (0.0, 0), "moe_gmm": (0.0, 0)}
