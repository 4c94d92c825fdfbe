"""The DSE plans and tuning-cache keys behind the benchmark's cells.

Each case calls the DSE exactly as one benchmark cell's setup does
(Q6's fused pipeline at 2**29 and 2**22 rows, the paged-decode pick for
the three serving pools) plus one pattern-engine case, uncached and
uncalibrated, and pins every field of the returned plan and its key.
The kernels are built from these plans and the on-disk tuning cache is
keyed by these keys, so a refactor of the exploration engine must
reproduce them exactly.  The device kind is pinned to ``cpu`` so the
keys do not depend on where the test runs.
"""
import pytest

from repro.core import dse, measure
from repro.core.options import Options
from repro.patterns.analytics import PIPELINES


def _q6(rows):
    return lambda o: dse.explore_pipeline(PIPELINES["tpchq6"](rows)[0],
                                          options=o)


def _paged(max_len, d):
    def run(o):
        choice, plan = dse.select_paged_decode_blocks(max_len, d, options=o)
        return plan, choice
    return run


CASES = {
    # q6-scan: the whole 2**29-row lineitem column in one fused kernel
    "q6-scan": (_q6(2**29), None, dict(
        block=65536, groups=((0, 2),), group_blocks=(65536,), depths=(2,),
        traffic_words=1610612737, unfused_traffic_words=2684354561,
        vmem_bytes=16777216, modeled_seconds=0.007661816393870395,
        explored=207, pruned=117, measured=False, timed=0,
        key="e7cd2e12693a02e67ef968cf3706a6e8")),
    # q6-partition: one of 128 resident partitions of 2**22 rows
    "q6-partition": (_q6(2**22), None, dict(
        block=65536, groups=((0, 2),), group_blocks=(65536,), depths=(2,),
        traffic_words=12582913, unfused_traffic_words=20971521,
        vmem_bytes=16777216, modeled_seconds=6.051367955322091e-05,
        explored=144, pruned=54, measured=False, timed=0,
        key="fe1e2f9ed4e9c3c691e27f9f6de0ad37")),
    # granite-long-decode: 3072-token prompts + 256 out, head dim 64
    "granite-long-decode": (_paged(3328, 64), ("split", 8, 1664, 3), dict(
        sizes={"pd_kv": (1664,), "pd_page": (8,), "pd_layout": (0,)},
        depths={"pd_kv": 3}, traffic_words=426241, vmem_bytes=10240000,
        modeled_seconds=1.4375905185717694e-06, explored=432, pruned=20,
        thinned=False, measured=False, timed=0,
        key="4baa886b3627df8bd070a76049e959de")),
    # granite-chat-short: 512-token prompts + 128 out, head dim 64
    "granite-chat-short": (_paged(640, 64), ("fused", 8, 640, 4), dict(
        sizes={"pd_kv": (640,), "pd_page": (8,), "pd_layout": (1,)},
        depths={"pd_kv": 4}, traffic_words=82177, vmem_bytes=2633728,
        modeled_seconds=4.0135286935286933e-07, explored=216, pruned=0,
        thinned=False, measured=False, timed=0,
        key="a74a7bd712b119a9b49500cdc913f250")),
    # mellum2-swa-moe-decode: 8192-token prompts + 256 out, head dim 128
    "mellum2-swa-moe-decode": (_paged(8448, 128), ("split", 8, 768, 4), dict(
        sizes={"pd_kv": (768,), "pd_page": (8,), "pd_layout": (0,)},
        depths={"pd_kv": 4}, traffic_words=2163201, vmem_bytes=6307840,
        modeled_seconds=4.62074621637961e-06, explored=864, pruned=200,
        thinned=False, measured=False, timed=0,
        key="bc3f9ebd20a818a73a4da15df1657f25")),
    # the pattern engine: a tile per domain axis of the Table-3 GEMM
    "gemm-256": (lambda o: dse.explore(dse.gemm_program(256, 256, 256),
                                       options=o), None, dict(
        sizes={"gemm": (256, 256), "gemm_k": (256,)},
        depths={"gemm": 2, "gemm_k": 2}, traffic_words=131072,
        vmem_bytes=1572864, modeled_seconds=6.401562881562881e-07,
        explored=24, pruned=0, thinned=False, measured=False, timed=0,
        key="0aecbc20a0cdc7a2f7e087e9f48dcbb0")),
}


@pytest.mark.parametrize("cell", sorted(CASES))
def test_cell_plan_and_key_are_pinned(cell, monkeypatch):
    monkeypatch.setattr(measure, "device_kind", lambda: "cpu")
    run, want_choice, want = CASES[cell]
    got = run(Options(cache=False, profile=False))
    if want_choice is not None:
        got, choice = got
        assert choice == want_choice
    assert {f: getattr(got, f) for f in want} == want
