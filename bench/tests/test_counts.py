"""Operations and bytes from shapes, against values worked by hand."""
from __future__ import annotations

import json

import pytest

import counts
import peaks
from conftest import BENCH

GRANITE = json.loads((BENCH / "configs" / "granite-3-2b.json").read_text())


def test_granite_matmul_params():
    # per layer: wq 2048*2048 + wk, wv 2*2048*512 + wo 2048*2048
    # + w1, w3, w2 3*2048*8192 = 60,817,408; 40 layers; head 2048*49155
    assert counts.matmul_params(GRANITE) == 60_817_408 * 40 + 100_669_440


def test_granite_decode_step_flops():
    # one request with 1023 tokens before the step: 2 * 2,533,365,760
    # matmul flops + 4 * 32 heads * 64 * 1024 positions * 40 layers
    assert counts.decode_step_flops(GRANITE, [1023]) == \
        5_066_731_520 + 335_544_320
    assert counts.decode_step_flops(GRANITE, [1023, 1023]) == \
        2 * counts.decode_step_flops(GRANITE, [1023])


def test_granite_paged_attention_call():
    # a token row is 8 kv heads * 64 * 2 bytes = 1024 B: K and V of 1024
    # live tokens 2,097,152; appended K, V in and out 4,096; q (bf16) and
    # output (f32) of 32 heads * 64: 12,288
    assert counts.paged_attn_bytes(GRANITE, [1023]) == 2_113_536
    assert counts.paged_attn_flops(GRANITE, [1023]) == 8_388_608
    assert counts.paged_attn_bytes(GRANITE, []) == 0


def test_q6_scan():
    assert counts.scan_bytes(1 << 29, 3) == 6_442_450_944
    assert counts.scan_flops(1 << 29) == 1 << 30
    t, bound = peaks.roofline_s(counts.scan_flops(1 << 29),
                                counts.scan_bytes(1 << 29, 3),
                                peaks.peaks("TPU v5 lite"))
    assert bound == "memory" and t == pytest.approx(7.8662e-3, rel=1e-4)


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        peaks.peaks("TPU v4")
