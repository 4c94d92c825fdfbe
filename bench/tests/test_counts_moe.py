"""Operations and bytes of the windowed/full MoE cell from shapes and
routing counters, against values worked by hand."""
from __future__ import annotations

import json

import counts_moe
from conftest import BENCH

MELLUM = json.loads((BENCH / "configs" / "mellum2-12b-ep4.json").read_text())


def test_layers_and_positions():
    sliding = counts_moe.sliding_layers(MELLUM)
    assert len(sliding) == 28 and sum(sliding) == 21
    assert sliding[:4] == [True, True, True, False]
    assert counts_moe.positions(MELLUM, 4095, True) == 1024
    assert counts_moe.positions(MELLUM, 4095, False) == 4096
    assert counts_moe.positions(MELLUM, 99, True) == 100


def test_attention_of_one_request():
    # a token row is 4 kv heads * 128 * 2 bytes = 1024 B.  At 4095
    # tokens before the step: 7 full layers read K and V of 4096
    # positions (8,388,608 B each), 21 windowed ones of 1024 (2,097,152
    # B); every layer moves the appended K, V in and out (4,096 B) and
    # q in bf16 and the output in f32 (32 heads * 128 * 6 = 24,576 B)
    per_layer_extra = 4_096 + 24_576
    assert counts_moe.attn_bytes(MELLUM, [4095]) == (
        7 * 8_388_608 + 21 * 2_097_152 + 28 * per_layer_extra)
    # 4 * 32 heads * 128 = 16,384 operations per position
    assert counts_moe.attn_flops(MELLUM, [4095]) == \
        16_384 * (7 * 4096 + 21 * 1024)
    assert counts_moe.attn_bytes(MELLUM, []) == 0


def test_expert_matmuls():
    # an expert's three matrices: 3 * 2304 * 896 * 2 B = 12,386,304 B;
    # a pair's row in and out: 2 * 2304 * 2 B = 9,216 B
    assert counts_moe.gmm_bytes(MELLUM, pairs=36, touched=15) == \
        15 * 12_386_304 + 36 * 9_216
    assert counts_moe.gmm_flops(MELLUM, 36) == 36 * 6 * 2304 * 896


def test_decode_step_flops():
    # per layer: q and o 2 * 2304 * 4096, k and v 2 * 2304 * 512, the
    # router 2304 * 64: 21,381,120; 28 layers and the head 2304 * 98304
    dense = 28 * 21_381_120 + 226_492_416
    assert counts_moe.dense_params(MELLUM) == dense
    one = counts_moe.decode_step_flops(MELLUM, [4095], pairs=72)
    assert one == (2 * dense + counts_moe.gmm_flops(MELLUM, 72)
                   + counts_moe.attn_flops(MELLUM, [4095]))
