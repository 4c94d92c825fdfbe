"""Operations and bytes that the benchmarked work needs, computed from
shapes alone.

These are what the algorithm must do, not what a kernel happens to do:
page padding, pool copies and re-reads are not counted, so a share of
the roofline built on them can only fall when a kernel wastes work.
Configurations are the JSON dicts under ``bench/configs`` (Hugging
Face key names for models).
"""
from __future__ import annotations

from typing import Iterable


# ------------------------------------------------------------- scans
def scan_bytes(rows: int, columns: int, itemsize: int = 4) -> int:
    """HBM bytes of one fused scan: every column read once; the scalar
    it writes is noise."""
    return rows * columns * itemsize


def scan_flops(rows: int) -> int:
    """Floating-point operations of the Q6 stand-in per scan: one
    multiply and one add per row (the predicate's compares are not
    arithmetic)."""
    return 2 * rows


# ---------------------------------------------------- dense decoders
def _dims(cfg: dict):
    return (cfg["hidden_size"], cfg["intermediate_size"],
            cfg["num_hidden_layers"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"], cfg["vocab_size"])


def matmul_params(cfg: dict) -> int:
    """Weights each token multiplies by: the four attention
    projections and the three SwiGLU matrices of every layer, and the
    output head over the real (unpadded) vocabulary."""
    d, f, nl, hq, hkv, dh, v = _dims(cfg)
    per_layer = d * hq * dh + 2 * d * hkv * dh + hq * dh * d + 3 * d * f
    return nl * per_layer + d * v


def decode_step_flops(cfg: dict, live: Iterable[int]) -> int:
    """One decode step of the active requests whose contexts hold
    ``live`` tokens before the step: the matmuls of one token each,
    plus attention over ``l + 1`` positions (scores and weighted sum)
    in every layer."""
    live = list(live)
    _, _, nl, hq, _, dh, _ = _dims(cfg)
    attn = sum(4 * hq * dh * (n + 1) for n in live) * nl
    return 2 * matmul_params(cfg) * len(live) + attn


def paged_attn_bytes(cfg: dict, live: Iterable[int]) -> int:
    """HBM bytes of one paged-attention call (one layer, one step):
    for each active request the K and V rows of its ``l + 1`` live
    tokens (the appended one included), the appended rows read in and
    written to the pool, its bfloat16 query and its float32 output."""
    _, _, _, hq, hkv, dh, _ = _dims(cfg)
    row = hkv * dh * 2                      # one token's K (or V), bf16
    total = 0
    for n in live:
        total += 2 * (n + 1) * row          # K and V of the live tokens
        total += 2 * 2 * row                # appended K, V: in and out
        total += hq * dh * (2 + 4)          # q in, output out
    return total


def paged_attn_flops(cfg: dict, live: Iterable[int]) -> int:
    """Operations of one paged-attention call: q.k and p.v over the
    ``l + 1`` live positions of every active request."""
    _, _, _, hq, _, dh, _ = _dims(cfg)
    return sum(4 * hq * dh * (n + 1) for n in live)
