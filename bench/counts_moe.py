"""Operations and bytes that the benchmarked work of a model with
windowed and full attention layers and a dropless expert share needs,
computed from shapes and the program's routing counters.

As in ``counts``: what the algorithm must do, not what a kernel
happens to do (no page or tile padding, no re-reads), so a share of
the roofline built on them can only fall when a kernel wastes work.
Configurations are the JSON dicts under ``bench/configs`` (Hugging
Face key names; ``num_experts`` is the experts held here and
``router_experts`` the router's width).
"""
from __future__ import annotations

from typing import Iterable, List

BF16 = 2


def _dims(cfg: dict):
    return (cfg["hidden_size"], cfg["moe_intermediate_size"],
            cfg["num_hidden_layers"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"], cfg["vocab_size"],
            cfg["router_experts"])


def sliding_layers(cfg: dict) -> List[bool]:
    """For each layer, whether it attends in the sliding window."""
    return [t == "sliding_attention" for t in cfg["layer_types"]]


def positions(cfg: dict, n: int, sliding: bool) -> int:
    """Positions a request with ``n`` tokens before the step attends
    to in one layer: ``n + 1``, at most the window in a windowed one."""
    return min(n + 1, cfg["sliding_window"]) if sliding else n + 1


def attn_bytes(cfg: dict, live: Iterable[int]) -> int:
    """HBM bytes of one decode step's paged-attention calls, summed
    over the layers: per layer and request the K and V rows of the
    positions it attends to, the appended rows read in and written to
    the pool, its bfloat16 query and its float32 output."""
    d, f, nl, hq, hkv, dh, v, ne = _dims(cfg)
    row = hkv * dh * BF16
    live = list(live)
    total = 0
    for sliding in sliding_layers(cfg):
        for n in live:
            total += 2 * positions(cfg, n, sliding) * row
            total += 2 * 2 * row + hq * dh * (BF16 + 4)
    return total


def attn_flops(cfg: dict, live: Iterable[int]) -> int:
    """Operations of one decode step's paged-attention calls: q.k and
    p.v over the positions each request attends to, in every layer."""
    _, _, _, hq, _, dh, _, _ = _dims(cfg)
    live = list(live)
    return sum(4 * hq * dh * positions(cfg, n, sliding)
               for sliding in sliding_layers(cfg) for n in live)


def gmm_bytes(cfg: dict, pairs: int, touched: int) -> int:
    """HBM bytes of a step's grouped expert matmuls: the three SwiGLU
    matrices of every touched held expert once, and each token-expert
    pair's row in and out (bfloat16).  ``pairs`` and ``touched`` are
    the step's ``moe_tokens_held`` and ``moe_experts_touched``, summed
    over the layers."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    return touched * 3 * d * f * BF16 + pairs * 2 * d * BF16


def gmm_flops(cfg: dict, pairs: int) -> int:
    """Operations of a step's grouped expert matmuls: ``6 d f`` per
    token-expert pair (three matmuls of ``d x f``)."""
    return 6 * cfg["hidden_size"] * cfg["moe_intermediate_size"] * pairs


def dense_params(cfg: dict) -> int:
    """Weights every token multiplies by: the four attention
    projections and the router of every layer, and the output head."""
    d, f, nl, hq, hkv, dh, v, ne = _dims(cfg)
    return nl * (2 * d * hq * dh + 2 * d * hkv * dh + d * ne) + d * v


def decode_step_flops(cfg: dict, live: Iterable[int], pairs: int) -> int:
    """Active operations of one decode step of the requests whose
    contexts hold ``live`` tokens: the projections, routers and head
    for each token, the held experts' ``pairs``, and attention over
    the positions each layer sees."""
    live = list(live)
    return (2 * dense_params(cfg) * len(live) + gmm_flops(cfg, pairs)
            + attn_flops(cfg, live))
