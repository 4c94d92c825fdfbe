"""Mixture-of-experts FFN sublayers: a capacity-bounded GSPMD layer for
training and the dry-run, and a dropless expert-parallel share for
serving.

``moe_ffn`` (training, dry-run; GShard-style grouped dense dispatch):
tokens are split into groups (sharded over the data axis); each group
routes its tokens independently to (expert, capacity-slot) positions via
one-hot dispatch/combine tensors, so the whole layer is einsums --
GSPMD-friendly: with experts sharded over the "model" axis the dispatch
einsum lowers to the expert-parallel all-to-all.  The routing count
accumulation is a GroupByFold (the paper's CAM template -- see
kernels/groupby_fold.py for the validated kernel).  A token routed past
its expert's capacity is dropped.

``moe_dropless`` (serving prefill and paged decode): the router scores
all ``n_experts`` in f32 (softmax, top-k, renormalised), the chip
computes only the experts it holds (``cfg.experts_held`` of them,
starting at ``first``) and drops nothing: every token-expert pair of a
held expert is computed.  Pairs are sorted by expert into row tiles
and the grouped-matmul kernel (``codegen_pallas.lower_moe_gmm``)
streams each chosen expert's SwiGLU weights once per tile; an
unchosen expert's weights are never read.  The result is this share's
part of the layer: the other shares' experts add theirs on their own
chips.

Both take the router over every expert and hold ``experts_held``
experts' weights; any shared expert is computed whole.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from . import layers as L
from .config import ModelConfig
from .sharding import hint

GROUP_SIZE = 4096  # tokens per routing group (capacity is per group)


def param_shapes(cfg: ModelConfig, n_moe_layers: int) -> Dict[str, Tuple]:
    d, f, e, h = cfg.d_model, cfg.d_ff, cfg.n_experts, cfg.experts_held
    shapes = {
        "router": (n_moe_layers, d, e),
        "we1": (n_moe_layers, h, d, f),
        "we3": (n_moe_layers, h, d, f),
        "we2": (n_moe_layers, h, f, d),
    }
    if cfg.shared_expert:
        shapes.update({
            "ws1": (n_moe_layers, d, f),
            "ws3": (n_moe_layers, d, f),
            "ws2": (n_moe_layers, f, d),
        })
    return shapes


def capacity(cfg: ModelConfig, group_tokens: int) -> int:
    cap = int(cfg.capacity_factor * group_tokens * cfg.top_k
              / cfg.n_experts)
    return max(8, min(group_tokens, (cap + 7) // 8 * 8))


def moe_ffn(p: Dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """x: (B, S, D) -> (B, S, D).  ``p`` holds one layer's slices."""
    b, s, d = x.shape
    n_tok = b * s
    e, k = p["we1"].shape[0], cfg.top_k      # experts held here
    gsz = min(GROUP_SIZE, n_tok)
    assert n_tok % gsz == 0, (n_tok, gsz)
    g = n_tok // gsz
    cap = capacity(cfg, gsz)
    xt = x.reshape(g, gsz, d)
    xt = hint(xt, "data", None, None)

    gate_logits = jnp.einsum("gtd,de->gte", xt.astype(jnp.float32),
                             p["router"].astype(jnp.float32))
    topv, topi = jax.lax.top_k(gate_logits, k)               # (g, t, k)
    gates = jax.nn.softmax(topv, axis=-1)

    # slot assignment: rank within each expert's segment, computed by
    # sorting choices by expert id (MegaBlocks-style) -- O(t*k) memory
    # instead of the (t*k, e) one-hot cumsum (537 GB at 1M tokens x 128
    # experts).  This is a GroupByFold over the token stream (the CAM
    # template); the dense-histogram variant lives in router_counts.
    n = gsz * k
    held = topi < e                 # routed to an expert held here
    flat_e = jnp.where(held, topi, e).reshape(g, n)
    order = jnp.argsort(flat_e, axis=1, stable=True)         # (g, n)
    sorted_e = jnp.take_along_axis(flat_e, order, axis=1)
    idx = jnp.arange(n, dtype=jnp.int32)[None, :]
    is_new = jnp.concatenate(
        [jnp.ones((g, 1), bool), sorted_e[:, 1:] != sorted_e[:, :-1]],
        axis=1)
    seg_start = jax.lax.cummax(jnp.where(is_new, idx, 0), axis=1)
    slot_sorted = idx - seg_start                            # rank in segment
    inv = jnp.argsort(order, axis=1)
    slot = jnp.take_along_axis(slot_sorted, inv,
                               axis=1).reshape(g, gsz, k)
    keep = (slot < cap) & held

    # scatter dispatch: tokens land at flat slot e*cap + slot; dropped
    # tokens scatter out of bounds (mode="drop").  This never
    # materializes the (t, e, cap) one-hot dispatch tensor -- the same
    # "don't materialize the full intermediate" move as pattern tiling.
    nslots = e * cap
    dest = jnp.where(keep, topi * cap + slot, nslots)        # (g, t, k)

    def scatter_group(x_g, dest_g):
        buf = jnp.zeros((nslots, d), x_g.dtype)
        for kk in range(k):
            buf = buf.at[dest_g[:, kk]].add(x_g, mode="drop")
        return buf

    ex_in = jax.vmap(scatter_group)(xt, dest)                # (g, e*cap, d)
    ex_in = ex_in.reshape(g, e, cap, d)
    ex_in = hint(ex_in, "data", "model", None, None)
    act = L.activation("silu" if cfg.activation == "swiglu"
                       else cfg.activation)
    h = jnp.einsum("gecd,edf->gecf", ex_in, p["we1"])
    if cfg.activation == "swiglu":
        h = act(h) * jnp.einsum("gecd,edf->gecf", ex_in, p["we3"])
    else:
        h = act(h)
    ex_out = jnp.einsum("gecf,efd->gecd", h, p["we2"])
    ex_out = hint(ex_out, "data", "model", None, None)

    def gather_group(ex_g, dest_g, gates_g):
        # dropped tokens gather zeros (fill mode)
        got = jnp.take(ex_g.reshape(nslots, d), dest_g.reshape(-1),
                       axis=0, mode="fill", fill_value=0)
        got = got.reshape(gsz, k, d)
        return jnp.einsum("tkd,tk->td", got, gates_g.astype(ex_g.dtype))

    yt = jax.vmap(gather_group)(ex_out, dest, gates)         # (g, t, d)

    if cfg.shared_expert:
        hs = act(jnp.einsum("gtd,df->gtf", xt, p["ws1"]))
        if cfg.activation == "swiglu":
            hs = hs * jnp.einsum("gtd,df->gtf", xt, p["ws3"])
        yt = yt + jnp.einsum("gtf,fd->gtd", hs, p["ws2"])

    return yt.reshape(b, s, d).astype(x.dtype)


# ------------------------------------------------------------- dropless
def route(router: jax.Array, xt: jax.Array, top_k: int):
    """``(gates, experts)`` of each token, both ``(T, top_k)``: softmax
    over every expert's logit, the top ``top_k``, renormalised to sum
    to one; all in f32, the logits at full f32 precision."""
    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32),
                        router.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(probs, top_k)
    return topv / topv.sum(-1, keepdims=True), topi.astype(jnp.int32)


def gmm_row_block(n_tokens: int) -> int:
    """Rows of one grouped-matmul tile: every token of an expert in one
    tile at decode batch sizes (so its weights stream once), 128 in
    prefill."""
    return min(128, -(-n_tokens // 16) * 16)


def _swiglu_rows(xt, w1, w3, w2):
    """The kernel's arithmetic in XLA: products of the bf16 operands
    summed in f32 (operands widened to f32, which holds them exactly),
    the gated product rounded to the weights' dtype before ``w2``."""
    f32 = jnp.float32

    def mm(spec, a, b):
        return jnp.einsum(spec, a.astype(f32), b.astype(f32))

    h1 = mm("td,edf->tef", xt, w1)
    h3 = mm("td,edf->tef", xt, w3)
    g = (h1 * jax.nn.sigmoid(h1) * h3).astype(w2.dtype)
    return mm("tef,efd->ted", g, w2).astype(xt.dtype)


def moe_dropless(p: Dict, x: jax.Array, cfg: ModelConfig, *,
                 use_pallas: bool, first: int = 0):
    """This chip's share of a dropless MoE layer.  ``x`` (B, S, D);
    ``p`` holds one layer's ``router`` (D, n_experts) and the held
    experts' ``we1``/``we3`` (H, D, F) and ``we2`` (H, F, D), experts
    ``first .. first+H-1`` -- or, for the kernel, with a ``layer``
    index in ``p``, every MoE layer's experts stacked (L, H, ...), of
    which it reads that layer's.  Returns ``(y, (pairs, touched))``: the
    share's part of the layer output, the token-expert pairs computed
    here and the held experts at least one token chose.
    ``use_pallas`` runs the grouped-matmul kernel; otherwise every
    held expert is applied densely and weighted by the routing gates
    (zero where a token did not choose it)."""
    b, s, d = x.shape
    t, k = b * s, cfg.top_k
    h = p["we1"].shape[-3]
    xt = x.reshape(t, d)
    gates, topi = route(p["router"], xt, k)
    local = topi - first
    held = (local >= 0) & (local < h)
    key = jnp.where(held, local, h)                       # (T, k)
    counts = jnp.zeros((h + 1,), jnp.int32).at[key.reshape(-1)].add(1)[:h]
    stats = (counts.sum(), (counts > 0).sum().astype(jnp.int32))
    if use_pallas:
        y = _dropless_gmm(p, xt, gates, key, counts)
    else:
        dense_gates = jnp.zeros((t, h + 1), jnp.float32).at[
            jnp.arange(t)[:, None], key].add(gates)[:, :h]
        ye = _swiglu_rows(xt, p["we1"], p["we3"], p["we2"])  # (T, H, D)
        y = jnp.einsum("ted,te->td", ye.astype(jnp.float32), dense_gates)
    if cfg.shared_expert:
        hs = jax.nn.silu(xt @ p["ws1"]) * (xt @ p["ws3"])
        y = y + (hs @ p["ws2"]).astype(jnp.float32)
    return y.reshape(b, s, d).astype(x.dtype), stats


def _dropless_gmm(p, xt, gates, key, counts):
    """Pairs sorted by held expert into whole row tiles, the grouped
    matmul over the tiles, and each token's gated sum of its pairs'
    rows."""
    from repro.core.codegen_pallas import lower_moe_gmm

    t, d = xt.shape
    k = key.shape[1]
    h, _, f = p["we1"].shape[-3:]
    w1, w3, w2 = (p[n] if "layer" in p else p[n][None]
                  for n in ("we1", "we3", "we2"))
    tm = gmm_row_block(t)
    n_pairs = t * min(k, h)              # held pairs, at most
    n_tiles = -(-n_pairs // tm) + min(h, n_pairs)
    tiles = -(-counts // tm)                              # per expert
    ends = jnp.cumsum(tiles)
    row0 = (ends - tiles) * tm           # first row of each segment
    flat = key.reshape(-1)
    order = jnp.argsort(flat, stable=True)
    sk = flat[order]
    idx = jnp.arange(t * k, dtype=jnp.int32)
    is_new = jnp.concatenate([jnp.ones((1,), bool), sk[1:] != sk[:-1]])
    rank = idx - jax.lax.cummax(jnp.where(is_new, idx, 0))
    dest_sorted = jnp.where(sk < h, row0[jnp.minimum(sk, h - 1)] + rank,
                            n_tiles * tm)
    dest = jnp.zeros((t * k,), jnp.int32).at[order].set(dest_sorted)
    xs = jnp.zeros((n_tiles * tm, d), xt.dtype).at[dest].set(
        xt[idx // k], mode="drop")
    tile_expert = jnp.minimum(
        jnp.searchsorted(ends, jnp.arange(n_tiles), side="right"),
        h - 1).astype(jnp.int32)
    call = lower_moe_gmm(rows=n_tiles * tm, d_model=d, d_ff=f,
                         n_experts=h, row_block=tm, dtype=xt.dtype)
    ys = call(xs, w1, w3, w2, tile_expert, ends[-1:], p.get("layer", 0))
    yp = jnp.take(ys, dest, axis=0, mode="fill", fill_value=0)
    return jnp.einsum("tkd,tk->td", yp.reshape(t, k, d).astype(jnp.float32),
                      gates)


def router_counts(p: Dict, x: jax.Array, cfg: ModelConfig,
                  use_pallas: bool = False) -> jax.Array:
    """Tokens-per-expert histogram -- the GroupByFold of MoE routing.

    With ``use_pallas`` the validated CAM kernel computes it."""
    b, s, d = x.shape
    xt = x.reshape(b * s, d)
    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32),
                        p["router"].astype(jnp.float32))
    top1 = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    if use_pallas:
        from repro.kernels.groupby_fold import groupby_fold
        return groupby_fold(top1, jnp.ones((b * s,), jnp.float32),
                            cfg.n_experts)
    from repro.kernels import ref
    return ref.groupby_fold(top1, jnp.ones((b * s,)), cfg.n_experts)
