"""Paged KV cache + decode step on the pattern substrate.

Serving decode was the one hot path still running outside the pattern
stack (a plain jitted ``decode_step``, one dense ``(B, Hkv, C, dh)``
cache per length group).  This module re-expresses it over a *paged*
pool: KV lives in fixed-size pages, each request owns a page list
(``page_table`` row) and a live length (``seq_lens``), and one decode
step is the ``decode_attention`` pipeline DAG -- a KV-append producer
feeding a flash-attention fold over a ragged streaming domain
(``core.ir.RaggedExtent``: static page-count grid, in-kernel length
predication).

Two enumerable KV layouts (the DSE axis ``core.dse.
select_paged_decode_blocks`` searches):

  * ``split``  -- separate K and V pools, each ``(L, P, ps, Hkv*dh)``;
  * ``fused``  -- one pool ``(L, P, ps, 2*Hkv*dh)`` with K and V
    head-interleaved (K at even head slot ``2h``, V at odd ``2h+1``),
    so a page streams both operands of one head in a single burst.

A token is one lane-dense row of its heads, so a page is whole TPU
tiles and the decode kernel reads and writes the pool in place.

``paged_decode_step`` mirrors ``model.decode_step`` structurally (same
``scan_layers`` over stacked params, same einsums and casts, only the
cache write/read swapped for page scatter/gather -- both exact
permutations), so with a no-wrap dense cache of the page-padded extent
the oracle is *bit-identical*, not merely close: the ring mask reduces
to ``slot <= position`` and the gathered view equals the dense cache.
``use_pallas=True`` swaps the reference attention for the fused
``codegen_pallas.lower_paged_decode`` kernel (append + online-softmax
fold in one kernel); serving certifies it against the reference via
``core.resilience`` before trusting it.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from . import layers as L
from . import moe as moe_mod
from .config import ModelConfig
from .transformer import (Params, _dense_ffn, _embed_tokens,
                          _layer_stacks)

LAYOUTS = ("split", "fused")


@jax.tree_util.register_pytree_node_class
class PagedKVCache:
    """Blocked KV storage: ``buffers`` is a tuple of page pools
    (``(k_pages, v_pages)`` for split, ``(kv_pages,)`` for fused),
    ``page_table[b]`` the request's logical-page -> physical-page map,
    ``seq_lens[b]`` its live token count.  Physical page 0 is reserved
    as scratch so inactive slots always have somewhere valid to point.
    """

    def __init__(self, buffers: Tuple[jax.Array, ...],
                 page_table: jax.Array, seq_lens: jax.Array, *,
                 layout: str, page_size: int, head_dim: int):
        if layout not in LAYOUTS:
            raise ValueError(f"layout {layout!r}; one of {LAYOUTS}")
        self.buffers = tuple(buffers)
        self.page_table = page_table
        self.seq_lens = seq_lens
        self.layout = layout
        self.page_size = page_size
        self.head_dim = head_dim

    def tree_flatten(self):
        return ((self.buffers, self.page_table, self.seq_lens),
                (self.layout, self.page_size, self.head_dim))

    @classmethod
    def tree_unflatten(cls, aux, children):
        buffers, page_table, seq_lens = children
        return cls(buffers, page_table, seq_lens,
                   layout=aux[0], page_size=aux[1], head_dim=aux[2])

    def replace(self, *, buffers=None, page_table=None,
                seq_lens=None) -> "PagedKVCache":
        """The same layout with some arrays swapped."""
        return PagedKVCache(
            self.buffers if buffers is None else buffers,
            self.page_table if page_table is None else page_table,
            self.seq_lens if seq_lens is None else seq_lens,
            layout=self.layout, page_size=self.page_size,
            head_dim=self.head_dim)

    # ------------------------------------------------------------ shapes
    @property
    def n_pages(self) -> int:       # physical pool size
        return self.buffers[0].shape[1]

    @property
    def n_pages_max(self) -> int:   # logical pages per request
        return self.page_table.shape[1]

    @property
    def max_context(self) -> int:
        return self.n_pages_max * self.page_size

    @property
    def batch(self) -> int:
        return self.page_table.shape[0]

    @classmethod
    def init(cls, cfg: ModelConfig, batch: int, max_len: int, *,
             page_size: int, layout: str = "split", n_pages: int = 0,
             dtype=None) -> "PagedKVCache":
        """Fresh pool.  ``page_table`` starts with every request's
        pages linearly pre-assigned (request ``b`` owns pages
        ``1 + b*n .. 1 + (b+1)*n - 1``); continuous batching rewrites
        rows through :meth:`assign_pages` as requests come and go."""
        if cfg.sliding_window is not None:
            raise NotImplementedError(
                "paged decode has no ring semantics; sliding-window "
                f"config {cfg.name} needs the dense cache")
        dt = dtype or jnp.dtype(cfg.dtype)
        nl, hkv, dh = cfg.n_layers, cfg.n_kv_heads, cfg.head_dim
        npm = -(-max_len // page_size)
        pool = max(n_pages, 1 + batch * npm)   # + reserved page 0
        if layout == "fused":
            buffers = (jnp.zeros((nl, pool, page_size, 2 * hkv * dh), dt),)
        else:
            buffers = (jnp.zeros((nl, pool, page_size, hkv * dh), dt),
                       jnp.zeros((nl, pool, page_size, hkv * dh), dt))
        table = 1 + jnp.arange(batch * npm, dtype=jnp.int32
                               ).reshape(batch, npm)
        return cls(buffers, table, jnp.zeros((batch,), jnp.int32),
                   layout=layout, page_size=page_size, head_dim=dh)

    # ------------------------------------------------- slot bookkeeping
    def assign_pages(self, slot: int, pages, length: int
                     ) -> "PagedKVCache":
        """Point request ``slot`` at ``pages`` (list padded with 0)
        with ``length`` live tokens (continuous-batching admit/evict)."""
        row = jnp.zeros((self.n_pages_max,), jnp.int32)
        row = row.at[:len(pages)].set(jnp.asarray(pages, jnp.int32))
        return self.replace(
            page_table=self.page_table.at[slot].set(row),
            seq_lens=self.seq_lens.at[slot].set(jnp.int32(length)))

    def write_tokens(self, slot: int, k, v, start: int
                     ) -> "PagedKVCache":
        """Scatter prefilled K/V (``(L, Hkv, S, dh)``) for request
        ``slot`` at positions ``start..start+S-1`` (admit path: the
        dense prefill cache lands in this slot's pages)."""
        s = k.shape[2]
        pos = start + jnp.arange(s)
        flat = self.page_table[slot, pos // self.page_size] \
            * self.page_size + pos % self.page_size
        buffers = list(self.buffers)
        if self.layout == "fused":
            nl, hkv, dh = k.shape[0], k.shape[1], k.shape[3]
            kv = jnp.stack([k, v], axis=2)          # (L, Hkv, 2, S, dh)
            kv = kv.reshape(nl, 2 * hkv, s, dh)     # head-interleaved
            kv = kv.transpose(0, 2, 1, 3)           # (L, S, 2Hkv, dh)
            fl = _flat(self.buffers[0], dh)
            buffers[0] = fl.at[:, flat].set(kv.astype(fl.dtype)
                                            ).reshape(self.buffers[0].shape)
        else:
            for i, t in enumerate((k, v)):
                fl = _flat(self.buffers[i], k.shape[3])
                buffers[i] = fl.at[:, flat].set(
                    t.transpose(0, 2, 1, 3).astype(fl.dtype)
                ).reshape(self.buffers[i].shape)
        return self.replace(buffers=tuple(buffers))

    def gather_dense(self, li: int) -> Tuple[jax.Array, jax.Array]:
        """Dense ``(B, Hkv, Cmax, dh)`` K and V views of layer ``li``
        (logical order; positions past ``seq_lens`` are whatever the
        mapped page holds and must be masked by the caller)."""
        pools = tuple(buf[li] for buf in self.buffers)
        return _gather_layer(pools, self.page_table, self.layout,
                             self.page_size, self.head_dim)


def _flat(buf: jax.Array, head_dim: int) -> jax.Array:
    """Pages flattened to one token axis of head rows:
    ``(..., P*ps, H, dh)``."""
    *lead, p, ps, width = buf.shape
    return buf.reshape(*lead, p * ps, width // head_dim, head_dim)


def _append_layer(pools, page_table, seq_lens, k, v, layout: str,
                  page_size: int) -> Tuple[jax.Array, ...]:
    """One layer's pools (each ``(P, ps, H*dh)``) with the token K/V
    (``(B, Hkv, dh)``) scattered at each request's ``seq_lens`` slot."""
    batch = page_table.shape[0]
    idx = page_table[jnp.arange(batch), seq_lens // page_size] \
        * page_size + seq_lens % page_size
    b_, hkv, dh = k.shape
    if layout == "fused":
        kv = jnp.stack([k, v], axis=2).reshape(b_, 2 * hkv, dh)
        fl = _flat(pools[0], dh)
        return (fl.at[idx].set(kv.astype(fl.dtype)
                               ).reshape(pools[0].shape),)
    out = []
    for pool, t in zip(pools, (k, v)):
        fl = _flat(pool, dh)
        out.append(fl.at[idx].set(t.astype(fl.dtype)
                                  ).reshape(pool.shape))
    return tuple(out)


def _gather_layer(pools, page_table, layout: str, page_size: int,
                  head_dim: int) -> Tuple[jax.Array, jax.Array]:
    """Dense ``(B, Hkv, Cmax, dh)`` K/V views of one layer's pools."""
    npm = page_table.shape[1]
    cmax = npm * page_size
    pos = jnp.arange(cmax)
    gidx = page_table[:, pos // page_size] * page_size \
        + pos % page_size                                # (B, Cmax)
    if layout == "fused":
        g = _flat(pools[0], head_dim)[gidx]              # (B, Cmax, 2H, dh)
        b_, _, h2, dh = g.shape
        g = g.reshape(b_, cmax, h2 // 2, 2, dh)
        ck, cv = g[..., 0, :], g[..., 1, :]
    else:
        ck = _flat(pools[0], head_dim)[gidx]
        cv = _flat(pools[1], head_dim)[gidx]
    return (ck.transpose(0, 2, 1, 3), cv.transpose(0, 2, 1, 3))


# -------------------------------------------------------------- decode
def reference_attn(q, k, v, pools, page_table, seq_lens, layout: str,
                   page_size: int) -> Tuple[jax.Array, Tuple[jax.Array, ...]]:
    """The plain paged decode attention the kernel is held to: the
    token K/V (``(B, Hkv, dh)``) scattered into its page, the pages
    gathered dense, softmax over the live slots in f32 at HIGHEST.
    ``q`` is ``(B, Hkv, group, dh)``; returns the f32 ``(B, Hkv, group,
    dh)`` output and the new pools."""
    dh = q.shape[-1]
    new_pools = _append_layer(pools, page_table, seq_lens, k, v, layout,
                              page_size)
    ck, cv = _gather_layer(new_pools, page_table, layout, page_size,
                           dh)                           # (B,Hkv,Cmax,dh)
    hi = jax.lax.Precision.HIGHEST   # true f32, like the kernel
    scores = jnp.einsum("bkgh,bkch->bkgc", q.astype(jnp.float32),
                        ck.astype(jnp.float32), precision=hi) * dh ** -0.5
    valid = jnp.arange(ck.shape[2])[None, :] <= seq_lens[:, None]
    scores = jnp.where(valid[:, None, None, :], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgc,bkch->bkgh", probs, cv.astype(jnp.float32),
                     precision=hi)
    return out, new_pools


def _paged_attn(p, x, cfg: ModelConfig, pools, page_table, seq_lens,
                layout: str, page_size: int, use_pallas: bool,
                block=None, depth: int = 2):
    """One layer's decode attention over its page pools; the math and
    casts of ``transformer._attn``'s decode branch with per-request
    positions.  ``block`` and ``depth`` are the kernel's streaming
    block and buffer depth.  Returns ``(attn_out, new_pools)``."""
    b, s, _ = x.shape
    hq, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = jnp.einsum("bsd,dq->bsq", x, p["wq"])
    k = jnp.einsum("bsd,dq->bsq", x, p["wk"])
    v = jnp.einsum("bsd,dq->bsq", x, p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    positions = seq_lens[:, None]                        # (B, 1)
    q = L.rope(q.reshape(b, s, hq, dh), positions, cfg.rope_theta)
    k = L.rope(k.reshape(b, s, hkv, dh), positions, cfg.rope_theta)
    v = v.reshape(b, s, hkv, dh)
    group = hq // hkv
    qg = q.reshape(b, s, hkv, group, dh)[:, 0]
    k1, v1 = k[:, 0], v[:, 0]                            # (B, Hkv, dh)

    if use_pallas:
        from repro.core.codegen_pallas import lower_paged_decode
        kern = lower_paged_decode(
            batch=b, kv_heads=hkv, group=group, head_dim=dh,
            page_size=page_size, n_pages_max=page_table.shape[1],
            layout=layout, block=block, depth=depth,
            dtype=pools[0].dtype)
        out, new_pools = kern(qg, k1, v1, pools, page_table, seq_lens)
    else:
        out, new_pools = reference_attn(qg, k1, v1, pools, page_table,
                                        seq_lens, layout, page_size)
    out = out.reshape(b, s, hq * dh).astype(x.dtype)
    return jnp.einsum("bsq,qd->bsd", out, p["wo"]), tuple(new_pools)


def paged_decode_step(params: Params, cfg: ModelConfig,
                      cache: PagedKVCache, tokens: jax.Array, *,
                      use_pallas: bool = False, block=None,
                      depth: int = 2):
    """One decode step for every active request: tokens ``(B, 1)``,
    per-request positions from ``cache.seq_lens``.  Returns
    ``(logits, cache')`` with every request's length advanced by one.
    Dense/MoE attention families only (recurrent families have no KV
    cache to page).  Structured exactly like ``model.decode_step``
    (same layer scan over the same stacked params) so the two paths
    stay bit-comparable.  ``block`` and ``depth`` go to the fused
    kernel (``codegen_pallas.lower_paged_decode``)."""
    if cfg.family not in ("dense", "moe"):
        raise NotImplementedError(
            f"paged decode supports dense/moe, not {cfg.family}")
    x = _embed_tokens(params, cfg, tokens)
    attn, dense, moe = _layer_stacks(params, cfg)
    period = cfg.moe_layer_period if cfg.n_experts else 1
    n_super = cfg.n_layers // period
    table, lens = cache.page_table, cache.seq_lens
    layout, ps = cache.layout, cache.page_size

    def super_block(carry, slices):
        x = carry
        a_slc, d_slc, m_slc, pools_slc = slices
        new_pools = [[] for _ in pools_slc]
        for i in range(period):
            is_moe = bool(moe) and i == period - 1
            sl = {k: v[i] for k, v in a_slc.items()}
            if is_moe:
                sl.update(m_slc)
            else:
                sl.update({k: v[i] for k, v in d_slc.items()})
            layer_pools = tuple(pp[i] for pp in pools_slc)
            a, lp = _paged_attn(sl, L.rms_norm(x, sl["ln1"]), cfg,
                                layer_pools, table, lens, layout, ps,
                                use_pallas, block, depth)
            x = x + a
            h = L.rms_norm(x, sl["ln2"])
            if is_moe:
                moe_p = {k[4:]: v for k, v in sl.items()
                         if k.startswith("moe_")}
                x = x + moe_mod.moe_ffn(moe_p, h, cfg)
            else:
                x = x + _dense_ffn(sl, h, cfg)
            for j, npool in enumerate(lp):
                new_pools[j].append(npool)
        return x, tuple(jnp.stack(nps) for nps in new_pools)

    def stack_reshape(t):
        return t.reshape((n_super, period) + t.shape[1:])

    a_stk = jax.tree.map(stack_reshape, attn)
    if dense and moe:
        d_stk = jax.tree.map(
            lambda t: t.reshape((n_super, period - 1) + t.shape[1:]),
            dense)
    else:
        d_stk = jax.tree.map(stack_reshape, dense) if dense else {}
    pools_stk = tuple(stack_reshape(buf) for buf in cache.buffers)

    x, new_stk = L.scan_layers(super_block, x,
                               (a_stk, d_stk, moe, pools_stk),
                               cfg.unroll)
    x = L.rms_norm(x, params["final_norm"])
    logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"])
    buffers = tuple(nb.reshape(buf.shape)
                    for nb, buf in zip(new_stk, cache.buffers))
    return logits, cache.replace(buffers=buffers, seq_lens=lens + 1)
