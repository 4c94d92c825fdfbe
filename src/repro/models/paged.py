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
tiles and the decode kernel reads and writes the pool in place.  The
decode step carries each kind's pools, stacked over its layers, through
the layer scan: the kernel takes the whole stacks and a layer index, so
no step slices a layer's pool out of the stack or stacks it back.

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

Two pool kinds live in one cache when a config mixes windowed and full
attention layers (Mellum 2).  Full layers keep ``buffers``, whose
pages grow with the context (``page_table``).  Windowed layers keep
``win_buffers``: each request a ring of ``ring_pages(window, ps)``
pages (``win_table``), logical page ``p`` in column ``p % ring``,
recycled as the window slides.  Both share ``seq_lens``.  A config
whose layers are all windowed keeps an empty (zero-layer) full pool.
MoE layers run the dropless expert share (``moe.moe_dropless``).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from . import layers as L
from . import moe as moe_mod
from .config import ModelConfig
from .transformer import (Params, _dense_ffn, _embed_tokens, _layer_slice,
                          _super_stacks, expert_stacks, kinds_in_plan,
                          layer_plan, layers_of_kind)

LAYOUTS = ("split", "fused")


def ring_pages(window: int, page_size: int, n_pages_max: int) -> int:
    """Pages of a windowed layer's per-request ring: the ``window``
    newest tokens span at most ``ceil(window / ps) + 1`` pages; a
    context of fewer pages needs no more than it has."""
    return min(-(-window // page_size) + 1, n_pages_max)


def _pools(cfg: ModelConfig, n_layers: int, n_pages: int, page_size: int,
           layout: str, dt) -> Tuple[jax.Array, ...]:
    width = cfg.n_kv_heads * cfg.head_dim
    if layout == "fused":
        return (jnp.zeros((n_layers, n_pages, page_size, 2 * width), dt),)
    return tuple(jnp.zeros((n_layers, n_pages, page_size, width), dt)
                 for _ in range(2))


@jax.tree_util.register_pytree_node_class
class PagedKVCache:
    """Blocked KV storage: ``buffers`` is a tuple of page pools
    (``(k_pages, v_pages)`` for split, ``(kv_pages,)`` for fused),
    ``page_table[b]`` the request's logical-page -> physical-page map,
    ``seq_lens[b]`` its live token count.  Physical page 0 is reserved
    as scratch so inactive slots always have somewhere valid to point.
    With ``window`` set, ``win_buffers`` and ``win_table`` are the
    windowed layers' pools and per-request rings, in the same layout.
    """

    def __init__(self, buffers: Tuple[jax.Array, ...],
                 page_table: jax.Array, seq_lens: jax.Array, *,
                 layout: str, page_size: int, head_dim: int,
                 win_buffers: Tuple[jax.Array, ...] = (),
                 win_table=None, window: Optional[int] = None):
        if layout not in LAYOUTS:
            raise ValueError(f"layout {layout!r}; one of {LAYOUTS}")
        self.buffers = tuple(buffers)
        self.page_table = page_table
        self.seq_lens = seq_lens
        self.layout = layout
        self.page_size = page_size
        self.head_dim = head_dim
        self.win_buffers = tuple(win_buffers)
        self.win_table = win_table
        self.window = window

    def tree_flatten(self):
        children = (self.buffers, self.page_table, self.seq_lens)
        if self.window is not None:
            children += (self.win_buffers, self.win_table)
        return children, (self.layout, self.page_size, self.head_dim,
                          self.window)

    @classmethod
    def tree_unflatten(cls, aux, children):
        layout, page_size, head_dim, window = aux
        win = children[3:] if window is not None else ((), None)
        return cls(*children[:3], layout=layout, page_size=page_size,
                   head_dim=head_dim, win_buffers=win[0], win_table=win[1],
                   window=window)

    def replace(self, *, buffers=None, page_table=None,
                seq_lens=None, win_buffers=None,
                win_table=None) -> "PagedKVCache":
        """The same layout with some arrays swapped."""
        return PagedKVCache(
            self.buffers if buffers is None else buffers,
            self.page_table if page_table is None else page_table,
            self.seq_lens if seq_lens is None else seq_lens,
            layout=self.layout, page_size=self.page_size,
            head_dim=self.head_dim,
            win_buffers=self.win_buffers if win_buffers is None
            else win_buffers,
            win_table=self.win_table if win_table is None else win_table,
            window=self.window)

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

    @property
    def ring(self) -> int:          # pages of a windowed layer's ring
        return self.win_table.shape[1]

    @classmethod
    def init(cls, cfg: ModelConfig, batch: int, max_len: int, *,
             page_size: int, layout: str = "split", n_pages: int = 0,
             dtype=None) -> "PagedKVCache":
        """Fresh pool.  ``page_table`` starts with every request's
        pages linearly pre-assigned (request ``b`` owns pages
        ``1 + b*n .. 1 + (b+1)*n - 1``), and so does ``win_table``
        with each request's ring where the config has windowed layers;
        continuous batching rewrites rows through :meth:`assign_pages`
        as requests come and go."""
        dt = dtype or jnp.dtype(cfg.dtype)
        npm = -(-max_len // page_size)
        pool = max(n_pages, 1 + batch * npm)   # + reserved page 0
        buffers = _pools(cfg, layers_of_kind(cfg, "full"), pool, page_size,
                         layout, dt)
        table = 1 + jnp.arange(batch * npm, dtype=jnp.int32
                               ).reshape(batch, npm)
        win = {}
        if "window" in cfg.attn_kinds:
            r = ring_pages(cfg.sliding_window, page_size, npm)
            win = dict(
                win_buffers=_pools(cfg, layers_of_kind(cfg, "window"),
                                   1 + batch * r, page_size, layout, dt),
                win_table=1 + jnp.arange(batch * r, dtype=jnp.int32
                                         ).reshape(batch, r),
                window=cfg.sliding_window)
        return cls(buffers, table, jnp.zeros((batch,), jnp.int32),
                   layout=layout, page_size=page_size, head_dim=cfg.head_dim,
                   **win)

    # ------------------------------------------------- slot bookkeeping
    def assign_pages(self, slot: int, pages, length: int, ring=()
                     ) -> "PagedKVCache":
        """Point request ``slot`` at ``pages`` (list padded with 0), and
        its windowed layers at the ``ring`` pages, with ``length`` live
        tokens (continuous-batching admit/evict)."""
        row = jnp.zeros((self.n_pages_max,), jnp.int32)
        row = row.at[:len(pages)].set(jnp.asarray(pages, jnp.int32))
        win = {}
        if self.window is not None:
            wrow = jnp.zeros((self.ring,), jnp.int32)
            wrow = wrow.at[:len(ring)].set(jnp.asarray(ring, jnp.int32))
            win = dict(win_table=self.win_table.at[slot].set(wrow))
        return self.replace(
            page_table=self.page_table.at[slot].set(row),
            seq_lens=self.seq_lens.at[slot].set(jnp.int32(length)), **win)

    def write_tokens(self, slot: int, k, v, start: int
                     ) -> "PagedKVCache":
        """Scatter prefilled K/V of the full layers (``(L, Hkv, S,
        dh)``) for request ``slot`` at positions ``start..start+S-1``
        (admit path: the dense prefill cache lands in this slot's
        pages)."""
        pos = start + jnp.arange(k.shape[2])
        flat = self.page_table[slot, pos // self.page_size] \
            * self.page_size + pos % self.page_size
        return self.replace(buffers=_scatter(self.buffers, flat, k, v,
                                             self.layout))

    def write_window(self, slot: int, k, v, start: int
                     ) -> "PagedKVCache":
        """Scatter the windowed layers' K/V (``(L, Hkv, S, dh)``, S at
        most the ring's tokens) at positions ``start..start+S-1`` into
        request ``slot``'s ring."""
        pos = start + jnp.arange(k.shape[2])
        col = (pos // self.page_size) % self.ring
        flat = self.win_table[slot, col] * self.page_size \
            + pos % self.page_size
        return self.replace(win_buffers=_scatter(self.win_buffers, flat, k,
                                                 v, self.layout))

    def gather_dense(self, li: int) -> Tuple[jax.Array, jax.Array]:
        """Dense ``(B, Hkv, Cmax, dh)`` K and V views of layer ``li``
        (logical order; positions past ``seq_lens`` are whatever the
        mapped page holds and must be masked by the caller)."""
        pools = tuple(buf[li] for buf in self.buffers)
        return _gather_layer(pools, self.page_table, self.layout,
                             self.page_size, self.head_dim)


def _scatter(buffers, flat, k, v, layout: str) -> Tuple[jax.Array, ...]:
    """``buffers`` with the K/V rows (``(L, Hkv, S, dh)``) written at
    the flat token slots ``flat`` (``(S,)``)."""
    s = k.shape[2]
    buffers = list(buffers)
    if layout == "fused":
        nl, hkv, dh = k.shape[0], k.shape[1], k.shape[3]
        kv = jnp.stack([k, v], axis=2)          # (L, Hkv, 2, S, dh)
        kv = kv.reshape(nl, 2 * hkv, s, dh)     # head-interleaved
        kv = kv.transpose(0, 2, 1, 3)           # (L, S, 2Hkv, dh)
        fl = _flat(buffers[0], dh)
        buffers[0] = fl.at[:, flat].set(kv.astype(fl.dtype)
                                        ).reshape(buffers[0].shape)
    else:
        for i, t in enumerate((k, v)):
            fl = _flat(buffers[i], k.shape[3])
            buffers[i] = fl.at[:, flat].set(
                t.transpose(0, 2, 1, 3).astype(fl.dtype)
            ).reshape(buffers[i].shape)
    return tuple(buffers)


def _flat(buf: jax.Array, head_dim: int) -> jax.Array:
    """Pages flattened to one token axis of head rows:
    ``(..., P*ps, H, dh)``."""
    *lead, p, ps, width = buf.shape
    return buf.reshape(*lead, p * ps, width // head_dim, head_dim)


def _append_layer(pools, page_table, seq_lens, k, v, layout: str,
                  page_size: int, ring: bool = False
                  ) -> Tuple[jax.Array, ...]:
    """One layer's pools (each ``(P, ps, H*dh)``) with the token K/V
    (``(B, Hkv, dh)``) scattered at each request's ``seq_lens`` slot
    (through the ring's column where ``ring``)."""
    batch = page_table.shape[0]
    col = seq_lens // page_size
    if ring:
        col = col % page_table.shape[1]
    idx = page_table[jnp.arange(batch), col] \
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
def ring_positions(seq_lens, ring: int, page_size: int) -> jax.Array:
    """``(B, ring * ps)``: the position each slot of a request's ring
    holds once the token at ``seq_lens`` is written (column ``c`` holds
    the newest logical page congruent to ``c``; negative where nothing
    has been written)."""
    c = jnp.arange(ring * page_size)
    last = (seq_lens // page_size)[:, None]
    page = last - (last - (c // page_size)[None, :]) % ring
    return page * page_size + c % page_size


def reference_attn(q, k, v, pools, page_table, seq_lens, layout: str,
                   page_size: int, window: Optional[int] = None
                   ) -> Tuple[jax.Array, Tuple[jax.Array, ...]]:
    """The plain paged decode attention the kernel is held to: the
    token K/V (``(B, Hkv, dh)``) scattered into its page, the pages
    gathered dense, softmax over the live slots in f32 at HIGHEST.
    ``q`` is ``(B, Hkv, group, dh)``; returns the f32 ``(B, Hkv, group,
    dh)`` output and the new pools.  With ``window`` the table rows are
    rings and only the ``window`` newest positions are live."""
    dh = q.shape[-1]
    new_pools = _append_layer(pools, page_table, seq_lens, k, v, layout,
                              page_size, ring=window is not None)
    ck, cv = _gather_layer(new_pools, page_table, layout, page_size,
                           dh)                           # (B,Hkv,Cmax,dh)
    hi = jax.lax.Precision.HIGHEST   # true f32, like the kernel
    scores = jnp.einsum("bkgh,bkch->bkgc", q.astype(jnp.float32),
                        ck.astype(jnp.float32), precision=hi) * dh ** -0.5
    if window is None:
        valid = jnp.arange(ck.shape[2])[None, :] <= seq_lens[:, None]
    else:
        pos = ring_positions(seq_lens, page_table.shape[1], page_size)
        valid = ((pos >= 0) & (pos <= seq_lens[:, None])
                 & (pos > seq_lens[:, None] - window))
    scores = jnp.where(valid[:, None, None, :], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgc,bkch->bkgh", probs, cv.astype(jnp.float32),
                     precision=hi)
    return out, new_pools


def _paged_attn(p, x, cfg: ModelConfig, pools, found, layer, page_table,
                seq_lens, layout: str, page_size: int, use_pallas: bool,
                block=None, depth: int = 2, kind: str = "full"):
    """One layer's decode attention over its page pools; the math and
    casts of ``transformer._attn``'s decode branch with per-request
    positions.  ``pools`` are stacked over the layers of the layer's
    attention ``kind`` and ``layer`` indexes them (a windowed layer's
    ``page_table`` holds rings); ``found`` are the stacks as the step
    received them.  ``block`` and ``depth`` are the kernel's streaming
    block and buffer depth.  Returns ``(attn_out, new_pools)``, the
    stacks with the layer's token appended."""
    b, s, _ = x.shape
    hq, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    window, yarn = cfg.window_of(kind), cfg.yarn_of(kind)
    q = jnp.einsum("bsd,dq->bsq", x, p["wq"])
    k = jnp.einsum("bsd,dq->bsq", x, p["wk"])
    v = jnp.einsum("bsd,dq->bsq", x, p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    positions = seq_lens[:, None]                        # (B, 1)
    q = L.rope(q.reshape(b, s, hq, dh), positions, cfg.rope_theta, yarn)
    k = L.rope(k.reshape(b, s, hkv, dh), positions, cfg.rope_theta, yarn)
    v = v.reshape(b, s, hkv, dh)
    group = hq // hkv
    qg = q.reshape(b, s, hkv, group, dh)[:, 0]
    k1, v1 = k[:, 0], v[:, 0]                            # (B, Hkv, dh)

    if use_pallas:
        from repro.core.codegen_pallas import (lower_paged_decode,
                                               window_block)
        if window is not None:
            block = window_block(block or page_size, page_size, window)
        kern = lower_paged_decode(
            batch=b, kv_heads=hkv, group=group, head_dim=dh,
            page_size=page_size, n_pages_max=page_table.shape[1],
            layout=layout, block=block, depth=depth,
            dtype=pools[0].dtype, window=window)
        out, new_pools = kern(qg, k1, v1, pools, page_table, seq_lens,
                              layer)
    else:
        # only this layer writes its pages, so the step's input holds
        # them as the carry does; reading it leaves the carried stacks
        # unread, and a caller that drops the new cache (certification)
        # keeps no copy of them
        out, new = reference_attn(qg, k1, v1,
                                  tuple(t[layer] for t in found),
                                  page_table, seq_lens, layout, page_size,
                                  window)
        new_pools = tuple(t.at[layer].set(n) for t, n in zip(pools, new))
    out = out.reshape(b, s, hq * dh).astype(x.dtype)
    return jnp.einsum("bsq,qd->bsd", out, p["wo"]), tuple(new_pools)


def paged_decode_step(params: Params, cfg: ModelConfig,
                      cache: PagedKVCache, tokens: jax.Array, *,
                      use_pallas: bool = False, block=None,
                      depth: int = 2, with_stats: bool = False):
    """One decode step for every active request: tokens ``(B, 1)``,
    per-request positions from ``cache.seq_lens``.  Returns
    ``(logits, cache')`` with every request's length advanced by one.
    Dense/MoE attention families only (recurrent families have no KV
    cache to page).  Structured exactly like ``model.decode_step``
    (same layer scan over the same stacked params, the same
    super-block of windowed/full and dense/MoE layers) so the two
    paths stay comparable.  The pools of each attention kind ride in
    the scan's carry, stacked, and each layer reads and writes its own
    layer of them in place.  ``block`` and ``depth`` go to the fused
    kernel (``codegen_pallas.lower_paged_decode``); ``use_pallas`` also
    runs the MoE layers' grouped-matmul kernel.  ``with_stats`` adds a
    third output: ``(pairs, touched)``, per MoE layer the token-expert
    pairs computed here and the held experts touched (``None`` without
    MoE layers)."""
    if cfg.family not in ("dense", "moe"):
        raise NotImplementedError(
            f"paged decode supports dense/moe, not {cfg.family}")
    x = _embed_tokens(params, cfg, tokens)
    plan = layer_plan(cfg)
    n_super = cfg.n_layers // len(plan)
    lens = cache.seq_lens
    layout, ps = cache.layout, cache.page_size
    tables = {"full": cache.page_table, "window": cache.win_table}
    bufs = {"full": cache.buffers, "window": cache.win_buffers}
    kinds = kinds_in_plan(cfg)
    per_super = {kind: sum(k == kind for _, k in plan) for kind in kinds}

    def super_block(carry, slices):
        x, pools = carry
        pools = dict(pools)
        a_slc, d_slc, m_slc, s = slices
        seen = dict.fromkeys(kinds, 0)
        stats = []
        for i, (is_moe, kind) in enumerate(plan):
            sl = _layer_slice(plan, i, a_slc, d_slc, m_slc)
            layer = s * per_super[kind] + seen[kind]
            seen[kind] += 1
            a, pools[kind] = _paged_attn(
                sl, L.rms_norm(x, sl["ln1"]), cfg, pools[kind], bufs[kind],
                layer, tables[kind], lens, layout, ps, use_pallas, block,
                depth, kind)
            x = x + a
            h = L.rms_norm(x, sl["ln2"])
            if is_moe:
                sl.update(experts)
                moe_p = {k[4:]: v for k, v in sl.items()
                         if k.startswith("moe_")}
                y, st = moe_mod.moe_dropless(moe_p, h, cfg,
                                             use_pallas=use_pallas)
                x = x + y
                stats.append(st)
            else:
                x = x + _dense_ffn(sl, h, cfg)
        return (x, pools), tuple(jnp.stack(c) for c in zip(*stats))

    stacks = _super_stacks(params, cfg, plan, use_pallas)
    experts = expert_stacks(params, use_pallas)
    (x, pools), stats = L.scan_layers(
        super_block, (x, {kind: bufs[kind] for kind in kinds}),
        stacks + (jnp.arange(n_super, dtype=jnp.int32),), cfg.unroll)
    x = L.rms_norm(x, params["final_norm"])
    logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"])
    cache = cache.replace(buffers=pools.get("full"),
                          win_buffers=pools.get("window"), seq_lens=lens + 1)
    if not with_stats:
        return logits, cache
    return logits, cache, (tuple(t.reshape(-1) for t in stats)
                           if stats else None)
