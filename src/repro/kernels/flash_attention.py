"""Flash attention (GQA, causal, optional sliding window) in Pallas.

This kernel is the paper's method applied to attention (DESIGN.md §4):
strip-mine the softmax MultiFold over keys, interchange it with the
query Map, and keep a running (max, sum, acc) accumulator forwarded
between the strided iterations -- the paper's "accumulator forwarding"
metapipeline optimization *is* online softmax.

Grid: (batch*kv_head, q_group, q_blocks, kv_blocks), kv innermost so the
running statistics live in VMEM scratch across kv steps.  Sliding-window
(Mixtral SWA) and causal masks are applied from block coordinates.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import backend

NEG_INF = -1e30


def _auto_blocks(sq: int, sk: int, d: int,
                 measure: Optional[str] = None, policy=None,
                 options=None) -> tuple:
    from .ops import resolve_plan  # shared memoized selector front door
    blocks, _ = resolve_plan("attention", sq, sk, d, measure=measure,
                             policy=policy, options=options)
    return blocks


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
               scale: float, causal: bool, window: Optional[int],
               n_kv: int, block_q: int, block_k: int, q_offset: int):
    kv_i = pl.program_id(3)

    @pl.when(kv_i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0]                   # (block_q, d)
    k = k_ref[0, 0]                   # (block_k, d)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale

    qpos = (pl.program_id(2) * block_q + jax.lax.iota(jnp.int32, block_q)
            + q_offset)[:, None]
    kpos = (kv_i * block_k + jax.lax.iota(jnp.int32, block_k))[None, :]
    mask = jnp.ones(s.shape, jnp.bool_)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(-1))
    p = jnp.exp(s - m_new[:, None])
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + p.sum(-1)
    acc_ref[...] = (acc_ref[...] * alpha[:, None]
                    + jnp.dot(p.astype(v_ref.dtype), v_ref[0, 0],
                              preferred_element_type=jnp.float32))
    m_ref[...] = m_new

    @pl.when(kv_i == n_kv - 1)
    def _done():
        denom = jnp.where(l_ref[...] == 0.0, 1.0, l_ref[...])
        o_ref[0, 0] = (acc_ref[...] / denom[:, None]).astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: Optional[int] = None,
                    scale: Optional[float] = None,
                    block_q: int = 128, block_k: int = 128,
                    auto_tile: bool = False,
                    measure: Optional[str] = None, policy=None,
                    options=None) -> jax.Array:
    """q: (B, Hq, Sq, D); k, v: (B, Hkv, Sk, D) -> (B, Hq, Sq, D).

    GQA: the q-head group dim is folded into the grid so each kv head's
    K/V tiles are loaded once per group member (reuse via grid order).
    ``auto_tile=True`` picks (block_q, block_k) by DSE on the attention
    proxy program (``repro.core.dse.attention_program``); ``policy``
    (a ``core.resilience.Policy``) bounds any measured exploration.
    """
    b, hq, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    assert hq % hkv == 0
    group = hq // hkv
    scale = scale if scale is not None else d ** -0.5
    if auto_tile:
        block_q, block_k = _auto_blocks(sq, sk, d, measure, policy,
                                        options)
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    assert sq % block_q == 0 and sk % block_k == 0
    n_q, n_kv = sq // block_q, sk // block_k
    q_offset = sk - sq  # decode/prefix: queries sit at the sequence tail

    qg = q.reshape(b * hkv, group, sq, d)
    kg = k.reshape(b * hkv, 1, sk, d)
    vg = v.reshape(b * hkv, 1, sk, d)
    grid = (b * hkv, group, n_q, n_kv)

    out = pl.pallas_call(
        functools.partial(_fa_kernel, scale=scale, causal=causal,
                          window=window, n_kv=n_kv, block_q=block_q,
                          block_k=block_k, q_offset=q_offset),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda bh, g, qi, ki: (bh, g, qi, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bh, g, qi, ki: (bh, 0, ki, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bh, g, qi, ki: (bh, 0, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda bh, g, qi, ki: (bh, g, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hkv, group, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=backend.interpret(), name="flash_attention",
    )(qg, kg, vg)
    return out.reshape(b, hq, sq, d)
