"""Mamba-2 SSD chunked scan in Pallas.

The SSD chunking of Mamba-2 (arXiv:2405.21060) is *exactly* the paper's
Table-1 MultiFold strip-mining rule applied to the state recurrence
(DESIGN.md §4): the sequence fold splits into an intra-chunk pattern
(dense matmuls on a tile -- MXU work) plus an inter-chunk combine (the
decayed state carry), with the chunk state forwarded between strided
iterations in VMEM scratch.

Grid: (batch, heads, n_chunks) with chunks innermost (sequential on TPU,
so the scratch state carry is well-defined).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import backend


def _auto_blocks(seq: int, n: int, dh: int,
                 measure: Optional[str] = None, policy=None,
                 options=None) -> int:
    from .ops import resolve_plan  # shared memoized selector front door
    chunk, _ = resolve_plan("scan", seq, n, dh, measure=measure,
                            policy=policy, options=options)
    return chunk


def _ssd_kernel(a_ref, x_ref, dt_ref, b_ref, c_ref, y_ref, h_ref, *,
                chunk: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    A = a_ref[0]                                  # scalar decay rate (<0)
    x = x_ref[0, :, 0, :].astype(jnp.float32)     # (L, dh)
    dt = dt_ref[0, :, 0].astype(jnp.float32)      # (L,)
    B = b_ref[0].astype(jnp.float32)              # (L, n)
    C = c_ref[0].astype(jnp.float32)              # (L, n)

    s = A * dt                                    # (L,)
    cum = jnp.cumsum(s)                           # (L,)
    # intra-chunk: M[t,u] = exp(cum_t - cum_u) * dt_u  for u <= t
    lmask = (jax.lax.iota(jnp.int32, chunk)[:, None]
             >= jax.lax.iota(jnp.int32, chunk)[None, :])
    M = jnp.where(lmask, jnp.exp(cum[:, None] - cum[None, :])
                  * dt[None, :], 0.0)             # (L, L)
    scores = jnp.dot(C, B.T, preferred_element_type=jnp.float32)  # (L, L)
    y_intra = jnp.dot(scores * M, x,
                      preferred_element_type=jnp.float32)         # (L, dh)
    # inter-chunk: contribution of the carried state
    h = h_ref[...]                                # (n, dh) fp32
    y_state = jnp.exp(cum)[:, None] * jnp.dot(
        C, h, preferred_element_type=jnp.float32)                 # (L, dh)
    y_ref[0, :, 0, :] = (y_intra + y_state).astype(y_ref.dtype)
    # state carry: h' = exp(cum_L) h + sum_u exp(cum_L - cum_u) dt_u B_u x_u
    w = jnp.exp(cum[-1] - cum) * dt               # (L,)
    h_ref[...] = (jnp.exp(cum[-1]) * h
                  + jnp.dot((B * w[:, None]).T, x,
                            preferred_element_type=jnp.float32))


def ssd_scan(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
             C: jax.Array, *, chunk: int = 128, auto_tile: bool = False,
             measure: Optional[str] = None, policy=None,
             options=None) -> jax.Array:
    """See ref.ssd_scan for semantics.  seq must divide ``chunk``.

    ``auto_tile=True`` picks the chunk length by DSE on the sequence-fold
    proxy (``repro.core.dse.scan_program``); ``policy`` (a
    ``core.resilience.Policy``) bounds any measured exploration."""
    bsz, seq, h, dh = x.shape
    n = B.shape[-1]
    if auto_tile:
        chunk = _auto_blocks(seq, n, dh, measure, policy, options)
    chunk = min(chunk, seq)
    assert seq % chunk == 0, (seq, chunk)
    nc = seq // chunk
    grid = (bsz, h, nc)

    return pl.pallas_call(
        functools.partial(_ssd_kernel, chunk=chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1,), lambda b, hh, c: (hh,)),                # A
            pl.BlockSpec((1, chunk, 1, dh),
                         lambda b, hh, c: (b, c, hh, 0)),              # x
            pl.BlockSpec((1, chunk, 1), lambda b, hh, c: (b, c, hh)),  # dt
            pl.BlockSpec((1, chunk, n), lambda b, hh, c: (b, c, 0)),   # B
            pl.BlockSpec((1, chunk, n), lambda b, hh, c: (b, c, 0)),   # C
        ],
        out_specs=pl.BlockSpec((1, chunk, 1, dh),
                               lambda b, hh, c: (b, c, hh, 0)),
        out_shape=jax.ShapeDtypeStruct((bsz, seq, h, dh), x.dtype),
        scratch_shapes=[pltpu.VMEM((n, dh), jnp.float32)],
        interpret=backend.interpret(), name="ssd_scan",
    )(A, x, dt, B, C)
