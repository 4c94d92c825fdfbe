"""Filter+reduce kernel (TPC-H Q6 shape): predicate mask, weighted sum.

The FlatMap(filter)+fold fusion of the paper lowered to TPU: the FPGA
streams records through a predicate FIFO into a reduction tree; here
each tile is masked on the VPU and reduced into a revisited scalar
accumulator block -- the dynamic-size FIFO disappears because the
reduction consumes values in place (the paper's vertical fusion).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import backend


def _auto_blocks(t: int, measure: Optional[str] = None,
                 policy=None, options=None) -> int:
    from .ops import resolve_plan  # shared memoized selector front door
    bt, _ = resolve_plan("filter_reduce", t, measure=measure,
                         policy=policy, options=options)
    return bt


def _fr_kernel(x_ref, w_ref, lo_ref, hi_ref, o_ref):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    lo = lo_ref[0]
    hi = hi_ref[0]
    pred = (x >= lo) & (x < hi)
    o_ref[0, 0] += jnp.sum(jnp.where(pred, x * w, 0.0))


def filter_reduce(x: jax.Array, weight: jax.Array, lo, hi, *,
                  block_t: int = 1024, auto_tile: bool = False,
                  measure: Optional[str] = None, policy=None,
                  options=None) -> jax.Array:
    """``auto_tile=True`` picks block_t by DSE on the fused filter+fold
    proxy (``repro.core.dse.filter_reduce_program``); ``measure="top_k"``
    backs the choice with real timings (hybrid DSE); ``policy`` (a
    ``core.resilience.Policy``) bounds the measured exploration;
    ``options`` (a ``core.dse.Options``) packs any exploration option."""
    (t,) = x.shape
    if auto_tile:
        block_t = _auto_blocks(t, measure, policy, options)
    block_t = min(block_t, t)
    assert t % block_t == 0
    lo = jnp.asarray([lo], jnp.float32)
    hi = jnp.asarray([hi], jnp.float32)
    out = pl.pallas_call(
        _fr_kernel,
        grid=(t // block_t,),
        in_specs=[
            pl.BlockSpec((block_t,), lambda i: (i,)),
            pl.BlockSpec((block_t,), lambda i: (i,)),
            pl.BlockSpec((1,), lambda i: (0,)),
            pl.BlockSpec((1,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((1, 1), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, 1), jnp.float32),
        interpret=backend.interpret(), name="filter_reduce",
    )(x, weight, lo, hi)
    return out[0, 0]
