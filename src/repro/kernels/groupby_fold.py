"""GroupByFold kernel: dense keyed reduction via one-hot matmul.

The TPU-idiomatic replacement for the paper's CAM template (Table 4):
instead of an associative key match, keys become a one-hot routing
matrix pushed through the MXU, accumulated into a revisited output
block across the (sequential) grid.  Used by MoE routing (expert counts
and dispatch sums) and the k-means/histogram benchmarks.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import backend


def _auto_blocks(t: int, num_keys: int, ew: int,
                 measure: Optional[str] = None, policy=None,
                 options=None) -> int:
    from .ops import resolve_plan  # shared memoized selector front door
    bt, _ = resolve_plan("groupby", t, num_keys, ew, measure=measure,
                         policy=policy, options=options)
    return bt


def _gbf_kernel(k_ref, v_ref, o_ref, *, num_keys: int):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    keys = k_ref[...]                             # (bt,)
    vals = v_ref[...].astype(jnp.float32)         # (bt, ew)
    onehot = jax.nn.one_hot(keys, num_keys, dtype=jnp.float32)
    o_ref[...] += jnp.dot(onehot.T, vals,
                          preferred_element_type=jnp.float32
                          ).astype(o_ref.dtype)


def groupby_fold(keys: jax.Array, values: jax.Array, num_keys: int, *,
                 block_t: int = 256, auto_tile: bool = False,
                 measure: Optional[str] = None, policy=None,
                 options=None) -> jax.Array:
    """out[k] = sum over i with keys[i]==k of values[i].

    keys: (T,) int32; values: (T,) or (T, E) -> out (num_keys, E).
    ``auto_tile=True`` picks block_t by DSE on the keyed-fold proxy
    (``repro.core.dse.groupby_program``); ``measure="top_k"`` backs the
    choice with real timings (hybrid DSE); ``policy`` (a
    ``core.resilience.Policy``) bounds the measured exploration."""
    squeeze = values.ndim == 1
    if squeeze:
        values = values[:, None]
    t, ew = values.shape
    if auto_tile:
        block_t = _auto_blocks(t, num_keys, ew, measure, policy, options)
    block_t = min(block_t, t)
    assert t % block_t == 0
    out = pl.pallas_call(
        functools.partial(_gbf_kernel, num_keys=num_keys),
        grid=(t // block_t,),
        in_specs=[
            pl.BlockSpec((block_t,), lambda i: (i,)),
            pl.BlockSpec((block_t, ew), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((num_keys, ew), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((num_keys, ew), jnp.float32),
        interpret=backend.interpret(), name="groupby_fold",
    )(keys, values)
    return out[:, 0] if squeeze else out
