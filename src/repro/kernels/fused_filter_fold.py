"""Fused filter->fold megakernel (TPC-H Q6 pipeline, paper Fig. 5b/6).

The two-stage pipeline lowered as ONE ``pallas_call``: the filter stage
masks and weights each record tile into a VMEM scratch buffer (the
pipeline intermediate -- it never touches HBM), and the fold stage
reduces that scratch in place into a revisited scalar accumulator
block.  Compare ``kernels.filter_reduce``, which hand-fuses the
predicate into the reduction: this kernel keeps the two stages distinct
(separate compute, explicit VMEM intermediate), which is exactly the
shape ``core.pipeline`` generates for arbitrary pattern chains.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import backend


def _auto_blocks(t: int, measure: Optional[str] = None,
                 policy=None, options=None) -> int:
    from .ops import resolve_plan  # shared memoized selector front door
    bt, _ = resolve_plan("fused_filter_fold", t, measure=measure,
                         policy=policy, options=options)
    return bt


def _ff_kernel(x_ref, w_ref, lo_ref, hi_ref, o_ref, mask_ref):
    g = pl.program_id(0)

    @pl.when(g == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    # stage 1 (filter): per-record contribution -> VMEM scratch
    x = x_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    pred = (x >= lo_ref[0]) & (x < hi_ref[0])
    mask_ref[...] = jnp.where(pred, x * w, 0.0)
    # stage 2 (fold): consume the scratch in place
    o_ref[0, 0] += jnp.sum(mask_ref[...])


def fused_filter_fold(x: jax.Array, weight: jax.Array, lo, hi, *,
                      block_t: int = 1024, auto_tile: bool = False,
                      measure: Optional[str] = None,
                      policy=None, options=None) -> jax.Array:
    """``sum(where(lo <= x < hi, x * weight, 0))`` as a fused two-stage
    megakernel.  ``auto_tile=True`` picks ``block_t`` by *joint* DSE on
    the filter+fold pipeline (``core.dse.select_fused_filter_fold_blocks``
    -- one plan for the whole chain, cached on the pipeline signature);
    ``measure="top_k"`` backs it with real timings (hybrid DSE), and
    ``policy`` (a ``core.resilience.Policy``) bounds that measured
    exploration with deadlines, quarantine and plan certification.
    """
    (t,) = x.shape
    if auto_tile:
        block_t = _auto_blocks(t, measure, policy, options)
    block_t = min(block_t, t)
    assert t % block_t == 0
    lo = jnp.asarray([lo], jnp.float32)
    hi = jnp.asarray([hi], jnp.float32)
    out = pl.pallas_call(
        _ff_kernel,
        grid=(t // block_t,),
        in_specs=[
            pl.BlockSpec((block_t,), lambda i: (i,)),
            pl.BlockSpec((block_t,), lambda i: (i,)),
            pl.BlockSpec((1,), lambda i: (0,)),
            pl.BlockSpec((1,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((1, 1), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, 1), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_t,), jnp.float32)],
        interpret=backend.interpret(), name="fused_filter_fold",
    )(x, weight, lo, hi)
    return out[0, 0]
