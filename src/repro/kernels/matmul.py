"""Tiled GEMM Pallas kernel -- the paper's Table 3 worked example.

The block structure is exactly the interchanged tiled form the PPL
transformation derives: grid (m/bm, n/bn, p/bk) with the reduction dim
innermost, operand tiles as BlockSpecs (= the xTile/yTile copies), and
an fp32 VMEM accumulator revisited across the reduction grid dim (= the
accumulator-dedup'd MultiFold).  Pallas's grid pipeliner double-buffers
the operand tiles between grid steps -- the metapipeline.

Tile sizes default to MXU-aligned (128); pass ``auto_tile=True`` to let
the PPL cost model pick them via design space exploration
(``repro.core.dse``, cached on disk per (signature, shapes, dtype)).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import backend


def _auto_blocks(m: int, n: int, k: int,
                 measure: Optional[str] = None, policy=None,
                 options=None) -> Tuple[int, int, int]:
    from .ops import resolve_plan  # shared memoized selector front door
    blocks, _ = resolve_plan("gemm", m, n, k, measure=measure,
                             policy=policy, options=options)
    return blocks


def _matmul_kernel(x_ref, y_ref, o_ref, acc_ref, *, n_k: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], y_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == n_k - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def matmul(x: jax.Array, y: jax.Array, *,
           block_m: int = 128, block_n: int = 128, block_k: int = 128,
           out_dtype: Optional[jnp.dtype] = None,
           auto_tile: bool = False,
           measure: Optional[str] = None, policy=None,
           options=None) -> jax.Array:
    """``x @ y`` with explicit VMEM tiling. Shapes must divide blocks.

    ``auto_tile=True`` replaces the block arguments with the DSE-selected
    tile plan for this (m, n, k); ``measure="top_k"`` additionally backs
    the plan with real timings (hybrid DSE, ``core.measure``);
    ``policy`` (a ``core.resilience.Policy``) bounds that measured
    exploration with deadlines, quarantine and plan certification;
    ``options`` (a ``core.dse.Options``) packs any exploration option,
    including ``bucketing=True`` warm starts.
    """
    m, k = x.shape
    k2, n = y.shape
    assert k == k2, (x.shape, y.shape)
    if auto_tile:
        block_m, block_n, block_k = _auto_blocks(m, n, k, measure,
                                                 policy, options)
    block_m = min(block_m, m)
    block_n = min(block_n, n)
    block_k = min(block_k, k)
    assert m % block_m == 0 and n % block_n == 0 and k % block_k == 0, (
        (m, n, k), (block_m, block_n, block_k))
    out_dtype = out_dtype or x.dtype
    n_k = k // block_k
    grid = (m // block_m, n // block_n, n_k)

    return pl.pallas_call(
        functools.partial(_matmul_kernel, n_k=n_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((block_k, block_n), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        interpret=backend.interpret(), name="matmul",
    )(x, y)
