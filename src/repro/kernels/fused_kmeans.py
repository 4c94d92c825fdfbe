"""Fused k-means step megakernel (the paper's Fig. 4/5 DAG, one kernel).

The assign -> {scatter-sum, count} DAG lowered as ONE ``pallas_call``
with TWO outputs: the assign stage computes each point tile's nearest
centroid into a VMEM scratch buffer (the fan-out intermediate -- it
never touches HBM and is computed once per grid step however many
consumers it has), and both terminal accumulators consume that scratch
in place: the per-cluster coordinate sums and the per-cluster counts,
each a revisited CAM-template block.  The points tile is DMA'd once per
grid step and read by both the assign stage and the sum scatter; the
centroids are loop-invariant (the Pipe-0 preload, constant index map).

This is the hand-written shape that ``core.pipeline.lower_pipeline``
generates for ``patterns.analytics.kmeans_pipeline``; keeping it as an
explicit kernel (like ``kernels.fused_filter_fold`` for the chain case)
pins down the multi-output megakernel template in plain Pallas.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import backend


def _auto_blocks(n: int, k: int, d: int,
                 measure: Optional[str] = None, policy=None,
                 options=None) -> int:
    from .ops import resolve_plan  # shared memoized selector front door
    bn, _ = resolve_plan("fused_kmeans", n, k, d, measure=measure,
                         policy=policy, options=options)
    return bn


def _km_kernel(pts_ref, cents_ref, sums_ref, counts_ref, assign_ref):
    g = pl.program_id(0)

    @pl.when(g == 0)
    def _init():
        sums_ref[...] = jnp.zeros_like(sums_ref)
        counts_ref[...] = jnp.zeros_like(counts_ref)

    # stage (fan-out intermediate): nearest centroid per point -> VMEM
    pts = pts_ref[...]                       # (b, d)
    cents = cents_ref[...]                   # (k, d) preload
    d2 = ((pts[:, None, :] - cents[None, :, :]) ** 2).sum(-1)  # (b, k)
    assign_ref[...] = jnp.argmin(d2, axis=1).astype(jnp.int32)

    # both terminals consume the SAME scratch (ref-counted fan-out)
    onehot = jax.nn.one_hot(assign_ref[...], cents.shape[0],
                            dtype=sums_ref.dtype)               # (b, k)
    sums_ref[...] += jnp.dot(onehot.T, pts,
                             preferred_element_type=sums_ref.dtype)
    counts_ref[...] += onehot.sum(0)[:, None]


def fused_kmeans_step(points: jax.Array, centroids: jax.Array, *,
                      block_n: int = 128, auto_tile: bool = False,
                      measure: Optional[str] = None, policy=None,
                      options=None) -> Tuple[jax.Array, jax.Array]:
    """One k-means update step as a single two-output megakernel:
    returns ``(sums, counts)`` with ``sums[k] = sum of points assigned
    to centroid k`` and ``counts[k]`` their number.  ``auto_tile=True``
    picks ``block_n`` by joint DSE on the assign -> {sum, count} DAG
    (``core.dse.select_fused_kmeans_blocks`` -- one plan for the whole
    DAG, cached on its topological signature); ``policy`` (a
    ``core.resilience.Policy``) bounds any measured exploration with
    deadlines, quarantine and plan certification."""
    n, d = points.shape
    k, d2 = centroids.shape
    assert d == d2, (points.shape, centroids.shape)
    if auto_tile:
        block_n = _auto_blocks(n, k, d, measure, policy, options)
    block_n = min(block_n, n)
    assert n % block_n == 0
    sums, counts = pl.pallas_call(
        _km_kernel,
        grid=(n // block_n,),
        in_specs=[
            pl.BlockSpec((block_n, d), lambda i: (i, 0)),
            pl.BlockSpec((k, d), lambda i: (0, 0)),   # Pipe-0 preload
        ],
        out_specs=[
            pl.BlockSpec((k, d), lambda i: (0, 0)),   # revisited
            pl.BlockSpec((k, 1), lambda i: (0, 0)),   # revisited
        ],
        out_shape=[
            jax.ShapeDtypeStruct((k, d), jnp.float32),
            jax.ShapeDtypeStruct((k, 1), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_n,), jnp.int32)],
        interpret=backend.interpret(), name="fused_kmeans",
    )(points, centroids)
    return sums, counts[:, 0]
