"""Pallas backend vs jnp oracle for the tiled canonical forms."""
import numpy as np

from repro.core.codegen_jax import execute
from repro.core.codegen_pallas import lower
from repro.core.strip_mine import tile
from repro.core.scheduling import build_schedule
from repro.core.memory import plan_memory

import sys, os
sys.path.insert(0, os.path.dirname(__file__))
from test_core_transforms import mk_filter, mk_gemm, mk_hist, mk_map_2x, _rng


def test_pallas_tiled_map():
    p = tile(mk_map_2x(64), {"m": (16,)})
    x = _rng(64)
    out = lower(p)(x=x)
    np.testing.assert_allclose(out, 2 * x, rtol=1e-6)


def test_pallas_tiled_gemm():
    g = mk_gemm(16, 24, 32)
    t = tile(g, {"gemm": (8, 12), "kfold": (16,)})
    x, y = _rng(16, 32), _rng(32, 24)
    out = lower(t)(x=x, y=y)
    np.testing.assert_allclose(out, x @ y, rtol=1e-4, atol=1e-4)


def test_pallas_tiled_groupby():
    p = tile(mk_hist(64, 8), {"h": (16,)})
    x = np.abs(_rng(64)) * 4
    out = lower(p)(x=x)
    ref = execute(p, {"x": x})
    np.testing.assert_allclose(out, ref, rtol=1e-6)


def test_pallas_tiled_flatmap():
    p = tile(mk_filter(64), {"f": (16,)})
    x = _rng(64)
    buf, cnt = lower(p)(x=x)
    ref = x[x > 0]
    assert int(cnt) == len(ref)
    np.testing.assert_allclose(np.asarray(buf)[:len(ref)], ref, rtol=1e-6)


def test_schedule_and_memory_kmeans():
    from test_core_transforms import mk_kmeans
    scatter, *_ = mk_kmeans(24, 6, 5)
    t = tile(scatter, {"scatter": (8,), "assign": (3,)})
    mp = build_schedule(t)
    assert mp is not None
    kinds = [s.kind for s in mp.stages]
    # load points tile, compute assignment stage, scatter body, store
    assert "load" in kinds and "compute" in kinds and "body" in kinds
    # all cross-stage buffers double buffered
    assert all(s.double_buffered for s in mp.stages
               if s.kind in ("load", "compute", "body"))
    plan = plan_memory(t)
    assert plan.fits
    kinds = {b.kind for b in plan.buffers}
    assert "double_buffer" in kinds and "cam_dense" in kinds


# ------------------------------------------------ vector tile templates
def test_tile_view_slices_rows_and_rejects_strided_reads():
    import jax.numpy as jnp
    import pytest

    from repro.core import ir
    from repro.core.affine import AffineMap
    from repro.core.codegen_pallas import _tile_view

    tile = jnp.arange(32.0).reshape(8, 4)
    src = ir.Tensor("x", (8, 4))
    row = ir.Access(src, AffineMap((0, 0), ((0, 1), (0, 0)), arity=2),
                    (1, 4))
    view, dims = _tile_view(tile, row, 1, (4,), row0=2)
    np.testing.assert_array_equal(view, tile[2:6])
    assert dims == (0,)
    shared = ir.Access(src, AffineMap((0, 0), ((0, 0), (0, 0)), arity=2),
                       (8, 4))
    view, dims = _tile_view(tile, shared, 1, (4,))
    np.testing.assert_array_equal(view, tile)
    assert dims == ()
    strided = ir.Access(src, AffineMap((0, 0), ((0, 2), (0, 0)),
                                       arity=2), (1, 4))
    with pytest.raises(NotImplementedError, match="steps"):
        _tile_view(tile, strided, 1, (4,))
    moving = ir.Access(src, AffineMap((0, 0), ((4, 1), (0, 0)),
                                      arity=2), (1, 4))
    with pytest.raises(NotImplementedError, match="grid index"):
        _tile_view(tile, moving, 1, (4,))


def test_chunk_rows_bounds_the_vector_pass():
    from repro.core.codegen_pallas import _chunk_rows

    assert _chunk_rows(512) == 512
    assert _chunk_rows(131072) == 1024
    assert _chunk_rows(1536) == 512
    assert _chunk_rows(1000 * 3) == 3000     # no lane-aligned part
    assert _chunk_rows(131072, 2048) == 2048


def test_fused_fold_with_non_additive_combine():
    """A max-fold terminal folds each chunk by a halving tree of the
    combine (no vector sum applies) and still matches the oracle."""
    import jax.numpy as jnp

    from repro.core import ir
    from repro.core import pipeline as plmod
    from repro.core.codegen_pallas import lower_fused_dag

    n = 4096
    x = ir.Tensor("x", (n,))
    sq = ir.Map(domain=(n,), reads=(ir.elem(x),),
                fn=lambda s, e: e * e - e, name="sq")
    top = ir.MultiFold(
        domain=(n,), range_shape=(), init=lambda: jnp.full((), -jnp.inf),
        reads=(ir.elem(ir.Tensor("sq", (n,))),),
        out_index_map=lambda i: (), update_shape=(),
        fn=lambda s, acc, v: jnp.maximum(acc, v),
        combine=jnp.maximum, name="top")
    pipe = plmod.Pipeline(name="maxsq", stages=(sq, top))
    xs = _rng(n)
    fdag = plmod.fuse_dag(pipe, 2048)
    out = lower_fused_dag(fdag.terminals, fdag.grid)(x=xs)["top"]
    np.testing.assert_allclose(out, np.max(xs * xs - xs), rtol=1e-6)
