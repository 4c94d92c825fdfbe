"""Property-based tests (hypothesis) on the system's invariants.

Invariants under test:
  * tiling is semantics-preserving for EVERY tile size that divides the
    domain, on every pattern type (the paper's core correctness claim);
  * tile-copy traffic never exceeds the untiled streaming traffic for
    sumrows/gemm-like programs (tiling only helps);
  * MultiFold parallel partials == sequential fold (combine/identity);
  * kernels match oracles across random shapes (per-kernel sweeps);
  * data pipeline shards partition the global stream for any world size.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core import ir
from repro.core.codegen_jax import execute
from repro.core.cost import traffic
from repro.core.strip_mine import tile
from repro.data.pipeline import TokenPipeline

SETTINGS = dict(max_examples=20, deadline=None)


def _divisors(n):
    return [d for d in range(1, n + 1) if n % d == 0]


@st.composite
def map_case(draw):
    d = draw(st.sampled_from([8, 12, 16, 24]))
    b = draw(st.sampled_from(_divisors(d)))
    seed = draw(st.integers(0, 2 ** 16))
    return d, b, seed


@given(map_case())
@settings(**SETTINGS)
def test_map_tiling_preserves_semantics(case):
    d, b, seed = case
    x = ir.Tensor("x", (d,))
    p = ir.Map(domain=(d,), reads=(ir.elem(x),),
               fn=lambda s, e: 3.0 * e + 1.0, name="m")
    t = tile(p, {"m": (b,)})
    xs = np.random.RandomState(seed).randn(d).astype(np.float32)
    # atol guards catastrophic cancellation near 3x+1 == 0
    np.testing.assert_allclose(execute(t, {"x": xs}), 3 * xs + 1,
                               rtol=1e-5, atol=1e-5)


@st.composite
def fold_case(draw):
    m = draw(st.sampled_from([4, 6, 8]))
    n = draw(st.sampled_from([4, 8, 12]))
    bm = draw(st.sampled_from(_divisors(m)))
    bn = draw(st.sampled_from(_divisors(n)))
    seed = draw(st.integers(0, 2 ** 16))
    return m, n, bm, bn, seed


@given(fold_case())
@settings(**SETTINGS)
def test_multifold_tiling_preserves_semantics(case):
    m, n, bm, bn, seed = case
    x = ir.Tensor("x", (m, n))
    p = ir.MultiFold(
        domain=(m, n), range_shape=(m,), init=lambda: jnp.zeros((m,)),
        reads=(ir.elem(x),), out_index_map=lambda i, j: (i,),
        update_shape=(1,), fn=lambda s, acc, e: acc + e,
        combine=lambda a, b: a + b, name="sr")
    t = tile(p, {"sr": (bm, bn)})
    xs = np.random.RandomState(seed).randn(m, n).astype(np.float32)
    np.testing.assert_allclose(execute(t, {"x": xs}), xs.sum(1),
                               rtol=1e-4)


@given(fold_case())
@settings(**SETTINGS)
def test_tiling_never_increases_traffic(case):
    m, n, bm, bn, seed = case
    x = ir.Tensor("x", (m, n))
    p = ir.MultiFold(
        domain=(m, n), range_shape=(m,), init=lambda: jnp.zeros((m,)),
        reads=(ir.elem(x),), out_index_map=lambda i, j: (i,),
        update_shape=(1,), fn=lambda s, acc, e: acc + e,
        combine=lambda a, b: a + b, name="sr")
    base = traffic(p).total_reads
    tiled = traffic(tile(p, {"sr": (bm, bn)})).total_reads
    assert tiled <= base


@given(st.sampled_from([1, 2, 3, 4, 6, 12]), st.integers(0, 2 ** 16))
@settings(**SETTINGS)
def test_parallel_partials_match_sequential(parts, seed):
    m, n = 12, 8
    x = ir.Tensor("x", (m, n))
    p = ir.MultiFold(
        domain=(m, n), range_shape=(m,), init=lambda: jnp.zeros((m,)),
        reads=(ir.elem(x),), out_index_map=lambda i, j: (i,),
        update_shape=(1,), fn=lambda s, acc, e: acc + e,
        combine=lambda a, b: a + b, name="sr")
    xs = np.random.RandomState(seed).randn(m, n).astype(np.float32)
    seq = execute(p, {"x": xs})
    par = execute(p, {"x": xs}, parallel_partials=parts)
    np.testing.assert_allclose(seq, par, rtol=1e-4)


@st.composite
def groupby_case(draw):
    d = draw(st.sampled_from([16, 32, 48]))
    b = draw(st.sampled_from([d_ for d_ in _divisors(d) if d_ > 1]))
    k = draw(st.sampled_from([2, 4, 8]))
    seed = draw(st.integers(0, 2 ** 16))
    return d, b, k, seed


@given(groupby_case())
@settings(**SETTINGS)
def test_groupbyfold_tiling_preserves_semantics(case):
    d, b, k, seed = case
    x = ir.Tensor("x", (d,))

    def fn(s, e):
        return jnp.clip(jnp.abs(e * 3).astype(jnp.int32), 0, k - 1), e

    p = ir.GroupByFold(domain=(d,), num_keys=k,
                       init=lambda: jnp.zeros(k), reads=(ir.elem(x),),
                       fn=fn, combine=lambda a, b: a + b, name="h")
    # multiples of 2**-6: every partial sum is exact in f32, so tiling
    # (which reorders the sums) must give the same bits; with raw randn
    # a near-cancelling key sum differs by rounding order alone
    xs = (np.round(np.random.RandomState(seed).randn(d) * 64) / 64
          ).astype(np.float32)
    np.testing.assert_allclose(
        execute(tile(p, {"h": (b,)}), {"x": xs}),
        execute(p, {"x": xs}), rtol=1e-5)


# --------------------------------------------------------- kernel sweeps
@st.composite
def matmul_shape(draw):
    m = draw(st.sampled_from([16, 32, 64]))
    k = draw(st.sampled_from([16, 32, 64]))
    n = draw(st.sampled_from([16, 32, 64]))
    bm = draw(st.sampled_from(_divisors(m)[-2:]))
    bk = draw(st.sampled_from(_divisors(k)[-2:]))
    bn = draw(st.sampled_from(_divisors(n)[-2:]))
    return m, k, n, bm, bk, bn


@given(matmul_shape())
@settings(max_examples=10, deadline=None)
def test_matmul_kernel_property(shape):
    from repro.kernels import ref
    from repro.kernels.matmul import matmul
    m, k, n, bm, bk, bn = shape
    x = jax.random.normal(jax.random.PRNGKey(m * k), (m, k))
    y = jax.random.normal(jax.random.PRNGKey(k * n + 1), (k, n))
    out = matmul(x, y, block_m=bm, block_k=bk, block_n=bn)
    np.testing.assert_allclose(out, ref.matmul(x, y), rtol=2e-4,
                               atol=2e-4)


@given(st.sampled_from([16, 32, 64]), st.sampled_from([1, 2, 4]),
       st.integers(0, 100))
@settings(max_examples=10, deadline=None)
def test_flash_attention_property(s, group, seed):
    from repro.kernels import ref
    from repro.kernels.flash_attention import flash_attention
    hkv, d = 2, 16
    q = jax.random.normal(jax.random.PRNGKey(seed), (1, hkv * group, s, d))
    k = jax.random.normal(jax.random.PRNGKey(seed + 1), (1, hkv, s, d))
    v = jax.random.normal(jax.random.PRNGKey(seed + 2), (1, hkv, s, d))
    out = flash_attention(q, k, v, block_q=min(16, s), block_k=min(16, s))
    np.testing.assert_allclose(out, ref.attention(q, k, v), rtol=2e-4,
                               atol=2e-4)


# ------------------------------------------------------------- pipeline
@given(st.sampled_from([1, 2, 4, 8]), st.integers(0, 2 ** 10),
       st.integers(0, 5))
@settings(**SETTINGS)
def test_pipeline_sharding_partition_property(world, seed, step):
    p = TokenPipeline(vocab=97, global_batch=8, seq_len=12, seed=seed)
    full = p.batch_slice(step, 0, 8)["tokens"]
    per = 8 // world
    parts = [p.batch_slice(step, r * per, (r + 1) * per)["tokens"]
             for r in range(world)]
    np.testing.assert_array_equal(np.concatenate(parts, 0), full)
