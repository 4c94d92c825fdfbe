"""Tile-size DSE (the paper's future work, implemented)."""
import jax
import numpy as np

from repro.kernels import ref
from repro.kernels.autotile import select_gemm_tiles, tuned_matmul


def test_selection_prefers_reuse():
    """Bigger tiles (within VMEM) => less HBM traffic; the chosen tiles
    must beat the smallest-candidate traffic."""
    from repro.core.cost import traffic
    from repro.core.strip_mine import tile
    from repro.patterns.analytics import gemm
    m = n = k = 512
    best = select_gemm_tiles(m, n, k)
    p, sizes, _, _ = gemm(m, n, k, 128, 128, 128)
    base = traffic(tile(p, sizes)).total_reads
    assert best.traffic_words <= base
    assert best.vmem_bytes <= 16 * 2 ** 20


def test_selection_respects_vmem_budget():
    c = select_gemm_tiles(2048, 2048, 2048, vmem_budget=512 * 1024)
    assert c.vmem_bytes <= 512 * 1024


def test_tuned_matmul_correct():
    x = jax.random.normal(jax.random.PRNGKey(0), (256, 128))
    y = jax.random.normal(jax.random.PRNGKey(1), (128, 256))
    out = tuned_matmul(x, y)
    np.testing.assert_allclose(out, ref.matmul(x, y), rtol=2e-4, atol=2e-4)
