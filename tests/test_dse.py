"""The pattern-generic DSE subsystem (repro.core.dse).

Covers the ISSUE-1 acceptance surface: argmin == exhaustive search,
over-VMEM candidates rejected, tuning-cache round-trip + shape
invalidation, and the GEMM front-end matching-or-beating the hardcoded
block choice under the cost model.
"""
import itertools
import json
import os

import numpy as np
import pytest

from repro.core import dse, ir
from repro.core.cost import VMEM_BYTES, traffic
from repro.core.strip_mine import tile


# ------------------------------------------------------- candidate space
def test_axis_candidates_aligned_divisors():
    assert dse.axis_candidates(512, 128) == [128, 256, 512]
    assert dse.axis_candidates(64, 128) == [64]      # align clamps
    assert dse.axis_candidates(1, 128) == [1]


def test_axis_candidates_admit_ragged_divisors():
    """Non-power-of-two divisors are candidates too (24/48 for a
    96-wide domain), but every candidate stays a multiple of the
    alignment floor -- a non-128-multiple lane tile is not expressible
    on the hardware."""
    assert dse.axis_candidates(96, 8) == [8, 16, 24, 32, 48, 96]
    assert dse.axis_candidates(192, 64) == [64, 192]
    assert dse.axis_candidates(384, 128) == [128, 384]  # 192 misaligns
    assert dse.axis_candidates(768, 128) == [128, 256, 384, 768]
    for extent in (96, 192, 360, 4096):
        for c in dse.axis_candidates(extent, 8):
            assert extent % c == 0          # strip mining requirement
            assert c == extent or c % 8 == 0  # align floor preserved


def test_axis_candidates_dtype_sublane_alignment():
    """bf16 wants 16-row and int8 32-row sublane multiples; candidates
    that misalign are dropped unless they are the whole extent."""
    assert dse.dtype_sublane("float32") == 8
    assert dse.dtype_sublane("bfloat16") == 16
    assert dse.dtype_sublane("int8") == 32
    assert dse.axis_candidates(96, 8, sublane=8) == [8, 16, 24, 32, 48,
                                                     96]
    assert dse.axis_candidates(96, 8, sublane=16) == [16, 32, 48, 96]
    assert dse.axis_candidates(96, 8, sublane=32) == [32, 96]
    # extent below the sublane: the whole extent stays available
    assert dse.axis_candidates(8, 8, sublane=32) == [8]


def test_tile_space_uses_pattern_dtype():
    import jax.numpy as jnp

    def prog(dtype):
        x = ir.Tensor("x", (96, 128), dtype)
        return ir.Map(domain=(96, 128), reads=(ir.elem(x),),
                      fn=lambda s, e: e, name="m", dtype=dtype)

    rows32 = sorted({c[0] for c in dse.tile_space(prog("float32"),
                                                  align=8)["m"]})
    rows16 = sorted({c[0] for c in dse.tile_space(prog("bfloat16"),
                                                  align=8)["m"]})
    assert 8 in rows32 and 24 in rows32
    assert rows16 == [16, 32, 48, 96]


def test_tile_space_covers_all_named_domains():
    p = dse.gemm_program(256, 256, 256)
    space = dse.tile_space(p)
    assert set(space) == {"gemm", "gemm_k"}
    assert (256, 256) in space["gemm"]
    assert (128,) in space["gemm_k"]


# ------------------------------------------------- argmin == brute force
def test_argmin_matches_exhaustive_search():
    """Brute force over the full (sizes x depth) cross product, with
    the same rank key the shortlist uses (shallowest depth wins
    modeled-seconds ties)."""
    p = dse.gemm_program(256, 256, 256)
    plan = dse.explore(p, cache=False)

    space = dse.tile_space(p)
    names = sorted(space)
    best_key, best_sizes, best_depth = None, None, None
    for combo in itertools.product(*(space[n] for n in names)):
        sizes = dict(zip(names, combo))
        for d in dse.DEPTHS:
            priced = dse.price(p, sizes, depth=d)
            if priced is None:
                continue
            key = (priced.traffic_words, priced.modeled_seconds, d,
                   -priced.vmem_bytes)
            if best_key is None or key < best_key:
                best_key, best_sizes, best_depth = key, sizes, d
    assert best_sizes is not None
    assert plan.sizes == {k: tuple(v) for k, v in best_sizes.items()}
    assert plan.traffic_words == best_key[0]
    assert plan.depth == best_depth


# ------------------------------------------------------- VMEM pruning
def test_over_vmem_candidates_rejected():
    budget = 512 * 1024   # the smallest 128-tile GEMM takes 384 KiB
    plan = dse.explore(dse.gemm_program(2048, 2048, 2048),
                       vmem_budget=budget, cache=False)
    assert plan.vmem_bytes <= budget
    assert plan.pruned > 0  # the big tiles really were rejected


def test_no_fitting_candidate_raises():
    with pytest.raises(ValueError, match="no tile candidate fits"):
        dse.explore(dse.gemm_program(256, 256, 256), vmem_budget=16,
                    cache=False)


def test_priced_plan_respects_memory_plan():
    """plan_memory on the plan's tiled IR (at the plan's chosen buffer
    depth) agrees with the plan."""
    p = dse.gemm_program(512, 512, 512)
    plan = dse.explore(p, cache=False)
    from repro.core.memory import plan_memory
    mem = plan_memory(tile(p, plan.sizes), vmem_budget_bytes=VMEM_BYTES,
                      depth=plan.depth)
    assert mem.fits
    assert mem.total_bytes == plan.vmem_bytes


# ------------------------------------------------------- tuning cache
def test_cache_round_trip(tmp_path):
    path = str(tmp_path / "dse.json")
    p = dse.gemm_program(256, 256, 256)
    plan1 = dse.explore(p, cache=path)
    assert not plan1.cached
    assert os.path.exists(path)
    plan2 = dse.explore(p, cache=path)
    assert plan2.cached
    assert plan2.sizes == plan1.sizes
    assert plan2.traffic_words == plan1.traffic_words


def test_cache_invalidates_on_shape_change(tmp_path):
    path = str(tmp_path / "dse.json")
    dse.explore(dse.gemm_program(256, 256, 256), cache=path)
    plan = dse.explore(dse.gemm_program(512, 256, 256), cache=path)
    assert not plan.cached  # different shape -> different key -> recompute
    with open(path) as f:
        assert len(json.load(f)) == 2


def test_cache_survives_corruption(tmp_path):
    path = str(tmp_path / "dse.json")
    with open(path, "w") as f:
        f.write("{not json")
    plan = dse.explore(dse.gemm_program(256, 256, 256), cache=path)
    assert not plan.cached
    assert plan.sizes  # recomputed despite the corrupt file


def test_cache_keys_on_resolved_space(tmp_path):
    """A caller-restricted space must not be served a cached plan from a
    full exploration (the key covers the resolved candidate space)."""
    path = str(tmp_path / "dse.json")
    p = dse.gemm_program(512, 512, 512)
    dse.explore(p, cache=path)  # full space: argmin is (512, 512, 512)
    restricted = {"gemm": [(128, 128)], "gemm_k": [(128,)]}
    plan = dse.explore(p, space=restricted, cache=path)
    assert plan.sizes == {"gemm": (128, 128), "gemm_k": (128,)}


def test_pattern_key_sensitive_to_access_windows():
    """Programs differing only in read windows must not share a key."""
    import jax.numpy as jnp

    def build(win):
        x = ir.Tensor("x", (64, 64))
        return ir.MultiFold(
            domain=(64,), range_shape=(), init=lambda: jnp.zeros(()),
            reads=(ir.Access(x, lambda i: (i, 0), win),),
            out_index_map=lambda i: (), update_shape=(),
            fn=lambda s, acc, e: acc, combine=lambda a, b: a + b,
            name="f")

    assert dse.pattern_key(build((1, 64))) != dse.pattern_key(build((2, 64)))


def test_thinning_is_recorded():
    p = dse.gemm_program(512, 512, 512)  # 27-point space
    plan = dse.explore(p, cache=False, max_points=8)
    assert plan.thinned
    full = dse.explore(p, cache=False)
    assert not full.thinned


def test_pattern_key_sensitive_to_budget_and_align():
    p = dse.gemm_program(256, 256, 256)
    k1 = dse.pattern_key(p)
    k2 = dse.pattern_key(p, vmem_budget=VMEM_BYTES // 2)
    k3 = dse.pattern_key(p, align=8)
    assert len({k1, k2, k3}) == 3


# --------------------------------------------- GEMM front-end acceptance
def test_gemm_plan_beats_or_matches_hardcoded():
    """DSE-selected GEMM tiles match or beat the previous hardcoded
    (128, 128, 128) choice under the cost model."""
    from repro.patterns.analytics import gemm
    m = n = k = 512
    plan = dse.explore(dse.gemm_program(m, n, k), cache=False)
    p, hand_sizes, _, _ = gemm(m, n, k, 128, 128, 128)
    hand_traffic = traffic(tile(p, hand_sizes)).total_reads
    assert plan.traffic_words <= hand_traffic
    assert plan.vmem_bytes <= VMEM_BYTES


# --------------------------------------------------- proxy programs
@pytest.mark.parametrize("build,names", [
    (lambda: dse.attention_program(256, 256, 64), {"fa_q", "fa_kv"}),
    (lambda: dse.scan_program(256, 16, 32), {"ssd"}),
    (lambda: dse.filter_reduce_program(2048), {"fr"}),
    (lambda: dse.groupby_program(512, 16, 4), {"gbf"}),
])
def test_proxy_programs_explore(build, names):
    plan = dse.explore(build(), cache=False)
    assert set(plan.sizes) == names
    assert plan.vmem_bytes <= VMEM_BYTES
    for name, sizes in plan.sizes.items():
        assert all(s >= 1 for s in sizes)


def test_selectors_divide_shapes():
    (bq, bk), _ = dse.select_attention_blocks(512, 256, 64, cache=False)
    assert 512 % bq == 0 and 256 % bk == 0
    chunk, _ = dse.select_scan_blocks(512, 16, 32, cache=False)
    assert 512 % chunk == 0
    bt, _ = dse.select_filter_reduce_blocks(4096, cache=False)
    assert 4096 % bt == 0
    bt, _ = dse.select_groupby_blocks(512, 16, 4, cache=False)
    assert 512 % bt == 0


# --------------------------------------------------- codegen integration
def test_lower_auto_gemm_end_to_end(tmp_path):
    from repro.core.codegen_pallas import lower_auto
    p = dse.gemm_program(256, 256, 256)
    kern = lower_auto(p, cache=str(tmp_path / "dse.json"))
    assert kern.tile_plan.sizes
    rng = np.random.RandomState(0)
    x = rng.randn(256, 256).astype(np.float32)
    y = rng.randn(256, 256).astype(np.float32)
    np.testing.assert_allclose(np.asarray(kern(x=x, y=y)), x @ y,
                               rtol=2e-3, atol=2e-3)
