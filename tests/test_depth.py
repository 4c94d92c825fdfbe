"""Metapipeline buffer depth as a searched DSE dimension (ISSUE 6).

Covers the acceptance surface: plan_memory charges ``depth x`` bytes
for stage-crossing buffers, over-deep candidates are pruned at the
VMEM cap, the chosen depth round-trips through the persistent tuning
cache and invalidates on a MODEL_VERSION bump, the pipeline DSE
enumerates and prices at least depths {2, 3, 4}, and a fused pipeline
forced to depth 4 matches the depth-2 megakernel numerically.
"""
import dataclasses
import itertools

import numpy as np
import pytest

from repro.core import dse, ir
from repro.core import pipeline as plmod
from repro.core.cost import (DMA_ISSUE_LATENCY_S, VMEM_BYTES, StageCost,
                             metapipeline_time)
from repro.core.memory import plan_memory
from repro.core.scheduling import build_schedule
from repro.core.strip_mine import tile


# ------------------------------------------------------ memory charging
def test_plan_memory_charges_depth_times_scratch():
    p = dse.gemm_program(512, 512, 512)
    plan = dse.explore(p, cache=False)
    t = tile(p, plan.sizes)
    plans = {d: plan_memory(t, depth=d) for d in (2, 3, 4)}
    base = {b.name: b for b in plans[2].buffers}
    for d in (3, 4):
        for b in plans[d].buffers:
            ref = base[b.name]
            if ref.kind == "double_buffer":
                assert b.depth == d
            else:  # hoisted preloads / caches stay single-copy
                assert b.depth == ref.depth
    # one copy's worth of every rotating buffer: each +1 of depth
    # charges exactly this many extra bytes
    per_copy = sum(b.bytes // b.depth for b in plans[2].buffers
                   if b.kind == "double_buffer")
    assert per_copy > 0
    for d in (3, 4):
        assert (plans[d].total_bytes
                == plans[2].total_bytes + (d - 2) * per_copy)


def test_plan_memory_rejects_shallow_depth():
    p = dse.gemm_program(256, 256, 256)
    t = tile(p, dse.explore(p, cache=False).sizes)
    with pytest.raises(ValueError, match="depth"):
        plan_memory(t, depth=1)
    with pytest.raises(ValueError, match="depth"):
        build_schedule(t, depth=0)


def test_schedule_carries_depth():
    p = dse.gemm_program(256, 256, 256)
    t = tile(p, dse.explore(p, cache=False).sizes)
    mp = build_schedule(t, depth=3)
    assert mp.depth == 3
    for s in mp.stages:
        if s.double_buffered:
            assert s.depth == 3
    for s in mp.preloads:
        assert s.depth == 1


# ------------------------------------------------------ cost model
def test_deeper_buffering_hides_dma_latency():
    """With tiny stages (step << latency) each extra copy hides one
    step's worth of latency; once (d-1)*step >= latency the term
    saturates and deeper buys nothing."""
    small = [StageCost("ld", "load", 1e-8), StageCost("b", "body", 1e-8)]
    pipes = [metapipeline_time(small, 100, depth=d)[1] for d in (2, 3, 4)]
    assert pipes[0] > pipes[1] > pipes[2]  # still latency-bound

    big_step = DMA_ISSUE_LATENCY_S * 2
    big = [StageCost("ld", "load", big_step),
           StageCost("b", "body", big_step)]
    p2, p3 = (metapipeline_time(big, 100, depth=d)[1] for d in (2, 3))
    assert p2 == p3  # saturated at depth 2: exposure already zero


def test_compute_only_schedule_has_no_exposure():
    costs = [StageCost("b", "body", 1e-8)]
    seq, pipe = metapipeline_time(costs, 10, depth=2)
    assert pipe <= seq


# ------------------------------------------------------ VMEM pruning
def test_deep_candidates_pruned_at_vmem_cap():
    """A budget sized so the best tile fits double- but not quadruple-
    buffered: depth-4 pricing of that tile must return None, and the
    explored plan must still fit."""
    p = dse.gemm_program(2048, 2048, 2048)
    plan = dse.explore(p, cache=False)
    mem2 = plan_memory(tile(p, plan.sizes), depth=2)
    mem4 = plan_memory(tile(p, plan.sizes), depth=4)
    budget = (mem2.total_bytes + mem4.total_bytes) // 2
    assert dse.price(p, plan.sizes, vmem_budget=budget, depth=2)
    assert dse.price(p, plan.sizes, vmem_budget=budget, depth=4) is None
    capped = dse.explore(p, vmem_budget=budget, cache=False)
    assert capped.vmem_bytes <= budget
    assert capped.pruned > 0


# ------------------------------------------------------ cache round-trip
def test_depth_round_trips_through_cache(tmp_path):
    path = str(tmp_path / "dse.json")
    p = dse.attention_program(512, 512, 64)
    plan1 = dse.explore(p, cache=path)
    assert not plan1.cached
    plan2 = dse.explore(p, cache=path)
    assert plan2.cached
    assert plan2.depths == plan1.depths
    assert plan2.depth == plan1.depth

    pipe = dse.filter_fold_pipeline(1 << 14)
    pp1 = dse.explore_pipeline(pipe, cache=path)
    pp2 = dse.explore_pipeline(pipe, cache=path)
    assert pp2.cached
    assert pp2.depths == pp1.depths


def test_cache_invalidates_on_model_version_bump(tmp_path, monkeypatch):
    path = str(tmp_path / "dse.json")
    p = dse.gemm_program(256, 256, 256)
    dse.explore(p, cache=path)
    monkeypatch.setattr(dse, "MODEL_VERSION", dse.MODEL_VERSION + 1)
    plan = dse.explore(p, cache=path)
    assert not plan.cached  # stale pricing must not replay


def test_cache_keys_on_depth_set(tmp_path):
    """A depth-restricted exploration must not be served the full-set
    entry (the key covers the resolved depth tuple)."""
    path = str(tmp_path / "dse.json")
    p = dse.gemm_program(256, 256, 256)
    dse.explore(p, cache=path)
    plan = dse.explore(p, cache=path, depths=(2,))
    assert not plan.cached
    assert plan.depth == 2


# ------------------------------------------------------ pipeline DSE
def test_pipeline_dse_enumerates_depths_234():
    """explore_pipeline prices every (block, depth) pair: the explored
    counter scales with the depth set and the chosen depth lands in
    PipelinePlan.depths."""
    pipe = dse.filter_fold_pipeline(1 << 14)
    base = dse.explore_pipeline(pipe, cache=False, depths=(2,))
    full = dse.explore_pipeline(pipe, cache=False, depths=(2, 3, 4))
    assert full.explored + full.pruned \
        >= 3 * (base.explored + base.pruned)
    assert len(full.depths) == len(full.groups)
    assert all(d in (2, 3, 4) for d in full.depths)


def test_streaming_pipeline_prefers_deeper_buffering():
    """A latency-bound streaming pipeline (tiny per-step tiles) models
    faster with deeper buffers, so the DSE picks a non-default depth."""
    pipe = dse.filter_fold_pipeline(1 << 14)
    full = dse.explore_pipeline(pipe, cache=False)
    shallow = dse.explore_pipeline(pipe, cache=False, depths=(2,))
    assert full.depths[0] > 2
    assert full.modeled_seconds < shallow.modeled_seconds


def test_single_pattern_ties_break_shallow():
    """When depth cannot improve the model (no latency left exposed),
    the rank key must settle on depth 2, not burn VMEM on deeper."""
    p = dse.gemm_program(512, 512, 512)
    plan = dse.explore(p, cache=False)
    pr2 = dse.price(p, plan.sizes, depth=2)
    prb = dse.price(p, plan.sizes, depth=plan.depth)
    if pr2 is not None and prb.modeled_seconds == pr2.modeled_seconds:
        assert plan.depth == 2


# ------------------------------------------------------ numerics
def test_forced_depth4_pipeline_matches_depth2():
    from repro.core.codegen_pallas import lower_fused_pipeline
    from repro.core.measure import synth_inputs

    pipe = dse.filter_fold_pipeline(1 << 12)
    plan = dse.explore_pipeline(pipe, cache=False)
    inputs = synth_inputs(plmod.external_inputs(pipe), seed=0)
    outs = {}
    for d in (2, 4):
        variant = dataclasses.replace(plan,
                                      depths=(d,) * len(plan.groups))
        call = lower_fused_pipeline(pipe, plan=variant)
        assert dict(call.group_lowerings)[
            plmod.output_names(pipe)[-1]] == "megakernel"
        outs[d] = np.asarray(call(**inputs))
    np.testing.assert_allclose(outs[4], outs[2], rtol=1e-6, atol=1e-6)

    ref = np.asarray(plmod.run_unfused(pipe, inputs))
    np.testing.assert_allclose(outs[4], ref, rtol=1e-5, atol=1e-5)


def test_plan_json_round_trip_keeps_depths():
    p = dse.attention_program(256, 256, 64)
    plan = dse.explore(p, cache=False)
    back = dse.TilePlan.from_json(plan.to_json())
    assert back.depths == plan.depths

    pipe = dse.filter_fold_pipeline(1 << 12)
    pp = dse.explore_pipeline(pipe, cache=False)
    ppb = dse.PipelinePlan.from_json(pp.to_json())
    assert ppb.depths == pp.depths
