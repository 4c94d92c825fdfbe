"""Pipeline fusion (core.pipeline + dse.explore_pipeline + the fused
megakernel): the ISSUE-2/ISSUE-3 acceptance surface.

Covers: fused IR structure (chains and fan-out DAGs), fused program ==
codegen_jax oracle == numpy reference for all PIPELINES (including the
multi-output kmeans / gda_moments DAGs and the Map-terminal normalize),
the modeled-traffic win, joint-plan caching (hit on second call,
invalidated on stage change, insensitive to declaration order), the
split fallback when VMEM is tight, and the block-alignment bugfix in
codegen_pallas._block_index_map.
"""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import dse, ir
from repro.core import pipeline as plmod
from repro.core.affine import AffineMap
from repro.core.codegen_jax import execute
from repro.core.codegen_pallas import (_block_index_map,
                                       lower_fused_pipeline)
from repro.patterns.analytics import PIPELINES

ALL = sorted(PIPELINES)


def _setup(name):
    """(pipe, inputs, ref) with ref normalized to {output: array}."""
    pipe, make_inputs, reference = PIPELINES[name]()
    inputs = {k: jnp.asarray(v) for k, v in make_inputs().items()}
    ref = reference(make_inputs())
    if not isinstance(ref, dict):
        ref = {plmod.output_names(pipe)[0]: np.asarray(ref)}
    return pipe, inputs, ref


def _check(pipe, got, ref):
    if not isinstance(got, dict):
        got = {plmod.output_names(pipe)[0]: got}
    assert set(got) >= set(ref)
    for k, want in ref.items():
        np.testing.assert_allclose(np.asarray(got[k]), want,
                                   rtol=2e-3, atol=2e-3)


# ------------------------------------------------------- fused IR shape
@pytest.mark.parametrize("name", ALL)
def test_fuse_structure(name):
    pipe, _, _ = _setup(name)
    fdag = plmod.fuse_dag(pipe, 128)
    producers = set(plmod.intermediate_names(pipe))
    stage_uids = {}
    for _, t in fdag.terminals:
        assert t.strided and len(t.domain) == 1
        for tc in t.loads:
            if isinstance(tc.src, ir.Pattern):
                stage_uids.setdefault(tc.name, set()).add(tc.uid)
        # intermediates are VMEM-resident: no main-memory tensor by
        # that name anywhere in the terminal tree
        assert not (producers & {x.name for x in ir.inputs_of(t)})
        # every external tensor read became a tile copy (no streaming)
        for q in ir.walk(t):
            for a in q.accesses:
                assert not isinstance(a.src, ir.Tensor)
    # one lifted stage per producer, and -- fan-out contract -- a
    # producer referenced from several terminal trees keeps ONE uid
    assert set(stage_uids) == {p + "_stage" for p in producers}
    assert all(len(uids) == 1 for uids in stage_uids.values())


@pytest.mark.parametrize("name", ALL)
def test_fused_ir_matches_oracle_and_reference(name):
    pipe, inputs, ref = _setup(name)
    _check(pipe, plmod.run_unfused(pipe, inputs), ref)
    fdag = plmod.fuse_dag(pipe, 128)
    for oname, t in fdag.terminals:
        np.testing.assert_allclose(np.asarray(execute(t, inputs)),
                                   ref[oname], rtol=2e-3, atol=2e-3)


# --------------------------------------------------- megakernel lowering
@pytest.mark.parametrize("name", ALL)
def test_megakernel_matches_oracle(name):
    pipe, inputs, ref = _setup(name)
    kern = lower_fused_pipeline(pipe, cache=False)
    assert kern.pipeline_plan.fused
    _check(pipe, kern(**inputs), ref)


def test_lower_pipeline_unfused_path():
    pipe, inputs, ref = _setup("tpchq6")
    run = plmod.lower_pipeline(pipe, fused=False)
    _check(pipe, run(**inputs), ref)


def test_lower_pipeline_unfused_multi_output():
    pipe, inputs, ref = _setup("kmeans")
    run = plmod.lower_pipeline(pipe, fused=False)
    _check(pipe, run(**inputs), ref)


# ------------------------------------------------------- traffic model
def test_fused_traffic_at_least_1p5x_lower_on_most():
    ratios = {}
    for name in ALL:
        pipe, _, _ = _setup(name)
        plan = dse.explore_pipeline(pipe, cache=False)
        assert plan.fused
        assert plan.traffic_words < plan.unfused_traffic_words, name
        ratios[name] = plan.traffic_ratio
    assert sum(r >= 1.5 for r in ratios.values()) >= len(ALL) - 1, ratios
    # and the intermediates really contribute zero on the fused path:
    # fused words == external reads + output write
    pipe, _, _ = _setup("tpchq6")
    plan = dse.explore_pipeline(pipe, cache=False)
    n = pipe.shared_extent
    assert plan.traffic_words == 3 * n + 1       # qty/price/disc + scalar
    assert plan.unfused_traffic_words == 5 * n + 1   # + write/read of mask
    # the standalone accounting helpers agree with the joint-DSE plan
    assert plmod.fused_traffic_words(pipe, plan.block) \
        == plan.traffic_words
    assert plmod.unfused_traffic_words(pipe) == plan.unfused_traffic_words


def test_fanout_producer_loaded_once_per_outer_step():
    """kmeans DAG acceptance: the fan-out producer's tiles come from
    VMEM (zero HBM reads for the intermediate), the points tile feeding
    assign AND scatter-sum is DMA'd exactly once per outer step, and
    the fused traffic is strictly below unfused."""
    pipe, _, _ = _setup("kmeans")
    n, block = pipe.shared_extent, 128
    fdag = plmod.fuse_dag(pipe, block)
    assert fdag.refcounts["km_assign"] == 2      # fan-out, ref-counted
    reads = plmod.dag_external_reads(fdag)
    assert "km_assign" not in reads              # never touches HBM
    d = 16
    assert reads["points"] == (n // block) * block * d   # once per step
    assert reads["centroids"] == 8 * d           # Pipe-0 preload, once
    assert plmod.fused_traffic_words(pipe, block) \
        < plmod.unfused_traffic_words(pipe)


def test_fanout_memory_plan_counts_scratch_once():
    """plan_memory over the whole terminal set charges the fan-out
    stage's double-buffered scratch once, with a port per reader."""
    pipe, _, _ = _setup("kmeans")
    mem = plmod.fused_memory_plan(pipe, 128)
    assert mem.fits
    stage = [b for b in mem.buffers if b.name.startswith("km_assign_stage")]
    assert len(stage) == 1
    assert stage[0].double_buffered
    assert stage[0].ports >= 3                   # 2 readers + writer
    # the shared points tile: one buffer despite two terminal trees
    pts = [b for b in mem.buffers if b.name.startswith("points_tile")]
    assert len(pts) == 1


def test_fused_vmem_plan_double_buffers_intermediate():
    pipe, _, _ = _setup("gda")
    mem = plmod.fused_memory_plan(pipe, 128)
    assert mem.fits
    stage = [b for b in mem.buffers if b.name.startswith("gda_feat_stage")]
    assert stage and all(b.double_buffered for b in stage)


def test_schedule_has_stage_and_preload():
    pipe, _, _ = _setup("kmeans")
    mp = plmod.schedule(pipe, 128)
    kinds = [s.kind for s in mp.stages]
    assert "compute" in kinds and "body" in kinds
    assert all(s.double_buffered for s in mp.stages
               if s.kind in ("load", "compute", "body"))
    # centroids are loop-invariant: Pipe-0 preload, single-buffered
    assert any("centroids" in s.name for s in mp.preloads)


# ------------------------------------------------------- joint-plan cache
def test_pipeline_plan_cached_and_replayed(tmp_path):
    path = str(tmp_path / "dse.json")
    pipe, _, _ = _setup("tpchq6")
    plan1 = dse.explore_pipeline(pipe, cache=path)
    assert not plan1.cached
    plan2 = dse.explore_pipeline(pipe, cache=path)
    assert plan2.cached
    assert plan2.block == plan1.block
    assert plan2.groups == plan1.groups
    assert plan2.group_blocks == plan1.group_blocks
    assert plan2.traffic_words == plan1.traffic_words


def test_pipeline_plan_invalidated_on_stage_change(tmp_path):
    from repro.patterns.analytics import tpchq6_pipeline
    path = str(tmp_path / "dse.json")
    pipe, _, _ = tpchq6_pipeline()
    dse.explore_pipeline(pipe, cache=path)
    smaller, _, _ = tpchq6_pipeline(n=2048)
    plan = dse.explore_pipeline(smaller, cache=path)
    assert not plan.cached  # any stage signature change -> new key


def test_pipeline_key_sensitive_to_each_stage():
    pipe, _, _ = _setup("gda")
    k0 = dse.pipeline_key(pipe)
    # change only the *producer* stage's external input (same shapes,
    # same wiring -- the stage signature alone must move the key)
    feat = pipe.stages[0]
    other = ir.Tensor("pts_alt", (pipe.shared_extent, 8))
    feat2 = ir.Map(domain=feat.domain, elem_shape=feat.elem_shape,
                   reads=(ir.Access(other, lambda i: (i, 0), (1, 8)),),
                   fn=feat.fn, name=feat.name)
    pipe2 = plmod.Pipeline(name=pipe.name,
                           stages=(feat2,) + pipe.stages[1:])
    assert dse.pipeline_key(pipe2) != k0


def test_pipeline_key_is_topological():
    """The DSE cache key hashes the DAG, not the declaration order:
    reordering independent stages yields the same key (and the same
    cached plan), while rewiring an edge changes it."""
    pipe, _, _ = _setup("kmeans")
    reordered = plmod.Pipeline(
        name=pipe.name,
        stages=(pipe.stages[0], pipe.stages[2], pipe.stages[1]))
    assert dse.pipeline_key(reordered) == dse.pipeline_key(pipe)
    assert plmod.output_names(reordered) == plmod.output_names(pipe)


# ------------------------------------------------------- split fallback
def test_split_fallback_when_vmem_tight():
    pipe, inputs, ref = _setup("gda")
    # 270 KB: the fully fused kernel (274 KB of lane-padded VMEM at the
    # smallest candidate) busts VMEM but each stage alone fits (262 KB,
    # 143 KB) -> cheapest-cut split
    plan = dse.explore_pipeline(pipe, vmem_budget=270_000, cache=False)
    assert not plan.fused
    assert plan.groups == ((0, 1), (1, 2))
    assert len(plan.group_blocks) == 2   # per-group block sizes
    # the split pays the intermediate round-trip the fused plan deletes
    full = dse.explore_pipeline(pipe, cache=False)
    assert plan.traffic_words > full.traffic_words
    kern = lower_fused_pipeline(pipe, plan=plan, vmem_budget=270_000)
    _check(pipe, kern(**inputs), ref)


def test_no_candidate_raises():
    pipe, _, _ = _setup("tpchq6")
    with pytest.raises(ValueError, match="no tile candidate fits"):
        dse.explore_pipeline(pipe, vmem_budget=64, cache=False)


def test_group_lowerings_report_what_ran():
    pipe, _, _ = _setup("tpchq6")
    kern = lower_fused_pipeline(pipe, cache=False)
    assert kern.group_lowerings == (("q6_sum", "megakernel"),)
    split = dse.explore_pipeline(_setup("gda")[0], vmem_budget=270_000,
                                 cache=False)
    kern2 = lower_fused_pipeline(_setup("gda")[0], plan=split,
                                 vmem_budget=270_000)
    assert len(kern2.group_lowerings) == 2
    # the bare-Map first group now lowers through the write-once
    # streaming template -- a megakernel, not a per-stage fallback
    assert all(how == "megakernel" for _, how in kern2.group_lowerings)


def test_megakernel_scalar_element_groupby():
    """GroupByFold terminal with elem_shape=() (a keyed count): the
    rank-1 (k,) accumulator must pad to a 2-D block like the fold
    template does."""
    n, k = 256, 8
    x = ir.Tensor("x", (n,))
    keymap = ir.Map(domain=(n,), reads=(ir.elem(x),),
                    fn=lambda s, e: jnp.floor(e * k), name="keys")
    hist = ir.GroupByFold(
        domain=(n,), num_keys=k, elem_shape=(),
        init=lambda: jnp.zeros((k,)),
        reads=(ir.elem(ir.Tensor("keys", (n,))),),
        fn=lambda s, ke: (ke.astype(jnp.int32), jnp.float32(1.0)),
        combine=lambda a, b: a + b, name="hist")
    pipe = plmod.Pipeline(name="hist", stages=(keymap, hist))
    rng = np.random.RandomState(3)
    xs = rng.rand(n).astype(np.float32) * 0.999
    ref = np.bincount((xs * k).astype(np.int32), minlength=k
                      ).astype(np.float32)
    kern = lower_fused_pipeline(pipe, cache=False)
    out = np.asarray(kern(x=jnp.asarray(xs)))
    assert out.shape == (k,)
    np.testing.assert_allclose(out, ref, rtol=1e-6)


# ------------------------------------------------------- validation
def test_pipeline_validation_basics():
    x = ir.Tensor("x", (64,))
    m = ir.Map(domain=(64,), reads=(ir.elem(x),),
               fn=lambda s, e: e, name="a")
    bad = ir.Map(domain=(32,), reads=(ir.elem(x),),
                 fn=lambda s, e: e, name="b")
    with pytest.raises(ValueError, match="shared"):
        plmod.Pipeline(name="p", stages=(m, bad))


def test_pipeline_stages_may_be_declared_out_of_order():
    """DAG semantics: declaration order is irrelevant; the consumer may
    precede its producer in ``stages`` (the old chain API raised)."""
    x = ir.Tensor("x", (64,))
    consumer = ir.Map(domain=(64,),
                      reads=(ir.elem(ir.Tensor("z", (64,))),),
                      fn=lambda s, e: e, name="a2")
    z = ir.Map(domain=(64,), reads=(ir.elem(x),),
               fn=lambda s, e: e, name="z")
    pipe = plmod.Pipeline(name="p", stages=(consumer, z))
    assert [s.name for s in plmod.topo_stages(pipe)] == ["z", "a2"]
    assert plmod.output_names(pipe) == ("a2",)


# ---------------------------------------------- kernels.fused_filter_fold
def test_fused_filter_fold_kernel(tmp_path, monkeypatch):
    from repro.kernels.fused_filter_fold import fused_filter_fold
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.rand(2048).astype(np.float32))
    w = jnp.asarray(rng.rand(2048).astype(np.float32))
    lo, hi = 0.1, 0.9
    ref = np.sum(np.where((np.asarray(x) >= lo) & (np.asarray(x) < hi),
                          np.asarray(x) * np.asarray(w), 0.0))
    out = fused_filter_fold(x, w, lo, hi, block_t=256)
    np.testing.assert_allclose(float(out), ref, rtol=1e-5)
    monkeypatch.setenv("REPRO_DSE_CACHE", str(tmp_path / "dse.json"))
    out = fused_filter_fold(x, w, lo, hi, auto_tile=True)
    np.testing.assert_allclose(float(out), ref, rtol=1e-5)


# ------------------------------------------------ kernels.fused_kmeans
def test_fused_kmeans_kernel(tmp_path, monkeypatch):
    from repro.kernels.fused_kmeans import fused_kmeans_step
    n, k, d = 256, 8, 16
    rng = np.random.RandomState(0)
    pts = jnp.asarray(rng.randn(n, d).astype(np.float32))
    cents = jnp.asarray(rng.randn(k, d).astype(np.float32))
    d2 = ((np.asarray(pts)[:, None] - np.asarray(cents)[None]) ** 2
          ).sum(-1)
    idx = d2.argmin(1)
    ref_s = np.zeros((k, d), np.float32)
    ref_c = np.zeros((k,), np.float32)
    for i in range(n):
        ref_s[idx[i]] += np.asarray(pts)[i]
        ref_c[idx[i]] += 1
    sums, counts = fused_kmeans_step(pts, cents, block_n=64)
    np.testing.assert_allclose(np.asarray(sums), ref_s,
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(counts), ref_c)
    monkeypatch.setenv("REPRO_DSE_CACHE", str(tmp_path / "dse.json"))
    sums, counts = fused_kmeans_step(pts, cents, auto_tile=True)
    np.testing.assert_allclose(np.asarray(sums), ref_s,
                               rtol=1e-4, atol=1e-4)


# -------------------------------------- _block_index_map alignment bugfix
def test_block_index_map_rejects_misaligned_base():
    # base 8 into 16-wide blocks: offset lands mid-block; previously the
    # dead `or base == 0` arm let nothing through *except* this -- the
    # check now raises instead of silently mis-addressing the DMA
    amap = AffineMap((8,), ((16,),), arity=1)
    with pytest.raises(ValueError, match="block-aligned"):
        _block_index_map(amap, (16,), 1)


def test_block_index_map_rejects_partial_stride():
    amap = AffineMap((0,), ((8,),), arity=1)  # stride 8, tile 16
    with pytest.raises(ValueError, match="partial blocks"):
        _block_index_map(amap, (16,), 1)


def test_block_index_map_accepts_aligned():
    amap = AffineMap((32,), ((16,),), arity=1)
    imap = _block_index_map(amap, (16,), 1)
    assert imap(3) == (5,)  # (32 + 3*16) // 16


# ------------------------------------------------------ fold templates
# the template each PIPELINE's terminals lower to, in terminal order,
# as the codegen.lower_fused_dag span's ``fold`` attribute reports it
FOLDS = {"tpchq6": "lanes", "gda": "cam", "kmeans": "cam,cam",
         "gda_moments": "cam,cam", "normalize": "map"}


def _lower_reporting_fold(fdag):
    """The fused kernel of ``fdag`` and its span's ``fold`` attribute."""
    from repro.core import telemetry
    from repro.core.codegen_pallas import lower_fused_dag

    telemetry.enable()
    kern = lower_fused_dag(fdag.terminals, fdag.grid)
    [span] = [s for s in telemetry.span_log()
              if s["name"] == "codegen.lower_fused_dag"]
    return kern, span["args"]["fold"]


@pytest.mark.parametrize("name", ALL)
def test_megakernel_reports_fold_template(name):
    """Only an additive scalar fold (tpchq6's q6_sum) takes the lanes
    template; keyed folds stay CAM and Map terminals stream, with the
    results they had."""
    pipe, inputs, ref = _setup(name)
    kern, fold = _lower_reporting_fold(plmod.fuse_dag(pipe, 128))
    assert fold == FOLDS[name]
    _check(pipe, kern(**inputs), ref)


def test_q6_lanes_fold_over_steps_and_chunks_matches_float64():
    """The lanes template across 16 grid steps of 8 chunks each: the
    per-lane partial carried through the chunk loop, flushed into the
    accumulator every step and reduced once at the last, gives Q6's
    answer within the benchmark's limit of the float64 reference."""
    from repro.core.codegen_pallas import _chunk_rows

    n, block = 2 ** 18, 2 ** 14
    pipe, _, _ = PIPELINES["tpchq6"](n)
    fdag = plmod.fuse_dag(pipe, block)
    assert fdag.grid == 16 and block // _chunk_rows(block, 2048) == 8
    kern, fold = _lower_reporting_fold(fdag)
    assert fold == "lanes"
    rng = np.random.RandomState(3)
    cols = {c: rng.rand(n).astype(np.float32)
            for c in ("qty", "price", "disc")}
    got = float(kern(**{c: jnp.asarray(v) for c, v in cols.items()}
                     )["q6_sum"])
    q = cols["qty"]
    keep = (q >= np.float32(0.05)) & (q < np.float32(0.95))
    want = float(np.sum(np.where(keep, cols["price"].astype(np.float64)
                                 * cols["disc"], 0.0)))
    assert abs(got - want) / abs(want) < 2.5e-4


def _square_folds(n, names):
    """``sq = x * x`` folded by the named terminals: ``total`` (a sum)
    and ``top`` (a max)."""
    x = ir.Tensor("x", (n,))
    sq = ir.Map(domain=(n,), reads=(ir.elem(x),),
                fn=lambda s, e: e * e, name="sq")

    def fold(name, init, combine):
        return ir.MultiFold(
            domain=(n,), range_shape=(), init=init,
            reads=(ir.elem(ir.Tensor("sq", (n,))),),
            out_index_map=lambda i: (), update_shape=(),
            fn=lambda s, acc, v: combine(acc, v), combine=combine,
            name=name)

    folds = {"total": fold("total", lambda: jnp.zeros(()),
                           lambda a, b: a + b),
             "top": fold("top", lambda: jnp.full((), -jnp.inf),
                         jnp.maximum)}
    return plmod.Pipeline(name="sqfolds",
                          stages=(sq,) + tuple(folds[k] for k in names))


def _column_sums(n, d=4):
    x = ir.Tensor("x", (n,))
    spread = ir.Map(domain=(n,), elem_shape=(d,), reads=(ir.elem(x),),
                    fn=lambda s, e: jnp.stack([e * (j + 1.0)
                                               for j in range(d)]),
                    name="spread")
    total = ir.MultiFold(
        domain=(n,), range_shape=(d,), init=lambda: jnp.zeros((d,)),
        reads=(ir.Access(ir.Tensor("spread", (n, d)), lambda i: (i, 0),
                         (1, d)),),
        out_index_map=lambda i: (0,), update_shape=(d,),
        fn=lambda s, acc, row: acc + row, combine=lambda a, b: a + b,
        name="colsum")
    return plmod.Pipeline(name="colsum", stages=(spread, total))


@pytest.mark.parametrize("names,fold", [
    (("top",), "tree"),                # a non-additive combine
    (("total", "top"), "tree,lanes"),  # both in one kernel: top, total
])
def test_fold_template_follows_combine_and_shape(names, fold):
    """A fold takes the lanes template only where its combine is
    addition and its update a scalar; a max fold keeps the tree, side
    by side with a lanes fold in one kernel, over several steps and
    chunks."""
    n = 2 ** 13
    kern, got_fold = _lower_reporting_fold(
        plmod.fuse_dag(_square_folds(n, names), 2048))
    assert got_fold == fold
    xs = np.random.RandomState(4).rand(n).astype(np.float32)
    out = kern(x=jnp.asarray(xs))
    sq = xs.astype(np.float64) ** 2
    want = {"total": sq.sum(), "top": sq.max()}
    for k in names:
        np.testing.assert_allclose(np.asarray(out[k]), want[k], rtol=1e-5)


def test_vector_fold_keeps_tree_template():
    """An additive fold whose update is a vector is not a lanes fold."""
    _, fold = _lower_reporting_fold(plmod.fuse_dag(_column_sums(2 ** 13),
                                                   2048))
    assert fold == "tree"
