"""Paged serving decode on the pattern substrate (ISSUE 9).

The load-bearing claim: ``paged_decode_step`` -- page-scattered KV,
per-request ragged lengths, both KV layouts, reference and fused
Pallas paths -- is *token-identical* to the ``model.decode_step``
oracle, across mixed lengths and page-boundary crossings.  Plus the
regression tests for the mesh axis types, the ``resolve_plan``
unhashable-key memo and the dry-run ``cost_analysis`` normalization,
and the DSE provenance of the joint layout x page_size x block axes.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import ir
from repro.core.pipeline import Pipeline, ragged_extent
from repro.kernels import ops
from repro.models import model, paged

ARCH = "granite-3-2b"
LENS = (3, 5, 9)      # crosses page boundaries at 4 and 8 (ps=4)
PS = 4
GEN = 5


def _greedy(logits, cfg):
    logits = model.mask_vocab_pad(logits, cfg)
    return jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)


def _decode_tokens_oracle(cfg, params, prompt, gen, cmax):
    """Greedy tokens from ``model.decode_step`` with a dense no-wrap
    cache of the page-padded extent (== the paged gather extent, so
    the comparison is bit-exact, not tolerance-based)."""
    cache = model.init_cache(cfg, 1, cmax)
    out, nxt = [], None
    ln = prompt.shape[1]
    for i in range(ln + gen):
        tok = (prompt[:, i:i + 1] if i < ln
               else np.asarray(nxt).reshape(1, 1))
        logits, cache = model.decode_step(params, cfg, cache,
                                          jnp.asarray(tok, jnp.int32),
                                          jnp.int32(i))
        nxt = _greedy(logits, cfg)
        if i >= ln:
            out.append(int(np.asarray(nxt)[0]))
    return out


def _decode_tokens_paged(cfg, params, prompt, gen, cmax, layout,
                         use_pallas):
    cache = paged.PagedKVCache.init(cfg, 1, cmax, page_size=PS,
                                    layout=layout)

    @jax.jit
    def step(params, cache, tok):
        logits, cache = paged.paged_decode_step(params, cfg, cache, tok,
                                                use_pallas=use_pallas)
        return _greedy(logits, cfg), cache

    out, nxt = [], None
    ln = prompt.shape[1]
    for i in range(ln + gen):
        tok = (prompt[:, i:i + 1] if i < ln
               else np.asarray(nxt).reshape(1, 1))
        nxt, cache = step(params, cache, jnp.asarray(tok, jnp.int32))
        if i >= ln:
            out.append(int(np.asarray(nxt)[0]))
    return out


@pytest.mark.parametrize("layout", paged.LAYOUTS)
def test_cache_scatter_gather_roundtrip(layout):
    """``write_tokens`` then ``gather_dense`` is an exact permutation
    round-trip for both KV layouts (including the head-interleaved
    fused packing: K at even head index, V at odd)."""
    cfg = get_config(ARCH, smoke=True)
    cmax = 3 * PS
    cache = paged.PagedKVCache.init(cfg, 2, cmax, page_size=PS,
                                    layout=layout)
    rng = np.random.RandomState(0)
    shp = (cfg.n_layers, cfg.n_kv_heads, 7, cfg.head_dim)
    k = jnp.asarray(rng.randn(*shp), cache.buffers[0].dtype)
    v = jnp.asarray(rng.randn(*shp), cache.buffers[0].dtype)
    cache = cache.assign_pages(1, [3, 5, 1], 7)   # non-linear page map
    cache = cache.write_tokens(1, k, v, 0)
    for li in range(cfg.n_layers):
        ck, cv = cache.gather_dense(li)
        np.testing.assert_array_equal(np.asarray(ck[1, :, :7], np.float32),
                                      np.asarray(k[li], np.float32))
        np.testing.assert_array_equal(np.asarray(cv[1, :, :7], np.float32),
                                      np.asarray(v[li], np.float32))


@pytest.mark.parametrize("layout", paged.LAYOUTS)
def test_paged_decode_token_identical_to_oracle(layout):
    """Reference AND fused-Pallas paged decode match the dense-cache
    oracle token-for-token: mixed prompt lengths, page-boundary
    crossings, both KV layouts."""
    cfg = get_config(ARCH, smoke=True)
    params = model.init_params(cfg, jax.random.PRNGKey(0))
    cmax = -(-(max(LENS) + GEN) // PS) * PS
    rng = np.random.RandomState(1)
    for ln in LENS:
        prompt = rng.randint(0, cfg.vocab, (1, ln))
        want = _decode_tokens_oracle(cfg, params, prompt, GEN, cmax)
        got_ref = _decode_tokens_paged(cfg, params, prompt, GEN, cmax,
                                       layout, use_pallas=False)
        got_pl = _decode_tokens_paged(cfg, params, prompt, GEN, cmax,
                                      layout, use_pallas=True)
        assert got_ref == want, f"reference path diverged at ln={ln}"
        assert got_pl == want, f"pallas path diverged at ln={ln}"


KERNEL_PAGES = 6          # logical pages per request in the kernel tests
KERNEL_LAYERS = 3         # layers stacked in the kernel tests' pools


def check_stacked_pools(new_pools, pools, want_pools, layer):
    """Layer ``layer`` of the kernel's stacked pools holds the
    reference's pools after the append, and every other layer is
    bit-identical to its input: a DMA into the wrong layer fails."""
    for got, before, ref in zip(new_pools, pools, want_pools):
        got, before = np.asarray(got, np.float32), np.asarray(before,
                                                              np.float32)
        np.testing.assert_array_equal(got[layer],
                                      np.asarray(ref, np.float32))
        others = [li for li in range(got.shape[0]) if li != layer]
        np.testing.assert_array_equal(got[others], before[others])


def _check_kernel_against_reference(layout, page_size, block_pages, depth,
                                    layer, kv_dtype=jnp.bfloat16,
                                    q_dtype=jnp.bfloat16):
    """The fused kernel (interpret mode) against ``reference_attn``,
    the reference branch of ``_paged_attn``: the same output, and the
    same pools after the append, in layer ``layer`` of pools stacked
    over ``KERNEL_LAYERS`` layers.  The batch holds a parked slot
    (length 0, every page 0), lengths at a block boundary, and the
    step's token in the first, a middle and the last block, over a
    shuffled page table, so the requests stream different numbers of
    blocks and the prefetch crosses from one request to the next."""
    from repro.core.codegen_pallas import lower_paged_decode

    hkv, group, dh = 2, 2, 16
    ps, npm = page_size, KERNEL_PAGES
    ctx, block = npm * ps, block_pages * ps
    lens = [0, 1, block - 1, min(block, ctx - 1), ctx // 2 + 1, ctx - 1]
    b = len(lens)
    rng = np.random.RandomState(7)
    n_phys = 1 + b * npm + 2
    width = (2 if layout == "fused" else 1) * hkv * dh
    pools = tuple(jnp.asarray(rng.randn(KERNEL_LAYERS, n_phys, ps, width),
                              kv_dtype)
                  for _ in range(1 if layout == "fused" else 2))
    table = rng.permutation(np.arange(1, n_phys))[:b * npm].reshape(b, npm)
    table[0] = 0                                    # the parked slot
    table, lens = jnp.asarray(table, jnp.int32), jnp.asarray(lens, jnp.int32)
    q = jnp.asarray(rng.randn(b, hkv, group, dh), q_dtype)
    k = jnp.asarray(rng.randn(b, hkv, dh), kv_dtype)
    v = jnp.asarray(rng.randn(b, hkv, dh), kv_dtype)

    kern = lower_paged_decode(batch=b, kv_heads=hkv, group=group,
                              head_dim=dh, page_size=ps, n_pages_max=npm,
                              layout=layout, block=block, depth=depth,
                              dtype=kv_dtype)
    assert (kern.block, kern.depth) == (block, depth)
    out, new_pools = jax.jit(kern)(q, k, v, pools, table, lens,
                                   jnp.int32(layer))
    want, want_pools = paged.reference_attn(
        q, k, v, tuple(p[layer] for p in pools), table, lens, layout, ps)
    # f32 sums of at most 48 terms, in another order
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    check_stacked_pools(new_pools, pools, want_pools, layer)


@pytest.mark.parametrize("layer", (0, KERNEL_LAYERS - 1))
@pytest.mark.parametrize("depth", (2, 3))
@pytest.mark.parametrize("block_pages", (1, 2, KERNEL_PAGES),
                         ids=("one_page", "two_pages", "whole_context"))
@pytest.mark.parametrize("page_size", (4, 8))
@pytest.mark.parametrize("layout", paged.LAYOUTS)
def test_paged_kernel_matches_reference_attention(layout, page_size,
                                                  block_pages, depth, layer):
    _check_kernel_against_reference(layout, page_size, block_pages, depth,
                                    layer)


@pytest.mark.parametrize("layer", (0, KERNEL_LAYERS - 1))
@pytest.mark.parametrize("kv_dtype", ("bfloat16", "float32"))
@pytest.mark.parametrize("layout", paged.LAYOUTS)
def test_paged_kernel_keeps_f32_operands_whole(layout, kv_dtype, layer):
    """An f32 query meets bf16 K/V as its three exact bf16 terms, and
    f32 pools take the f32 matmul: both agree with the reference."""
    _check_kernel_against_reference(layout, 4, 2, 2, layer,
                                    kv_dtype=kv_dtype, q_dtype=jnp.float32)


# granite-3-2b widths: 8 KV heads of 64, bf16
CLAMP_CASES = {
    # the DSE's picks for the two serving cells stand as they are
    "long_decode_dse": ("split", 8, 416, 1664, 3, 1664),
    "chat_short_dse": ("fused", 8, 80, 640, 4, 640),
    "one_page": ("split", 8, 416, None, 2, 8),
    "off_page": ("split", 16, 208, 1000, 2, 992),
    "past_context": ("fused", 8, 80, 4096, 2, 640),
    "past_vmem_split": ("split", 8, 416, 3328, 4, None),
    "past_vmem_fused": ("fused", 64, 52, 3328, 3, None),
}


@pytest.mark.parametrize("case", sorted(CLAMP_CASES))
def test_paged_decode_block_clamp(case):
    """The kernel's block is whole pages, at most the context, and its
    ``depth`` buffers fit the scoped VMEM at granite widths."""
    from repro.core.codegen_pallas import paged_decode_blocks
    from repro.core.cost import VMEM_BYTES

    layout, ps, npm, block, depth, want = CLAMP_CASES[case]
    got, got_depth = paged_decode_blocks(
        block=block, depth=depth, page_size=ps, n_pages_max=npm,
        kv_heads=8, head_dim=64, layout=layout, dtype=jnp.bfloat16)
    assert got_depth == depth
    assert got % ps == 0 and ps <= got <= npm * ps
    width = (2 if layout == "fused" else 1) * 8 * 64
    pools = 1 if layout == "fused" else 2
    assert depth * got * width * 2 * pools <= VMEM_BYTES * 3 // 4
    if want is not None:
        assert got == want
    else:                       # cut: one more page would not fit
        assert depth * (got + ps) * width * 2 * pools > VMEM_BYTES * 3 // 4


def test_paged_decode_dse_axes_in_provenance():
    """KV layout, page size, streaming block and buffer depth are
    jointly searched axes, recorded in the plan's provenance."""
    (layout, ps, block, depth), plan = ops.resolve_plan(
        "paged_decode", 48, 16)
    assert layout in paged.LAYOUTS
    assert plan.sizes["pd_layout"] == (paged.LAYOUTS.index(layout),)
    assert plan.sizes["pd_page"] == (ps,)
    assert plan.sizes["pd_kv"] == (block,)
    assert plan.depths["pd_kv"] == depth
    assert plan.traffic_words > 0 and plan.modeled_seconds > 0


def test_ragged_extent_on_pipeline_stages():
    """Ragged streaming domains validate (shared extent, granularity
    divides it) and change the stage signature -- so plans for ragged
    and dense variants of the same DAG never collide in the cache."""
    from repro.core import dse

    pipe = dse.paged_decode_pipeline(12, 4, 8, "fused")
    rag = ragged_extent(pipe)
    assert rag is not None and rag.granularity == 4
    assert rag.max == 12 and rag.max_units == 3
    dense_like = [s for s in pipe.stages if s.ragged is None]
    assert not dense_like
    no_rag = ir.Map(domain=pipe.stages[0].domain,
                    elem_shape=pipe.stages[0].elem_shape,
                    reads=pipe.stages[0].reads,
                    fn=pipe.stages[0].fn, name=pipe.stages[0].name)
    assert ir.signature(pipe.stages[0]) != ir.signature(no_rag)

    bad = ir.RaggedExtent(max=12, length_name="seq_len", granularity=5)
    with pytest.raises(ValueError):
        Pipeline(name="bad", stages=(
            ir.Map(domain=(12,), elem_shape=(), reads=no_rag.reads,
                   fn=no_rag.fn, name="m", ragged=bad),)).validate()


def test_resolve_plan_survives_unhashable_memo_key():
    """Regression (ISSUE 9 satellite): an unhashable policy/options
    must skip the in-process memo, not crash the resolve -- and the
    second resolve must return the same plan."""
    b1, _ = ops.resolve_plan("paged_decode", 16, 8,
                             policy={"unhashable": True})
    b2, _ = ops.resolve_plan("paged_decode", 16, 8,
                             policy={"unhashable": True})
    assert b1 == b2


def test_mesh_axis_type_guard():
    """Mesh construction names an axis type for every axis (the
    installed jax asks for them explicitly)."""
    from repro.launch import mesh as mesh_mod

    for n in (2, 3):
        kw = mesh_mod._axis_type_kwargs(n)
        assert kw["axis_types"] == (jax.sharding.AxisType.Auto,) * n
    mesh = mesh_mod.make_elastic_mesh(jax.devices()[:1], model_parallel=1)
    assert mesh.axis_names == ("data", "model")


def test_dryrun_cost_analysis_normalization():
    """``cost_analysis()`` comes back as the dict itself; a backend
    that reports nothing normalizes to an empty dict."""
    from repro.launch.dryrun import _cost_analysis_dict

    assert _cost_analysis_dict({"flops": 2.0}) == {"flops": 2.0}
    assert _cost_analysis_dict({}) == {}
    assert _cost_analysis_dict(None) == {}


def test_paged_rejects_sliding_window_and_recurrent():
    """A sliding-window config is no longer rejected: its layers get a
    ring pool (a window of 4 over pages of 4 needs 2 pages per request)
    beside an empty full-layer pool.  Recurrent families have no KV
    cache to page and are still rejected."""
    cfg = get_config(ARCH, smoke=True)
    import dataclasses
    swcfg = dataclasses.replace(cfg, sliding_window=4)
    cache = paged.PagedKVCache.init(swcfg, 1, 16, page_size=4)
    assert cache.window == 4 and cache.ring == 2
    assert cache.buffers[0].shape[0] == 0
    assert cache.win_buffers[0].shape[0] == cfg.n_layers
    ssm = get_config("mamba2-370m", smoke=True)
    with pytest.raises(NotImplementedError):
        paged.paged_decode_step({}, ssm, cache, jnp.zeros((1, 1), jnp.int32))


def test_decode_traffic_model_prefers_live_pages():
    """The modeled paged decode traffic charges live pages only, so a
    ragged batch undercuts the dense max-context accounting."""
    from repro.core import cost

    dense = cost.dense_decode_traffic_words(3, 64, 2, 16)
    pg = cost.paged_decode_traffic_words([5, 9, 33], 8, 2, 16)
    assert pg < dense
    # page granularity: 9 live tokens pay for 2 pages of 8
    one = cost.paged_decode_traffic_words([9], 8, 2, 16)
    assert one == 2 * 2 * 8 * 2 * 16 + 3 * 2 * 16
