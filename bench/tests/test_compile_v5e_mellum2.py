"""Compile rehearsal of the mellum2 cell's programs for a described TPU
v5e (no chip), in the way of ``test_compile_v5e.py``: the decode step
over both pool kinds with the fused kernels, the certification step
through the reference paths, the dropless prefill at each prompt
length beside the resident pools, and the reference's forward and
weight draw.  Each must compile and fit one chip's 16 GiB.  Whole-model
compiles take minutes on the CPU; these tests sit outside the
repository's tier-1 paths.  Run them in one process with
``test_compile_v5e.py`` (one process may load the TPU compiler)."""
from __future__ import annotations

import json

import pytest

import harness
from conftest import BENCH

HBM = 16 * 2**30
MELLUM = json.loads((BENCH / "configs" / "mellum2-12b-ep4.json").read_text())
TRAFFIC = json.loads((BENCH / "traffic" / "swa-moe-decode.json").read_text())


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                      # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_for_tpu(monkeypatch, tmp_path):
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    from repro.core import backend

    monkeypatch.setenv("REPRO_DSE_CACHE", str(tmp_path / "dse_cache.json"))
    monkeypatch.setattr(backend, "interpret", lambda: False)
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)


def _sds(tree, sharding):
    import jax

    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _bytes(tree) -> int:
    import jax
    import numpy as np

    return sum(int(np.prod(a.shape)) * a.dtype.itemsize
               for a in jax.tree.leaves(tree))


def _fits(compiled, resident: int = 0):
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.temp_size_in_bytes
             + m.output_size_in_bytes - m.alias_size_in_bytes + resident)
    print(f"args {m.argument_size_in_bytes / 1e9:.2f} GB, temps "
          f"{m.temp_size_in_bytes / 1e9:.2f} GB, out "
          f"{m.output_size_in_bytes / 1e9:.2f} GB, aliased "
          f"{m.alias_size_in_bytes / 1e9:.2f} GB, resident "
          f"{resident / 1e9:.2f} GB: {total / 1e9:.2f} GB")
    assert total < HBM
    return m


def _serving():
    import jax

    from repro.configs import get_config
    from repro.core.codegen_pallas import paged_decode_blocks
    from repro.kernels import ops
    from repro.models import model, paged

    cfg = get_config(MELLUM["arch"], smoke=False)
    max_ctx = max(TRAFFIC["prompt_lens"]) + TRAFFIC["gen"]
    (layout, ps, blk, depth), _ = ops.resolve_plan(
        "paged_decode", int(max_ctx), int(cfg.head_dim))
    npm = -(-max_ctx // ps)
    cache = jax.eval_shape(lambda: paged.PagedKVCache.init(
        cfg, TRAFFIC["slots"], npm * ps, page_size=ps, layout=layout))
    blk, depth = paged_decode_blocks(
        block=blk, depth=depth, page_size=ps, n_pages_max=npm,
        kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim, layout=layout,
        dtype=cache.buffers[0].dtype)
    return cfg, model.param_specs(cfg), cache, blk, depth


def test_configuration_states_what_the_program_runs():
    from repro.configs import get_config

    m = get_config(MELLUM["arch"], smoke=False)
    rope = MELLUM["rope_parameters"]
    yarn = rope["full_attention"]
    assert (m.d_model, m.d_ff, m.n_layers, m.n_heads, m.n_kv_heads,
            m.head_dim, m.vocab, m.n_experts_held, m.n_experts, m.top_k,
            m.sliding_window, m.rope_theta, m.dtype, m.tie_embeddings,
            m.qkv_bias) == (
        MELLUM["hidden_size"], MELLUM["moe_intermediate_size"],
        MELLUM["num_hidden_layers"], MELLUM["num_attention_heads"],
        MELLUM["num_key_value_heads"], MELLUM["head_dim"],
        MELLUM["vocab_size"], MELLUM["num_experts"],
        MELLUM["router_experts"], MELLUM["num_experts_per_tok"],
        MELLUM["sliding_window"], rope["sliding_attention"]["rope_theta"],
        MELLUM["torch_dtype"] if "torch_dtype" in MELLUM else "bfloat16",
        MELLUM["tie_word_embeddings"], MELLUM["attention_bias"])
    assert m.yarn == (yarn["factor"], yarn["original_max_position_embeddings"],
                      yarn["beta_fast"], yarn["beta_slow"],
                      yarn["attention_factor"])
    kinds = ["window" if t == "sliding_attention" else "full"
             for t in MELLUM["layer_types"]]
    assert kinds == list(m.layer_kinds) * (m.n_layers // len(m.layer_kinds))
    assert MELLUM["published"]["num_experts"] == m.n_experts


@pytest.mark.parametrize("pallas", [True, False], ids=["kernel", "certify"])
def test_decode_step(one_chip, compiled_for_tpu, pallas):
    import jax
    import jax.numpy as jnp

    from repro.models import paged

    cfg, params, cache, blk, depth = _serving()

    def step(p, c, t):
        logits, c, st = paged.paged_decode_step(
            p, cfg, c, t, use_pallas=pallas, block=blk, depth=depth,
            with_stats=True)
        return (logits[:, -1], c, st) if pallas else logits[:, -1]

    tok = jax.ShapeDtypeStruct((TRAFFIC["slots"], 1), jnp.int32)
    c = jax.jit(step, donate_argnums=(1,) if pallas else ()).lower(
        _sds(params, one_chip), _sds(cache, one_chip),
        _sds(tok, one_chip)).compile()
    if pallas:       # a paged-attention and an expert kernel per layer
        assert c.as_text().count("tpu_custom_call") >= 2
    _fits(c)


@pytest.mark.parametrize("length", TRAFFIC["prompt_lens"])
def test_prefill(one_chip, compiled_for_tpu, length):
    import jax
    import jax.numpy as jnp

    from repro.launch import serve, steps
    from repro.models import model

    cfg, params, cache, _, _ = _serving()
    dense = model.cache_specs(cfg, 1, length)
    chunk = serve._ring_len(cfg, length)
    tok = jax.ShapeDtypeStruct((1, chunk), jnp.int32)
    idx = jax.ShapeDtypeStruct((), jnp.int32)
    c = jax.jit(steps.make_cache_prefill_step(cfg, "kernel"),
                donate_argnums=(1,)).lower(
        _sds(params, one_chip), _sds(dense, one_chip), _sds(tok, one_chip),
        _sds(idx, one_chip)).compile()
    _fits(c, _bytes((cache.buffers, cache.win_buffers)))


@pytest.mark.parametrize("fp8", [False, True], ids=["bf16", "fp8"])
def test_reference_forward(one_chip, compiled_for_tpu, fp8):
    import jax
    import jax.numpy as jnp

    ref = harness.load_module(BENCH / "reference" / "mellum2_12b_ep4.py")
    b = TRAFFIC["sample_requests"]
    s = max(TRAFFIC["prompt_lens"]) + TRAFFIC["gen"]
    s = -(-s // ref.Q_BLOCK) * ref.Q_BLOCK
    w = jax.eval_shape(lambda: ref.init_weights(MELLUM, 0))
    toks = jax.ShapeDtypeStruct((b, s), jnp.int32)
    tg = jax.ShapeDtypeStruct((b, 2, s), jnp.int32)
    c = ref._forward.lower(_sds(w, one_chip), _sds(toks, one_chip),
                           _sds(tg, one_chip), ref._cfg_tuple(MELLUM),
                           fp8).compile()
    _fits(c)


def test_weight_draw(one_chip, compiled_for_tpu):
    import jax
    import jax.numpy as jnp

    ref = harness.load_module(BENCH / "reference" / "mellum2_12b_ep4.py")
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    for shape, _, _ in ref.weight_shapes(MELLUM).values():
        _fits(ref._draw.lower(_sds(key, one_chip), shape, 0.1).compile())
