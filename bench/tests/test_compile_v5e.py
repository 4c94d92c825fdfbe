"""Compile rehearsal of every program the cells run, for a described
TPU v5e (no chip): the Q6 fused call at both row counts, the serving
cells' decode step and certification step over their pools, the
prefill at each of their prompt lengths, and the benchmark's own
reference forward.  Each must compile and fit one chip's 16 GiB with
what stays resident beside it.  Whole-model compiles take minutes on
the CPU; these tests sit outside the repository's tier-1 paths."""
from __future__ import annotations

import json

import pytest

import harness
from conftest import BENCH

HBM = 16 * 2**30
GRANITE = json.loads((BENCH / "configs" / "granite-3-2b.json").read_text())
SERVE = {name: json.loads((BENCH / "traffic" / f"{name}.json").read_text())
         for name in ("long-decode", "chat-short")}


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
    """Kernels compiled by Mosaic, not interpreted; no persistent cache
    (a compile for a described chip cannot be read back); the DSE's
    tuning cache in a temporary directory."""
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


@pytest.mark.parametrize("rows", [1 << 29, 1 << 22])
def test_q6_fused_call(one_chip, compiled_for_tpu, rows):
    import jax
    import jax.numpy as jnp

    from repro.core import pipeline as plmod
    from repro.patterns.analytics import tpchq6_pipeline

    pipe, _, _ = tpchq6_pipeline(rows)
    call = plmod.lower_pipeline(pipe, fused=True)
    assert [h for _, h in call.group_lowerings] == ["megakernel"]
    col = jax.ShapeDtypeStruct((rows,), jnp.float32, sharding=one_chip)
    c = jax.jit(lambda q, p, d: call(qty=q, price=p, disc=d)).lower(
        col, col, col).compile()
    assert "tpu_custom_call" in c.as_text()
    _fits(c)


def _serving(cell):
    import jax

    from repro.configs import get_config
    from repro.kernels import ops
    from repro.models import model, paged

    cfg = get_config(GRANITE["arch"], smoke=False)
    tr = SERVE[cell]
    max_ctx = max(tr["prompt_lens"]) + tr["gen"]
    (layout, ps, _, _), _ = ops.resolve_plan(
        "paged_decode", int(max_ctx), int(cfg.head_dim))
    npm = -(-max_ctx // ps)
    cache = jax.eval_shape(lambda: paged.PagedKVCache.init(
        cfg, tr["slots"], npm * ps, page_size=ps, layout=layout))
    return cfg, tr, model.param_specs(cfg), cache


@pytest.mark.parametrize("cell", sorted(SERVE))
@pytest.mark.parametrize("pallas", [True, False], ids=["kernel", "certify"])
def test_decode_step(one_chip, compiled_for_tpu, cell, pallas):
    import jax
    import jax.numpy as jnp

    from repro.models import paged

    cfg, tr, params, cache = _serving(cell)

    def step(p, c, t):
        logits, c = paged.paged_decode_step(p, cfg, c, t, use_pallas=pallas)
        return logits[:, -1], c

    tok = jax.ShapeDtypeStruct((tr["slots"], 1), jnp.int32)
    c = jax.jit(step, donate_argnums=(1,) if pallas else ()).lower(
        _sds(params, one_chip), _sds(cache, one_chip),
        _sds(tok, one_chip)).compile()
    _fits(c)


@pytest.mark.parametrize("cell,length", [
    (cell, n) for cell in sorted(SERVE) for n in SERVE[cell]["prompt_lens"]])
def test_prefill(one_chip, compiled_for_tpu, cell, length):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.launch import steps
    from repro.models import model

    cfg, tr, params, pool = _serving(cell)
    dense = model.cache_specs(cfg, 1, length)
    tok = jax.ShapeDtypeStruct((1, length), jnp.int32)
    idx = jax.ShapeDtypeStruct((), jnp.int32)
    c = jax.jit(steps.make_cache_prefill_step(cfg),
                donate_argnums=(1,)).lower(
        _sds(params, one_chip), _sds(dense, one_chip), _sds(tok, one_chip),
        _sds(idx, one_chip)).compile()
    resident = sum(int(np.prod(b.shape)) * b.dtype.itemsize
                   for b in pool.buffers)
    _fits(c, resident)


@pytest.mark.parametrize("cell", sorted(SERVE))
def test_reference_forward(one_chip, compiled_for_tpu, cell):
    import jax
    import jax.numpy as jnp

    ref = harness.load_module(BENCH / "reference" / "granite_3_2b.py")
    tr = SERVE[cell]
    b = tr["sample_requests"]
    s = max(tr["prompt_lens"]) + tr["gen"]
    s = -(-s // ref.Q_BLOCK) * ref.Q_BLOCK if s > ref.Q_BLOCK else s
    w = jax.eval_shape(lambda: ref.init_weights(GRANITE, 0))
    toks = jax.ShapeDtypeStruct((b, s), jnp.int32)
    tg = jax.ShapeDtypeStruct((b, 2, s), jnp.int32)
    c = ref._forward.lower(_sds(w, one_chip), _sds(toks, one_chip),
                           _sds(tg, one_chip), ref._cfg_tuple(GRANITE),
                           False).compile()
    _fits(c)
