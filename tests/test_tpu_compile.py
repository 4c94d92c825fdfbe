"""Compile rehearsals for a TPU v5e, with no chip attached.

Interpret mode never checks what Mosaic refuses on the chip: blocks
and slices off the (sublane, 128-lane) tiling, gathers, and VMEM past
the scoped limit.  These tests compile the main path's kernels at real
widths for a described ``v5e:2x2`` topology (one of its chips), so that
a kernel the chip would refuse fails here, at no chip time.  Interpret
mode is switched off inside each test; the topology is described in a
fixture and every test skips where it cannot be.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import backend, codegen_pallas
from repro.core import pipeline as plmod
from repro.core.dse import explore_pipeline
from repro.patterns.analytics import PIPELINES

# the chip_smoke.py extents: tpchq6 at TPC-H SF ~11, the others at 2**20
SIZES = {"tpchq6": 2 ** 26, "gda": 2 ** 20, "kmeans": 2 ** 20,
         "gda_moments": 2 ** 20, "normalize": 2 ** 20}
# granite-3-2b decode: 8 KV heads of 64, 32 query heads, 40 layers; 4
# requests over a 2048-page pool at the kernel's default block, and the
# serving cells' shapes at the blocks the DSE picks for them
GRANITE = dict(kv_heads=8, group=4, head_dim=64)
GRANITE_LAYERS = 40
PAGED_SHAPES = {
    "split": dict(layout="split", batch=4, page_size=16, n_pages_max=128,
                  pool_pages=2048),
    "fused": dict(layout="fused", batch=4, page_size=16, n_pages_max=128,
                  pool_pages=2048),
    "split-long-decode": dict(layout="split", batch=8, page_size=8,
                              n_pages_max=416, pool_pages=1 + 8 * 416,
                              block=1664, depth=3),
    "fused-chat-short": dict(layout="fused", batch=24, page_size=8,
                             n_pages_max=80, pool_pages=1 + 24 * 80,
                             block=640, depth=4),
}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    # a compile for a described chip cannot be read back from the
    # persistent cache without one: keep it out of the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def mosaic(monkeypatch):
    """Kernels lower for Mosaic, as they do on a TPU backend."""
    monkeypatch.setattr(backend, "interpret", lambda: False)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype),
                                sharding=sharding)


@pytest.mark.parametrize("name", sorted(PIPELINES))
def test_fused_dag_compiles_for_v5e(name, one_chip, mosaic):
    """Every PIPELINE lowers as one Mosaic megakernel at the block the
    DSE picks for it, within the scoped VMEM the DSE plans against."""
    pipe, _, _ = PIPELINES[name](SIZES[name])
    plan = explore_pipeline(pipe, cache=False)
    assert plan.fused
    (block,) = plan.group_blocks
    fdag = plmod.fuse_dag(pipe, block)
    call = codegen_pallas.lower_fused_dag(fdag.terminals, fdag.grid,
                                          depth=plan.depths[0])
    specs = {t.name: _spec(t.shape, t.dtype, one_chip)
             for t in plmod.external_inputs(pipe)}
    compiled = jax.jit(lambda **kw: call(**kw)).lower(**specs).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1
    # the kernel is the named program, not an anonymous wrapper
    assert "jit(fused_dag)/fused_dag/pallas_call" in compiled.as_text()


@pytest.mark.parametrize("shape", list(PAGED_SHAPES))
def test_paged_decode_compiles_for_v5e(shape, one_chip, mosaic):
    """The paged-decode kernel at granite-3-2b widths, as one Mosaic
    kernel named ``paged_decode``, with its blocks cut to the scoped
    VMEM: the pools, stacked over every layer, stay in HBM and are
    updated in place (aliased, no temporary copy of them)."""
    kw = dict(PAGED_SHAPES[shape])
    n_pool_pages = kw.pop("pool_pages")
    layout, b, h, dh = kw["layout"], kw["batch"], GRANITE["kv_heads"], \
        GRANITE["head_dim"]
    kern = codegen_pallas.lower_paged_decode(**GRANITE, **kw)
    if "block" in kw:           # the DSE's picks fit as they are
        assert (kern.block, kern.depth) == (kw["block"], kw["depth"])
    heads = (2 if layout == "fused" else 1) * h
    pool = _spec((GRANITE_LAYERS, n_pool_pages, kw["page_size"], heads * dh),
                 jnp.bfloat16, one_chip)
    pools = (pool,) if layout == "fused" else (pool, pool)
    step = jax.jit(lambda q, k, v, pools, pt, ln, ly: kern(q, k, v, pools,
                                                             pt, ln, ly),
                   donate_argnums=(3,))
    compiled = step.lower(
        _spec((b, h, GRANITE["group"], dh), jnp.bfloat16, one_chip),
        _spec((b, h, dh), jnp.bfloat16, one_chip),
        _spec((b, h, dh), jnp.bfloat16, one_chip), pools,
        _spec((b, kw["n_pages_max"]), jnp.int32, one_chip),
        _spec((b,), jnp.int32, one_chip),
        _spec((), jnp.int32, one_chip)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert "/paged_decode/pallas_call" in text
    mem = compiled.memory_analysis()
    pool_bytes = sum(int(np.prod(p.shape)) * 2 for p in pools)
    assert mem.alias_size_in_bytes == pool_bytes
    assert mem.temp_size_in_bytes < pool_bytes // 100


# an instruction's name, result shape and opcode in the compiled HLO
HLO_OP = re.compile(r"%([\w.-]+) = \w+\[([\d,]*)\]\S* ([\w-]+)\(")
POOL_MOVES = ("copy", "dynamic-slice", "dynamic-update-slice")


def _pool_moves(text, pool_shape):
    """Copies, slices and update-slices (alone or fused) whose result
    is a whole pool stack or one layer's pool, unit dimensions aside."""
    def squeeze(dims):
        return tuple(d for d in dims if d != 1)

    shapes = {squeeze(pool_shape), squeeze(pool_shape[1:])}
    moves = []
    for name, dims, op in HLO_OP.findall(text):
        if op not in POOL_MOVES and not any(m in name for m in POOL_MOVES):
            continue
        if squeeze(int(d) for d in dims.split(",") if d) in shapes:
            moves.append(name)
    return moves


SERVING_SHAPES = ["split-long-decode", "fused-chat-short"]


def _granite_step(shape, sharding, pallas):
    """The whole granite-3-2b decode step compiled at a serving cell's
    shapes: with the kernel and the cache donated, as the server runs
    it, or through the reference returning the logits alone, as its
    certification runs it.  Returns the compiled step and the bytes of
    the pools and of one pool stack."""
    from repro.configs import get_config
    from repro.models import model, paged

    kw = PAGED_SHAPES[shape]
    cfg = get_config("granite-3-2b", smoke=False)
    assert cfg.n_layers == GRANITE_LAYERS
    cache = jax.eval_shape(lambda: paged.PagedKVCache.init(
        cfg, kw["batch"], kw["n_pages_max"] * kw["page_size"],
        page_size=kw["page_size"], layout=kw["layout"]))
    assert cache.n_pages == kw["pool_pages"]

    def step(p, c, t):
        logits, c = paged.paged_decode_step(
            p, cfg, c, t, use_pallas=pallas, block=kw["block"],
            depth=kw["depth"])
        return (logits[:, -1], c) if pallas else logits[:, -1]

    def specs(tree):
        return jax.tree.map(lambda a: _spec(a.shape, a.dtype, sharding),
                            tree)

    compiled = jax.jit(step, donate_argnums=(1,) if pallas else ()).lower(
        specs(model.param_specs(cfg)), specs(cache),
        _spec((kw["batch"], 1), jnp.int32, sharding)).compile()
    pool_bytes = sum(int(np.prod(b.shape)) * b.dtype.itemsize
                     for b in cache.buffers)
    return compiled, pool_bytes, pool_bytes // len(cache.buffers)


@pytest.mark.parametrize("shape", SERVING_SHAPES)
def test_paged_decode_step_keeps_pools_in_place(shape, one_chip, mosaic):
    """The served step: the layer scan carries the stacked pools and
    the kernel indexes them by layer, so no pool, stacked or one
    layer's, is copied, sliced out or written back, and the step's
    temporaries stay far below the pools."""
    compiled, pool_bytes, stack = _granite_step(shape, one_chip, True)
    text = compiled.as_text()
    assert "/paged_decode/pallas_call" in text
    kw = PAGED_SHAPES[shape]
    width = (2 if kw["layout"] == "fused" else 1) * GRANITE["kv_heads"] \
        * GRANITE["head_dim"]
    assert _pool_moves(text, (GRANITE_LAYERS, kw["pool_pages"],
                              kw["page_size"], width)) == []
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes
    # what stays is the attention weights' re-layout (0.3-0.5 GB), not
    # a pool: a scan that moved them needed more than a whole stack
    assert mem.temp_size_in_bytes < stack // 2


@pytest.mark.parametrize("shape", SERVING_SHAPES)
def test_certify_step_copies_no_pool(shape, one_chip, mosaic):
    """The certification step reads the served cache and keeps only
    the logits: it must not copy the pools it leaves undonated (the
    reference's own temporaries, its dense f32 views of a layer, stay
    well under a stack)."""
    compiled, _, stack = _granite_step(shape, one_chip, False)
    assert compiled.memory_analysis().temp_size_in_bytes < stack


# the Q6 cells' partitions: q6-partition's 2**22 rows, q6-scan's 2**29
Q6_ROWS = [2 ** 22, 2 ** 29]
COLUMN_MOVES = ("copy", "transpose")


@pytest.mark.parametrize("rows", Q6_ROWS)
def test_q6_columns_reach_the_kernel_unmoved(rows, one_chip, mosaic):
    """The Q6 query as the benchmark calls it, ``lower_pipeline(pipe,
    fused=True)`` at the DSE's plan: one Mosaic kernel, which the
    columns reach as parameters or bitcasts, with no column-sized copy
    or transpose in front of it."""
    pipe, _, _ = PIPELINES["tpchq6"](rows)
    call = plmod.lower_pipeline(pipe, fused=True)
    specs = {t.name: _spec(t.shape, t.dtype, one_chip)
             for t in plmod.external_inputs(pipe)}
    text = jax.jit(lambda **kw: call(**kw)).lower(**specs).compile(
    ).as_text()
    assert text.count("tpu_custom_call") == 1
    assert "/fused_dag_tpchq6/pallas_call" in text
    moves = [name for name, dims, op in HLO_OP.findall(text)
             if (op in COLUMN_MOVES or any(m in name for m in COLUMN_MOVES))
             and np.prod([int(d) for d in dims.split(",") if d]) >= rows]
    assert moves == []
