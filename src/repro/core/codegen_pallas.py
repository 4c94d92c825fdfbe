"""Hardware generation: lower tiled PPL IR to Pallas TPU kernels.

This is the paper's §5 code generation step with TPU templates in place
of MaxJ templates (see Table 4 mapping in DESIGN.md):

  * the outer strided pattern's domain      -> ``pallas_call`` grid
  * each TileCopy                           -> ``pl.BlockSpec(tile_shape,
                                               index_map)`` (HBM->VMEM DMA)
  * double buffers between metapipe stages  -> Pallas grid pipelining
    (the Mosaic pipeliner double-buffers every BlockSpec operand between
    grid steps -- exactly the paper's metapipeline semantics)
  * Map over scalars (Vector template)      -> vectorized body on the tile
  * MultiFold over scalars (Reduction tree) -> ``jnp.dot``/``jnp.sum`` (MXU)
  * GroupByFold (CAM template)              -> one-hot matmul accumulation
    into a revisited output block (sequential TPU grid)
  * FlatMap (Parallel FIFO template)        -> masked prefix-sum compaction
    at a dynamic offset carried in SMEM scratch across grid steps
  * fused pipeline DAG (``lower_fused_dag``)-> one multi-output kernel:
    producer stages in VMEM scratch, fold/CAM terminals revisit their
    accumulator block, Map terminals stream a write-once output block
    per grid step (never revisited)

Every kernel is validated against the ``codegen_jax`` oracle in Pallas
interpret mode on the CPU; on a TPU Mosaic compiles it
(``backend.interpret`` decides, from the JAX backend).
"""
from __future__ import annotations

import dataclasses
import math
import re
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import backend, ir, resilience, telemetry
from .affine import AffineMap


def _call_map(amap: "AffineMap", stack: Tuple) -> Tuple:
    """Call an AffineMap with a kernel-local stack: pad absent leading
    (enclosing) indices with zeros, or drop leading entries the map has
    zero columns for anyway (local maps ignore grid dims)."""
    n = amap.n_in
    if len(stack) == n:
        return amap(*stack)
    if len(stack) < n:
        return amap(*((0,) * (n - len(stack)) + tuple(stack)))
    return amap(*stack[len(stack) - n:])


def program_name(kind: str, name: str) -> str:
    """A device program's name, ``<kind>_<name>`` in identifier
    characters: the ``name=`` of its ``pallas_call`` and the function
    name of its jitted wrapper, which a device trace shows."""
    return re.sub(r"\W", "_", f"{kind}_{name}")


def _block_index_map(copy_map: AffineMap, tile_shape: Tuple[int, ...],
                     grid_rank: int) -> Callable:
    """BlockSpec index maps return *block* indices: element base / tile.

    The copy's element-level map must address whole blocks: every base
    offset and every grid stride has to be a multiple of the tile
    extent in that dimension, or the ``elem // tile`` division below
    silently lands the DMA on the wrong block.
    """
    for d_out in range(copy_map.n_out):
        base = copy_map.base[d_out]
        if base % tile_shape[d_out] != 0:
            raise ValueError(
                f"tile copy base {copy_map.base} is not block-aligned: "
                f"dim {d_out} offset {base} is not a multiple of tile "
                f"extent {tile_shape[d_out]} (tile {tile_shape}); "
                "BlockSpec index maps address whole blocks")
        for d_in in range(copy_map.n_in):
            s = copy_map.mat[d_out][d_in]
            if s % tile_shape[d_out] != 0:
                raise ValueError(
                    f"tile copy stride {s} (out dim {d_out}, grid dim "
                    f"{d_in}) is not a multiple of tile extent "
                    f"{tile_shape[d_out]} (tile {tile_shape}); the "
                    "grid would address partial blocks")

    def imap(*grid_idx):
        full = tuple(grid_idx) + (0,) * (copy_map.n_in - len(grid_idx))
        elem = copy_map(*full[:copy_map.n_in])
        return tuple(e // t for e, t in zip(elem, tile_shape))

    return imap


def _tile_view(ref, a: ir.Access, grid_rank: int,
               dom: Tuple[int, ...], row0=0, at: Tuple = ()):
    """What ``a`` reads at local indices ``row0 + [0, dom[0])`` x
    ``[0, dom[1:])`` in one grid step, loaded from the on-chip tile
    ``ref[at]`` as one slice (``at`` indexes leading buffer dims, such
    as a stage scratch's rotating slot).

    Reads must be tile-local: the window may not move with the grid
    index, and each local dim either leaves it in place (one window
    shared by the whole tile) or advances one tile dim by one where the
    window is 1 wide (a row read).  Returns ``(view, dims)``: the
    per-index windows, squeezed as the oracle squeezes them, stacked
    along the local dims ``dims`` as leading axes in order.
    """
    shape = tuple(ref.shape[len(at):])
    nd, k = len(shape), len(dom)
    window = tuple(a.window)

    def start(stack):
        return tuple(int(s) for s in _call_map(a.index_map, stack)[-nd:])

    base = start((0,) * (grid_rank + k))

    def step(j):
        unit = tuple(int(i == j) for i in range(grid_rank + k))
        return tuple(s - b for s, b in zip(start(unit), base))

    src = getattr(a.src, "name", type(a.src).__name__)
    if any(any(step(j)) for j in range(grid_rank)):
        raise NotImplementedError(
            f"tile read of {src} moves with the grid index; only "
            "tile-local reads lower to a kernel")
    stepped: Dict[int, int] = {}          # tile dim -> local dim
    for j in range(k):
        st = step(grid_rank + j)
        hot = [d for d, s in enumerate(st) if s]
        if not hot:
            continue
        if len(hot) != 1 or st[hot[0]] != 1 or window[hot[0]] != 1 \
                or hot[0] in stepped:
            raise NotImplementedError(
                f"tile read of {src} steps {st} per local index; only "
                "unit-stride row reads and shared windows lower")
        stepped[hot[0]] = j
    idx = []
    for d in range(nd):
        j = stepped.get(d)
        ext = window[d] if j is None else dom[j]
        if base[d] < 0 or base[d] + ext > shape[d]:
            raise NotImplementedError(
                f"tile read of {src} leaves its {shape} tile")
        if j == 0 and not isinstance(row0, int):
            idx.append(pl.ds(base[d] + row0, ext))
        else:
            lo = base[d] + (row0 if j == 0 else 0)
            idx.append(slice(lo, lo + ext))
    view = ref[tuple(at) + tuple(idx)]
    order = sorted(stepped, key=stepped.get)
    rest = [d for d in range(nd) if d not in stepped]
    if order + rest != list(range(nd)):
        view = jnp.transpose(view, order + rest)
    vshape = tuple(dom[stepped[d]] for d in order) \
        + tuple(window[d] for d in rest if window[d] != 1)
    return view.reshape(vshape), tuple(stepped[d] for d in order)


def _tile_apply(fn: Callable, grid_idx, dom: Tuple[int, ...], refs,
                reads, row0=0, ats=None) -> Any:
    """Vector template: evaluate the per-index body
    ``fn(stack, *windows)`` at local indices ``row0 + [0, dom[0])`` x
    ``[0, dom[1:])`` at once.

    The body is vmapped over slices of the on-chip tiles
    (``_tile_view``), so it runs as vector code on the tile; nothing is
    gathered per element.  Returns the body's value(s) stacked to
    ``dom + value shape``.
    """
    grid_idx = tuple(grid_idx)
    k = len(dom)
    ats = ats or [()] * len(refs)
    views = [_tile_view(r, a, len(grid_idx), dom, row0, at)
             for r, a, at in zip(refs, reads, ats)]

    def body(*args):
        return fn(grid_idx + tuple(args[:k]), *args[k:])

    for j in reversed(range(k)):   # outermost vmap strips local dim 0
        axes = tuple(0 if i == j else None for i in range(k)) \
            + tuple(0 if j in dims else None for _, dims in views)
        body = jax.vmap(body, in_axes=axes)
    local = [jax.lax.broadcasted_iota(jnp.int32, (e,), 0) for e in dom]
    local[0] = local[0] + row0
    return body(*local, *[v for v, _ in views])


def _is_add(combine: Callable, shape, dtype) -> bool:
    """True when ``combine(a, b)`` is exactly ``a + b``."""
    spec = jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype))
    jaxpr = jax.make_jaxpr(combine)(spec, spec).jaxpr
    if len(jaxpr.eqns) != 1:
        return False
    eqn = jaxpr.eqns[0]
    return (eqn.primitive is jax.lax.add_p
            and list(eqn.invars) == list(jaxpr.invars)
            and list(eqn.outvars) == list(jaxpr.outvars))


def _combine_rows(combine: Callable, vals):
    """Fold the leading axis of ``vals`` with the associative
    ``combine``: one vector sum when it is addition, a halving tree of
    vectorised combines otherwise."""
    if _is_add(combine, vals.shape[1:], vals.dtype):
        return jnp.sum(vals, axis=0)
    while vals.shape[0] > 1:
        h = vals.shape[0] // 2
        head = jax.vmap(combine)(vals[:h], vals[h:2 * h])
        vals = (jnp.concatenate([head, vals[2 * h:]])
                if vals.shape[0] % 2 else head)
    return vals[0]


def _cam_update(keys, vals, num_keys: int, dtype):
    """CAM template: a tile's (key, value) pairs scattered into a dense
    ``(num_keys, value words)`` block by one one-hot MXU matmul (keys
    outside ``[0, num_keys)`` drop out, as with ``jax.nn.one_hot``)."""
    b = keys.shape[0]
    onehot_t = (jax.lax.broadcasted_iota(jnp.int32, (num_keys, b), 0)
                == keys.astype(jnp.int32)[None, :]).astype(dtype)
    vals2 = jnp.asarray(vals, dtype).reshape(b, -1)
    # HIGHEST: the MXU's default pass would round f32 values to bf16
    return jnp.dot(onehot_t, vals2, preferred_element_type=dtype,
                   precision=jax.lax.Precision.HIGHEST)


# --------------------------------------------------------------------
# Tiled Map: MultiFold(grid) write-once { loads; Map(tile) }
# --------------------------------------------------------------------


def lower_tiled_map(p: ir.MultiFold) -> Callable:
    assert p.strided and p.combine is None and isinstance(p.inner, ir.Map)
    inner = p.inner
    grid = tuple(p.domain)
    loads = [tc for tc in p.loads if isinstance(tc.src, ir.Tensor)]
    slot = {tc.uid: i for i, tc in enumerate(loads)}
    assert all(getattr(a.src, "uid", None) in slot for a in inner.reads), \
        "all reads must be tiled"

    in_specs = [
        pl.BlockSpec(tc.tile_shape,
                     _block_index_map(tc.index_map, tc.tile_shape,
                                      len(grid)))
        for tc in loads
    ]
    out_tile = tuple(p.update_shape)
    out_map = AffineMap.probe(lambda *g: p.out_index_map(*g), len(grid))
    out_spec = pl.BlockSpec(out_tile,
                            _block_index_map(out_map, out_tile, len(grid)))

    def kernel(*refs):
        *ins, out = refs
        gidx = tuple(pl.program_id(i) for i in range(len(grid)))
        tiles = [ins[slot[a.src.uid]] for a in inner.reads]
        vals = _tile_apply(inner.fn, gidx, tuple(inner.domain), tiles,
                           inner.reads)
        out[...] = vals.reshape(out.shape).astype(out.dtype)

    def call(**tensors):
        args = [jnp.asarray(tensors[tc.src.name]) for tc in loads]
        return pl.pallas_call(
            kernel, grid=grid, in_specs=in_specs, out_specs=out_spec,
            out_shape=jax.ShapeDtypeStruct(tuple(p.range_shape),
                                           jnp.dtype(p.dtype)),
            interpret=backend.interpret(),
            name=program_name("tiled_map", p.name))(*args)

    return call


# --------------------------------------------------------------------
# Tiled GEMM (Table 3 interchanged form):
#   MultiFold(gi,gj) write-once { MultiFold(kk) fold { Map(bi,bj){fold} } }
# --------------------------------------------------------------------


def match_tiled_gemm(p: ir.Pattern) -> bool:
    return (isinstance(p, ir.MultiFold) and p.strided and p.combine is None
            and isinstance(p.inner, ir.MultiFold) and p.inner.strided
            and p.inner.is_fold and isinstance(p.inner.inner, ir.Map))


def lower_tiled_gemm(p: ir.MultiFold) -> Callable:
    """MXU template: the inner Map{fold} is a tile matmul; the strided
    fold revisits the output block across the reduction grid dim."""
    assert match_tiled_gemm(p)
    f = p.inner
    gi, gj = p.domain
    (kk,) = f.domain
    loads = [tc for tc in f.loads if isinstance(tc.src, ir.Tensor)]
    assert len(loads) == 2, "gemm expects two tiled operands"
    # operand order from the leaf fold's reads: [0] -> x (bi, bk) indexed
    # (i, k); [1] -> y (bk, bj) indexed (k, j)  (paper Table 3 layout)
    leaf = f.inner.inner
    assert isinstance(leaf, ir.MultiFold) and len(leaf.reads) == 2
    x_tc = leaf.reads[0].src
    y_tc = leaf.reads[1].src
    assert x_tc in loads and y_tc in loads
    bi, bj = f.range_shape
    bk = x_tc.tile_shape[1]
    assert x_tc.tile_shape == (bi, bk) and y_tc.tile_shape == (bk, bj)

    grid = (gi, gj, kk)  # reduction dim innermost: output block revisited
    in_specs = [
        pl.BlockSpec((bi, bk), lambda i, j, k: (i, k)),
        pl.BlockSpec((bk, bj), lambda i, j, k: (k, j)),
    ]
    out_spec = pl.BlockSpec((bi, bj), lambda i, j, k: (i, j))

    def kernel(x_ref, y_ref, o_ref):
        @pl.when(pl.program_id(2) == 0)
        def _init():
            o_ref[...] = jnp.zeros_like(o_ref)

        o_ref[...] += jnp.dot(
            x_ref[...], y_ref[...],
            preferred_element_type=o_ref.dtype)  # MXU reduction tree

    def call(**tensors):
        x = jnp.asarray(tensors[x_tc.src.name])
        y = jnp.asarray(tensors[y_tc.src.name])
        return pl.pallas_call(
            kernel, grid=grid, in_specs=in_specs, out_specs=out_spec,
            out_shape=jax.ShapeDtypeStruct(tuple(p.range_shape),
                                           jnp.dtype(p.dtype)),
            interpret=backend.interpret(),
            name=program_name("tiled_gemm", p.name))(x, y)

    return call


# --------------------------------------------------------------------
# Tiled GroupByFold: GroupByFold(grid){ loads; GroupByFold(tile) }
# --------------------------------------------------------------------


def lower_tiled_groupby(p: ir.GroupByFold,
                        combine_is_add: bool = True) -> Callable:
    """CAM template: dense one-hot accumulation.  The output block is
    revisited on every grid step (constant index map); the TPU grid is
    sequential so accumulation across steps is well defined."""
    assert p.strided and isinstance(p.inner, ir.GroupByFold)
    inner = p.inner
    (g,) = p.domain
    (b,) = inner.domain
    loads = [tc for tc in p.loads if isinstance(tc.src, ir.Tensor)]
    assert len(loads) == len(inner.reads)
    elem = tuple(p.elem_shape)
    k = p.num_keys

    in_specs = [
        pl.BlockSpec(tc.tile_shape,
                     _block_index_map(tc.index_map, tc.tile_shape, 1))
        for tc in loads
    ]
    out_shape = (k,) + elem
    out_spec = pl.BlockSpec(out_shape, lambda i: (0,) * (1 + len(elem)))

    def kernel(*refs):
        *ins, out = refs
        gi = pl.program_id(0)

        @pl.when(gi == 0)
        def _init():
            out[...] = jnp.asarray(p.init(), out.dtype)

        keys, vals = _tile_apply(inner.fn, (gi,), (b,), ins, inner.reads)
        out[...] += _cam_update(keys, vals, k, out.dtype
                                ).reshape(out_shape)

    def call(**tensors):
        args = [jnp.asarray(tensors[tc.src.name]) for tc in loads]
        return pl.pallas_call(
            kernel, grid=(g,), in_specs=in_specs, out_specs=out_spec,
            out_shape=jax.ShapeDtypeStruct(out_shape, jnp.dtype(p.dtype)),
            interpret=backend.interpret(),
            name=program_name("tiled_groupby", p.name))(*args)

    return call


# --------------------------------------------------------------------
# Tiled FlatMap: FlatMap(grid){ loads; FlatMap(tile) }
# --------------------------------------------------------------------


def lower_tiled_flatmap(p: ir.FlatMap) -> Callable:
    """Parallel-FIFO template: per-tile mask + prefix-sum compaction,
    appended at a dynamic offset carried in SMEM across grid steps."""
    assert p.strided and isinstance(p.inner, ir.FlatMap)
    inner = p.inner
    (g,) = p.domain
    (b,) = inner.domain
    m = inner.max_per_iter
    cap_tile = b * m
    cap = g * cap_tile
    loads = [tc for tc in p.loads if isinstance(tc.src, ir.Tensor)]
    assert len(loads) == len(inner.reads)

    in_specs = [
        pl.BlockSpec(tc.tile_shape,
                     _block_index_map(tc.index_map, tc.tile_shape, 1))
        for tc in loads
    ]
    out_specs = [
        pl.BlockSpec((cap,), lambda i: (0,)),   # FIFO buffer (revisited)
        pl.BlockSpec((1,), lambda i: (0,)),     # total count
    ]

    def kernel(*refs):
        *ins, buf, cnt = refs
        gi = pl.program_id(0)

        @pl.when(gi == 0)
        def _init():
            buf[...] = jnp.zeros_like(buf)
            cnt[...] = jnp.zeros_like(cnt)

        vals, cnts = _tile_apply(inner.fn, (gi,), (b,), ins, inner.reads)
        vals = vals.reshape(b * m)
        lane = jnp.arange(m)[None, :]
        valid = (lane < cnts[:, None]).reshape(b * m)
        # intra-tile prefix-sum compaction (the "parallel FIFO" fill)
        pos = jnp.cumsum(valid) - 1
        local_n = valid.sum().astype(jnp.int32)
        compact = jnp.zeros((cap_tile,), vals.dtype)
        compact = compact.at[jnp.where(valid, pos, cap_tile - 1)].set(
            jnp.where(valid, vals, compact[cap_tile - 1]), mode="drop")
        base = cnt[0]
        window = jax.lax.dynamic_slice(buf[...], (base,), (cap_tile,))
        take = jnp.arange(cap_tile) < local_n
        merged = jnp.where(take, compact, window)
        buf[...] = jax.lax.dynamic_update_slice(buf[...], merged, (base,))
        cnt[0] = base + local_n

    def call(**tensors):
        args = [jnp.asarray(tensors[tc.src.name]) for tc in loads]
        buf, cnt = pl.pallas_call(
            kernel, grid=(g,), in_specs=in_specs, out_specs=out_specs,
            out_shape=[
                jax.ShapeDtypeStruct((cap,), jnp.dtype(p.dtype)),
                jax.ShapeDtypeStruct((1,), jnp.int32),
            ],
            interpret=backend.interpret(),
            name=program_name("tiled_flatmap", p.name))(*args)
        return buf, cnt[0]

    return call


# --------------------------------------------------------------------
# Fused pipelines: megakernel with VMEM-resident stage intermediates
# --------------------------------------------------------------------


# rows one vector pass of a fused kernel evaluates: the body's code and
# its temporaries stay the size of one chunk however large the DSE
# makes the tile (Mosaic emits straight-line code per vreg)
_CHUNK_ROWS = 1024


def _chunk_rows(b: int, most: int = _CHUNK_ROWS) -> int:
    """All of a small tile; else the largest power-of-two part of ``b``
    up to ``most``, kept lane-aligned (a multiple of 128)."""
    if b <= most:
        return b
    c = math.gcd(b, most)
    return c if c >= 128 else b


def _env_apply(q: ir.Pattern, fn: Callable, g, env: Dict[str, Any],
               r0, rows: int):
    """``_tile_apply`` of a fused tile pattern over its local rows
    ``r0 + [0, rows)``, reading the in-kernel buffers that ``env``
    keys by TileCopy uid as ``(ref, leading index)`` (input blocks and
    VMEM stage scratch alike)."""
    for a in q.reads:
        if not isinstance(a.src, ir.TileCopy):
            raise NotImplementedError(
                f"fused chain: read of {type(a.src).__name__} left in "
                "place (expected every source tiled into VMEM)")
    bufs = [env[a.src.uid] for a in q.reads]
    return _tile_apply(fn, (g,), (rows,), [r for r, _ in bufs], q.reads,
                       r0, [at for _, at in bufs])


def _collect_dag_loads(terminals):
    """Union the terminal trees' root loads for one kernel.

    Tensor tile copies dedupe by ``fusion.tile_copy_key`` (two terminal
    trees reading the same tile carry distinct uids for the same DMA)
    -- each group becomes ONE BlockSpec operand whose value binds every
    member uid.  Producer stages dedupe by uid (``fuse_dag_stages``
    already shares the TileCopy across consumers); first-appearance
    order is topological because each terminal's stage list is a
    topologically closed prefix-consistent sequence.
    """
    from .fusion import tile_copy_key

    tensor_groups: List[Tuple[Any, List[ir.TileCopy]]] = []
    by_key: Dict[Any, List[ir.TileCopy]] = {}
    stage_loads: List[ir.TileCopy] = []
    stage_seen = set()
    for _, t in terminals:
        for tc in t.loads:
            if isinstance(tc.src, ir.Tensor):
                key = tile_copy_key(tc)
                if key not in by_key:
                    by_key[key] = []
                    tensor_groups.append((key, by_key[key]))
                by_key[key].append(tc)
            else:
                if tc.uid in stage_seen:
                    continue
                stage_seen.add(tc.uid)
                stage_loads.append(tc)
    return tensor_groups, stage_loads


@dataclasses.dataclass(frozen=True)
class _Terminal:
    """How one fused-DAG terminal writes its output: the full output
    array shape, the logical shape results reshape to, the output
    BlockSpec, ``init(out, acc)`` run at grid step 0 (None for streamed
    outputs), and ``emit(g, out, env, r0, rows, part)`` which folds or
    writes the terminal's rows ``r0 + [0, rows)`` of grid step ``g`` and
    returns the chunk loop's carry.  ``template`` names the template
    (``lanes``, ``tree``, ``cam``, ``map``).  A ``lanes`` terminal
    carries a per-lane ``(rows,)`` partial through the chunk loop and
    keeps a VMEM accumulator ``acc`` of that shape; its ``flush(g, out,
    acc, part)`` merges the two after each grid step's loop.  The others
    carry None and have no ``flush``."""

    full: Tuple[int, ...]
    shape: Tuple[int, ...]
    spec: Any
    init: Optional[Callable]
    emit: Callable
    template: str
    flush: Optional[Callable] = None


def _terminal_emitter(p: ir.Pattern, grid_n: int) -> _Terminal:
    """Template selection for one fused-DAG terminal:

      * additive scalar fold -> lanes template: each chunk's rows added
                               elementwise into a carried per-lane
                               partial, the partial into a VMEM
                               accumulator once per grid step, and the
                               lanes reduced to the output block once,
                               at the last grid step (``grid_n - 1``)
      * other fold terminal -> revisited accumulator block (init at
                               g == 0, each chunk's partial folded by a
                               tree and merged via combine)
      * keyed-fold terminal -> CAM template, one-hot MXU scatter into a
                               revisited dense block
      * Map terminal        -> write-once streaming template: the tile
                               computed this step IS output block ``g``;
                               no init, no revisit, no accumulator
    """
    q = p.inner
    if q is None:
        raise NotImplementedError("fused terminal: tiled body expected")
    (b,) = q.domain

    if isinstance(p, ir.MultiFold) and p.combine is None:
        # write-once tiled Map (the paper's "(_)"): out block g streams
        if not isinstance(q, ir.Map):
            raise NotImplementedError(
                "fused chain: write-once terminal must wrap a Map tile")
        elem = tuple(q.elem_shape)
        if len(elem) > 1:
            raise NotImplementedError(
                "Map terminals stream blocks of rank <= 2")
        row = elem if elem else (1,)
        out_shape = tuple(p.range_shape)            # (n,) + elem

        def emit_map(g, out, env, r0, rows, part):
            vals = _env_apply(q, q.fn, g, env, r0, rows)
            out[pl.ds(r0, rows)] = jnp.asarray(vals, out.dtype
                                               ).reshape((rows,) + row)

        spec = pl.BlockSpec((b,) + row, lambda g: (g,) + (0,) * len(row))
        return _Terminal((out_shape[0],) + row, out_shape, spec, None,
                         emit_map, "map")

    if isinstance(p, ir.MultiFold):
        # terminal fold: revisited accumulator block, inner partial
        # folded from the combine identity then merged (executor
        # semantics; accumulator dedup keeps this single block).
        if not isinstance(q, ir.MultiFold) or not q.is_fold:
            raise NotImplementedError(
                "fused chain terminal must be a fold (update covers the "
                "whole accumulator)")
        range_shape = tuple(p.range_shape)
        out_block = _padded_out(range_shape)
        if len(range_shape) > 2:
            raise NotImplementedError("fold accumulators of rank <= 2")
        dtype = jnp.dtype(p.dtype)

        # every index's update applied to the identity, then folded
        # with combine: a fold body updates its accumulator by
        # combining in its own contribution, f(acc, x) =
        # combine(acc, f(init, x)), so any order of combines is the
        # sequential fold
        def one(s, *w):
            z = jnp.asarray(q.init(), dtype)
            return jnp.asarray(q.fn(s, z, *w), dtype)

        def init_fold(out, acc):
            out[...] = jnp.asarray(p.init(), dtype).reshape(out_block)

        spec = pl.BlockSpec(out_block, lambda g: (0,) * len(out_block))
        if range_shape == () and _is_add(p.combine, (), dtype) \
                and _is_add(q.combine, (), dtype):
            # row r of a chunk adds into lane r of the partial: nothing
            # leaves the vector unit until the last grid step
            def init_lanes(out, acc):
                init_fold(out, acc)
                acc[...] = jnp.zeros(acc.shape, dtype)

            def emit_lanes(g, out, env, r0, rows, part):
                return part + _env_apply(q, one, g, env, r0, rows)

            def flush_lanes(g, out, acc, part):
                acc[...] += part

                @pl.when(g == grid_n - 1)
                def _():
                    out[...] += jnp.sum(acc[...]).reshape(out_block)

            return _Terminal(out_block, range_shape, spec, init_lanes,
                             emit_lanes, "lanes", flush_lanes)

        def emit_fold(g, out, env, r0, rows, part):
            per = _env_apply(q, one, g, env, r0, rows)
            partial = _combine_rows(q.combine, per).reshape(out_block)
            out[...] = jnp.asarray(p.combine(out[...], partial), dtype)

        return _Terminal(out_block, range_shape, spec, init_fold,
                         emit_fold, "tree")

    if isinstance(p, ir.GroupByFold):
        # terminal keyed fold: CAM template (one-hot MXU scatter) into a
        # revisited dense accumulator; combine must be elementwise add.
        if not isinstance(q, ir.GroupByFold):
            raise NotImplementedError("fused chain: keyed-fold tile "
                                      "expected under GroupByFold root")
        if not _is_add(p.combine, p.shape, p.dtype):
            raise NotImplementedError(
                "fused keyed-fold terminal: combine must be addition "
                "(the one-hot MXU scatter sums)")
        elem = tuple(p.elem_shape)
        k = p.num_keys
        # scalar elements would make a rank-1 (k,) block; pad to (k, 1)
        # (Mosaic wants >= 2-D blocks, same as _padded_out for folds)
        out_block = (k,) + (elem if elem else (1,))

        def init_cam(out, acc):
            out[...] = jnp.asarray(p.init(), out.dtype).reshape(out_block)

        def emit_cam(g, out, env, r0, rows, part):
            keys, vals = _env_apply(q, q.fn, g, env, r0, rows)
            out[...] += _cam_update(keys, vals, k, out.dtype
                                    ).reshape(out_block)

        spec = pl.BlockSpec(out_block, lambda g: (0,) * len(out_block))
        return _Terminal(out_block, (k,) + elem, spec, init_cam, emit_cam,
                         "cam")

    raise NotImplementedError(
        f"no fused-chain template for terminal {type(p).__name__}")


def _padded_out(range_shape: Tuple[int, ...]) -> Tuple[int, ...]:
    """Pallas wants >= 2-D blocks; pad scalar/vector accumulators."""
    if len(range_shape) >= 2:
        return tuple(range_shape)
    if len(range_shape) == 1:
        return (1,) + tuple(range_shape)
    return (1, 1)


def lower_fused_dag(terminals, grid_n: int, depth: int = 2,
                    name: str = "fused_dag") -> Callable:
    """ONE Pallas kernel for a fused pipeline DAG.

    ``terminals`` is a sequence of ``(output name, fused pattern)``
    pairs (``pipeline.fuse_dag`` output) sharing the 1-D strided grid
    ``grid_n``.  External tensors stream through double-buffered
    BlockSpecs (one operand per distinct tile, however many terminal
    trees read it); every producer stage runs once per grid step into
    its rotating ``depth``-deep VMEM scratch -- slot ``g % depth``, so
    ``depth - 1`` earlier stage tiles stay live behind the one being
    written, realizing the metapipeline buffer depth ``plan_memory``
    charges -- and is consumed in place by all its readers (fan-out
    pays a single stage execution and a single buffer); each terminal
    then updates its own output block -- revisited accumulator / CAM
    blocks for folds, a streamed write-once block for Map terminals.
    HBM is touched solely at the pipeline edges (paper Fig. 6).
    The kernel and its jitted wrapper are the device program ``name``.
    Returns ``call(**tensors) -> {name: array}``; each call is a
    ``pipeline.launch`` span (the kernel's dispatch) and a
    ``pipeline.finish`` span (its outputs reshaped).
    """
    terminals = tuple(terminals)
    # the span times kernel *construction* (host side); the emitted
    # kernel body stays telemetry-free
    with telemetry.span("codegen.lower_fused_dag",
                        terminals=len(terminals), grid=int(grid_n),
                        depth=int(depth)) as sp:
        return _lower_fused_dag_body(terminals, grid_n, depth, name, sp)


def _lower_fused_dag_body(terminals, grid_n: int, depth: int,
                          name: str, sp) -> Callable:
    from jax.experimental.pallas import tpu as pltpu

    if depth < 2:
        raise ValueError(f"metapipeline depth must be >= 2, got {depth}")
    for _, t in terminals:
        if not (t.strided and len(t.domain) == 1 and t.inner is not None):
            raise NotImplementedError(
                "fused chain: 1-D strided root expected")
        if tuple(t.domain) != (grid_n,):
            raise ValueError(
                f"terminal '{t.name}' grid {t.domain} != ({grid_n},)")

    tensor_groups, stage_loads = _collect_dag_loads(terminals)
    reps = [group[0] for _, group in tensor_groups]  # one DMA per group
    uid_lists = [[tc.uid for tc in group] for _, group in tensor_groups]
    in_specs = [
        pl.BlockSpec(tc.tile_shape,
                     _block_index_map(tc.index_map, tc.tile_shape, 1))
        for tc in reps
    ]
    # stage scratch: ``depth`` rotating slots on a leading untiled dim;
    # a 1-D stage tile sits as one (1, b) lane row per slot
    def slot_shape(tc):
        t = tuple(tc.tile_shape)
        return (depth,) + ((1,) + t if len(t) == 1 else t)

    scratch_shapes = [pltpu.VMEM(slot_shape(tc), jnp.dtype(tc.dtype))
                      for tc in stage_loads]
    stages = [tc.src for tc in stage_loads]
    for st in stages:
        if not isinstance(st, ir.Map) or len(st.domain) != 1:
            raise NotImplementedError(
                "fused chain: producer stages must be 1-D tile Maps")

    emitters = [_terminal_emitter(t, grid_n) for _, t in terminals]
    sp.set(fold=",".join(t.template for t in emitters))
    (b,) = terminals[0][1].inner.domain
    # a kernel of lanes folds alone takes two vregs a chunk: at one, its
    # loop and not the DMA set the grid step's time (a 6.44 GB Q6 query
    # took 8.94 ms at 1024 rows a chunk, 8.56 at 2048 and 4096; TPU v5e)
    lanes_only = all(t.flush for t in emitters)
    rows = _chunk_rows(b, 2 * _CHUNK_ROWS if lanes_only else _CHUNK_ROWS)
    dtypes = [jnp.dtype(p.dtype) for _, p in terminals]
    # a lanes terminal's accumulator follows the stage scratch
    scratch_shapes += [pltpu.VMEM((rows,), dt)
                       for t, dt in zip(emitters, dtypes) if t.flush]
    n_in, n_out, n_st = len(reps), len(terminals), len(stage_loads)

    def kernel(*refs):
        ins = refs[:n_in]
        outs = refs[n_in:n_in + n_out]
        scratch = refs[n_in + n_out:n_in + n_out + n_st]
        more = iter(refs[n_in + n_out + n_st:])
        accs = [next(more) if t.flush else None for t in emitters]
        g = pl.program_id(0)
        env: Dict[str, Any] = {}
        for uids, r in zip(uid_lists, ins):
            for uid in uids:  # every tree's alias of this tile
                env[uid] = (r, ())
        # consumers read the scratch, not the producing SSA value: the
        # scratch IS the stage's on-chip buffer (what plan_memory
        # charges); the slot rotates through the depth copies so
        # successive grid steps never overwrite a tile a deeper
        # pipeline stage could still be draining (WAR avoidance)
        slot = g % depth
        at = {tc.uid: (slot,) + (0,) * (len(sc.shape) - len(tc.tile_shape)
                                       - 1)
              for tc, sc in zip(stage_loads, scratch)}
        for tc, sc in zip(stage_loads, scratch):
            env[tc.uid] = (sc, at[tc.uid])

        @pl.when(g == 0)
        def _init():
            for t, out, acc in zip(emitters, outs, accs):
                if t.init is not None:
                    t.init(out, acc)

        def chunk(r0, parts):
            for tc, st, sc in zip(stage_loads, stages, scratch):
                vals = _env_apply(st, st.fn, g, env, r0, rows)
                sc[at[tc.uid] + (pl.ds(r0, rows),)] = jnp.asarray(
                    vals, sc.dtype).reshape((rows,) + tuple(st.elem_shape))
            return tuple(t.emit(g, out, env, r0, rows, part)
                         for t, out, part in zip(emitters, outs, parts))

        parts = tuple(jnp.zeros((rows,), dt) if t.flush else None
                      for t, dt in zip(emitters, dtypes))
        if rows == b:
            parts = chunk(0, parts)
        else:
            parts = jax.lax.fori_loop(
                0, b // rows,
                lambda i, c: chunk(pl.multiple_of(i * rows, rows), c),
                parts)
        for t, out, acc, part in zip(emitters, outs, accs, parts):
            if t.flush:
                t.flush(g, out, acc, part)

    kernel_call = pl.pallas_call(
        kernel, grid=(grid_n,), in_specs=in_specs,
        out_specs=[t.spec for t in emitters],
        out_shape=[jax.ShapeDtypeStruct(t.full, dt)
                   for t, dt in zip(emitters, dtypes)],
        scratch_shapes=scratch_shapes, interpret=backend.interpret(),
        name=name)

    def program(*args):
        return kernel_call(*args)

    program.__name__ = program.__qualname__ = name
    run = jax.jit(program)
    names = [out for out, _ in terminals]

    def call(**tensors):
        args = [jnp.asarray(tensors[tc.src.name]) for tc in reps]
        with telemetry.span("pipeline.launch"):
            outs = run(*args)
        with telemetry.span("pipeline.finish"):
            return {out_name: out.reshape(t.shape)
                    for out_name, t, out in zip(names, emitters, outs)}

    return call


def lower_fused_pipeline(pipe, *, plan=None,
                         vmem_budget: Optional[int] = None,
                         cache=None, measure: Optional[str] = None,
                         policy=None, options=None) -> Callable:
    """Lower a ``pipeline.Pipeline`` (DAG) with a joint-DSE
    ``PipelinePlan``.

    Each plan group lowers as one multi-output megakernel
    (``lower_fused_dag``) at its own block size (``plan.group_blocks``)
    and metapipeline buffer depth (``plan.depths``: the stage scratch
    rotates that many VMEM copies); group boundaries -- present only
    on the split-fallback path when no
    fully fused candidate fits VMEM -- materialize their cut
    intermediates and chain through them.  The selected plan is exposed
    on the returned callable as ``.pipeline_plan``, and
    ``.group_lowerings`` records what each group actually compiled to
    (``megakernel`` / ``oracle-chain``) -- check it before quoting the
    plan's fused traffic numbers for an execution.  Multi-output
    pipelines return a name -> array dict.  ``policy`` (a
    ``resilience.Policy``) bounds any measured exploration the call
    triggers: per-candidate deadlines, quarantine, certification.
    """
    from .cost import VMEM_BYTES
    from .dse import explore_pipeline
    from . import pipeline as plmod

    budget = VMEM_BYTES if vmem_budget is None else vmem_budget
    if plan is None:
        plan = explore_pipeline(pipe, vmem_budget=budget, cache=cache,
                                measure=measure, policy=policy,
                                options=options)

    group_depths = plan.depths or (2,) * len(plan.groups)
    runners = []
    lowerings = []
    for (i0, i1), b, d in zip(plan.groups, plan.group_blocks,
                              group_depths):
        sub = plmod.sub_pipeline(pipe, i0, i1)
        outs = plmod.output_names(sub)
        # one group is the whole pipeline: its program takes its name
        prog = program_name("fused_dag", pipe.name if len(plan.groups)
                            == 1 else sub.name)
        try:
            fdag = plmod.fuse_dag(sub, b, vmem_budget_words=budget // 4)
            runner = lower_fused_dag(fdag.terminals, fdag.grid, depth=d,
                                     name=prog)
            how = "megakernel"
        except NotImplementedError as e:
            runner = plmod.unfused_runner(sub)  # correctness first
            how = "oracle-chain"
            resilience.record("lower", resilience.classify(e), sub.name,
                              how, str(e))

            def as_dict(r, names):
                def run(**tensors):
                    out = r(**tensors)
                    return out if isinstance(out, dict) \
                        else {names[0]: out}
                return run

            runner = as_dict(runner, outs)
        runners.append((outs, runner))
        lowerings.append((outs[-1], how))

    out_names = plmod.output_names(pipe)

    def call(**tensors):
        with telemetry.span("pipeline.call"):
            env = {k: jnp.asarray(v) for k, v in tensors.items()}
            for _, runner in runners:
                env.update(runner(**env))
            if len(out_names) == 1:
                return env[out_names[0]]
            return {n: env[n] for n in out_names}

    call.pipeline_plan = plan
    call.group_lowerings = tuple(lowerings)
    return call


# --------------------------------------------------------------------
# dispatch
# --------------------------------------------------------------------


def lower(p: ir.Pattern) -> Callable:
    """Pick the template for a tiled pattern (paper: template selection)."""
    with telemetry.span("codegen.lower", kind=type(p).__name__,
                        pattern=p.name) as sp:
        if match_tiled_gemm(p):
            sp.set(template="gemm")
            return lower_tiled_gemm(p)
        if isinstance(p, ir.MultiFold) and p.combine is None \
                and isinstance(p.inner, ir.Map):
            sp.set(template="map")
            return lower_tiled_map(p)
        if isinstance(p, ir.GroupByFold) and p.strided:
            sp.set(template="groupby")
            return lower_tiled_groupby(p)
        if isinstance(p, ir.FlatMap) and p.strided:
            sp.set(template="flatmap")
            return lower_tiled_flatmap(p)
        raise NotImplementedError(
            f"no hardware template for {type(p).__name__} (strided="
            f"{p.strided}); supported: tiled Map/GEMM/GroupByFold/FlatMap")


def lower_for_timing(p: ir.Pattern, sizes: Dict[str, Tuple[int, ...]], *,
                     vmem_budget: Optional[int] = None,
                     seed: int = 0) -> Tuple[Callable[[], Any], str]:
    """Lower one tile-size candidate of an *untiled* pattern into a
    zero-arg callable ready for the timing harness (``core.measure``).

    Inputs are synthesized deterministically from the pattern's tensor
    metadata; the Pallas template is preferred, and candidates with no
    template fall back to the jitted ``codegen_jax`` oracle of the
    *tiled* IR -- the same executable the fig7 rows time, so measured
    rankings stay comparable across candidates.  On CPU the Pallas path
    runs in interpret mode (``backend.interpret``); the timing DB
    records that.
    Returns ``(fn, how)`` with ``how`` in {"pallas", "oracle"}.
    """
    from .codegen_jax import execute
    from .cost import VMEM_BYTES
    from .measure import synth_inputs
    from .strip_mine import insert_tile_copies, strip_mine, tile

    budget = VMEM_BYTES if vmem_budget is None else vmem_budget
    # chaos hook: REPRO_FAULTS=lower:<p> fails this lowering before any
    # fallback can mask it -- the caller's quarantine path must fire
    resilience.inject("lower", f"{type(p).__name__}:{p.name}")
    with telemetry.span("codegen.lower_for_timing",
                        kind=type(p).__name__, pattern=p.name) as sp:
        try:
            t = tile(p, sizes, vmem_budget_words=budget // 4)
        except resilience.EXPECTED_ERRORS:
            # same fallback as dse._tile_ir: interchange/lift may not
            # apply
            t = insert_tile_copies(strip_mine(p, sizes),
                                   vmem_budget_words=budget // 4)
        inputs = synth_inputs(ir.inputs_of(p), seed=seed)
        try:
            kern = lower(t)
            # abstract-trace probe: template-shape mismatches that only
            # surface at call time must route to the oracle, not blow up
            # (or silently skip) the candidate
            jax.eval_shape(lambda: kern(**inputs))
            sp.set(how="pallas")
            return (lambda: kern(**inputs)), "pallas"
        except resilience.EXPECTED_ERRORS as e:
            resilience.record_once(
                "lower", resilience.classify(e),
                f"{type(p).__name__}:{p.name}", "fallback",
                f"pallas template unusable ({e}); codegen_jax oracle of "
                "the tiled IR times instead")
            def oracle(**kw):
                return execute(t, kw)

            run = jax.jit(oracle)
            sp.set(how="oracle")
            return (lambda: run(**inputs)), "oracle"


def lower_pipeline_for_timing(pipe, plan, *,
                              vmem_budget: Optional[int] = None,
                              seed: int = 0) -> Callable[[], Any]:
    """Lower one fused-pipeline plan candidate into a zero-arg callable
    over synthesized inputs, for the timing harness.  The plan is taken
    as-is (no DSE re-entry), so each shortlisted (block, depth) variant
    times exactly the megakernel it would ship as -- ``plan.depths``
    sizes the rotating stage scratch via ``lower_fused_pipeline``."""
    from . import pipeline as plmod
    from .measure import synth_inputs

    # chaos hook mirroring the single-pattern path
    resilience.inject("lower", f"Pipeline:{pipe.name}")
    with telemetry.span("codegen.lower_pipeline_for_timing",
                        pipeline=pipe.name, block=int(plan.block),
                        depth=int(plan.depth)):
        inputs = synth_inputs(plmod.external_inputs(pipe), seed=seed)
        call = lower_fused_pipeline(pipe, plan=plan,
                                    vmem_budget=vmem_budget)
    return lambda: call(**inputs)


def lower_auto(p: ir.Pattern, *, plan=None, vmem_budget: Optional[int] = None,
               cache=None, measure: Optional[str] = None,
               policy=None, options=None) -> Callable:
    """Tile an *untiled* pattern with a DSE-chosen ``TilePlan`` and lower
    it (paper §4 automated tile-size selection feeding §5 codegen).

    ``plan=None`` runs ``core.dse.explore`` (with its persistent tuning
    cache); pass an explicit ``TilePlan`` to reuse a prior exploration,
    or ``measure="top_k"`` to let hybrid DSE back the plan with real
    timings.  The selected plan is exposed on the returned callable as
    ``.tile_plan``, including the searched metapipeline buffer depth
    (``plan.depths``).  Single-pattern templates delegate buffering to
    the Pallas/Mosaic grid pipeliner, so the depth shapes the *pricing*
    (VMEM charge + exposed-latency model) rather than the emitted
    kernel; fused pipelines (``lower_fused_pipeline``) realize it as
    rotating stage scratch.  ``policy`` (a ``resilience.Policy``)
    bounds any measured exploration: deadlines, quarantine,
    certification.
    """
    from .cost import VMEM_BYTES
    from .dse import explore
    from .strip_mine import tile

    budget = VMEM_BYTES if vmem_budget is None else vmem_budget
    with telemetry.span("codegen.lower_auto", kind=type(p).__name__,
                        pattern=p.name):
        if plan is None:
            plan = explore(p, vmem_budget=budget, cache=cache,
                           measure=measure, policy=policy,
                           options=options)
        call = lower(tile(p, plan.sizes, vmem_budget_words=budget // 4))
    call.tile_plan = plan
    return call


# --------------------------------------------------------------------
# paged decode (serving): KV-append producer + flash-attention fold
# --------------------------------------------------------------------


def paged_decode_blocks(*, block: Optional[int], depth: int,
                        page_size: int, n_pages_max: int, kv_heads: int,
                        head_dim: int, layout: str, dtype
                        ) -> Tuple[int, int]:
    """The ``(block, depth)`` the paged-decode kernel streams with.

    ``block`` (tokens, ``None`` for one page) is cut to whole pages, to
    the context bound ``n_pages_max * page_size``, and until ``depth``
    buffers of it for every pool take at most three quarters of the
    scoped VMEM (the rest holds the block's scores and probabilities).
    The DSE prices a per-head row; the kernel's token row is every
    head's K (and V, for ``fused``), so the cut depends on the widths
    the kernel sees.  Pages are whole (sublane, lane) tiles, so a
    buffer takes its bytes unpadded."""
    from .cost import VMEM_BYTES

    budget = VMEM_BYTES * 3 // 4
    n_pools = 1 if layout == "fused" else 2
    row = ((2 if layout == "fused" else 1) * kv_heads * head_dim
           * jnp.dtype(dtype).itemsize)
    depth = max(int(depth), 1)
    pages = min(max(int(block or page_size) // page_size, 1), n_pages_max)
    pages = max(min(pages, budget // (depth * n_pools * page_size * row)),
                1)
    return pages * page_size, depth


def window_block(block: int, page_size: int, window: int) -> int:
    """The streaming block of a windowed layer's kernel: whole pages,
    at most ``block`` and a quarter of the window, so the blocks that
    meet the window hold little outside it."""
    pages = max(min(block, window // 4) // page_size, 1)
    return pages * page_size


def lower_paged_decode(*, batch: int, kv_heads: int, group: int,
                       head_dim: int, page_size: int, n_pages_max: int,
                       layout: str = "split", block: Optional[int] = None,
                       depth: int = 2, dtype=jnp.bfloat16,
                       window: Optional[int] = None) -> Callable:
    """Emit the fused decode megakernel over a paged KV cache.

    The ``decode_attention`` DAG lowered as one kernel per layer: the
    KV-append producer writes the step's token into its page slot, and
    the flash-attention fold streams the request's pages with online
    softmax.  The streaming domain is *ragged* (``ir.RaggedExtent``):
    each request folds only the blocks that hold its live tokens, and
    masks the slots past its length to ``-1e30`` before the running-max
    update, so the result never depends on what unassigned pages hold.

    The fold streams blocks of ``block`` tokens (whole pages; see
    ``paged_decode_blocks`` for the cut) through a ``depth``-deep
    rotating VMEM buffer: while block ``i`` computes, the page DMAs of
    blocks ``i+1 .. i+depth-1`` are in flight.  The blocks of all
    requests form one stream -- the grid runs in order and the fetch
    cursor lives in SMEM scratch across grid steps -- so the next
    request's first blocks load while this one's last block computes.
    Each block is scored for all heads in one matmul (the query heads
    laid block-diagonally over the token row), its probabilities
    weight V in a second.  Both take bf16 operands with f32
    accumulation: K and V are bf16, and an f32 operand is split into
    the three bf16 terms that sum to it, which is exactly what
    ``Precision.HIGHEST`` computes from bf16 K/V.

    The step's token reaches the pool in place: its page is in the
    block that holds the token, so the row is merged into that VMEM
    block (which may have been fetched before the append) and the page
    is written back from it while the block computes.

    Layouts: ``split`` takes/returns two pools ``(L, P, ps, Hkv*dh)``;
    ``fused`` one head-interleaved pool ``(L, P, ps, 2*Hkv*dh)`` (K of
    head ``h`` at head slot ``2h``, V at ``2h+1``).  Each pool is
    stacked over the ``L`` layers of its kind and the kernel touches
    only layer ``layer``'s pages, so a layer scan carries the stacks
    whole and never slices a layer out.  A token is one lane-dense
    row, so a page is whole (sublane, lane) tiles.  The page table,
    the lengths and the layer are scalar-prefetched into SMEM; the
    pools stay in HBM, aliased input to output, and no step copies a
    pool.

    Returns ``call(q, new_k, new_v, pools, page_table, seq_lens, layer)
    -> (out, new_pools)`` with ``q`` ``(B, Hkv, group, dh)``, ``new_k``
    / ``new_v`` ``(B, Hkv, dh)`` (already rotated), ``layer`` an int32
    scalar, ``out`` the f32 ``(B, Hkv, group, dh)`` attention output.
    The call carries the streamed ``block`` and ``depth`` as
    attributes.

    With ``window`` (a sliding-window layer) each request's table row
    is a ring of ``n_pages_max`` pages: logical page ``p`` lives in
    column ``p % n_pages_max``, and the ring holds at least the
    ``window`` newest tokens.  The request folds only the blocks that
    meet ``(len - window, len]`` and masks every position outside it,
    so slots that hold older or not yet written tokens never count.
    """
    if layout not in ("split", "fused"):
        raise ValueError(f"layout {layout!r}")
    block, depth = paged_decode_blocks(
        block=block, depth=depth, page_size=page_size,
        n_pages_max=n_pages_max, kv_heads=kv_heads, head_dim=head_dim,
        layout=layout, dtype=dtype)
    # span times host-side kernel construction; nothing lands in the
    # traced/jitted kernel body
    with telemetry.span("codegen.lower_paged_decode", layout=layout,
                        batch=int(batch), page_size=int(page_size),
                        n_pages_max=int(n_pages_max), block=block,
                        depth=depth, pages_per_block=block // page_size,
                        window=window):
        call = _lower_paged_decode_body(
            batch=batch, kv_heads=kv_heads, group=group,
            head_dim=head_dim, page_size=page_size,
            n_pages_max=n_pages_max, layout=layout, block=block,
            depth=depth, window=window)
    call.block, call.depth = block, depth
    return call


def _bf16_terms(x):
    """bf16 values whose sum is ``x`` exactly: ``x`` itself if bf16,
    else (f32) the leading, middle and trailing 8 bits of its
    significand."""
    if x.dtype == jnp.bfloat16:
        return [x]
    terms = []
    for _ in range(3):
        t = x.astype(jnp.bfloat16)
        terms.append(t)
        x = x - t.astype(jnp.float32)
    return terms


def _exact_dot(a, b, dims):
    """``a @ b`` (``dot_general`` over ``dims``) in f32 with ``a``'s
    f32 values kept whole, as ``Precision.HIGHEST`` keeps them: a bf16
    ``b`` meets the bf16 terms of ``a`` in one matmul (the terms
    stacked as rows, their products summed after), anything else takes
    the f32 matmul at HIGHEST."""
    if b.dtype != jnp.bfloat16:
        return jax.lax.dot_general(
            a.astype(jnp.float32), b.astype(jnp.float32), dims,
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
    terms = _bf16_terms(a)
    r = a.shape[0]
    out = jax.lax.dot_general(jnp.concatenate(terms, axis=0), b, dims,
                              preferred_element_type=jnp.float32)
    acc = out[:r]
    for i in range(1, len(terms)):
        acc = acc + out[i * r:(i + 1) * r]
    return acc


def _lower_paged_decode_body(*, batch: int, kv_heads: int, group: int,
                             head_dim: int, page_size: int,
                             n_pages_max: int, layout: str, block: int,
                             depth: int, window: Optional[int] = None
                             ) -> Callable:
    from jax.experimental.pallas import tpu as pltpu

    fused = layout == "fused"
    ps, dh = page_size, head_dim
    ppb = block // ps                      # pages per block
    n_pools = 1 if fused else 2
    width = (2 if fused else 1) * kv_heads * dh   # lanes of one token row
    rows = kv_heads * group                # query rows, head-major
    ctx = n_pages_max * ps                 # context bound
    NEG = -1e30
    scale = head_dim ** -0.5
    QK = (((1,), (1,)), ((), ()))          # contract the token row
    PV = (((1,), (0,)), ((), ()))          # contract the block's tokens

    def kernel(pt_ref, len_ref, ly_ref, q_ref, *refs):
        new_rows = refs[:n_pools]              # (1, 1, width) VMEM
        # refs[n_pools:2 * n_pools] are the input pools, the same HBM
        # buffers as the aliased output pools used below
        out_ref = refs[2 * n_pools]
        pools = refs[2 * n_pools + 1:3 * n_pools + 1]  # (L, P, ps, w)
        bufs = refs[3 * n_pools + 1:4 * n_pools + 1]  # (depth, ppb, ps, w)
        sem, wsem, cur = refs[4 * n_pools + 1:]
        b = pl.program_id(0)
        layer = ly_ref[0]
        n_phys = pools[0].shape[1]

        # indices stay in bounds whatever the host wrote (a DMA off the
        # pool is a fault, not a garbage read); gathers clamp the same
        # way on the reference path
        def page_of(r, p):
            col = (jnp.minimum(p, n_pages_max - 1) if window is None
                   else p % n_pages_max)
            return jnp.clip(pt_ref[r, col], 0, n_phys - 1)

        def last(r):
            """The last position request ``r`` folds."""
            if window is not None:
                return len_ref[r]
            return jnp.minimum(len_ref[r], ctx - 1)

        def first_block(r):
            """The first block request ``r`` folds."""
            if window is None:
                return 0
            return jnp.maximum(len_ref[r] - window + 1, 0) // block

        def page_copy(k, slot, i, page):
            return pltpu.make_async_copy(pools[k].at[layer, page],
                                         bufs[k].at[slot, i],
                                         sem.at[k, slot])

        # the fetch cursor in SMEM: request, block, blocks started
        def fetch_next():
            r, j, started = cur[0], cur[1], cur[2]

            @pl.when(r < batch)
            def _():
                slot = started % depth

                def start(i, c):
                    page = page_of(r, j * ppb + i)
                    for k in range(n_pools):
                        page_copy(k, slot, i, page).start()
                    return c

                jax.lax.fori_loop(0, ppb, start, 0)
                done = j + 1 > last(r) // block
                cur[0] = jnp.where(done, r + 1, r)
                nxt0 = (0 if window is None
                        else first_block(jnp.minimum(r + 1, batch - 1)))
                cur[1] = jnp.where(done, nxt0, j + 1)
                cur[2] = started + 1

        @pl.when(b == 0)
        def _prime():
            for i in range(4):
                cur[i] = 0
            if window is not None:
                cur[1] = first_block(0)
            for _ in range(depth - 1):
                fetch_next()

        ln = len_ref[b]
        lim = last(b)
        # where the step's token goes: its logical page (clamped to the
        # table, as the reference clamps), that page's block and slot
        tok_page = (jnp.minimum(ln // ps, n_pages_max - 1) if window is None
                    else ln // ps)
        tok_block, tok_in = tok_page // ppb, tok_page % ppb
        hit = jax.lax.broadcasted_iota(jnp.int32, (ps, 1), 0) == ln % ps

        qbd = q_ref[0]                          # (rows, width)

        j0 = first_block(b)

        def fold_block(j, carry):
            m, el, acc = carry
            fetch_next()                # the block depth - 1 ahead
            slot = (cur[3] + j if window is None
                    else cur[3] + j - j0) % depth

            def wait(i, c):
                for k in range(n_pools):
                    page_copy(k, slot, i, 0).wait()
                return c

            jax.lax.fori_loop(0, ppb, wait, 0)

            @pl.when(j == tok_block)
            def _append():
                page = page_of(b, tok_page)
                for k in range(n_pools):
                    buf = bufs[k].at[slot, tok_in]
                    buf[...] = jnp.where(
                        hit, new_rows[k][0].astype(jnp.float32),
                        buf[...].astype(jnp.float32)).astype(buf.dtype)
                    pltpu.make_async_copy(buf, pools[k].at[layer, page],
                                          wsem.at[k]).start()

            kv = [buf[slot].reshape(block, width) for buf in bufs]
            s_ = _exact_dot(qbd, kv[0], QK) * scale      # (rows, block)
            pos = j * block + jax.lax.broadcasted_iota(
                jnp.int32, (1, block), 1)
            live = pos <= lim                            # ragged predicate
            if window is not None:
                live &= pos > lim - window
            s_ = jnp.where(live, s_, NEG)
            m_new = jnp.maximum(m, s_.max(-1, keepdims=True))
            pexp = jnp.exp(s_ - m_new)
            alpha = jnp.exp(m - m_new)
            el = el * alpha + pexp.sum(-1, keepdims=True)
            acc = acc * alpha + _exact_dot(pexp, kv[-1], PV)

            @pl.when(j == tok_block)
            def _written():
                for k in range(n_pools):
                    pltpu.make_async_copy(bufs[k].at[slot, tok_in],
                                          pools[k].at[layer, 0],
                                          wsem.at[k]).wait()

            return m_new, el, acc

        init = (jnp.full((rows, 1), NEG, jnp.float32),
                jnp.zeros((rows, 1), jnp.float32),
                jnp.zeros((rows, width), jnp.float32))
        n_blocks = lim // block + 1
        _, el, acc = jax.lax.fori_loop(j0, n_blocks, fold_block, init)
        cur[3] = (cur[3] + n_blocks if window is None
                  else cur[3] + n_blocks - j0)
        # the step's own token is always live, so el > 0
        acc = acc / el
        for h in range(kv_heads):
            # head h's V lanes: fused rows interleave K and V per head
            v0 = (2 * h + 1) * dh if fused else h * dh
            out_ref[0, h] = acc[h * group:(h + 1) * group, v0:v0 + dh]

    def call(q, new_k, new_v, pools, page_table, seq_lens, layer):
        pools = tuple(jnp.asarray(p) for p in pools)
        kv_dt = pools[0].dtype
        if fused:
            new = (jnp.stack([new_k, new_v], axis=2),)
        else:
            new = (new_k, new_v)
        new = tuple(t.reshape(batch, 1, width).astype(kv_dt) for t in new)
        # the query heads block-diagonal over the token row: row
        # h*group+g holds q[h, g] on head h's K lanes, zeros elsewhere
        diag = jnp.eye(kv_heads, dtype=bool)[None, :, None, :, None]
        qbd = jnp.where(diag, q[:, :, :, None, :], 0).astype(q.dtype)
        if fused:                            # (B, Hkv, g, Hkv, [K, V], dh)
            qbd = jnp.stack([qbd, jnp.zeros_like(qbd)], axis=4)
        qbd = qbd.reshape(batch, rows, width)
        hbm = pl.BlockSpec(memory_space=pl.ANY)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(batch,),
            in_specs=[pl.BlockSpec((1, rows, width),
                                   lambda b, pt, ln, ly: (b, 0, 0))]
            + [pl.BlockSpec((1, 1, width), lambda b, pt, ln, ly: (b, 0, 0))
               for _ in new]
            + [hbm] * n_pools,
            out_specs=[pl.BlockSpec((1, kv_heads, group, dh),
                                    lambda b, pt, ln, ly: (b, 0, 0, 0))]
            + [hbm] * n_pools,
            scratch_shapes=[pltpu.VMEM((depth, ppb, ps, width), kv_dt)
                            for _ in range(n_pools)]
            + [pltpu.SemaphoreType.DMA((n_pools, depth)),
               pltpu.SemaphoreType.DMA((n_pools,)),
               pltpu.SMEM((4,), jnp.int32)])
        first_pool = 3 + 1 + n_pools           # scalars, q, new rows
        outs = pl.pallas_call(
            kernel, grid_spec=grid_spec,
            out_shape=[jax.ShapeDtypeStruct(
                (batch, kv_heads, group, dh), jnp.float32)]
            + [jax.ShapeDtypeStruct(p.shape, p.dtype) for p in pools],
            input_output_aliases={first_pool + j: 1 + j
                                  for j in range(n_pools)},
            # the block stream and its cursor run across grid steps
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            interpret=backend.interpret(), name="paged_decode")(
                jnp.asarray(page_table, jnp.int32),
                jnp.asarray(seq_lens, jnp.int32),
                jnp.asarray(layer, jnp.int32).reshape(1), qbd, *new, *pools)
        return outs[0], tuple(outs[1:])

    return call


# --------------------------------------------------------------------
# dropless MoE (serving): grouped SwiGLU matmul over the held experts
# --------------------------------------------------------------------


MOE_GMM_WEIGHT_VMEM = 32 * 2 ** 20   # two buffers of a step's weights


def moe_gmm_ff_block(*, d_model: int, d_ff: int, dtype) -> int:
    """Columns of the expert width one grid step streams: the whole
    width where two buffers of its three weight blocks fit
    ``MOE_GMM_WEIGHT_VMEM``, else the widest multiple of 128 that
    divides it and fits."""
    item = jnp.dtype(dtype).itemsize
    cands = [d_ff] + [c for c in range(d_ff - d_ff % 128, 0, -128)
                      if d_ff % c == 0]
    for c in cands:
        if 2 * 3 * d_model * c * item <= MOE_GMM_WEIGHT_VMEM:
            return c
    return cands[-1]


def lower_moe_gmm(*, rows: int, d_model: int, d_ff: int, n_experts: int,
                  row_block: int, dtype=jnp.bfloat16) -> Callable:
    """Emit the grouped SwiGLU matmul of a dropless MoE share.

    Token rows arrive sorted by expert, each expert's segment padded
    to whole tiles of ``row_block`` rows, so tile ``t`` holds rows of
    one expert, ``tile_expert[t]``.  The row domain is a ragged fold
    over the experts' token counts (``ir.RaggedExtent``): the grid is
    the static tile bound, and only the leading ``n_tiles`` tiles are
    live.  Grid step ``(t, n)`` streams columns ``n`` of the expert's
    ``w1``/``w3`` and rows ``n`` of its ``w2`` through the pipeline's
    double buffer (the metapipeline: step ``(t, n+1)``'s weights load
    while ``(t, n)`` computes) and accumulates
    ``(silu(x w1) * (x w3)) w2`` for the tile in f32.  Steps past the
    live tiles keep the last live tile's block indices, so the
    pipeline fetches nothing for them and they compute nothing: the
    weights of an expert no token chose are never read (unless no
    tile is live, when one block of expert ``tile_expert[0]`` is).

    Returns ``call(xs, w1, w3, w2, tile_expert, n_tiles, layer=0) ->
    ys`` with ``xs``/``ys`` ``(rows, D)``, ``w1``/``w3`` ``(L, E, D,
    F)``, ``w2`` ``(L, E, F, D)`` -- every layer's experts, of which the
    kernel reads layer ``layer``'s, so no layer is copied out of the
    stack -- ``tile_expert`` ``(rows // row_block,)`` and ``n_tiles``
    ``(1,)`` int32.  Rows of tiles past ``n_tiles`` are left unwritten.
    """
    from jax.experimental.pallas import tpu as pltpu

    rag = ir.RaggedExtent(max=rows, length_name="n_tiles",
                          granularity=row_block)
    n_t = rag.max_units
    tf = moe_gmm_ff_block(d_model=d_model, d_ff=d_ff, dtype=dtype)
    if rows % row_block:
        raise ValueError(f"row_block {row_block} must divide rows {rows}")
    n_f = d_ff // tf
    item = jnp.dtype(dtype).itemsize
    vmem = (2 * 3 * d_model * tf * item + 4 * row_block * d_model * item
            + row_block * d_model * 4 + 3 * row_block * tf * 4)
    with telemetry.span("codegen.lower_moe_gmm", rows=int(rows),
                        row_block=int(row_block), ff_block=int(tf),
                        experts=int(n_experts)):

        # nt holds the live tile count and the layer
        def live(t, nt):
            ok = t < nt[0]
            return ok, jnp.where(ok, t, jnp.maximum(nt[0] - 1, 0))

        def x_map(t, n, te, nt):
            return (live(t, nt)[1], 0)

        def w_in_map(t, n, te, nt):
            ok, tt = live(t, nt)
            return (nt[1], te[tt], 0, jnp.where(ok, n, n_f - 1))

        def w_out_map(t, n, te, nt):
            ok, tt = live(t, nt)
            return (nt[1], te[tt], jnp.where(ok, n, n_f - 1), 0)

        if backend.interpret():
            # the CPU has no bf16 x bf16 -> f32 dot; bf16 values are
            # exact in f32, so the products and sums are the same
            def dot(a, b):
                return jnp.dot(a.astype(jnp.float32), b.astype(jnp.float32))
        else:
            def dot(a, b):
                return jnp.dot(a, b, preferred_element_type=jnp.float32)

        def kernel(te_ref, nt_ref, x_ref, w1_ref, w3_ref, w2_ref, o_ref,
                   acc_ref):
            t, n = pl.program_id(0), pl.program_id(1)

            @pl.when(t < nt_ref[0])
            def _():
                x = x_ref[...]
                h1 = dot(x, w1_ref[0, 0])
                h3 = dot(x, w3_ref[0, 0])
                g = (h1 * jax.nn.sigmoid(h1) * h3).astype(w2_ref.dtype)
                part = dot(g, w2_ref[0, 0])

                @pl.when(n == 0)
                def _first():
                    acc_ref[...] = part

                @pl.when(n > 0)
                def _more():
                    acc_ref[...] += part

                @pl.when(n == n_f - 1)
                def _store():
                    o_ref[...] = acc_ref[...].astype(o_ref.dtype)

        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(n_t, n_f),
            in_specs=[pl.BlockSpec((row_block, d_model), x_map),
                      pl.BlockSpec((1, 1, d_model, tf), w_in_map),
                      pl.BlockSpec((1, 1, d_model, tf), w_in_map),
                      pl.BlockSpec((1, 1, tf, d_model), w_out_map)],
            out_specs=pl.BlockSpec((row_block, d_model), x_map),
            scratch_shapes=[pltpu.VMEM((row_block, d_model), jnp.float32)])
        kern = pl.pallas_call(
            kernel, grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((rows, d_model), dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary"),
                vmem_limit_bytes=max(int(vmem * 1.25) + (4 << 20),
                                     32 << 20)),
            interpret=backend.interpret(), name="moe_gmm")

    def call(xs, w1, w3, w2, tile_expert, n_tiles, layer=0):
        nt = jnp.concatenate([jnp.asarray(n_tiles, jnp.int32).reshape(1),
                              jnp.asarray(layer, jnp.int32).reshape(1)])
        return kern(jnp.asarray(tile_expert, jnp.int32), nt, xs, w1, w3, w2)

    return call
