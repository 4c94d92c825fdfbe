"""Execution semantics for the PPL IR: lower patterns to pure JAX.

This is both the *oracle* (every transformation must preserve the value
computed here) and the CPU execution path used by benchmarks.  All loops
lower to ``jax.lax`` control flow so programs jit cleanly.

Index-map convention (see ir.py): every ``Access.index_map``,
``TileCopy.index_map`` and ``out_index_map`` receives the concatenated
index stack of all *enclosing* pattern domains, outermost first, ending
with the indices of the pattern that owns it.  Body ``fn``s receive the
same stack as their first argument.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from . import ir


def _key(src: ir.Source):
    """Binding key: TileCopies use their rewrite-stable uid."""
    return src.uid if isinstance(src, ir.TileCopy) else id(src)


def _unflatten(flat_idx, domain):
    """Flat loop index -> multi-index (row-major)."""
    idxs = []
    rem = flat_idx
    for extent in reversed(domain):
        idxs.append(rem % extent)
        rem = rem // extent
    return tuple(reversed(idxs))


def _squeeze(x):
    """Windows with singleton dims are squeezed; all-singleton -> scalar."""
    out = jnp.squeeze(x)
    return out


class Env:
    """Maps symbolic sources to concrete arrays during evaluation."""

    def __init__(self, inputs: Dict[str, Any]):
        self.inputs = inputs
        self.bindings: Dict[int, Any] = {}

    def resolve(self, src: ir.Source, idx_stack: Tuple) -> Any:
        if isinstance(src, ir.Tensor):
            if src.name not in self.inputs:
                raise KeyError(f"input tensor '{src.name}' not provided")
            return self.inputs[src.name]
        if _key(src) in self.bindings:
            return self.bindings[_key(src)]
        if isinstance(src, ir.Pattern):
            val = _execute(src, self, idx_stack)
            self.bindings[id(src)] = val
            return val
        if isinstance(src, ir.TileCopy):
            # lazy load: AffineMap index maps know their input arity, so we
            # can slice the correct stack prefix at the use site
            from .affine import AffineMap
            if isinstance(src.index_map, AffineMap):
                arr = self.resolve(src.src, idx_stack)
                starts = src.index_map(*idx_stack[:src.index_map.n_in])
                starts = tuple(jnp.asarray(s, jnp.int32) for s in starts)
                val = jax.lax.dynamic_slice(arr, starts, src.tile_shape)
                self.bindings[src.uid] = val
                return val
        raise KeyError(f"unbound source {src!r}")

    def bind(self, src: ir.Source, value: Any) -> None:
        self.bindings[_key(src)] = value


def _read_window(env: Env, access: ir.Access, idx_stack: Tuple) -> Any:
    arr = env.resolve(access.src, idx_stack)
    starts = access.index_map(*idx_stack)
    starts = tuple(jnp.asarray(s, jnp.int32) for s in starts)
    win = jax.lax.dynamic_slice(arr, starts, access.window)
    return _squeeze(win)


def _load_tiles(env: Env, p: ir.Pattern, idx_stack: Tuple) -> None:
    # tensor tile-loads first, then pattern-valued stages (which may read
    # the freshly loaded tiles) -- the metapipeline stage order
    loads = sorted(p.loads, key=lambda t: isinstance(t.src, ir.Pattern))
    for tc in loads:
        arr = env.resolve(tc.src, idx_stack)
        starts = tuple(jnp.asarray(s, jnp.int32)
                       for s in tc.index_map(*idx_stack))
        tile = jax.lax.dynamic_slice(arr, starts, tc.tile_shape)
        env.bind(tc, tile)


def _windows(env: Env, p: ir.Pattern, idx_stack: Tuple):
    return [_read_window(env, a, idx_stack) for a in p.accesses]


# --------------------------------------------------------------------------
# Per-pattern evaluators.  Each returns the pattern's realized value:
#   Map          -> array of shape domain + elem_shape
#   MultiFold    -> array of range_shape
#   FlatMap      -> (buffer, count)
#   GroupByFold  -> dense (num_keys,)+elem_shape accumulator
# --------------------------------------------------------------------------


def _execute_map(p: ir.Map, env: Env, outer_idx: Tuple) -> Any:
    n = p.trip_count

    def body(flat_i):
        idx = _unflatten(flat_i, p.domain)
        stack = outer_idx + idx
        sub = Env(env.inputs)
        sub.bindings = dict(env.bindings)
        _load_tiles(sub, p, stack)
        if p.inner is not None:
            val = _execute(p.inner, sub, stack)
            if isinstance(p.inner, ir.FlatMap):
                raise TypeError("FlatMap cannot nest inside Map (dynamic size)")
        else:
            val = p.fn(stack, *_windows(sub, p, stack))
        return jnp.asarray(val)

    vals = jax.vmap(body)(jnp.arange(n, dtype=jnp.int32))
    return vals.reshape(tuple(p.domain) + vals.shape[1:])


def _execute_multifold(p: ir.MultiFold, env: Env, outer_idx: Tuple,
                       flat_range: Optional[Tuple[int, int]] = None) -> Any:
    acc0 = jnp.asarray(p.init())
    assert acc0.shape == tuple(p.range_shape), (
        f"init shape {acc0.shape} != range {p.range_shape}")
    lo, hi = flat_range if flat_range is not None else (0, p.trip_count)
    upd_shape = tuple(p.update_shape)

    def body(flat_i, acc):
        idx = _unflatten(flat_i, p.domain)
        stack = outer_idx + idx
        sub = Env(env.inputs)
        sub.bindings = dict(env.bindings)
        _load_tiles(sub, p, stack)
        starts = tuple(jnp.asarray(s, jnp.int32)
                       for s in p.out_index_map(*stack))
        acc_slice = jax.lax.dynamic_slice(acc, starts, upd_shape)
        if p.inner is not None:
            partial = _execute(p.inner, sub, stack)
            partial = jnp.asarray(partial).reshape(upd_shape)
            if p.combine is None:  # write-once (tiled Map), paper's "(_)"
                new = partial
            else:
                new = p.combine(acc_slice, partial)
        else:
            new = p.fn(stack, acc_slice, *_windows(sub, p, stack))
        new = jnp.asarray(new, acc.dtype).reshape(upd_shape)
        return jax.lax.dynamic_update_slice(acc, new, starts)

    return jax.lax.fori_loop(lo, hi, body, acc0)


def _execute_multifold_parallel(p: ir.MultiFold, env: Env, outer_idx: Tuple,
                                num_partials: int) -> Any:
    """Fold ``num_partials`` contiguous chunks of the (row-major flattened)
    domain independently from ``init``, then merge with ``combine`` --
    validates that combine is associative with identity ``init`` (the
    parallel-partials path the FPGA reduction tree exploits)."""
    assert p.combine is not None, "write-once MultiFold has no combine"
    n = p.trip_count
    assert n % num_partials == 0
    chunk = n // num_partials
    partials = [
        _execute_multifold(p, env, outer_idx,
                           flat_range=(c * chunk, (c + 1) * chunk))
        for c in range(num_partials)
    ]
    out = partials[0]
    for q in partials[1:]:
        out = p.combine(out, q)
    return out


def _execute_flatmap(p: ir.FlatMap, env: Env, outer_idx: Tuple) -> Any:
    n = p.trip_count
    m = p.max_per_iter
    cap = n * m
    buf0 = jnp.zeros((cap,) + tuple(p.elem_shape),
                     dtype=jnp.result_type(p.dtype))

    def body(flat_i, carry):
        buf, count = carry
        idx = _unflatten(flat_i, p.domain)
        stack = outer_idx + idx
        sub = Env(env.inputs)
        sub.bindings = dict(env.bindings)
        _load_tiles(sub, p, stack)
        if p.inner is not None:
            vals, cnt = _execute(p.inner, sub, stack)
        else:
            vals, cnt = p.fn(stack, *_windows(sub, p, stack))
        vals = jnp.asarray(vals).reshape((-1,) + tuple(p.elem_shape))
        k = vals.shape[0]
        local = jnp.arange(k, dtype=jnp.int32)
        # invalid lanes scatter out of bounds and are dropped
        dest = jnp.where(local < cnt, count + local, cap)
        buf = buf.at[dest].set(vals, mode="drop")
        return (buf, count + jnp.asarray(cnt, jnp.int32))

    return jax.lax.fori_loop(0, n, body, (buf0, jnp.int32(0)))


def _execute_groupbyfold(p: ir.GroupByFold, env: Env, outer_idx: Tuple) -> Any:
    acc0 = jnp.asarray(p.init())
    assert acc0.shape == (p.num_keys,) + tuple(p.elem_shape)
    n = p.trip_count

    def body(flat_i, acc):
        idx = _unflatten(flat_i, p.domain)
        stack = outer_idx + idx
        sub = Env(env.inputs)
        sub.bindings = dict(env.bindings)
        _load_tiles(sub, p, stack)
        if p.inner is not None:
            # tiled form: inner yields a dense partial; combine keywise.
            # Correct because init is the identity of combine (required).
            partial = _execute(p.inner, sub, stack)
            return p.combine(acc, partial)
        key, val = p.fn(stack, *_windows(sub, p, stack))
        starts = (jnp.asarray(key, jnp.int32),) \
            + (jnp.int32(0),) * len(p.elem_shape)
        cur = jax.lax.dynamic_slice(acc, starts,
                                    (1,) + tuple(p.elem_shape))
        new = p.combine(cur[0], jnp.asarray(val, acc.dtype))
        new = jnp.asarray(new, acc.dtype).reshape((1,) + tuple(p.elem_shape))
        return jax.lax.dynamic_update_slice(acc, new, starts)

    return jax.lax.fori_loop(0, n, body, acc0)


def _execute(p: ir.Pattern, env: Env, outer_idx: Tuple) -> Any:
    if isinstance(p, ir.Map):
        return _execute_map(p, env, outer_idx)
    if isinstance(p, ir.MultiFold):
        return _execute_multifold(p, env, outer_idx)
    if isinstance(p, ir.FlatMap):
        return _execute_flatmap(p, env, outer_idx)
    if isinstance(p, ir.GroupByFold):
        return _execute_groupbyfold(p, env, outer_idx)
    raise TypeError(f"unknown pattern {type(p)}")


def execute(p: ir.Pattern, inputs: Dict[str, Any], *,
            parallel_partials: Optional[int] = None) -> Any:
    """Evaluate pattern ``p`` with concrete ``inputs`` (name -> array)."""
    env = Env({k: jnp.asarray(v) for k, v in inputs.items()})
    if parallel_partials and isinstance(p, ir.MultiFold):
        return _execute_multifold_parallel(p, env, (), parallel_partials)
    return _execute(p, env, ())


def jit_execute(p: ir.Pattern):
    """A jitted closure over the pattern (inputs as kwargs)."""

    @jax.jit
    def run(**inputs):
        return execute(p, inputs)

    return run
