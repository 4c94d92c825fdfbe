"""Plain reference for the TPC-H Q6 stand-in, and the table it runs on.

The stand-in (the configuration's ``predicate``) is
``sum(price * disc) where lo <= qty < hi`` over three float32 columns.
The table is made on the device from the seed, one partition at a
time; the reference sums each block of rows on the device in float32
and adds the blocks' sums on the host in float64.  It imports nothing
of the program.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# The one number compared: the largest relative error of a query's
# answer against this reference.  Readings and the reasons for the
# limit are in PERF.md ("Correctness").
LIMITS = {"rel_err": 2.5e-4}

COLUMNS = ("qty", "price", "disc")
BLOCK = 1 << 20


def seed_key(seed: int) -> jax.Array:
    """A key for any whole seed, including those past 32 bits."""
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


@functools.partial(jax.jit, static_argnums=(1,))
def _columns(key, rows):
    ks = jax.random.split(key, len(COLUMNS))
    return {c: jax.random.uniform(k, (rows,), jnp.float32)
            for c, k in zip(COLUMNS, ks)}


def make_columns(cfg: dict, seed: int, part: int, rows: int):
    """Partition ``part`` of the table: uniform [0, 1) float32 columns,
    the stand-in's own distributions, made in one jitted call."""
    return _columns(jax.random.fold_in(seed_key(seed), part), rows)


def _block_sums(cols, lo, hi, dtype):
    q, pr, dc = (cols[c].astype(dtype) for c in COLUMNS)
    n = q.shape[0]
    blk = min(BLOCK, n)
    q, pr, dc = (t.reshape(n // blk, blk) for t in (q, pr, dc))
    keep = (q >= jnp.asarray(lo, dtype)) & (q < jnp.asarray(hi, dtype))
    return jnp.sum(jnp.where(keep, pr * dc, jnp.zeros((), dtype)),
                   axis=1, dtype=dtype)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _sums_f32(cols, lo, hi):
    return _block_sums(cols, lo, hi, jnp.float32)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _sums_bf16(cols, lo, hi):
    return _block_sums(cols, lo, hi, jnp.bfloat16)


def answer(cols, cfg: dict) -> float:
    """The query's answer: float32 rows, blocks of 2**20 summed on the
    device, their sums added in float64."""
    pred = cfg["predicate"]
    sums = _sums_f32(cols, float(pred["lo"]), float(pred["hi"]))
    return float(np.sum(np.asarray(sums, np.float64)))


def control_answer(cols, cfg: dict) -> float:
    """The same query computed one precision below the configuration's
    float32: columns, products and block sums in bfloat16."""
    pred = cfg["predicate"]
    sums = _sums_bf16(cols, float(pred["lo"]), float(pred["hi"]))
    return float(np.sum(np.asarray(sums, np.float64)))
