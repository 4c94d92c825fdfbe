"""Shared pure-JAX building blocks: norms, RoPE, activations, inits."""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


def rms_norm(x: jax.Array, w: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * (1.0 + w.astype(jnp.float32))).astype(dt)


def activation(name: str):
    if name == "squared_relu":          # Nemotron-4 / Primer
        return lambda x: jnp.square(jax.nn.relu(x))
    if name == "gelu":
        return jax.nn.gelu
    if name == "silu":
        return jax.nn.silu
    raise KeyError(name)


def yarn_inv_freq(dim: int, theta: float, yarn) -> np.ndarray:
    """YaRN's per-pair rotation frequencies (arXiv:2309.00071, as
    Hugging Face computes them): dimensions that turn fewer than
    ``beta_slow`` times over the original context are interpolated by
    ``factor``, those that turn more than ``beta_fast`` times keep
    their frequency, with a linear ramp between."""
    factor, orig, beta_fast, beta_slow, _ = yarn
    half = dim // 2
    pos_freqs = theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)

    def corr_dim(n_rot):
        return (dim * math.log(orig / (n_rot * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(corr_dim(beta_fast)), 0)
    high = min(math.ceil(corr_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(half) - low) / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp
    return (1.0 / (factor * pos_freqs) * (1.0 - keep)
            + 1.0 / pos_freqs * keep)


def rope(x: jax.Array, positions: jax.Array,
         theta: float = 1e4, yarn=None) -> jax.Array:
    """x: (..., S, H, D) rotary over D; positions: (..., S).  With
    ``yarn`` (factor, original_max_positions, beta_fast, beta_slow,
    attention_factor) the frequencies are YaRN's and cos/sin carry its
    attention factor."""
    d = x.shape[-1]
    half = d // 2
    if yarn is None:
        freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    else:
        freqs = jnp.asarray(yarn_inv_freq(d, theta, yarn), jnp.float32)
    ang = positions[..., None].astype(jnp.float32) * freqs      # (..,S,half)
    cos = jnp.cos(ang)[..., None, :]                            # (..,S,1,half)
    sin = jnp.sin(ang)[..., None, :]
    if yarn is not None:
        cos, sin = cos * yarn[4], sin * yarn[4]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin,
                           x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def dense_init(key, shape, in_axis: int = 0, dtype=jnp.bfloat16):
    fan_in = shape[in_axis]
    std = fan_in ** -0.5
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def embed_init(key, shape, dtype=jnp.bfloat16):
    return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dtype)


def causal_conv1d(x: jax.Array, w: jax.Array,
                  state: Optional[jax.Array] = None):
    """Depthwise causal conv (Mamba).  x: (B, S, C); w: (K, C).

    Returns (y, new_state) where state is the last K-1 inputs."""
    k = w.shape[0]
    if state is None:
        pad = jnp.zeros(x.shape[:-2] + (k - 1, x.shape[-1]), x.dtype)
    else:
        pad = state
    xp = jnp.concatenate([pad, x], axis=-2)          # (B, S+K-1, C)
    ys = sum(xp[..., i:i + x.shape[-2], :] * w[i] for i in range(k))
    new_state = xp[..., -(k - 1):, :]
    return ys.astype(x.dtype), new_state


def softmax_xent(logits: jax.Array, labels: jax.Array,
                 z_loss: float = 1e-4) -> jax.Array:
    """Mean token cross-entropy with optional z-loss, fp32 accumulate."""
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None],
                               axis=-1).squeeze(-1)
    loss = lse - gold
    if z_loss:
        loss = loss + z_loss * jnp.square(lse)
    return loss.mean()


def scan_layers(body, carry, xs, unroll: bool = False):
    """lax.scan over stacked layer params, or a Python unroll (used by
    the dry-run's cost extrapolation: XLA cost analysis counts a while
    body once, but counts unrolled layers individually)."""
    import jax

    if not unroll:
        return jax.lax.scan(body, carry, xs)
    n = jax.tree.leaves(xs)[0].shape[0]
    ys = []
    for i in range(n):
        sl = jax.tree.map(lambda t: t[i], xs)
        carry, y = body(carry, sl)
        ys.append(y)
    if ys and ys[0] is not None:
        import jax.numpy as jnp
        ys = jax.tree.map(lambda *ts: jnp.stack(ts), *ys)
    else:
        ys = None
    return carry, ys
