"""Plain reference for granite-3-2b as the configuration file states it,
and the random weights both it and the program run on.

The forward pass is straightforward ``jax.numpy`` in float32 with
``jax.default_matmul_precision("highest")``: embedding (times
``embedding_multiplier``), then per layer an RMS norm, grouped-query
attention with rotary positions (halves rotated, scale
``attention_multiplier``), the residual add (times
``residual_multiplier``), an RMS norm and a SwiGLU feed-forward; a
final norm and the output head (divided by ``logits_scaling``).  It
walks the layers with a scan and the queries in blocks, so it fits on
the chip beside nothing else.  It imports nothing of the program.

Where the file's numbers depart from the published Granite config
(multipliers of 1, an untied head, eps 1e-6), the reference follows
the file: that is what the program runs.
"""
from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# The one number compared: the widest gap by which a served token's
# reference logit lies below the reference's best at that position.
# Readings and the reasons for the limit are in PERF.md.
LIMITS = {"logit_gap": 0.25}

Q_BLOCK = 512
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def seed_key(seed: int) -> jax.Array:
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def _dims(cfg):
    return (cfg["hidden_size"], cfg["intermediate_size"],
            cfg["num_hidden_layers"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"], cfg["vocab_size"])


def weight_shapes(cfg: dict) -> Dict[str, Tuple[Tuple[int, ...], str, int]]:
    """name -> (shape, kind, fan_in) of every weight, stacked over
    layers, in the order they are drawn."""
    d, f, nl, hq, hkv, dh, v = _dims(cfg)
    vp = v + int(cfg.get("vocab_pad", 0))
    return {
        "embed": ((vp, d), "embed", d),
        "final_norm": ((d,), "norm", d),
        "ln1": ((nl, d), "norm", d),
        "ln2": ((nl, d), "norm", d),
        "lm_head": ((d, vp), "dense", d),
        "wq": ((nl, d, hq * dh), "dense", d),
        "wk": ((nl, d, hkv * dh), "dense", d),
        "wv": ((nl, d, hkv * dh), "dense", d),
        "wo": ((nl, hq * dh, d), "dense", hq * dh),
        "w1": ((nl, d, f), "dense", d),
        "w3": ((nl, d, f), "dense", d),
        "w2": ((nl, f, d), "dense", f),
    }


@functools.partial(jax.jit, static_argnums=(1,))
def _init(key, spec):
    out = {}
    for i, (name, shape, kind, fan_in) in enumerate(spec):
        z = jax.random.normal(jax.random.fold_in(key, i), shape,
                              jnp.float32)
        std = {"embed": 0.02, "norm": 0.05}.get(kind, fan_in ** -0.5)
        out[name] = (z * std).astype(jnp.bfloat16)
    return out


def init_weights(cfg: dict, seed: int) -> Dict[str, jax.Array]:
    """The served weights: bfloat16, drawn on the device from the seed
    in one jitted call.  Norm gains are stored as offsets from 1."""
    spec = tuple((n, s, k, f) for n, (s, k, f) in weight_shapes(cfg).items())
    return _init(seed_key(seed), spec)


# ---------------------------------------------------------- forward
def _q8(t, axis=None):
    """float8 e4m3 with one scale per tensor (``axis=None``) or per
    row: the precision one step below bfloat16."""
    amax = jnp.max(jnp.abs(t), axis=axis, keepdims=axis is not None)
    s = jnp.maximum(amax, 1e-30) / F8_MAX
    return (t / s).astype(F8).astype(jnp.float32) * s


def _rms(x, w, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + w)


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * freqs          # (S, half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _forward_one(w, cfg_t, tokens, targets, fp8: bool):
    """One sequence: the best logit, the logits of each target row and
    the argmax at every position."""
    (d, f, nl, hq, hkv, dh, v, eps, theta, emb_m, att_m, res_m,
     logit_s) = cfg_t
    mm = (lambda a, b: _q8(a, -1) @ b) if fp8 else (lambda a, b: a @ b)
    s = tokens.shape[0]
    pos = jnp.arange(s)
    x = w["embed"][tokens].astype(jnp.float32) * emb_m
    g = hq // hkv
    nb = s // Q_BLOCK if s % Q_BLOCK == 0 else 1
    qb = s // nb

    def layer(x, lw):
        lw = {k: t.astype(jnp.float32) for k, t in lw.items()}
        if fp8:
            lw = {k: (t if k.startswith("ln") else _q8(t))
                  for k, t in lw.items()}
        h = _rms(x, lw["ln1"], eps)
        q = _rope(mm(h, lw["wq"]).reshape(s, hq, dh), pos, theta)
        k = _rope(mm(h, lw["wk"]).reshape(s, hkv, dh), pos, theta)
        vv = mm(h, lw["wv"]).reshape(s, hkv, dh)
        qs = q.reshape(nb, qb, hkv, g, dh)

        def block(i):
            sc = jnp.einsum("qkgd,tkd->kgqt", qs[i], k) * att_m
            qpos = i * qb + jnp.arange(qb)
            sc = jnp.where(pos[None, :] <= qpos[:, None], sc, -jnp.inf)
            return jnp.einsum("kgqt,tkd->qkgd", jax.nn.softmax(sc, -1), vv)

        a = jax.lax.map(block, jnp.arange(nb)).reshape(s, hq * dh)
        x = x + res_m * mm(a, lw["wo"])
        h = _rms(x, lw["ln2"], eps)
        ff = jax.nn.silu(mm(h, lw["w1"])) * mm(h, lw["w3"])
        return x + res_m * mm(ff, lw["w2"]), None

    stack = {k: w[k] for k in ("ln1", "ln2", "wq", "wk", "wv", "wo",
                               "w1", "w2", "w3")}
    x, _ = jax.lax.scan(layer, x, stack)
    x = _rms(x, w["final_norm"].astype(jnp.float32), eps)
    head = w["lm_head"][:, :v].astype(jnp.float32)
    if fp8:
        head = _q8(head)
    xs = x.reshape(nb, qb, d)
    ts = targets.reshape(targets.shape[0], nb, qb).transpose(1, 0, 2)

    def logits_block(args):
        xb, tb = args
        lg = mm(xb, head) / logit_s                     # (qb, V)
        picked = jnp.take_along_axis(lg[None], tb[..., None], -1)[..., 0]
        return lg.max(-1), picked, jnp.argmax(lg, -1).astype(jnp.int32)

    best, picked, top = jax.lax.map(logits_block, (xs, ts))
    return (best.reshape(s), picked.transpose(1, 0, 2).reshape(-1, s),
            top.reshape(s))


@functools.partial(jax.jit, static_argnums=(3, 4))
def _forward(w, tokens, targets, cfg_t, fp8):
    with jax.default_matmul_precision("highest"):
        return jax.lax.map(
            lambda a: _forward_one(w, cfg_t, a[0], a[1], fp8),
            (tokens, targets))


def _cfg_tuple(cfg):
    return _dims(cfg) + (
        float(cfg["rms_norm_eps"]), float(cfg["rope_theta"]),
        float(cfg["embedding_multiplier"]),
        float(cfg["attention_multiplier"]),
        float(cfg["residual_multiplier"]), float(cfg["logits_scaling"]))


def score(weights, cfg: dict, tokens: np.ndarray, targets: np.ndarray,
          fp8: bool = False):
    """``tokens`` (B, S) int32 and ``targets`` (B, K, S) int32 ->
    numpy ``best`` (B, S), ``picked`` (B, K, S) and ``top`` (B, S):
    the largest logit at each position, the logits of the K target
    tokens there, and the token that wins.  ``fp8`` computes every
    matmul operand in float8 (the control)."""
    best, picked, top = _forward(weights, jnp.asarray(tokens, jnp.int32),
                                 jnp.asarray(targets, jnp.int32),
                                 _cfg_tuple(cfg), bool(fp8))
    return np.asarray(best), np.asarray(picked), np.asarray(top)


def served_gaps(weights, cfg: dict, seqs, fp8_control: bool = False):
    """For served sequences ``[(prompt, served)]`` -- ``served`` holds
    every token the server produced for the prompt, in order -- the
    gap ``best - logit(served token)`` at each served position, and
    with ``fp8_control`` also the gap of the token the float8 forward
    puts first there.  Sequences are padded at the end to one length;
    attention is causal, so padding changes no earlier position."""
    length = max(len(p) + len(s) - 1 for p, s in seqs)
    if length > Q_BLOCK:
        length = -(-length // Q_BLOCK) * Q_BLOCK
    b = len(seqs)
    tokens = np.zeros((b, length), np.int32)
    nxt = np.zeros((b, length), np.int32)
    live = np.zeros((b, length), bool)
    for i, (p, s) in enumerate(seqs):
        full = np.concatenate([np.asarray(p), np.asarray(s)])
        tokens[i, :len(full) - 1] = full[:-1]
        nxt[i, :len(full) - 1] = full[1:]
        live[i, len(p) - 1:len(full) - 1] = True
    rows = [nxt]
    if fp8_control:
        _, _, top8 = score(weights, cfg, tokens, nxt[:, None], fp8=True)
        rows.append(top8)
    best, picked, _ = score(weights, cfg, tokens, np.stack(rows, 1))
    gaps = best[:, None, :] - picked                    # (B, K, S)
    served = gaps[:, 0][live]
    control = gaps[:, 1][live] if fp8_control else None
    return served, control
