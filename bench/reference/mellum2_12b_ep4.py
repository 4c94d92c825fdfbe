"""Plain reference for mellum2-12b-ep4 as the configuration file states
it, and the random weights both it and the program run on.

The forward pass is straightforward ``jax.numpy`` in float32 with
``jax.default_matmul_precision("highest")``: the embedding, then per
layer an RMS norm, grouped-query attention (32 query heads over 4 KV
heads of 128, no bias, scale ``head_dim**-0.5``), the residual add, an
RMS norm and the MoE layer, the residual add; a final norm and the
untied output head.  ``layer_types`` says which layers attend within a
sliding window of ``sliding_window`` positions (a query at ``i`` sees
keys ``j`` with ``i - window < j <= i``) with default RoPE, and which
attend over the whole prefix with YaRN RoPE (frequencies and
attention factor from ``rope_parameters``; both rotate halves).

The MoE layer is this chip's share: the router scores all
``router_experts`` (64) experts, softmax, the top ``num_experts_per_tok``
renormalised to sum to one; the layer output is the gated sum of the
SwiGLU experts held here (``num_experts``, experts 0-15) over the
tokens that chose them.  What the other chips' experts would add is
left out, as the program leaves it out.  No QK norm and no MTP head
(see the file's ``assumed``).

It walks the layers in groups of the ``layer_types`` period with a
scan, and the queries in blocks, so it fits on the chip beside nothing
else.  It imports nothing of the program.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# The one number compared (the driver, ``serve_waves_moe``): the mean,
# over every served position of the sampled requests, of the gap by
# which the served token's reference logit lies below the reference's
# best.  Not the widest gap: with random routers, the 8th and 9th
# expert of a token often lie within bf16 rounding of each other, and
# a flip moves that token's logits by up to ~0.1, about as far as the
# float8 control's worst positions (widest gaps on a TPU v5e:
# program 0.032-0.125 over 14 seeds, float8 0.119-0.288 over 5).  A
# flip is rare per position, so the mean stays small for bf16 and
# grows with float8's error at every position: program 0-0.00245 over
# 9 seeds, float8 0.0111-0.0213 over 3 (means, TPU v5e).
# 0.005 lies between, twice the program's largest and half the
# control's smallest.  Readings are in PERF.md.
LIMITS = {"logit_gap": 0.005}

Q_BLOCK = 512
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def seed_key(seed: int) -> jax.Array:
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def _dims(cfg):
    return (cfg["hidden_size"], cfg["moe_intermediate_size"],
            cfg["num_hidden_layers"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"], cfg["vocab_size"],
            cfg["num_experts"], cfg["router_experts"])


def weight_shapes(cfg: dict) -> Dict[str, Tuple[Tuple[int, ...], str, int]]:
    """name -> (shape, kind, fan_in) of every weight, stacked over
    layers, in the order they are drawn."""
    d, f, nl, hq, hkv, dh, v, held, ne = _dims(cfg)
    return {
        "embed": ((v, d), "embed", d),
        "final_norm": ((d,), "norm", d),
        "ln1": ((nl, d), "norm", d),
        "ln2": ((nl, d), "norm", d),
        "lm_head": ((d, v), "dense", d),
        "wq": ((nl, d, hq * dh), "dense", d),
        "wk": ((nl, d, hkv * dh), "dense", d),
        "wv": ((nl, d, hkv * dh), "dense", d),
        "wo": ((nl, hq * dh, d), "dense", hq * dh),
        "moe_router": ((nl, d, ne), "dense", d),
        "moe_we1": ((nl, held, d, f), "dense", d),
        "moe_we3": ((nl, held, d, f), "dense", d),
        "moe_we2": ((nl, held, f, d), "dense", f),
    }


@functools.partial(jax.jit, static_argnums=(1, 2))
def _draw(key, shape, std):
    """One weight: N(0, std**2) in float32, stored as bfloat16; a stack
    of three or more dimensions is drawn one layer at a time (layer
    ``l`` from ``fold_in(key, l)``), so only one layer's float32 draw
    is ever on the device."""
    def one(k, shp):
        return (jax.random.normal(k, shp, jnp.float32) * std
                ).astype(jnp.bfloat16)

    if len(shape) < 3:
        return one(key, shape)
    return jax.lax.map(lambda i: one(jax.random.fold_in(key, i), shape[1:]),
                       jnp.arange(shape[0]))


def init_weights(cfg: dict, seed: int) -> Dict[str, jax.Array]:
    """The served weights: bfloat16, drawn on the device from the seed,
    weight ``i`` of ``weight_shapes`` from ``fold_in(key, i)``.  Norm
    gains are stored as offsets from 1."""
    key = seed_key(seed)
    out = {}
    for i, (name, (shape, kind, fan_in)) in enumerate(
            weight_shapes(cfg).items()):
        std = {"embed": 0.02, "norm": 0.05}.get(kind, fan_in ** -0.5)
        out[name] = _draw(jax.random.fold_in(key, i), shape, std)
    return out


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


def inv_freq(rope: dict, dim: int) -> Tuple[np.ndarray, float]:
    """Rotation frequency of each of the ``dim / 2`` pairs and the
    factor cos and sin are scaled by, for a ``rope_parameters`` entry:
    ``default`` is ``theta ** (-2i / dim)``; ``yarn`` (arXiv:2309.00071)
    blends that with its ``factor``-interpolated version, keeping the
    pairs that turn more than ``beta_fast`` times over the original
    context, interpolating those that turn fewer than ``beta_slow``
    times, and a linear ramp between."""
    theta = float(rope["rope_theta"])
    base = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if rope["rope_type"] == "default":
        return base, 1.0
    assert rope["rope_type"] == "yarn", rope
    orig = rope["original_max_position_embeddings"]

    def dim_turning(turns):
        # the pair index whose wavelength fits ``turns`` times in orig
        return (dim * math.log(orig / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    lo = max(math.floor(dim_turning(rope["beta_fast"])), 0)
    hi = min(math.ceil(dim_turning(rope["beta_slow"])), dim - 1)
    if lo == hi:
        hi += 0.001
    interp = np.clip((np.arange(dim // 2) - lo) / (hi - lo), 0.0, 1.0)
    freq = base / rope["factor"] * interp + base * (1.0 - interp)
    return freq, float(rope["attention_factor"])


def _rope(x, pos, freq, scale):
    half = x.shape[-1] // 2
    ang = pos[:, None].astype(jnp.float32) * freq           # (S, half)
    cos = jnp.cos(ang)[:, None, :] * scale
    sin = jnp.sin(ang)[:, None, :] * scale
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _period(cfg):
    """The repeating pattern of ``layer_types``: True where the layer
    attends in a sliding window."""
    types = cfg["layer_types"]
    for p in range(1, len(types) + 1):
        if len(types) % p == 0 and types == types[:p] * (len(types) // p):
            return tuple(t == "sliding_attention" for t in types[:p])


def _forward_one(w, cfg_t, tokens, targets, fp8: bool):
    """One sequence: the best logit, the logits of each target row and
    the argmax at every position."""
    (d, f, nl, hq, hkv, dh, v, held, ne, eps, top_k, window, period,
     ropes) = cfg_t
    mm = (lambda a, b: _q8(a, -1) @ b) if fp8 else (lambda a, b: a @ b)
    s = tokens.shape[0]
    pos = jnp.arange(s)
    x = w["embed"][tokens].astype(jnp.float32)
    g = hq // hkv
    nb = s // Q_BLOCK if s % Q_BLOCK == 0 else 1
    qb = s // nb

    def attention(h, lw, sliding):
        freq, scale = ropes[0] if sliding else ropes[1]
        freq = jnp.asarray(freq, jnp.float32)
        q = _rope(mm(h, lw["wq"]).reshape(s, hq, dh), pos, freq, scale)
        k = _rope(mm(h, lw["wk"]).reshape(s, hkv, dh), pos, freq, scale)
        vv = mm(h, lw["wv"]).reshape(s, hkv, dh)
        qs = q.reshape(nb, qb, hkv, g, dh)

        def block(i):
            sc = jnp.einsum("qkgd,tkd->kgqt", qs[i], k) * dh ** -0.5
            qpos = i * qb + jnp.arange(qb)
            seen = pos[None, :] <= qpos[:, None]
            if sliding:
                seen &= pos[None, :] > qpos[:, None] - window
            sc = jnp.where(seen, sc, -jnp.inf)
            return jnp.einsum("kgqt,tkd->qkgd", jax.nn.softmax(sc, -1), vv)

        a = jax.lax.map(block, jnp.arange(nb)).reshape(s, hq * dh)
        return mm(a, lw["wo"])

    def experts(h, lw):
        """The held experts' gated sum, over query blocks."""
        logits = mm(h, lw["moe_router"])                     # (S, ne)
        probs = jax.nn.softmax(logits, -1)
        topv, topi = jax.lax.top_k(probs, top_k)
        gates = topv / topv.sum(-1, keepdims=True)
        # each token's gate on each held expert, 0 where not chosen
        gate = jnp.einsum("tk,tke->te", gates,
                          jax.nn.one_hot(topi, ne)[..., :held])
        hs, gs = h.reshape(nb, qb, d), gate.reshape(nb, qb, held)

        def block(args):
            hb, gb = args
            if fp8:
                hb = _q8(hb, -1)
            up = jnp.einsum("td,edf->tef", hb, lw["moe_we1"])
            gt = jnp.einsum("td,edf->tef", hb, lw["moe_we3"])
            act = jax.nn.silu(up) * gt                        # (qb, H, F)
            if fp8:
                act = _q8(act, -1)
            out = jnp.einsum("tef,efd->ted", act, lw["moe_we2"])
            return jnp.einsum("ted,te->td", out, gb)

        return jax.lax.map(block, (hs, gs)).reshape(s, d)

    def super_layer(x, lws):
        for i, sliding in enumerate(period):
            lw = {k: t[i].astype(jnp.float32) for k, t in lws.items()}
            if fp8:
                lw = {k: (t if k.startswith("ln") else _q8(t))
                      for k, t in lw.items()}
            x = x + attention(_rms(x, lw["ln1"], eps), lw, sliding)
            x = x + experts(_rms(x, lw["ln2"], eps), lw)
        return x, None

    names = ("ln1", "ln2", "wq", "wk", "wv", "wo", "moe_router", "moe_we1",
             "moe_we3", "moe_we2")
    p = len(period)
    stack = {k: w[k].reshape((nl // p, p) + w[k].shape[1:]) for k in names}
    x, _ = jax.lax.scan(super_layer, x, stack)
    x = _rms(x, w["final_norm"].astype(jnp.float32), eps)
    head = w["lm_head"].astype(jnp.float32)
    if fp8:
        head = _q8(head)
    xs = x.reshape(nb, qb, d)
    ts = targets.reshape(targets.shape[0], nb, qb).transpose(1, 0, 2)

    def logits_block(args):
        xb, tb = args
        lg = mm(xb, head)                                 # (qb, V)
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
    ropes = cfg["rope_parameters"]
    hd = cfg["head_dim"]

    def rope(kind):
        freq, scale = inv_freq(ropes[kind], hd)
        return tuple(float(x) for x in freq), scale

    return _dims(cfg) + (
        float(cfg["rms_norm_eps"]), int(cfg["num_experts_per_tok"]),
        int(cfg["sliding_window"]), _period(cfg),
        (rope("sliding_attention"), rope("full_attention")))


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
