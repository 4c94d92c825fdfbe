"""Unified model API: every architecture family behind one interface.

    shapes  = model.param_shapes(cfg)
    params  = model.init_params(cfg, key)        (smoke/real runs)
    specs   = model.param_specs(cfg)             (dry-run, no alloc)
    logits  = model.forward(params, cfg, batch)
    loss    = model.loss(params, cfg, batch)
    logits, cache = model.decode_step(params, cfg, cache, tokens, idx)
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from . import hybrid as hy
from . import layers as L
from . import ssm as ssm_mod
from . import transformer as tr
from .config import ModelConfig
from .sharding import hint_first

Params = Dict[str, Any]
Batch = Dict[str, jax.Array]


# --------------------------------------------------------------- shapes
def param_shapes(cfg: ModelConfig):
    if cfg.family in ("dense", "moe", "audio", "vlm"):
        return tr.param_shapes(cfg)
    if cfg.family == "ssm":
        d, v = cfg.d_model, cfg.padded_vocab
        shapes = {
            "embed": ((v, d), "embed"),
            "lm_head": ((d, v), "dense"),
            "final_norm": ((d,), "zeros"),
        }
        shapes.update(ssm_mod.block_param_shapes(cfg, cfg.n_layers, "m_"))
        return shapes
    if cfg.family == "hybrid":
        return hy.param_shapes(cfg)
    raise KeyError(cfg.family)


def param_specs(cfg: ModelConfig) -> Params:
    dt = jnp.dtype(cfg.dtype)
    out = {}
    for k, (s, kind) in param_shapes(cfg).items():
        d = jnp.float32 if k in ("m_A_log", "m_D", "m_dt_bias") else dt
        out[k] = jax.ShapeDtypeStruct(s, d)
    return out


def init_params(cfg: ModelConfig, key: jax.Array) -> Params:
    dt = jnp.dtype(cfg.dtype)
    out = {}
    shapes = param_shapes(cfg)
    keys = jax.random.split(key, len(shapes))
    for (name, (shape, kind)), k in zip(sorted(shapes.items()), keys):
        d = jnp.float32 if name in ("m_A_log", "m_D", "m_dt_bias") else dt
        if kind == "zeros":
            out[name] = jnp.zeros(shape, d)
        elif kind == "embed":
            out[name] = L.embed_init(k, shape, d)
        else:
            in_axis = -2 if len(shape) >= 2 else 0
            out[name] = L.dense_init(k, shape, in_axis=in_axis, dtype=d)
    if "m_A_log" in out:  # stable decay init: A in [-e, -1/e]
        out["m_A_log"] = jnp.zeros_like(out["m_A_log"]) - 0.5
    return out


# -------------------------------------------------------------- forward
def _ssm_forward(params: Params, cfg: ModelConfig,
                 tokens: jax.Array) -> jax.Array:
    x = jnp.take(params["embed"], tokens, axis=0)
    stacks = {k: v for k, v in params.items() if k.startswith("m_")}

    def body(x, slc):
        x, _ = ssm_mod.block_forward(slc, x, cfg, prefix="m_")
        return x, None

    if cfg.remat:
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.nothing_saveable)
    x, _ = L.scan_layers(body, x, stacks, cfg.unroll)
    x = L.rms_norm(x, params["final_norm"])
    return jnp.einsum("bsd,dv->bsv", x, params["lm_head"])


def forward(params: Params, cfg: ModelConfig, batch: Batch) -> jax.Array:
    tokens = batch["tokens"]
    if cfg.family in ("dense", "moe", "audio"):
        return tr.forward(params, cfg, tokens)
    if cfg.family == "vlm":
        return tr.forward(params, cfg, tokens,
                          prefix_embeds=batch.get("prefix_embeds"))
    if cfg.family == "ssm":
        return _ssm_forward(params, cfg, tokens)
    if cfg.family == "hybrid":
        return hy.forward(params, cfg, tokens)
    raise KeyError(cfg.family)


def mask_vocab_pad(logits: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Pad vocab columns never win: masked to -1e30 (exact for both
    softmax-xent and argmax decode)."""
    if cfg.vocab_pad == 0:
        return logits
    col = jnp.arange(logits.shape[-1])
    return jnp.where(col >= cfg.vocab, jnp.asarray(-1e30, logits.dtype),
                     logits)


def loss(params: Params, cfg: ModelConfig, batch: Batch) -> jax.Array:
    logits = mask_vocab_pad(forward(params, cfg, batch), cfg)
    if cfg.n_codebooks:
        logits = hint_first(logits, [("data", None, None, "model"),
                                     ("data", "model", None, None)])
    else:
        logits = hint_first(logits, [("data", None, "model"),
                                     ("data", "model", None)])
    labels = batch["labels"]
    if cfg.family == "vlm" and "prefix_embeds" in batch:
        # loss only on text positions (frontend prefix is unlabeled)
        p = batch["prefix_embeds"].shape[1]
        logits = logits[:, p:]
    if cfg.n_codebooks:
        # (B, S, n_cb, V) vs labels (B, S, n_cb)
        return L.softmax_xent(logits, labels)
    return L.softmax_xent(logits, labels)


# --------------------------------------------------------------- decode
def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> Dict:
    if cfg.family in ("dense", "moe", "audio", "vlm"):
        return tr.init_cache(cfg, batch, max_len)
    if cfg.family == "ssm":
        return {"ssm": ssm_mod.init_state(cfg, batch)}
    if cfg.family == "hybrid":
        return hy.init_cache(cfg, batch, max_len)
    raise KeyError(cfg.family)


def cache_specs(cfg: ModelConfig, batch: int, max_len: int) -> Dict:
    if cfg.family in ("dense", "moe", "audio", "vlm"):
        return tr.cache_specs(cfg, batch, max_len)
    if cfg.family == "ssm":
        return {"ssm": ssm_mod.state_specs(cfg, batch)}
    if cfg.family == "hybrid":
        return hy.cache_specs(cfg, batch, max_len)
    raise KeyError(cfg.family)


def _ssm_decode(params: Params, cfg: ModelConfig, cache: Dict,
                tokens: jax.Array, index: jax.Array):
    x = jnp.take(params["embed"], tokens, axis=0)
    stacks = {k: v for k, v in params.items() if k.startswith("m_")}

    def body(x, slices):
        slc, conv_st, ssm_st = slices
        x, st = ssm_mod.block_forward(
            slc, x, cfg, state={"conv": conv_st, "ssm": ssm_st},
            prefix="m_")
        return x, (st["conv"], st["ssm"])

    x, (nc, ns) = L.scan_layers(
        body, x, (stacks, cache["ssm"]["conv"], cache["ssm"]["ssm"]),
        cfg.unroll)
    x = L.rms_norm(x, params["final_norm"])
    logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"])
    return logits, {"ssm": {"conv": nc, "ssm": ns}}


def decode_step(params: Params, cfg: ModelConfig, cache: Dict,
                tokens: jax.Array, index: jax.Array,
                moe_impl: str = "capacity"):
    """``moe_impl`` picks the MoE layer of attention families
    (``transformer._block``); the other families have none."""
    if cfg.family in ("dense", "moe", "audio", "vlm"):
        return tr.decode_step(params, cfg, cache, tokens, index, moe_impl)
    if cfg.family == "ssm":
        return _ssm_decode(params, cfg, cache, tokens, index)
    if cfg.family == "hybrid":
        return hy.decode_step(params, cfg, cache, tokens, index)
    raise KeyError(cfg.family)
