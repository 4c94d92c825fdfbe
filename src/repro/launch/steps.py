"""Train / prefill / serve step builders + input specs for every
(architecture x shape) cell.  Pure functions of (cfg, shape): the
dry-run lowers them against ShapeDtypeStructs; real runs jit them
against concrete arrays.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.configs.shapes import ShapeConfig
from repro.core import telemetry
from repro.models import model
from repro.models.config import ModelConfig
from repro.optim import adamw


# ------------------------------------------------------------ input specs
def input_specs(cfg: ModelConfig, shape: ShapeConfig) -> Dict[str, Any]:
    """ShapeDtypeStruct stand-ins for every model input (no allocation).

    train/prefill: the token batch (+ frontend stub embeddings).
    decode: one new token (+ scalar position index); the KV cache is a
    separate donated argument (see cache specs).
    """
    gb, s = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    if shape.kind in ("train", "prefill"):
        if cfg.n_codebooks:
            toks = jax.ShapeDtypeStruct((gb, s, cfg.n_codebooks), i32)
        elif cfg.family == "vlm":
            toks = jax.ShapeDtypeStruct((gb, s - cfg.frontend_tokens), i32)
        else:
            toks = jax.ShapeDtypeStruct((gb, s), i32)
        out = {"tokens": toks}
        if cfg.family == "vlm":
            out["prefix_embeds"] = jax.ShapeDtypeStruct(
                (gb, cfg.frontend_tokens, cfg.d_model), jnp.bfloat16)
        if shape.kind == "train":
            out["labels"] = jax.ShapeDtypeStruct(toks.shape, i32)
        return out
    # decode
    if cfg.n_codebooks:
        toks = jax.ShapeDtypeStruct((gb, 1, cfg.n_codebooks), i32)
    else:
        toks = jax.ShapeDtypeStruct((gb, 1), i32)
    return {"tokens": toks}


def decode_extras(cfg: ModelConfig, shape: ShapeConfig):
    cache = model.cache_specs(cfg, shape.global_batch, shape.seq_len)
    index = jax.ShapeDtypeStruct((), jnp.int32)
    return cache, index


# ------------------------------------------------------------ step fns
def make_train_step(cfg: ModelConfig, opt_cfg: adamw.AdamWConfig,
                    microbatches: int = 1, grad_shardings=None):
    """Train step, optionally with gradient accumulation: the global
    batch splits into ``microbatches`` slices processed sequentially
    (scan), with fp32 grad accumulators sharded like the params.  Peak
    activation/temp memory drops ~linearly; total FLOPs/bytes are
    unchanged -- this is what makes the 72B train_4k cell *fit* 16 GB
    HBM (EXPERIMENTS.md §Perf).

    ``grad_shardings`` (a pytree of NamedShardings like the params) pins
    the fp32 accumulators carried through the microbatch loop: without
    it GSPMD keeps them only TP-sharded (58 GB of stacked f32 grads for
    qwen2-72b -- the §Perf iteration log has the story)."""
    with telemetry.span("steps.build.train", family=cfg.family,
                        microbatches=microbatches):
        return _make_train_step_body(cfg, opt_cfg, microbatches,
                                     grad_shardings)


def _make_train_step_body(cfg: ModelConfig, opt_cfg: adamw.AdamWConfig,
                          microbatches: int, grad_shardings):
    def _pin(tree):
        if grad_shardings is None:
            return tree
        return jax.tree.map(jax.lax.with_sharding_constraint, tree,
                            grad_shardings)

    def train_step(params, opt_state, batch):
        if microbatches == 1:
            loss, grads = jax.value_and_grad(
                lambda p: model.loss(p, cfg, batch))(params)
        else:
            def split(x):
                return x.reshape((microbatches,
                                  x.shape[0] // microbatches)
                                 + x.shape[1:])

            mb = jax.tree.map(split, batch)

            def acc_step(carry, mslice):
                loss_acc, gacc = carry
                l, g = jax.value_and_grad(
                    lambda p: model.loss(p, cfg, mslice))(params)
                gacc = jax.tree.map(
                    lambda a, gg: a + gg.astype(jnp.float32), gacc, g)
                return (loss_acc + l, _pin(gacc)), None

            zeros = _pin(jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params))
            carry = (jnp.zeros((), jnp.float32), zeros)
            if cfg.unroll:  # dry-run cost extrapolation sees every step
                for i in range(microbatches):
                    carry, _ = acc_step(
                        carry, jax.tree.map(lambda x: x[i], mb))
                loss_sum, gsum = carry
            else:
                (loss_sum, gsum), _ = jax.lax.scan(acc_step, carry, mb)
            loss = loss_sum / microbatches
            grads = jax.tree.map(lambda g: g / microbatches, gsum)
        new_params, new_opt = adamw.update(grads, opt_state, params,
                                           opt_cfg)
        return loss, new_params, new_opt

    return train_step


def make_prefill_step(cfg: ModelConfig):
    with telemetry.span("steps.build.prefill", family=cfg.family):
        def prefill_step(params, batch):
            logits = model.forward(params, cfg, batch)
            # serving prefill hands off to decode: only the last
            # position's logits leave the step (full logits never hit
            # HBM as output)
            return logits[:, -1]

        return prefill_step


def make_serve_step(cfg: ModelConfig):
    with telemetry.span("steps.build.serve", family=cfg.family):
        def serve_step(params, cache, tokens, index):
            logits, new_cache = model.decode_step(params, cfg, cache,
                                                  tokens, index)
            logits = model.mask_vocab_pad(logits, cfg)
            # greedy next token (sampling lives in the server loop)
            nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
            return nxt, new_cache

        return serve_step


def make_cache_prefill_step(cfg: ModelConfig, moe_impl: str = "capacity"):
    """Prefill a whole prompt block into the decode cache in ONE jitted
    call: ``(params, cache, tokens(B, S[, ncb]), index) -> (next, cache)``
    with ``next`` the greedy token after the final prompt position.
    ``moe_impl`` picks the MoE layer (``transformer._block``): serving
    prefills through the dropless share.

    Attention families run the block through ``decode_step`` directly
    (S tokens written to the cache contiguously, causal within the
    block); recurrent families (ssm, hybrid) carry per-token state, so
    the block scans token-by-token *inside* the jit -- still one
    compiled call per prompt length, not one dispatch per token.  The
    block must not wrap the KV ring buffer; callers chunk long prompts
    at the ring boundary (``launch.serve`` does).
    """
    with telemetry.span("steps.build.cache_prefill", family=cfg.family):
        return _make_cache_prefill_body(cfg, moe_impl)


def _make_cache_prefill_body(cfg: ModelConfig, moe_impl: str):
    block = cfg.family in ("dense", "moe", "audio", "vlm")

    def _greedy(logits):
        logits = model.mask_vocab_pad(logits, cfg)
        return jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)

    def serve_prefill(params, cache, tokens, index):
        if block:
            logits, cache2 = model.decode_step(params, cfg, cache,
                                               tokens, index, moe_impl)
            return _greedy(logits), cache2

        def body(carry, tok):
            cache, i = carry
            # restore the step's token axis the scan consumed
            tok = tok[:, None] if tok.ndim == 1 else tok[:, None, :]
            logits, cache = model.decode_step(params, cfg, cache, tok, i)
            return (cache, i + 1), _greedy(logits)

        xs = jnp.moveaxis(tokens, 1, 0)   # (S, B[, ncb])
        (cache2, _), nxts = jax.lax.scan(
            body, (cache, jnp.asarray(index, jnp.int32)), xs)
        return nxts[-1], cache2

    return serve_prefill
