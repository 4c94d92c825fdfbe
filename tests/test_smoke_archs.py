"""Per-architecture smoke tests: reduced config, one forward/train step
on CPU, asserting output shapes and no NaNs; plus one decode step."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_config
from repro.models import model
from repro.models.config import ModelConfig

BATCH, SEQ = 2, 32


def make_batch(cfg: ModelConfig, key):
    k1, k2, k3 = jax.random.split(key, 3)
    if cfg.n_codebooks:
        tokens = jax.random.randint(k1, (BATCH, SEQ, cfg.n_codebooks),
                                    0, cfg.vocab)
    else:
        tokens = jax.random.randint(k1, (BATCH, SEQ), 0, cfg.vocab)
    batch = {"tokens": tokens, "labels": tokens}
    if cfg.family == "vlm":
        batch["prefix_embeds"] = jax.random.normal(
            k3, (BATCH, cfg.frontend_tokens, cfg.d_model), jnp.float32)
    return batch


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_forward_shapes_no_nans(arch):
    cfg = get_config(arch, smoke=True)
    params = model.init_params(cfg, jax.random.PRNGKey(0))
    batch = make_batch(cfg, jax.random.PRNGKey(1))
    logits = jax.jit(lambda p, b: model.forward(p, cfg, b))(params, batch)
    s = SEQ + (cfg.frontend_tokens if cfg.family == "vlm" else 0)
    if cfg.n_codebooks:
        assert logits.shape == (BATCH, s, cfg.n_codebooks, cfg.vocab)
    else:
        assert logits.shape == (BATCH, s, cfg.vocab)
    assert not bool(jnp.any(jnp.isnan(logits.astype(jnp.float32))))


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_train_step_no_nans(arch):
    cfg = get_config(arch, smoke=True)
    params = model.init_params(cfg, jax.random.PRNGKey(0))
    batch = make_batch(cfg, jax.random.PRNGKey(1))

    @jax.jit
    def step(p, b):
        l, g = jax.value_and_grad(lambda pp: model.loss(pp, cfg, b))(p)
        return l, g

    l, g = step(params, batch)
    assert np.isfinite(float(l))
    flat = jax.tree.leaves(g)
    assert all(bool(jnp.all(jnp.isfinite(x.astype(jnp.float32))))
               for x in flat)


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_decode_step(arch):
    cfg = get_config(arch, smoke=True)
    params = model.init_params(cfg, jax.random.PRNGKey(0))
    cache = model.init_cache(cfg, BATCH, max_len=64)
    if cfg.n_codebooks:
        tok = jnp.zeros((BATCH, 1, cfg.n_codebooks), jnp.int32)
    else:
        tok = jnp.zeros((BATCH, 1), jnp.int32)
    step = jax.jit(lambda p, c, t, i: model.decode_step(p, cfg, c, t, i))
    logits, cache = step(params, cache, tok, jnp.int32(0))
    logits2, cache = step(params, cache, tok, jnp.int32(1))
    assert not bool(jnp.any(jnp.isnan(logits2.astype(jnp.float32))))


def test_param_counts_match_published():
    """Analytic param counts should land near the published sizes."""
    expect = {
        "starcoder2-15b": (15e9, 0.25),
        "nemotron-4-15b": (15e9, 0.30),   # large embed share
        "granite-3-2b": (2.5e9, 0.35),
        "qwen2-72b": (72e9, 0.15),
        "mamba2-370m": (370e6, 0.25),
        "mixtral-8x22b": (141e9, 0.15),
        "llama4-maverick-400b-a17b": (400e9, 0.20),
        "zamba2-2.7b": (2.7e9, 0.40),
        "musicgen-medium": (1.5e9, 0.5),
        "internvl2-1b": (0.9e9, 0.5),     # LM backbone only
        "mellum2-12b": (12e9, 0.05),      # all 64 experts counted
    }
    for arch, (want, tol) in expect.items():
        got = get_config(arch).param_count()
        assert abs(got - want) / want < tol, (arch, got, want)
