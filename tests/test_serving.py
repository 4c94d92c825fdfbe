"""Serving path: one-call block prefill == token-by-token oracle, and
the mixed-prompt-length driver preserving request order.

``steps.make_cache_prefill_step`` runs attention families as a single
block ``decode_step`` and recurrent families as an in-jit token scan;
either way the resulting cache and next token must match feeding the
prompt one token at a time (the pre-ISSUE-8 serve loop).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.launch import serve, steps
from repro.models import model


def _greedy(logits, cfg):
    logits = model.mask_vocab_pad(logits, cfg)
    return jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)


@pytest.mark.parametrize("arch", ["granite-3-2b",   # dense: block path
                                  "mamba2-370m"])   # ssm: scan path
def test_cache_prefill_matches_token_by_token(arch):
    cfg = get_config(arch, smoke=True)
    params = model.init_params(cfg, jax.random.PRNGKey(0))
    B, S, room = 2, 8, 4
    rng = np.random.RandomState(1)
    prompt = jnp.asarray(rng.randint(0, cfg.vocab, (B, S)), jnp.int32)

    prefill = jax.jit(steps.make_cache_prefill_step(cfg))
    nxt_a, cache_a = prefill(params, model.init_cache(cfg, B, S + room),
                             prompt, jnp.int32(0))

    cache_b = model.init_cache(cfg, B, S + room)
    for i in range(S):
        logits, cache_b = model.decode_step(params, cfg, cache_b,
                                            prompt[:, i:i + 1],
                                            jnp.int32(i))
    np.testing.assert_array_equal(np.asarray(nxt_a),
                                  np.asarray(_greedy(logits, cfg)))
    for a, b in zip(jax.tree_util.tree_leaves(cache_a),
                    jax.tree_util.tree_leaves(cache_b)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=2e-4, atol=2e-4)


def test_prefill_chunks_at_ring_boundary():
    """A prompt longer than the KV ring serves through ``_prefill``'s
    chunking (a block write must not wrap the ring)."""
    cfg = get_config("granite-3-2b", smoke=True)
    params = model.init_params(cfg, jax.random.PRNGKey(0))
    B, total = 1, 12
    ring = serve._ring_len(cfg, total)
    S = ring + 3 if ring < total else total   # force >= 2 chunks if we can
    rng = np.random.RandomState(2)
    prompt = jnp.asarray(rng.randint(0, cfg.vocab, (B, S)), jnp.int32)

    prefill = jax.jit(steps.make_cache_prefill_step(cfg))
    nxt_a, _ = serve._prefill(prefill, params,
                              model.init_cache(cfg, B, total),
                              prompt, ring)

    cache_b = model.init_cache(cfg, B, total)
    for i in range(S):
        logits, cache_b = model.decode_step(params, cfg, cache_b,
                                            prompt[:, i:i + 1],
                                            jnp.int32(i))
    np.testing.assert_array_equal(np.asarray(nxt_a),
                                  np.asarray(_greedy(logits, cfg)))


def test_serve_mixed_prompt_lengths_preserve_order():
    """Requests re-grouped by prompt length come back in input order:
    the rows sharing the uniform run's length generate identical
    tokens, regardless of which group they decoded in."""
    uniform = serve.serve("granite-3-2b", True, 3, 6, 2)
    mixed = serve.serve("granite-3-2b", True, 3, 6, 2,
                        prompt_lens=(6, 4, 6))
    assert mixed.shape == (3, 2)
    np.testing.assert_array_equal(mixed[[0, 2]], uniform[[0, 2]])


def test_zero_length_prompts_rejected():
    """Regression (ISSUE 9): a zero-length prompt must fail loudly at
    validation, not prefill garbage."""
    with pytest.raises(ValueError, match="positive"):
        serve.serve("granite-3-2b", True, 3, 6, 2,
                    prompt_lens=(6, 0, 6))
    cfg = get_config("granite-3-2b", smoke=True)
    params = model.init_params(cfg, jax.random.PRNGKey(0))
    prefill = jax.jit(steps.make_cache_prefill_step(cfg))
    empty = jnp.zeros((1, 0), jnp.int32)
    with pytest.raises(ValueError, match="zero-length"):
        serve._prefill(prefill, params, model.init_cache(cfg, 1, 4),
                       empty, 4)


def test_serve_continuous_matches_oracle_per_request():
    """Continuous batching over the paged pool (admit/evict churn,
    more requests than slots, fused Pallas decode, certification on)
    returns every request's tokens in input order, token-identical to
    a per-request dense-cache oracle decode."""
    lens, gen, slots = (3, 5, 9, 4), 3, 2
    toks, stats = serve.serve_continuous("granite-3-2b", True, slots,
                                         gen, prompt_lens=lens)
    assert toks.shape == (len(lens), gen)
    assert stats["certified"] is True and stats["use_pallas"]
    assert stats["admitted"] == stats["evicted"] == len(lens)
    assert 0 < stats["occupancy"] <= 1
    assert 0 < stats["modeled_paged_traffic_words"] \
        < stats["modeled_dense_traffic_words"]

    cfg = get_config("granite-3-2b", smoke=True)
    params = model.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    pool = rng.randint(0, cfg.vocab, (len(lens), max(lens)))
    cmax = -(-(max(lens) + gen) // stats["page_size"]) \
        * stats["page_size"]
    step = jax.jit(steps.make_serve_step(cfg))
    for r, ln in enumerate(lens):
        cache = model.init_cache(cfg, 1, cmax)
        nxt, want = None, []
        for i in range(ln + gen):
            tok = (pool[r:r + 1, i:i + 1] if i < ln
                   else np.asarray(nxt).reshape(1, 1))
            nxt, cache = step(params, cache,
                              jnp.asarray(tok, jnp.int32), jnp.int32(i))
            if i >= ln:
                want.append(int(np.asarray(nxt)[0]))
        assert list(toks[r]) == want, f"request {r} diverged"


def test_serve_continuous_raises_on_failed_certification(monkeypatch):
    """A fused kernel that disagrees with the reference paged path
    stops the server; it never falls back to the reference silently."""
    monkeypatch.setattr(serve, "CERTIFY_RTOL", -1.0)
    with pytest.raises(serve.CertificationError, match="reference"):
        serve.serve_continuous("granite-3-2b", True, 2, 2,
                               prompt_lens=(3, 5))


SERVE_LEAVES = ("serve.admit.prefill", "serve.admit.scatter",
                "serve.certify", "serve.step.launch", "serve.step.wait",
                "serve.step.host")


def _within(outer, inner):
    return (outer["ts"] <= inner["ts"]
            and inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"])


def test_serve_continuous_leaf_spans_cover_the_loop():
    """From the first admission to the last step, the loop's time lies
    in its leaf spans, which do not overlap; every admission holds one
    prefill and one scatter, every step one launch and one wait."""
    from repro.core import telemetry

    telemetry.enable()
    lens, gen, slots = (3, 5, 9, 4), 3, 2
    serve.serve_continuous("granite-3-2b", True, slots, gen,
                           prompt_lens=lens)
    spans = telemetry.span_log()
    by = {}
    for s in spans:
        by.setdefault(s["name"], []).append(s)
    admits, steps_ = by["serve.admit"], by["serve.decode_step"]
    assert len(admits) == len(lens)
    assert len(steps_) == len(by["serve.step.launch"]) \
        == len(by["serve.step.wait"])
    for a in admits:
        for child in ("serve.admit.prefill", "serve.admit.scatter"):
            assert sum(_within(a, c) for c in by[child]) == 1
    for st in steps_:
        for child in ("serve.step.launch", "serve.step.wait"):
            assert sum(_within(st, c) for c in by[child]) == 1
    assert len(by["serve.step.host"]) == 2 * len(steps_)
    assert "serve.evict" not in by

    lo = min(a["ts"] for a in admits)
    hi = max(s["ts"] + s["dur"] for s in steps_)
    leaves = sorted((s["ts"], s["ts"] + s["dur"]) for s in spans
                    if s["name"] in SERVE_LEAVES)
    for (_, end), (start, _) in zip(leaves, leaves[1:]):
        assert start >= end - 1e-3          # microseconds: no overlap
    covered = sum(min(b, hi) - max(a, lo) for a, b in leaves
                  if b > lo and a < hi)
    assert covered >= 0.95 * (hi - lo)
    hists = telemetry.metrics_snapshot()["histograms"]
    assert "serve.admit_s" in hists
    assert not {"serve.decode_token_s", "serve.evict_s",
                "serve.prefill_s"} & set(hists)
