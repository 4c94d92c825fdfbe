"""Mellum 2 served through the normal paged path, against the plain
reference of ``bench/reference/mellum2_12b_ep4.py`` on seeded random
weights at SMOKE size: windowed and full KV pools (the ring wraps),
the windowed paged-decode kernel, and the dropless expert share.

Tolerances: the program runs bfloat16 weights and activations, the
reference float32 at HIGHEST precision.  Logits of SMOKE's random
weights lie within about 4 of zero, where one bf16 ulp is 2**-6; the
two paths differ by rounding, a few ulps, on average well under 0.02.
Rounding the hidden state to bf16 can also flip a near-tie in a
router's top-k, which moves that token's logits by up to about 0.1.
A real fault (a masked slot read, a dropped or misrouted pair, a wrong
rotation) moves every later logit by tenths or more.
"""
from __future__ import annotations

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.codegen_pallas import lower_paged_decode
from repro.launch import serve, steps
from repro.models import model, moe, paged
from test_paged_decode import check_stacked_pools

ROOT = Path(__file__).resolve().parents[1]
CFG = get_config("mellum2-12b", smoke=True)
PS = 4                       # ring of 3 pages: 12 slots for a window of 8
LOGIT_TOL = 0.25             # one token's logits after a router near-tie
MEAN_TOL = 0.02              # bf16 rounding of logits near 4: a few ulps


def _reference():
    import importlib.util

    path = ROOT / "bench" / "reference" / "mellum2_12b_ep4.py"
    spec = importlib.util.spec_from_file_location("mellum2_ref", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()


def _ref_cfg():
    """The configuration file cut to SMOKE's sizes."""
    cfg = json.loads((ROOT / "bench" / "configs" /
                      "mellum2-12b-ep4.json").read_text())
    m = CFG
    cfg.update(
        hidden_size=m.d_model, moe_intermediate_size=m.d_ff,
        num_hidden_layers=m.n_layers, num_attention_heads=m.n_heads,
        num_key_value_heads=m.n_kv_heads, head_dim=m.head_dim,
        vocab_size=m.vocab, num_experts=m.n_experts_held,
        router_experts=m.n_experts, num_experts_per_tok=m.top_k,
        sliding_window=m.sliding_window,
        layer_types=(["sliding_attention"] * 3 + ["full_attention"])
        * (m.n_layers // 4))
    cfg["rope_parameters"]["full_attention"][
        "original_max_position_embeddings"] = m.yarn[1]
    return cfg


def test_serve_continuous_matches_reference_past_the_ring_wrap(monkeypatch):
    """``serve_continuous`` (both pool kinds, the fused kernels,
    certification on, more requests than slots) serves tokens whose
    reference logits are the reference's best, within rounding, at
    every position of prefill and decode, long after each ring wraps."""
    rcfg = _ref_cfg()
    w = REF.init_weights(rcfg, 7)
    monkeypatch.setattr(model, "init_params", lambda cfg, key: w)
    firsts = {}
    orig = serve._prefill

    def prefill(fn, params, cache, prompt, ring, index0=0):
        nxt, cache = orig(fn, params, cache, prompt, ring, index0)
        firsts[prompt.shape[1], int(prompt[0, 0])] = int(np.asarray(nxt)[0])
        return nxt, cache

    monkeypatch.setattr(serve, "_prefill", prefill)
    lens, gen = (12, 30, 9, 21), 16
    toks, stats = serve.serve_continuous("mellum2-12b", True, 3, gen,
                                         seed=3, prompt_lens=lens,
                                         page_size=PS)
    assert stats["certified"] is True and stats["ring_pages"] == 3
    assert max(lens) + gen > CFG.sliding_window + stats["ring_pages"] * PS
    pool = np.random.RandomState(3).randint(0, CFG.vocab,
                                            (len(lens), max(lens)))
    seqs = [(pool[r, :ln],
             np.concatenate([[firsts[ln, int(pool[r, 0])]], toks[r]]))
            for r, ln in enumerate(lens)]
    gaps, control = REF.served_gaps(w, rcfg, seqs, fp8_control=True)
    assert gaps.shape == (len(lens) * (gen + 1),)
    assert gaps.max() <= LOGIT_TOL and gaps.mean() <= MEAN_TOL
    assert control.max() > LOGIT_TOL        # float8 is visibly worse


def test_paged_decode_logits_match_the_reference():
    """Prefill through the dropless kernel path, then paged decode
    with the fused kernels, teacher-forced: every logit of every step
    against the reference's full-sequence forward."""
    rcfg = _ref_cfg()
    w = REF.init_weights(rcfg, 11)
    rng = np.random.RandomState(5)
    lens, steps_n = (5, 14), 20
    seq = rng.randint(0, CFG.vocab, (2, max(lens) + steps_n))
    cache = paged.PagedKVCache.init(CFG, 2, max(lens) + steps_n,
                                    page_size=PS)
    prefill = jax.jit(steps.make_cache_prefill_step(CFG, "kernel"))
    for r, ln in enumerate(lens):
        dense = model.init_cache(CFG, 1, ln)
        _, dense = serve._prefill(prefill, w, dense,
                                  jnp.asarray(seq[r:r + 1, :ln]),
                                  serve._ring_len(CFG, ln))
        cache = cache.assign_pages(r, list(np.asarray(cache.page_table[r])),
                                   ln, list(np.asarray(cache.win_table[r])))
        cache = serve._write_prompt(CFG, cache, r, dense, ln)
    step = jax.jit(lambda p, c, t: paged.paged_decode_step(
        p, CFG, c, t, use_pallas=True))
    got = []
    for i in range(steps_n):
        tok = np.asarray([seq[r, ln + i] for r, ln in enumerate(lens)])
        logits, cache = step(w, cache, jnp.asarray(tok[:, None], jnp.int32))
        got.append(np.asarray(logits[:, 0], np.float32))
    got = np.stack(got, 1)                               # (2, steps, V)
    vocab = np.broadcast_to(np.arange(CFG.vocab)[None, :, None],
                            (2, CFG.vocab, seq.shape[1]))
    _, want, _ = REF.score(w, rcfg, seq, vocab)          # (2, V, S)
    diff = np.concatenate([np.abs(got[r] - want[r][:, ln:ln + steps_n].T)
                           for r, ln in enumerate(lens)])
    assert diff.max() <= LOGIT_TOL and diff.mean() <= MEAN_TOL


@pytest.mark.parametrize("layer", [0, 2])
@pytest.mark.parametrize("layout", paged.LAYOUTS)
@pytest.mark.parametrize("block", [PS, 2 * PS])
def test_windowed_kernel_matches_reference_attn(layout, block, layer):
    """The windowed ``lower_paged_decode`` (interpreted) against
    ``reference_attn`` with the window: ragged lengths below, at and
    past the window, rings wrapped several times, in layer ``layer`` of
    rings stacked over three layers (the others left untouched)."""
    window, ring = 8, paged.ring_pages(8, PS, 100)
    lens = jnp.asarray([0, 3, 7, 8, 9, 15, 23, 40], jnp.int32)
    b, hkv, group, dh = len(lens), 2, 2, 16
    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    width = (2 if layout == "fused" else 1) * hkv * dh
    n_pools = 1 if layout == "fused" else 2
    n_phys = 1 + b * ring
    pools = tuple(jax.random.normal(keys[i], (3, n_phys, PS, width)
                                    ).astype(jnp.bfloat16)
                  for i in range(n_pools))
    table = jax.random.permutation(keys[2], jnp.arange(1, n_phys)
                                   ).reshape(b, ring).astype(jnp.int32)
    q = jax.random.normal(keys[3], (b, hkv, group, dh), jnp.float32)
    k = jax.random.normal(keys[4], (b, hkv, dh), jnp.float32)
    v = jax.random.normal(keys[5], (b, hkv, dh), jnp.float32)
    kern = lower_paged_decode(batch=b, kv_heads=hkv, group=group,
                              head_dim=dh, page_size=PS, n_pages_max=ring,
                              layout=layout, block=block, window=window)
    out, new = kern(q, k, v, pools, table, lens, layer)
    want, want_pools = paged.reference_attn(
        q, k, v, tuple(p[layer] for p in pools), table, lens, layout, PS,
        window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    check_stacked_pools(new, pools, want_pools, layer)


def _moe_layer(seed: int, held: int):
    params = model.init_params(CFG.with_(n_experts_held=held),
                               jax.random.PRNGKey(seed))
    return {k[4:]: v[0] for k, v in params.items() if k.startswith("moe_")}


@pytest.mark.parametrize("use_pallas", [True, False],
                         ids=["kernel", "dense"])
def test_expert_shares_add_up_to_the_uncut_layer(use_pallas):
    """Four chips' shares of 4 experts each: their partial outputs sum
    to the layer with all 16 experts held, and to a plain float64
    computation of it."""
    n = CFG.n_experts
    p = _moe_layer(1, n)
    cfg_all = CFG.with_(n_experts_held=n)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 9, CFG.d_model)
                          ).astype(jnp.bfloat16)
    whole, (pairs, _) = moe.moe_dropless(p, x, cfg_all,
                                         use_pallas=use_pallas)
    h = CFG.experts_held
    parts, pair_sum = 0.0, 0
    for c in range(n // h):
        share = dict(p, **{k: p[k][c * h:(c + 1) * h]
                           for k in ("we1", "we3", "we2")})
        y, (pc, _) = moe.moe_dropless(share, x, CFG, use_pallas=use_pallas,
                                      first=c * h)
        parts = parts + np.asarray(y, np.float64)
        pair_sum += int(pc)
    assert pair_sum == int(pairs) == 2 * 9 * CFG.top_k
    np.testing.assert_allclose(parts, np.asarray(whole, np.float64),
                               atol=2e-2)
    # plain float64: softmax over every expert, top-k renormalised
    xt = np.asarray(x, np.float64).reshape(-1, CFG.d_model)
    logits = xt @ np.asarray(p["router"], np.float64)
    prob = np.exp(logits - logits.max(-1, keepdims=True))
    prob /= prob.sum(-1, keepdims=True)
    top = np.argsort(-prob, -1)[:, :CFG.top_k]
    want = np.zeros_like(xt)
    for t in range(len(xt)):
        g = prob[t, top[t]] / prob[t, top[t]].sum()
        for e, ge in zip(top[t], g):
            w1, w3, w2 = (np.asarray(p[k][e], np.float64)
                          for k in ("we1", "we3", "we2"))
            a = xt[t] @ w1
            want[t] += ge * ((a / (1 + np.exp(-a)) * (xt[t] @ w3)) @ w2)
    np.testing.assert_allclose(parts.reshape(-1, CFG.d_model), want,
                               atol=3e-2)


def test_dropless_computes_every_token_of_a_crowded_expert():
    """Every token routes to held expert 2 (150 tokens: two row tiles
    of it); the kernel computes each pair, where the capacity path
    would drop all but its capacity."""
    p = _moe_layer(3, CFG.experts_held)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(4),
                                  (1, 150, CFG.d_model))).astype(jnp.bfloat16)
    p["router"] = p["router"].at[:, 2].set(1.0)          # logit ~ sum(x)
    y, (pairs, touched) = moe.moe_dropless(p, x, CFG, use_pallas=True)
    y_ref, (pairs_ref, _) = moe.moe_dropless(p, x, CFG, use_pallas=False)
    _, topi = moe.route(p["router"], x.reshape(150, -1), CFG.top_k)
    assert np.all(np.asarray(topi)[:, 0] == 2)
    held = np.asarray(topi) < CFG.experts_held
    assert int(pairs) == int(pairs_ref) == int(held.sum()) >= 150
    assert int(touched) == len(np.unique(np.asarray(topi)[held]))
    assert moe.gmm_row_block(150) < 150                  # two tiles
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_ref, np.float32), atol=1e-2)
    capped = moe.moe_ffn(p, x, CFG)
    assert moe.capacity(CFG, 150) < 150
    assert np.abs(np.asarray(capped, np.float32)
                  - np.asarray(y, np.float32)).max() > 0.1
