"""The plain references checked once against a straightforward
computation and against the system, on the CPU at small sizes."""
from __future__ import annotations

import json

import numpy as np

import harness
from conftest import BENCH, GRANITE_SMALL, Q6_SMALL


def _ref(name):
    return harness.load_module(BENCH / "reference" / f"{name}.py")


def test_q6_reference_matches_float64():
    ref = _ref("tpchq6_2p29")
    cols = ref.make_columns(Q6_SMALL, 11, 0, 1 << 12)
    q, pr, dc = (np.asarray(cols[c], np.float64) for c in ref.COLUMNS)
    want = np.sum(np.where((q >= 0.05) & (q < 0.95), pr * dc, 0.0))
    got = ref.answer(cols, Q6_SMALL)
    assert abs(got - want) / want < 1e-6
    low = ref.control_answer(cols, Q6_SMALL)
    assert abs(low - want) / want > ref.LIMITS["rel_err"]


def test_granite_reference_matches_the_program_forward():
    """The reference's logits against the program's own full-sequence
    forward on the same weights, both in float32."""
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.models import model

    ref = _ref("granite_3_2b")
    cfg = dict(GRANITE_SMALL, rms_norm_eps=1e-6)
    w = ref.init_weights(cfg, 4)
    toks = np.random.RandomState(0).randint(0, cfg["vocab_size"], (2, 24))
    mcfg = get_config("granite-3-2b", smoke=True).with_(dtype="float32")
    params = {k: v.astype(jnp.float32) for k, v in w.items()}
    with jax.default_matmul_precision("highest"):
        logits = np.asarray(model.forward(params, mcfg,
                                          {"tokens": jnp.asarray(toks)}))
    top = logits.argmax(-1)
    best, picked, arg = ref.score(w, cfg, toks, top[:, None, :])
    np.testing.assert_allclose(best, logits.max(-1), rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(picked[:, 0], best, rtol=2e-5, atol=2e-5)
    assert (arg == top).mean() > 0.99


def test_granite_served_gaps_of_the_reference_own_tokens_are_zero():
    ref = _ref("granite_3_2b")
    cfg = GRANITE_SMALL
    w = ref.init_weights(cfg, 1)
    prompt = np.arange(10) % cfg["vocab_size"]
    toks = np.zeros((1, 16), np.int32)
    toks[0, :10] = prompt
    for i in range(10, 16):    # greedy decode by the reference itself
        _, _, top = ref.score(w, cfg, toks, toks[:, None])
        toks[0, i] = top[0, i - 1]
    _, _, top = ref.score(w, cfg, toks, toks[:, None])
    served = np.concatenate([toks[0, 10:], top[0, 15:16]])
    gaps, control = ref.served_gaps(w, cfg, [(prompt, served)], True)
    assert gaps.shape == (7,) and np.all(gaps == 0)
    assert control.shape == (7,) and np.all(control >= 0)


def test_configs_state_what_the_program_runs():
    from repro.configs import get_config

    cfg = json.loads((BENCH / "configs" / "granite-3-2b.json").read_text())
    m = get_config(cfg["arch"], smoke=False)
    assert (m.d_model, m.d_ff, m.n_layers, m.n_heads, m.n_kv_heads,
            m.head_dim, m.vocab, m.vocab_pad, m.rope_theta, m.dtype,
            m.tie_embeddings) == (
        cfg["hidden_size"], cfg["intermediate_size"],
        cfg["num_hidden_layers"], cfg["num_attention_heads"],
        cfg["num_key_value_heads"], cfg["head_dim"], cfg["vocab_size"],
        cfg["vocab_pad"], cfg["rope_theta"], cfg["torch_dtype"],
        cfg["tie_word_embeddings"])
    assert cfg["attention_multiplier"] == cfg["head_dim"] ** -0.5
