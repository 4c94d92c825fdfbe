"""The whole run with the timed path broken underneath it: each fault
a cell can have must make ``correct`` come out false.  (One chip per
cell, so there is no exchange between chips to leave out.)"""
from __future__ import annotations

import jax.numpy as jnp
import pytest

from conftest import run_small


def _answer_altered(monkeypatch):
    from repro.core import pipeline

    orig = pipeline.lower_pipeline

    def lower(pipe, **kw):
        call = orig(pipe, **kw)
        return lambda **cols: call(**cols) * (1 + 1e-3)

    monkeypatch.setattr(pipeline, "lower_pipeline", lower)


def _half_the_rows(monkeypatch):
    """Half of the rows left out, the sum of the rest doubled."""
    from repro.core import pipeline

    orig = pipeline.lower_pipeline

    def lower(pipe, **kw):
        call = orig(pipe, **kw)

        def half(**cols):
            n = cols["qty"].shape[0]
            qty = cols["qty"].at[n // 2:].set(2.0)   # fails the predicate
            return 2 * call(**dict(cols, qty=qty))

        return half

    monkeypatch.setattr(pipeline, "lower_pipeline", lower)


def _token_altered(monkeypatch):
    from repro.models import paged

    orig = paged.paged_decode_step

    def step(params, cfg, cache, tokens, **kw):
        logits, cache = orig(params, cfg, cache, tokens, **kw)
        return logits.at[..., 5].add(1e4), cache

    monkeypatch.setattr(paged, "paged_decode_step", step)


def _state_unchanged(monkeypatch):
    from repro.models import paged

    orig = paged.paged_decode_step

    def step(params, cfg, cache, tokens, **kw):
        logits, _ = orig(params, cfg, cache, tokens, **kw)
        return logits, cache

    monkeypatch.setattr(paged, "paged_decode_step", step)


@pytest.mark.parametrize("workload,fault", [
    ("q6-small", _answer_altered),
    ("q6-small", _half_the_rows),
    ("q6-parts-small", _answer_altered),
    ("q6-parts-small", _half_the_rows),
    ("granite-small", _token_altered),
    ("granite-small", _state_unchanged),
], ids=lambda x: getattr(x, "__name__", x))
def test_fault_makes_the_run_incorrect(small, monkeypatch, workload, fault):
    fault(monkeypatch)
    r = run_small(small, workload)
    assert r["correct"] is False
    assert any(c["value"] > c["limit"] for c in r["checks"].values())
    assert jnp is not None
