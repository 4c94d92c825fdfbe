"""The traffic generators: a seed fixes everything, and every seed
gets the same work in another order."""
from __future__ import annotations

import json
from collections import Counter

import numpy as np

from conftest import BENCH
import harness

LONG = json.loads((BENCH / "traffic" / "long-decode.json").read_text())
CHAT = json.loads((BENCH / "traffic" / "chat-short.json").read_text())


def _serve():
    return harness.load_module(BENCH / "drivers" / "serve_waves.py")


def test_waves_are_fixed_by_the_seed():
    drv = _serve()
    for tr in (LONG, CHAT):
        a = drv.wave_lengths(tr, 2**31 + 17, 40)
        assert a == drv.wave_lengths(tr, 2**31 + 17, 40)
        b = drv.wave_lengths(tr, 5, 40)
        assert a != b
        slots = tr["slots"]
        waves = [a[i:i + slots] for i in range(0, len(a), slots)]
        assert len(a) % slots == 0 and len(waves) == round(40 / tr["wave_s"])
        # the same multiset in every wave and every seed, each length in
        # the first wave
        assert all(Counter(w) == Counter(waves[0]) for w in waves)
        assert Counter(b[:slots]) == Counter(waves[0])
        assert set(waves[0]) == set(tr["prompt_lens"])


def test_chat_wave_shares():
    drv = _serve()
    wave = Counter(drv.wave_lengths(CHAT, 1, CHAT["wave_s"]))
    assert wave == {128: 12, 256: 7, 512: 5}


def test_prompts_are_fixed_by_the_seed():
    drv = _serve()
    lens = drv.wave_lengths(LONG, 9, 14)
    p = drv.prompts(49155, 2**31 + 9, lens)
    assert np.array_equal(p, drv.prompts(49155, 2**31 + 9, lens))
    assert not np.array_equal(p, drv.prompts(49155, 10, lens))
    assert p.shape == (8, 3072) and p.min() >= 0 and p.max() < 49155


def test_table_is_fixed_by_the_seed():
    ref = harness.load_module(BENCH / "reference" / "tpchq6_2p29.py")
    cfg = {}
    a = ref.make_columns(cfg, 2**31 + 3, 1, 4096)
    b = ref.make_columns(cfg, 2**31 + 3, 1, 4096)
    c = ref.make_columns(cfg, 3, 1, 4096)
    d = ref.make_columns(cfg, 2**31 + 3, 2, 4096)
    for col in ref.COLUMNS:
        x = np.asarray(a[col])
        assert np.array_equal(x, np.asarray(b[col]))
        assert not np.array_equal(x, np.asarray(c[col]))
        assert not np.array_equal(x, np.asarray(d[col]))
        assert x.dtype == np.float32 and 0 <= x.min() and x.max() < 1


def test_scan_picks_are_fixed_by_the_seed():
    picks = [np.random.default_rng(7).integers(128, size=50)
             for _ in range(2)]
    assert np.array_equal(*picks)
