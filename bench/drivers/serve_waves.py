"""Driver for an offline queue through the program's continuous-batching
server, ``launch.serve.serve_continuous``, called as it is.

Every request is queued at t=0 with the same output length, so the
slots admit and finish together, in waves.  Each wave holds the same
multiset of prompt lengths (the mix's ``shares`` of ``slots``), in an
order drawn from the seed, and the first wave holds every length, so
every prefill program compiles before the window.  The run serves
``round(seconds / wave_s)`` waves: a fixed amount of work, so a faster
program shows as a shorter window.

The window runs from the end of the second decode step to the end of
the last: the first compiles the step and certifies the kernel, and
the bookkeeping after it compiles the server's length mask.  It is
read from the program's ``serve.decode_step`` and ``serve.admit``
spans.

The benchmark makes the weights (the configuration's reference module
draws them from the seed) and hands them to the server in place of its
own ``model.init_params``; it records each admission's first token,
which the server feeds to decoding but does not return.  The prompts
are the server's own draw from the seed, which this driver repeats and
checks against what was admitted.
"""
from __future__ import annotations

import contextlib
import threading
import time
from typing import Dict, List

import numpy as np

from harness import Run


def wave_lengths(traffic: dict, seed: int, seconds: float) -> List[int]:
    """Prompt length of every request, in queue order."""
    slots = int(traffic["slots"])
    lens = [int(x) for x in traffic["prompt_lens"]]
    shares = np.asarray(traffic["shares"], np.float64)
    want = shares / shares.sum() * slots
    count = np.floor(want).astype(int)
    for i in np.argsort(-(want - count), kind="stable")[:slots - count.sum()]:
        count[i] += 1
    if (count < 1).any():
        raise ValueError(f"a wave of {slots} slots must hold every prompt "
                         f"length {lens}; counts {count.tolist()}")
    waves = max(1, round(seconds / float(traffic["wave_s"])))
    wave = np.repeat(lens, count)
    rng = np.random.default_rng(seed)
    return [int(x) for _ in range(waves) for x in rng.permutation(wave)]


def program_seed(seed: int) -> int:
    return seed & 0x7FFFFFFF


def prompts(vocab: int, seed: int, lens: List[int]) -> np.ndarray:
    """The prompts the server draws for ``seed``: uniform token ids."""
    rng = np.random.RandomState(program_seed(seed))
    return rng.randint(0, vocab, (len(lens), max(lens)))


@contextlib.contextmanager
def _hooks(make_weights, admitted: list):
    import jax

    from repro.launch import serve as serve_mod
    from repro.models import model

    orig_init, orig_prefill = model.init_params, serve_mod._prefill

    def init_params(cfg, key):
        want = jax.eval_shape(lambda k: orig_init(cfg, k), key)
        got = make_weights()
        shapes = {k: (v.shape, v.dtype) for k, v in got.items()}
        if shapes != {k: (v.shape, v.dtype) for k, v in want.items()}:
            raise RuntimeError(f"the program's {cfg.name} weights differ "
                               f"from the configuration file's: {want} "
                               f"vs {shapes}")
        return got

    def prefill(prefill_fn, params, cache, prompt, ring, index0=0):
        nxt, cache = orig_prefill(prefill_fn, params, cache, prompt, ring,
                                  index0)
        admitted.append((prompt, nxt))
        return nxt, cache

    model.init_params, serve_mod._prefill = init_params, prefill
    try:
        yield
    finally:
        model.init_params, serve_mod._prefill = orig_init, orig_prefill


def _trace_when_open(trace, lead, trace_s, done: threading.Event):
    from repro.core import telemetry

    while not done.wait(0.05):
        if sum(s["name"] == "serve.decode_step"
               for s in telemetry.span_log()) >= 3:
            break
    if done.wait(lead):
        return
    trace.start()
    done.wait(trace_s)
    trace.stop()


def _schedule(spans, offset: float, lens: List[int], gen: int):
    """Host times of every decode step and admission, and which
    requests each step served."""
    steps = [s for s in spans if s["name"] == "serve.decode_step"]
    admits = [s for s in spans if s["name"] == "serve.admit"]
    to_pc = (lambda us: offset + us * 1e-6)
    step_t = np.asarray([(to_pc(s["ts"]), to_pc(s["ts"] + s["dur"]))
                         for s in steps])
    active = np.asarray([s["args"]["active"] for s in steps])
    adm = [(s["args"]["request"], to_pc(s["ts"]), to_pc(s["ts"] + s["dur"]))
           for s in admits]
    # a request joins the first step that starts after its admission
    # and stays for ``gen`` steps
    first_step = {r: int(np.searchsorted(step_t[:, 0], end))
                  for r, _, end in adm}
    live = [[] for _ in steps]
    for r, k in first_step.items():
        for j in range(gen):
            live[k + j].append(lens[r] + j)
    return step_t, active, adm, first_step, live


def run(cell, *, seed: int, seconds: float, trace, t_start: float) -> Run:
    import jax

    from repro.core import telemetry
    from repro.launch import serve as serve_mod

    cfg, tr, ref = cell.config, cell.traffic, cell.reference
    gen, slots = int(tr["gen"]), int(tr["slots"])
    lens = wave_lengths(tr, seed, seconds)
    want_prompts = prompts(int(cfg["vocab_size"]), seed, lens)
    admitted: list = []

    telemetry.reset()
    telemetry.enable()
    with telemetry.span("bench.mark"):
        mark = time.perf_counter()
    done = threading.Event()
    tracer = None
    if trace is not None:
        tracer = threading.Thread(
            target=_trace_when_open, daemon=True,
            args=(trace, 1.0, float(tr["trace_s"]), done))
        tracer.start()
    try:
        with _hooks(lambda: ref.init_weights(cfg, seed), admitted):
            toks, _ = serve_mod.serve_continuous(
                cfg["arch"], smoke=bool(cfg.get("smoke", False)),
                slots=slots, gen=gen, seed=program_seed(seed),
                prompt_lens=lens, use_pallas=True, certify=True)
    finally:
        done.set()
        if tracer is not None:
            tracer.join()
            if trace.started is not None and trace.stopped is None:
                trace.stop()
    spans = telemetry.span_log()
    telemetry.disable()
    m = next(s for s in spans if s["name"] == "bench.mark")
    offset = mark - m["ts"] * 1e-6
    step_t, active, adm, first_step, live = _schedule(spans, offset, lens,
                                                      gen)
    if len(step_t) != len(lens) // slots * gen:
        raise RuntimeError(f"{len(step_t)} decode steps for "
                           f"{len(lens)} requests of {gen} tokens over "
                           f"{slots} slots: the waves did not line up")
    first = np.asarray([int(np.asarray(n)[0]) for _, n in admitted])
    for r, (p, _) in enumerate(admitted):
        if not np.array_equal(np.asarray(p)[0], want_prompts[r, :lens[r]]):
            raise RuntimeError(f"request {r}: the server admitted other "
                               "prompt tokens than the traffic's")
    t0, t1 = step_t[1, 1], step_t[-1, 1]
    served = np.concatenate([first[:, None], np.asarray(toks)], axis=1)
    failed = 0 if served.shape == (len(lens), gen + 1) else len(lens)

    def sample() -> List[int]:
        """Requests to compare, drawn from the seed, the longest among
        them."""
        rng = np.random.default_rng(seed)
        longest = [r for r in range(len(lens)) if lens[r] == max(lens)]
        pick = [int(rng.choice(longest))]
        rest = [r for r in range(len(lens)) if r != pick[0]]
        return pick + [int(r) for r in rng.choice(
            rest, int(tr["sample_requests"]) - 1, replace=False)]

    def gaps(fp8_control: bool):
        seqs = [(want_prompts[r, :lens[r]], served[r]) for r in sample()]
        return ref.served_gaps(ref.init_weights(cfg, seed), cfg, seqs,
                               fp8_control)

    def check() -> Dict[str, tuple]:
        g, _ = gaps(False)
        return {"logit_gap": (float(np.max(g)), ref.LIMITS["logit_gap"])}

    def control() -> Dict[str, float]:
        _, g8 = gaps(True)
        return {"logit_gap": float(np.max(g8))}

    red = None
    if trace is not None and trace.started is not None:
        red = _reduce(trace, cell, step_t, live, adm)
    return Run(setup_end=t0, window=(t0, t1), attempted=len(lens),
               failed=failed, check=check, control=control, trace=red,
               data={"steps": step_t, "active": active, "admits": adm,
                     "first_step": first_step, "live": live, "gen": gen,
                     "lens": lens})


def _reduce(trace, cell, step_t, live, admits):
    import counts
    import reduce_trace

    cfg = cell.config
    spans = [("serve.decode_step", a, b) for a, b in step_t]
    spans += [("serve.admit", a, b) for _, a, b in admits]
    red = reduce_trace.reduce(
        trace.xplane(), sync_pc=trace.sync_pc,
        window=(trace.started, trace.stopped), host_spans=spans)
    inside = [k for k, (a, b) in enumerate(step_t)
              if a >= trace.started and b <= trace.stopped]
    per_call_bytes = [counts.paged_attn_bytes(cfg, live[k]) for k in inside]
    per_call_flops = [counts.paged_attn_flops(cfg, live[k]) for k in inside]
    calls = red["kernel_calls"]
    if inside:
        red["kernel_bytes"] = calls * float(np.mean(per_call_bytes))
        red["kernel_flops"] = calls * float(np.mean(per_call_flops))
    return red
