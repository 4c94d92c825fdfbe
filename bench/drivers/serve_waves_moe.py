"""Driver for a model with windowed and full attention and a dropless
expert share, served through ``launch.serve.serve_continuous``: the
``serve_waves`` driver as it is, plus what its cell's readers need.

* The program's routing counters of every decode step, read from the
  ``moe_tokens_held`` and ``moe_experts_touched`` attributes of its
  ``serve.decode_step`` spans (``run.data["moe_pairs"]`` and
  ``["moe_touched"]``), where the program records them.
* The traced slice's kernel time split by kernel name: the paged
  attention kernel (``paged_decode*``) and the grouped expert matmul
  (``moe_gmm*``), each with the bytes and operations its calls in the
  slice need (``counts_moe``), for the roofline readers.
* Its own comparison with the reference: ``logit_gap`` is the mean,
  over every served position of the sampled requests, of the gap by
  which the served token's reference logit lies below the reference's
  best (the reference module's ``LIMITS`` says why the mean and not
  the widest).  The served tokens are taken from the server's return
  and its admissions as ``serve_waves`` takes them; the same requests
  are sampled.
"""
from __future__ import annotations

from typing import Dict, Iterable, List, Sequence, Tuple

import numpy as np

import harness

KERNELS = ("paged_decode", "moe_gmm")


def _base(cell):
    return harness.load_module(cell.bench_dir / "drivers" / "serve_waves.py")


def split_kernels(planes: Dict[str, list], lo: float, hi: float,
                  prefixes: Sequence[str] = KERNELS
                  ) -> Dict[str, Tuple[float, int]]:
    """``prefix -> (seconds, calls)`` of the device operations whose
    name starts with ``prefix`` and that overlap ``[lo, hi]`` (trace
    nanoseconds), averaged over the device planes.  ``planes`` is
    ``reduce_trace.device_ops``'s ``plane -> [(start, end, name,
    stats)]``."""
    import reduce_trace

    out = {p: [0.0, 0] for p in prefixes}
    for ops in planes.values():
        for a, b, name, _ in ops:
            if b <= lo or a >= hi:
                continue
            op = reduce_trace.op_name(name)
            for p in prefixes:
                if op.startswith(p):
                    out[p][0] += (b - a) * 1e-9
                    out[p][1] += 1
    n = max(len(planes), 1)
    return {p: (t / n, c // n) for p, (t, c) in out.items()}


def _step_args() -> List[dict]:
    from repro.core import telemetry

    return [s.get("args", {}) for s in telemetry.span_log()
            if s["name"] == "serve.decode_step"]


def sample(lens: List[int], n: int, seed: int) -> List[int]:
    """The requests ``serve_waves`` compares: one of the longest, then
    ``n - 1`` others, drawn from the seed."""
    rng = np.random.default_rng(seed)
    longest = [r for r in range(len(lens)) if lens[r] == max(lens)]
    pick = [int(rng.choice(longest))]
    rest = [r for r in range(len(lens)) if r != pick[0]]
    return pick + [int(r) for r in rng.choice(rest, n - 1, replace=False)]


def run(cell, *, seed: int, seconds: float, trace, t_start: float):
    from repro.launch import serve as serve_mod

    base = _base(cell)
    firsts: List[int] = []
    served = {}
    orig_serve, orig_prefill = serve_mod.serve_continuous, serve_mod._prefill

    def serve_continuous(*a, **kw):
        out = orig_serve(*a, **kw)
        served["tokens"] = np.asarray(out[0])
        return out

    def prefill(*a, **kw):
        nxt, cache = orig_prefill(*a, **kw)
        firsts.append(int(np.asarray(nxt)[0]))
        return nxt, cache

    serve_mod.serve_continuous, serve_mod._prefill = serve_continuous, prefill
    try:
        run_ = base.run(cell, seed=seed, seconds=seconds, trace=trace,
                        t_start=t_start)
    finally:
        serve_mod.serve_continuous, serve_mod._prefill = (orig_serve,
                                                          orig_prefill)
    _compare(run_, cell, base, seed, firsts, served["tokens"])
    args = _step_args()
    if args and all("moe_tokens_held" in a for a in args):
        run_.data["moe_pairs"] = np.asarray(
            [a["moe_tokens_held"] for a in args], np.int64)
        run_.data["moe_touched"] = np.asarray(
            [a["moe_experts_touched"] for a in args], np.int64)
    if run_.trace is not None and trace is not None:
        run_.trace.update(_kernel_split(trace, cell, run_))
    return run_


def _compare(run_, cell, base, seed: int, firsts: List[int], tokens):
    """``run_.check`` / ``run_.control`` on the mean gap of the sampled
    requests' served positions."""
    cfg, ref = cell.config, cell.reference
    lens = run_.data["lens"]
    prompts = base.prompts(int(cfg["vocab_size"]), seed, lens)
    served = np.concatenate([np.asarray(firsts)[:, None], tokens], axis=1)
    seqs = [(prompts[r, :lens[r]], served[r])
            for r in sample(lens, int(cell.traffic["sample_requests"]), seed)]

    def gaps(fp8_control: bool):
        return ref.served_gaps(ref.init_weights(cfg, seed), cfg, seqs,
                               fp8_control)

    def check():
        g, _ = gaps(False)
        return {"logit_gap": (float(np.mean(g)), ref.LIMITS["logit_gap"])}

    def control():
        _, g8 = gaps(True)
        return {"logit_gap": float(np.mean(g8))}

    run_.check, run_.control = check, control


def _per_call(values: Iterable[float], calls: int, per_step: int) -> float:
    """Calls in the slice times the mean need of one call."""
    values = list(values)
    return calls * float(np.mean(values)) / per_step if values else 0.0


def _kernel_split(trace, cell, run_) -> Dict[str, float]:
    import counts_moe
    import reduce_trace

    pd = reduce_trace.load(trace.xplane())
    sync_ns = reduce_trace.host_event_ns(pd, reduce_trace.SYNC)

    def to_ns(t: float) -> float:
        return sync_ns + (t - trace.sync_pc) * 1e9

    split = split_kernels(reduce_trace.device_ops(pd),
                          to_ns(trace.started), to_ns(trace.stopped))
    cfg, data = cell.config, run_.data
    steps = data["steps"]
    inside = [k for k, (a, b) in enumerate(steps)
              if a >= trace.started and b <= trace.stopped]
    nl = cfg["num_hidden_layers"]
    out: Dict[str, float] = {}
    (t_pd, n_pd), (t_mg, n_mg) = split["paged_decode"], split["moe_gmm"]
    if n_pd and inside:
        live = [data["live"][k] for k in inside]
        out.update(
            paged_s=t_pd, paged_calls=n_pd,
            paged_bytes=_per_call((counts_moe.attn_bytes(cfg, lv)
                                   for lv in live), n_pd, nl),
            paged_flops=_per_call((counts_moe.attn_flops(cfg, lv)
                                   for lv in live), n_pd, nl))
    if n_mg and inside and "moe_pairs" in data:
        pairs = data["moe_pairs"][inside]
        touched = data["moe_touched"][inside]
        out.update(
            moe_gmm_s=t_mg, moe_gmm_calls=n_mg,
            moe_gmm_bytes=_per_call(
                (counts_moe.gmm_bytes(cfg, p, t)
                 for p, t in zip(pairs, touched)), n_mg, nl),
            moe_gmm_flops=_per_call(
                (counts_moe.gmm_flops(cfg, p) for p in pairs), n_mg, nl))
    return out
