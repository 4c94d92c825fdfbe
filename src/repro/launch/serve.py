"""Serving driver: batched one-call prefill + decode with a KV cache.

    PYTHONPATH=src python -m repro.launch.serve --arch granite-3-2b \
        --smoke --batch 4 --prompt-len 32 --gen 16

Demonstrates the full inference path (the ``decode_*`` dry-run shapes
lower exactly this ``serve_step``): the whole prompt prefills the cache
in a single jitted call (``steps.make_cache_prefill_step`` -- block
decode for attention families, an in-jit token scan for recurrent
ones), then ``--gen`` tokens greedy-decode one step at a time.

``--prompt-lens 24,100,100,360`` serves a mixed batch: requests are
grouped by prompt length and each group prefills in one call.

``--continuous`` switches to continuous batching over a *paged* KV
pool (``models.paged``): requests are admitted into and evicted from a
fixed set of decode slots every step, decode runs as one joint
``paged_decode_step`` (the fused ``decode_attention`` DAG), and the KV
layout / page size come from the joint DSE plan.  The fused Pallas
kernel's logits are certified against the reference paged path on the
first decode step; a failed certification stops the server.
"""
from __future__ import annotations

import argparse
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCHS, get_config
from repro.core import backend, telemetry
from repro.launch import steps as steps_mod
from repro.models import model
from repro.models import transformer as tr


def _prefill(prefill_fn, params, cache, prompt, ring: int,
             index0: int = 0):
    """Prefill ``prompt`` into ``cache`` starting at ``index0``,
    chunking at the KV ring boundary (a block write must not wrap)."""
    plen = prompt.shape[1]
    if plen == 0:
        raise ValueError("cannot prefill a zero-length prompt")
    i, nxt = 0, None
    while i < plen:
        chunk = min(plen - i, ring - ((index0 + i) % ring))
        nxt, cache = prefill_fn(params, cache, prompt[:, i:i + chunk],
                                jnp.int32(index0 + i))
        i += chunk
    return nxt, cache


def _ring_len(cfg, max_len: int) -> int:
    """Prompt-chunk bound: the KV ring's slot count, or the window
    where windowed and full layers mix; the recurrent scan path has no
    ring, so any chunk length works."""
    if cfg.family in ("dense", "moe", "audio", "vlm"):
        return tr.prefill_chunk(cfg, max_len)
    return max_len


def serve(arch: str, smoke: bool, batch: int, prompt_len: int,
          gen: int, seed: int = 0,
          prompt_lens: Optional[Sequence[int]] = None,
          stats_out: Optional[Dict] = None) -> np.ndarray:
    """Serve ``batch`` requests; returns the (batch, gen) generated
    tokens (requests keep their input order even when mixed prompt
    lengths are re-grouped internally).  ``stats_out``, when given, is
    filled with prefill/decode wall times (benchmark hook)."""
    cfg = get_config(arch, smoke=smoke)
    params = model.init_params(cfg, jax.random.PRNGKey(seed))
    lens = list(prompt_lens) if prompt_lens else [prompt_len] * batch
    if len(lens) != batch:
        raise ValueError(f"--prompt-lens gave {len(lens)} lengths for "
                         f"--batch {batch}")
    if min(lens) <= 0:
        raise ValueError(f"prompt lengths must be positive: {lens}")
    prefill_fn = jax.jit(steps_mod.make_cache_prefill_step(cfg),
                         donate_argnums=(1,))
    step_fn = jax.jit(steps_mod.make_serve_step(cfg), donate_argnums=(1,))

    rng = np.random.RandomState(seed)
    tok_shape = ((batch, max(lens), cfg.n_codebooks) if cfg.n_codebooks
                 else (batch, max(lens)))
    prompt_pool = rng.randint(0, cfg.vocab, tok_shape)

    # group requests by prompt length: each group prefills its whole
    # prompt in one call (one compile per distinct length)
    groups: Dict[int, List[int]] = {}
    for r, ln in enumerate(lens):
        groups.setdefault(ln, []).append(r)

    out = np.zeros((batch, gen), np.int64)
    prefill_s = decode_s = 0.0
    for ln, rows in sorted(groups.items()):
        gb = len(rows)
        prompt = jnp.asarray(prompt_pool[rows][:, :ln], jnp.int32)
        cache = model.init_cache(cfg, gb, ln + gen)
        ring = _ring_len(cfg, ln + gen)

        t0 = time.time()
        with telemetry.span("serve.prefill", prompt_len=ln, batch=gb):
            nxt, cache = _prefill(prefill_fn, params, cache, prompt, ring)
            jax.block_until_ready(nxt)
        dt = time.time() - t0
        prefill_s += dt
        telemetry.observe("serve.prefill_s", dt)

        group_out = []
        t0 = time.time()
        for i in range(ln, ln + gen):
            if cfg.n_codebooks:
                tok = nxt.reshape(gb, 1, cfg.n_codebooks)
            else:
                tok = nxt.reshape(gb, 1)
            with telemetry.span("serve.decode_step", index=i, batch=gb):
                nxt, cache = step_fn(params, cache, tok, jnp.int32(i))
                group_out.append(np.asarray(nxt))
        decode_s += time.time() - t0

        toks = np.stack(group_out, axis=1)        # (gb, gen[, ncb])
        if cfg.n_codebooks:
            toks = toks[..., 0]                   # report codebook 0
        out[rows] = toks

    n_groups = len(groups)
    print(f"prefill {sorted(groups)} ({n_groups} group"
          f"{'s' if n_groups > 1 else ''}): {prefill_s:.2f}s; "
          f"decode {gen} tokens: {decode_s:.2f}s "
          f"({decode_s / max(gen, 1) * 1e3:.0f} ms/token)")
    if stats_out is not None:
        stats_out.update(prefill_s=prefill_s, decode_s=decode_s,
                         ms_per_token=decode_s / max(batch * gen, 1)
                         * 1e3)
    return out


# Certification tolerance on logits, relative to the largest reference
# logit.  Both paths read the same bf16 pool and compute attention in
# f32 (HIGHEST precision on the MXU): the kernel's attention output is
# within ~2e-6 of a float64 reference.  But each layer rounds that
# output to bf16, and the logits themselves are bf16, whose one ulp
# near the largest logit is 2**-8..2**-7 of it; a difference far below
# bf16 precision still flips some roundings.  On a TPU v5e, granite-3-2b
# with random weights differs by one such ulp (6.8e-3) at 1 layer and
# by 1.7e-2 at its 40.  Kernel faults move the logits further: one
# token off in the length by 8.4e-2, slots reading each other's pages
# by 1.7.  4e-2 sits about a factor two from each side.
CERTIFY_RTOL = 4e-2


def _memory_in_use(array) -> Dict[str, int]:
    """Bytes in use on ``array``'s device now and at its peak, where
    the backend reports them."""
    stats = next(iter(array.devices())).memory_stats() or {}
    return {k: int(stats[k]) for k in ("bytes_in_use", "peak_bytes_in_use")
            if k in stats}


class CertificationError(RuntimeError):
    """The fused paged-decode kernel disagreed with the reference."""


def _certify_logits(fused, ref) -> Tuple[bool, float]:
    """``(ok, err)``: the largest logit difference relative to the
    largest reference logit, checked against ``CERTIFY_RTOL``."""
    fused = np.asarray(fused, np.float32)
    ref = np.asarray(ref, np.float32)
    err = float(np.max(np.abs(fused - ref))
                / max(float(np.max(np.abs(ref))), 1e-30))
    return bool(np.isfinite(err) and err <= CERTIFY_RTOL), err


def serve_continuous(arch: str, smoke: bool, slots: int, gen: int,
                     seed: int = 0,
                     prompt_lens: Optional[Sequence[int]] = None,
                     prompt_len: int = 32,
                     page_size: Optional[int] = None,
                     layout: Optional[str] = None,
                     use_pallas: bool = True, certify: bool = True,
                     bucketing: bool = False
                     ) -> Tuple[np.ndarray, Dict]:
    """Continuous-batching serve over one shared paged KV pool.

    ``slots`` concurrent decode lanes share a page pool; each decode
    step first *admits* waiting requests into free slots (batch-1
    dense prefill, then the prefilled K/V is scattered into freshly
    allocated pages) and *evicts* finished ones (pages returned to the
    free list), then runs ONE joint ``paged_decode_step`` over all
    slots.  The KV layout and page size come from the joint DSE plan
    (``ops.resolve_plan("paged_decode", ...)``) unless overridden.
    With ``certify``, the first decode step also runs through the
    reference paged path and the fused kernel's logits must match it
    within ``CERTIFY_RTOL``; otherwise ``CertificationError`` is raised
    (there is no silent fallback to the reference path).

    A config with windowed layers keeps their K/V in a second pool of
    per-slot rings (``paged.PagedKVCache``): an admission takes pages
    of both kinds from their own free lists and writes the whole
    prompt into its full-layer pages and the last ``window`` tokens
    into its ring.  MoE layers run the dropless expert share, in
    prefill and decode, through the grouped-matmul kernel where
    ``use_pallas``.

    Returns ``(tokens, stats)``: the (n_requests, gen) generated
    tokens in request order, and occupancy/latency/provenance stats.

    Spans (``core.telemetry``): each admission is a ``serve.admit``
    whose children are ``serve.admit.prefill`` (until its first token
    is on the host) and ``serve.admit.scatter`` (page assignment and
    the K/V scatter into the pool, until the pool is written; it
    carries the pages taken of each kind, ``pages_full`` and
    ``pages_window``, and the device's ``bytes_in_use`` and
    ``peak_bytes_in_use``).  Each step is a ``serve.decode_step``
    (with MoE layers, carrying ``moe_tokens_held`` and
    ``moe_experts_touched``, summed over the layers; the counters of
    those names add them up) whose children are
    ``serve.step.launch`` (the step program dispatched) and
    ``serve.step.wait`` (its tokens on the host).  The bookkeeping
    before and after a step is a ``serve.step.host`` each, and the
    first step's reference run ``serve.certify``.  Every moment of
    the loop lies in one of these leaves.
    """
    from repro.core.codegen_pallas import paged_decode_blocks, window_block
    from repro.core.options import Options
    from repro.kernels import ops
    from repro.models import paged

    cfg = get_config(arch, smoke=smoke)
    if cfg.family not in ("dense", "moe"):
        raise NotImplementedError(
            f"continuous paged serving supports dense/moe attention "
            f"families, not {cfg.family}")
    params = model.init_params(cfg, jax.random.PRNGKey(seed))
    lens = list(prompt_lens) if prompt_lens else [prompt_len] * slots
    if min(lens) <= 0:
        raise ValueError(f"prompt lengths must be positive: {lens}")
    n_req = len(lens)
    head_dim = cfg.head_dim or (cfg.d_model // max(cfg.n_heads, 1))
    max_ctx = max(lens) + gen

    # layout x page_size x block resolved jointly by the DSE (bucketed
    # on the padded max length when --bucketing is on)
    opts = Options(bucketing=True) if bucketing else None
    (sel_layout, sel_ps, blk, depth), plan = ops.resolve_plan(
        "paged_decode", int(max_ctx), int(head_dim), options=opts)
    layout = layout or sel_layout
    page_size = int(page_size or sel_ps)

    npm = -(-max_ctx // page_size)
    cache = paged.PagedKVCache.init(cfg, slots, npm * page_size,
                                    page_size=page_size, layout=layout)
    # the DSE's streaming block, cut as the kernel cuts it
    blk, depth = paged_decode_blocks(
        block=blk, depth=depth, page_size=page_size, n_pages_max=npm,
        kv_heads=cfg.n_kv_heads, head_dim=head_dim, layout=layout,
        dtype=cache.buffers[0].dtype)
    free_pages = list(range(cache.n_pages - 1, 0, -1))  # page 0 reserved
    window = cache.window
    n_full = tr.layers_of_kind(cfg, "full")
    if window is not None:
        ring = cache.ring
        free_ring = list(range(cache.win_buffers[0].shape[1] - 1, 0, -1))
        # the block a windowed layer's kernel streams
        win_blk, _ = paged_decode_blocks(
            block=window_block(blk, page_size, window), depth=depth,
            page_size=page_size, n_pages_max=ring, kv_heads=cfg.n_kv_heads,
            head_dim=head_dim, layout=layout, dtype=cache.buffers[0].dtype)
    for s in range(slots):                              # park every slot
        cache = cache.assign_pages(s, [0] * npm, 0)

    moe_impl = "kernel" if use_pallas else "dropless"
    prefill_fn = jax.jit(steps_mod.make_cache_prefill_step(cfg, moe_impl),
                         donate_argnums=(1,))

    def _step(params, cache, tok, pallas):
        logits, cache, *moe_stats = paged.paged_decode_step(
            params, cfg, cache, tok, use_pallas=pallas, block=blk,
            depth=depth, with_stats=bool(cfg.n_experts))
        moe_stats = moe_stats[0] if moe_stats else None
        last = logits[:, -1]
        nxt = jnp.argmax(model.mask_vocab_pad(last, cfg), axis=-1)
        return nxt.astype(jnp.int32), last[:, :cfg.vocab], cache, moe_stats

    def serve_step(p, c, t):
        return _step(p, c, t, use_pallas)

    def certify_reference(p, c, t):
        # the logits alone: the reference's pools are never used, so
        # they are never kept beside the served ones
        return _step(p, c, t, False)[1]

    step_fn = jax.jit(serve_step, donate_argnums=(1,))
    certified, certify_err = None, None
    if use_pallas and certify:
        ref_fn = jax.jit(certify_reference)

    rng = np.random.RandomState(seed)
    prompt_pool = rng.randint(0, cfg.vocab, (n_req, max(lens)))

    from collections import deque
    queue = deque(range(n_req))
    slot_req: List[Optional[int]] = [None] * slots
    slot_pages: List[List[int]] = [[] for _ in range(slots)]
    slot_ring: List[List[int]] = [[] for _ in range(slots)]
    slot_done = [0] * slots
    next_tok = np.zeros(slots, np.int32)
    out = np.zeros((n_req, gen), np.int64)
    steps = active_steps = admitted = evicted = 0
    prefill_s = decode_s = 0.0
    dense_words = paged_words = 0   # modeled HBM traffic over the trace
    kv_blocks = 0                   # blocks the kernel streams, all steps

    from repro.core import cost as cost_mod
    hkv = cfg.n_kv_heads

    while queue or any(r is not None for r in slot_req):
        for s in range(slots):                               # admit
            if slot_req[s] is not None or not queue:
                continue
            r = queue[0]
            ln = lens[r]
            need = -(-(ln + gen) // page_size)
            need_ring = min(ring, need) if window is not None else 0
            if len(free_pages) < need or (
                    window is not None and len(free_ring) < need_ring):
                break
            queue.popleft()
            pages = [free_pages.pop() for _ in range(need)]
            rpages = [free_ring.pop() for _ in range(need_ring)]
            t0 = time.time()
            with telemetry.span("serve.admit", request=r, slot=s,
                                prompt_len=ln, pages=need):
                with telemetry.span("serve.admit.prefill"):
                    dcache = model.init_cache(cfg, 1, ln)
                    prompt = jnp.asarray(prompt_pool[r:r + 1, :ln],
                                         jnp.int32)
                    first, dcache = _prefill(prefill_fn, params, dcache,
                                             prompt, _ring_len(cfg, ln))
                    next_tok[s] = int(np.asarray(first)[0])
                with telemetry.span("serve.admit.scatter",
                                    pages_full=need if n_full else 0,
                                    pages_window=need_ring) as sp:
                    cache = cache.assign_pages(s, pages, ln, rpages)
                    cache = _write_prompt(cfg, cache, s, dcache, ln)
                    jax.block_until_ready((cache.buffers,
                                           cache.win_buffers))
                    if telemetry.enabled():
                        sp.set(**_memory_in_use(cache.buffers[0]))
            dt = time.time() - t0
            prefill_s += dt
            telemetry.observe("serve.admit_s", dt)
            slot_req[s], slot_pages[s], slot_done[s] = r, pages, 0
            slot_ring[s] = rpages
            admitted += 1

        with telemetry.span("serve.step.host"):
            active = [s for s in range(slots) if slot_req[s] is not None]
            # modeled decode traffic for THIS step: a dense continuous
            # server sizes every lane's cache to the longest possible
            # context, the paged pool streams only live pages
            live = [lens[slot_req[s]] + slot_done[s] for s in active]
            dense_words += cfg.n_layers * cost_mod.dense_decode_traffic_words(
                len(active), max_ctx, hkv, head_dim)
            paged_words += cfg.n_layers * cost_mod.paged_decode_traffic_words(
                live, page_size, hkv, head_dim)
            # each live slot folds the blocks that hold its tokens and
            # the step's own, in every full layer; in a windowed layer
            # only the blocks that meet its window
            n_blk = n_full * sum(
                min(n, npm * page_size - 1) // blk + 1 for n in live)
            if window is not None:
                n_blk += (cfg.n_layers - n_full) * sum(
                    n // win_blk - max(n - window + 1, 0) // win_blk + 1
                    for n in live)
            kv_blocks += n_blk
            telemetry.count("serve.kv_blocks", n_blk)
            tok = jnp.asarray(next_tok.reshape(slots, 1))
            check = certified is None and use_pallas and certify
        if check:   # the reference path reads the cache before the
            # fused step donates it
            with telemetry.span("serve.certify", layout=layout,
                                page_size=page_size):
                ref_logits = ref_fn(params, cache, tok)
        t0 = time.time()
        with telemetry.span("serve.decode_step", step=steps,
                            active=len(active)) as step_sp:
            with telemetry.span("serve.step.launch"):
                nxt, logits, cache, moe_stats = step_fn(params, cache, tok)
                if moe_stats is not None:   # on its way while nxt is made
                    for t in moe_stats:
                        t.copy_to_host_async()
            with telemetry.span("serve.step.wait"):
                nxt = np.asarray(nxt)
                if moe_stats is not None:
                    held, touched = (int(np.sum(t)) for t in moe_stats)
                    telemetry.count("moe.tokens_held", held)
                    telemetry.count("moe.experts_touched", touched)
                    step_sp.set(moe_tokens_held=held,
                                moe_experts_touched=touched)
        dt = time.time() - t0
        with telemetry.span("serve.step.host"):
            if check:
                certified, certify_err = _certify_logits(logits,
                                                         ref_logits)
                if not certified:
                    raise CertificationError(
                        f"paged_decode/{layout}/p{page_size}: fused "
                        f"logits differ from the reference paged path "
                        f"by {certify_err:.3e} of their scale "
                        f"(> {CERTIFY_RTOL})")
            decode_s += dt
            steps += 1
            active_steps += len(active)

            # parked slots wrote their garbage token to reserved page
            # 0; pin their lengths back to zero so they never walk off
            # the page table
            mask = np.zeros(slots, np.int32)
            mask[active] = 1
            cache = cache.replace(seq_lens=cache.seq_lens
                                  * jnp.asarray(mask))

            for s in active:
                r = slot_req[s]
                out[r, slot_done[s]] = int(nxt[s])
                next_tok[s] = nxt[s]
                slot_done[s] += 1
                if slot_done[s] == gen:                      # evict
                    free_pages.extend(slot_pages[s])
                    if window is not None:
                        free_ring.extend(slot_ring[s])
                    cache = cache.assign_pages(s, [0] * npm, 0)
                    slot_req[s], slot_pages[s], slot_ring[s] = None, [], []
                    evicted += 1

    occupancy = active_steps / max(steps * slots, 1)
    tokens_out = n_req * gen
    stats = {
        "layout": layout, "page_size": page_size, "block": int(blk),
        "ring_pages": int(ring) if window is not None else 0,
        "depth": int(depth), "kv_blocks": int(kv_blocks),
        "plan_sizes": dict(plan.sizes),
        "use_pallas": bool(use_pallas), "certified": certified,
        "certify_err": certify_err,
        "slots": slots, "requests": n_req, "steps": steps,
        "occupancy": occupancy, "admitted": admitted,
        "evicted": evicted, "prefill_s": prefill_s,
        "decode_s": decode_s,
        "ms_per_token": decode_s / max(tokens_out, 1) * 1e3,
        "modeled_dense_traffic_words": int(dense_words),
        "modeled_paged_traffic_words": int(paged_words),
    }
    print(f"continuous serve: {n_req} requests over {slots} slots, "
          f"{steps} steps, occupancy {occupancy:.2f}; "
          f"layout={layout} page_size={page_size} block={blk} "
          f"depth={depth} pallas={use_pallas} certified={certified}; "
          f"decode {decode_s:.2f}s "
          f"({stats['ms_per_token']:.1f} ms/token)")
    return out, stats


def _write_prompt(cfg, cache, slot: int, dcache: Dict, ln: int):
    """The prefilled dense cache of one request into its pages: every
    position into the full layers' pages, the newest ``window`` into
    the windowed layers' ring (the dense ring holds position ``p`` at
    slot ``p % C``)."""
    for kind in tr.kinds_in_plan(cfg):
        kk, vk = tr.kv_keys(cfg, kind)
        if kind == "full":
            cache = cache.write_tokens(slot, dcache[kk][:, 0, :, :ln],
                                       dcache[vk][:, 0, :, :ln], 0)
            continue
        n = min(ln, cache.window)
        idx = (ln - n + np.arange(n)) % dcache[kk].shape[3]
        cache = cache.write_window(slot, dcache[kk][:, 0][:, :, idx],
                                   dcache[vk][:, 0][:, :, idx], ln - n)
    return cache


def _parse_lens(text: Optional[str]) -> Optional[Tuple[int, ...]]:
    if not text:
        return None
    return tuple(int(x) for x in text.split(",") if x.strip())


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHS), required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--prompt-lens", type=str, default=None,
                    help="comma-separated per-request prompt lengths "
                         "(mixed batch; overrides --prompt-len)")
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--bucketing", action="store_true",
                    help="resolve the paged-decode plan through the "
                         "shape-bucket warm-start layer (--continuous "
                         "only)")
    ap.add_argument("--continuous", action="store_true",
                    help="continuous batching over a paged KV pool: "
                         "--batch is the slot count, --prompt-lens the "
                         "request trace (admit/evict per decode step)")
    ap.add_argument("--page-size", type=int, default=None,
                    help="override the DSE-selected KV page size "
                         "(--continuous only)")
    ap.add_argument("--layout", choices=("split", "fused"), default=None,
                    help="override the DSE-selected KV layout "
                         "(--continuous only)")
    ap.add_argument("--no-pallas", action="store_true",
                    help="use the reference paged attention instead of "
                         "the fused Pallas kernel (--continuous only)")
    args = ap.parse_args()
    backend.enable_compile_cache()
    if args.continuous:
        toks, _ = serve_continuous(
            args.arch, args.smoke, args.batch, args.gen,
            prompt_lens=_parse_lens(args.prompt_lens),
            prompt_len=args.prompt_len, page_size=args.page_size,
            layout=args.layout, use_pallas=not args.no_pallas,
            bucketing=args.bucketing)
    else:
        toks = serve(args.arch, args.smoke, args.batch, args.prompt_len,
                     args.gen, prompt_lens=_parse_lens(args.prompt_lens))
    print("generated token block:", toks.shape)


if __name__ == "__main__":
    main()
