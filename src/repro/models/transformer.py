"""Decoder-only transformer (dense GQA / MoE / multimodal backbones).

Pure JAX: params are dict pytrees with a stacked leading layer dim,
consumed by ``jax.lax.scan`` so the lowered HLO stays small for 80-layer
72B-parameter configs compiled on 512 dry-run devices.  Supports:

  * GQA / MQA attention with RoPE, optional QKV bias (Qwen-2), optional
    sliding window (Mixtral), squared-ReLU FFN (Nemotron-4);
  * windowed and full attention layers side by side in a repeating
    period (Mellum 2: three windowed layers with default RoPE to each
    full layer with YaRN), each kind with its own decode cache;
  * MoE FFN layers (every ``moe_layer_period``-th layer): the capacity
    path for training, the dropless share (``moe.moe_dropless``) for
    serving;
  * multi-codebook token embeddings / heads (MusicGen) and prefix
    embeddings from a stubbed modality frontend (InternVL);
  * full-sequence forward (training / prefill) and single-token decode
    with a preallocated KV cache (sliding-window configs keep a
    ring-buffer cache of ``min(window, max_len)``; the windowed layers
    of a mixed config one of ``min(2 * window, max_len)``, so a prompt
    chunk of up to ``window`` tokens never overwrites keys the chunk's
    own queries still see).
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from . import layers as L
from . import moe as moe_mod
from .config import ModelConfig
from .sharding import hint

Params = Dict[str, Any]


# ----------------------------------------------------------------- shapes
def param_shapes(cfg: ModelConfig) -> Dict[str, Tuple[Tuple[int, ...], str]]:
    """name -> (shape, init_kind); init_kind in {embed, dense, zeros}."""
    d, f, v = cfg.d_model, cfg.d_ff, cfg.padded_vocab
    nl = cfg.n_layers
    qk, kv = cfg.qk_dim, cfg.kv_dim
    shapes: Dict[str, Tuple[Tuple[int, ...], str]] = {}
    if cfg.n_codebooks:
        shapes["embed"] = ((cfg.n_codebooks, v, d), "embed")
        shapes["lm_head"] = ((cfg.n_codebooks, d, v), "dense")
    else:
        shapes["embed"] = ((v, d), "embed")
        shapes["lm_head"] = ((d, v), "dense")
    shapes["final_norm"] = ((d,), "zeros")

    shapes.update({
        "ln1": ((nl, d), "zeros"),
        "ln2": ((nl, d), "zeros"),
        "wq": ((nl, d, qk), "dense"),
        "wk": ((nl, d, kv), "dense"),
        "wv": ((nl, d, kv), "dense"),
        "wo": ((nl, qk, d), "dense"),
    })
    if cfg.qkv_bias:
        shapes.update({"bq": ((nl, qk), "zeros"),
                       "bk": ((nl, kv), "zeros"),
                       "bv": ((nl, kv), "zeros")})

    n_moe = nl // cfg.moe_layer_period if cfg.n_experts else 0
    n_dense = nl - n_moe
    if n_dense:
        shapes.update({
            "w1": ((n_dense, d, f), "dense"),
            "w2": ((n_dense, f, d), "dense"),
        })
        if cfg.activation == "swiglu":
            shapes["w3"] = ((n_dense, d, f), "dense")
    if n_moe:
        for k_, s_ in moe_mod.param_shapes(cfg, n_moe).items():
            shapes[f"moe_{k_}"] = (s_, "dense")
    return shapes


def param_specs(cfg: ModelConfig) -> Params:
    dt = jnp.dtype(cfg.dtype)
    return {k: jax.ShapeDtypeStruct(s, dt)
            for k, (s, _) in param_shapes(cfg).items()}


def init_params(cfg: ModelConfig, key: jax.Array) -> Params:
    dt = jnp.dtype(cfg.dtype)
    out = {}
    shapes = param_shapes(cfg)
    keys = jax.random.split(key, len(shapes))
    for (name, (shape, kind)), k in zip(sorted(shapes.items()), keys):
        if kind == "zeros":
            out[name] = jnp.zeros(shape, dt)
        elif kind == "embed":
            out[name] = L.embed_init(k, shape, dt)
        else:
            in_axis = -2 if len(shape) >= 2 else 0
            out[name] = L.dense_init(k, shape, in_axis=in_axis, dtype=dt)
    return out


def layer_plan(cfg: ModelConfig) -> Tuple[Tuple[bool, str], ...]:
    """The super-block the layer scans walk: for each layer of the
    period, whether its FFN is MoE and its attention kind.  The period
    is the least common multiple of the MoE period and the attention
    kinds' period."""
    mp = cfg.moe_layer_period if cfg.n_experts else 1
    kinds = cfg.attn_kinds
    period = mp * len(kinds) // math.gcd(mp, len(kinds))
    return tuple((bool(cfg.n_experts) and i % mp == mp - 1,
                  kinds[i % len(kinds)]) for i in range(period))


def kinds_in_plan(cfg: ModelConfig) -> Tuple[str, ...]:
    """The attention kinds present, full first."""
    return tuple(k for k in ("full", "window") if k in cfg.attn_kinds)


def layers_of_kind(cfg: ModelConfig, kind: str) -> int:
    kinds = cfg.attn_kinds
    return cfg.n_layers // len(kinds) * kinds.count(kind)


def kv_keys(cfg: ModelConfig, kind: str) -> Tuple[str, str]:
    """Decode-cache keys of a kind's K and V stacks."""
    if cfg.mixed_attention and kind == "window":
        return ("k_win", "v_win")
    return ("k", "v")


# -------------------------------------------------------------- attention
def _attn(p: Dict, x: jax.Array, cfg: ModelConfig,
          positions: jax.Array,
          kv_cache: Optional[Tuple] = None,
          cache_index: Optional[jax.Array] = None, kind: str = "full"):
    """x: (B, S, D).  With kv_cache=(k,v) of (B, Hkv, C, dh), performs
    decode: writes this step's k/v at ``cache_index`` (mod C: ring
    buffer for sliding windows) and attends over the cache.  ``kind``
    picks the layer's window and RoPE."""
    b, s, d = x.shape
    hq, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = jnp.einsum("bsd,dq->bsq", x, p["wq"])
    k = jnp.einsum("bsd,dq->bsq", x, p["wk"])
    v = jnp.einsum("bsd,dq->bsq", x, p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(b, s, hq, dh)
    k = k.reshape(b, s, hkv, dh)
    v = v.reshape(b, s, hkv, dh)
    window, yarn = cfg.window_of(kind), cfg.yarn_of(kind)
    q = L.rope(q, positions, cfg.rope_theta, yarn)
    k = L.rope(k, positions, cfg.rope_theta, yarn)
    q = hint(q, "data", None, "model", None)
    k = hint(k, "data", None, "model", None)

    group = hq // hkv
    qg = q.reshape(b, s, hkv, group, dh)

    if kv_cache is None:
        out = _sdpa_chunked(q, k, v, positions, cfg, window)
        out = out.reshape(b, s, hq * dh)
        return jnp.einsum("bsq,qd->bsd", out, p["wo"]), None

    if kv_cache is not None:
        ck, cv = kv_cache                       # (B, Hkv, C, dh)
        c = ck.shape[2]
        widx = (cache_index % c).astype(jnp.int32)
        ck = jax.lax.dynamic_update_slice(
            ck, k.transpose(0, 2, 1, 3).astype(ck.dtype),
            (0, 0, widx, 0))
        cv = jax.lax.dynamic_update_slice(
            cv, v.transpose(0, 2, 1, 3).astype(cv.dtype),
            (0, 0, widx, 0))
        scores = jnp.einsum("bskgh,bkch->bskgc",
                            qg.astype(jnp.float32),
                            ck.astype(jnp.float32)) * dh ** -0.5
        slotpos = jnp.arange(c)
        # ring semantics relative to the LAST slot this block wrote
        # (slots widx .. widx+s-1 hold positions cache_index ..
        # cache_index+s-1; the block never wraps the ring): slot j
        # holds absolute position last - ((wlast - j) mod C).  Each
        # query row i sits at position cache_index + i and attends
        # causally; abspos < 0 marks never-written slots (their zero
        # k/v must not leak into the softmax).
        last = cache_index + s - 1
        wlast = widx + s - 1
        abspos = last - (wlast - slotpos) % c
        qpos = cache_index + jnp.arange(s)
        valid = (abspos[None, :] <= qpos[:, None]) & (abspos >= 0)[None, :]
        if window is not None:
            valid &= abspos[None, :] > qpos[:, None] - window
        scores = jnp.where(valid[None, :, None, None, :],
                           scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bskgc,bkch->bskgh", probs,
                         cv.astype(jnp.float32))
        out = out.reshape(b, s, hq * dh).astype(x.dtype)
        return jnp.einsum("bsq,qd->bsd", out, p["wo"]), (ck, cv)

    raise AssertionError("full-sequence path returns above")


ATTN_CHUNK = 1024  # q-block size for the tiled softmax (XLA-level flash)


def _sdpa_chunked(q: jax.Array, k: jax.Array, v: jax.Array,
                  positions: jax.Array, cfg: ModelConfig,
                  window: Optional[int] = None) -> jax.Array:
    """Tiled softmax attention: scan over query blocks so the (s x s)
    score tensor never materializes -- the paper's strip-mine +
    interchange applied to attention (the Pallas kernel in
    kernels/flash_attention.py is the TPU-native version; this is the
    same tiling expressed in XLA for the sharded full-model step).

    GQA keys/values are expanded to full query heads so sharding stays a
    single head axis: shard heads over "model" when divisible, else
    shard the query *sequence* (14-head InternVL, 40-head Llama-4 on a
    16-way axis); the kernel path avoids the expansion on real TPUs.

    q: (B, S, Hq, dh); k, v: (B, S, Hkv, dh) -> (B, S, Hq, dh)
    """
    from .sharding import hint_first, model_axis_size

    b, s, hq, dh = q.shape
    hkv = k.shape[2]
    group = hq // hkv
    kq = jnp.repeat(k, group, axis=2)
    vq = jnp.repeat(v, group, axis=2)
    # pad heads to a multiple of the model axis (Llama-4's 40, MusicGen's
    # 24, InternVL's 14 on a 16-way axis): a small flop tax instead of
    # replicated attention or seq-shard gathers in the chunk loop.
    hq_orig = hq
    ms = model_axis_size()
    if ms and hq % ms != 0:
        pad = (-hq) % ms
        zq = jnp.zeros((b, s, pad, dh), q.dtype)
        q = jnp.concatenate([q, zq], axis=2)
        kq = jnp.concatenate([kq, zq], axis=2)
        vq = jnp.concatenate([vq, zq], axis=2)
        hq += pad
    head = [("data", None, "model", None)]
    q = hint_first(q, head)
    kq = hint_first(kq, head)
    vq = hint_first(vq, head)

    bq = min(ATTN_CHUNK, s)
    if s % bq != 0:
        bq = s
    n_blk = s // bq
    scale = dh ** -0.5

    # k-block streams for the online-softmax scan (leading axis is the
    # UNSHARDED block index, so scan slicing stays local)
    bk = bq
    n_kb = s // bk
    kq_blk = jnp.moveaxis(kq.reshape(b, n_kb, bk, hq, dh), 1, 0)
    vq_blk = jnp.moveaxis(vq.reshape(b, n_kb, bk, hq, dh), 1, 0)
    kpos_blk = positions.reshape(n_kb, bk)

    def one_block(i):
        """Online softmax over k-blocks: the (bq x s) probs tensor never
        materializes -- the paper's accumulator-forwarding metapipeline
        (= the Pallas kernel's structure) expressed at the XLA level,
        with running (max, sum, acc) carried between strided iterations.
        """
        qb = jax.lax.dynamic_slice_in_dim(q, i * bq, bq, axis=1)
        pb = jax.lax.dynamic_slice_in_dim(positions, i * bq, bq)
        qb = hint_first(qb, head)  # stays bf16: f32 accumulate on MXU

        def kstep(carry, inp):
            m_run, l_run, acc = carry
            kb, vb, kp = inp
            s_ = jnp.einsum("bshd,bthd->bhst", qb, kb,
                            preferred_element_type=jnp.float32) * scale
            mask = kp[None, :] <= pb[:, None]
            if window is not None:
                mask &= kp[None, :] > pb[:, None] - window
            s_ = jnp.where(mask[None, None], s_, -1e30)
            m_new = jnp.maximum(m_run, s_.max(-1))
            p = jnp.exp(s_ - m_new[..., None])
            alpha = jnp.exp(m_run - m_new)
            l_new = l_run * alpha + p.sum(-1)
            acc = (acc * alpha[..., None]
                   + jnp.einsum("bhst,bthd->bhsd", p.astype(vb.dtype),
                                vb, preferred_element_type=jnp.float32))
            return (m_new, l_new, acc), None

        m0 = jnp.full((b, hq, bq), -1e30, jnp.float32)
        l0 = jnp.zeros((b, hq, bq), jnp.float32)
        a0 = jnp.zeros((b, hq, bq, dh), jnp.float32)
        # remat each k-step: its backward recomputes the (bq x bk) probs
        # instead of saving them for every step (flash backward)
        (m_f, l_f, acc), _ = jax.lax.scan(
            jax.checkpoint(kstep), (m0, l0, a0),
            (kq_blk, vq_blk, kpos_blk))
        denom = jnp.where(l_f == 0.0, 1.0, l_f)
        out = (acc / denom[..., None]).astype(vq.dtype)
        out = jnp.moveaxis(out, 1, 2)              # (b, bq, h, dh)
        return hint_first(out, head)

    if n_blk == 1:
        out = one_block(0)
    else:
        # remat each q-block: backward recomputes its k-scan
        outs = jax.lax.map(jax.checkpoint(one_block),
                           jnp.arange(n_blk, dtype=jnp.int32))
        out = jnp.moveaxis(outs, 0, 1).reshape(b, s, hq, dh)
    return out[:, :, :hq_orig, :]


def _dense_ffn(p: Dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    act = L.activation("silu" if cfg.activation == "swiglu"
                       else cfg.activation)
    h = jnp.einsum("bsd,df->bsf", x, p["w1"])
    if cfg.activation == "swiglu":
        h = act(h) * jnp.einsum("bsd,df->bsf", x, p["w3"])
    else:
        h = act(h)
    h = hint(h, "data", None, "model")
    return jnp.einsum("bsf,fd->bsd", h, p["w2"])


def _block(slc: Dict, x, cfg: ModelConfig, positions, is_moe: bool,
           kv_cache=None, cache_index=None, kind: str = "full",
           moe_impl: str = "capacity"):
    """One layer.  ``moe_impl``: ``capacity`` (``moe.moe_ffn``, the
    GSPMD training path) or the dropless share, ``dropless`` in XLA or
    ``kernel`` through the grouped-matmul kernel."""
    a, new_cache = _attn(slc, L.rms_norm(x, slc["ln1"]), cfg, positions,
                         kv_cache, cache_index, kind)
    x = x + a
    h = L.rms_norm(x, slc["ln2"])
    if is_moe:
        moe_p = {k[4:]: v for k, v in slc.items() if k.startswith("moe_")}
        if moe_impl == "capacity":
            x = x + moe_mod.moe_ffn(moe_p, h, cfg)
        else:
            y, _ = moe_mod.moe_dropless(moe_p, h, cfg,
                                        use_pallas=moe_impl == "kernel")
            x = x + y
    else:
        x = x + _dense_ffn(slc, h, cfg)
    # sequence parallelism: the residual stream (and thus the per-layer
    # activations the backward scan saves) lives sequence-sharded over
    # the model axis -- 16x less saved-activation HBM per device
    x = hint(x, "data", "model", None)
    return x, new_cache


_ATTN_KEYS = ("ln1", "ln2", "wq", "wk", "wv", "wo", "bq", "bk", "bv")
_DENSE_KEYS = ("w1", "w2", "w3")


def _layer_stacks(params: Params, cfg: ModelConfig):
    """Split params into per-scan stacks: attention (all layers), dense
    ffn (dense layers), moe ffn (moe layers)."""
    attn = {k: params[k] for k in _ATTN_KEYS if k in params}
    dense = {k: params[k] for k in _DENSE_KEYS if k in params}
    moe = {k: v for k, v in params.items() if k.startswith("moe_")}
    return attn, dense, moe


EXPERT_KEYS = ("moe_we1", "moe_we3", "moe_we2")


def _super_stacks(params: Params, cfg: ModelConfig, plan,
                  whole_experts: bool = False):
    """The attention, dense-FFN and MoE stacks reshaped to
    ``(n_super, layers of that kind per super-block, ...)``.  With
    ``whole_experts`` the expert weights stay out of the scan (the
    kernel reads them from the whole stack, ``expert_stacks``) and
    the MoE stack carries each layer's index, ``moe_layer``."""
    attn, dense, moe = _layer_stacks(params, cfg)
    n_super = cfg.n_layers // len(plan)
    n_moe = sum(m for m, _ in plan)
    if whole_experts and moe:
        moe = {k: v for k, v in moe.items() if k not in EXPERT_KEYS}
        moe["moe_layer"] = jnp.arange(n_super * n_moe, dtype=jnp.int32)

    def per_super(n):
        return lambda t: t.reshape((n_super, n) + t.shape[1:])

    return (jax.tree.map(per_super(len(plan)), attn),
            jax.tree.map(per_super(len(plan) - n_moe), dense),
            jax.tree.map(per_super(n_moe), moe))


def expert_stacks(params: Params, whole_experts: bool) -> Dict:
    """Every MoE layer's expert weights, for the layers to take whole
    (``_super_stacks(..., whole_experts=True)``); else nothing."""
    if not whole_experts:
        return {}
    return {k: params[k] for k in EXPERT_KEYS if k in params}


def _layer_slice(plan, i: int, a_slc, d_slc, m_slc) -> Dict:
    """Layer ``i`` of a super-block: its attention params and its dense
    or MoE FFN params."""
    is_moe = plan[i][0]
    j = sum(m == is_moe for m, _ in plan[:i])
    sl = {k: v[i] for k, v in a_slc.items()}
    sl.update({k: v[j] for k, v in (m_slc if is_moe else d_slc).items()})
    return sl


def _embed_tokens(params: Params, cfg: ModelConfig,
                  tokens: jax.Array) -> jax.Array:
    if cfg.n_codebooks:
        # tokens: (B, S, n_codebooks) -- EnCodec frame stack, summed
        embs = [jnp.take(params["embed"][i], tokens[..., i], axis=0)
                for i in range(cfg.n_codebooks)]
        return sum(embs)
    return jnp.take(params["embed"], tokens, axis=0)


def forward(params: Params, cfg: ModelConfig, tokens: jax.Array,
            prefix_embeds: Optional[jax.Array] = None) -> jax.Array:
    """Full-sequence forward.  tokens: (B, S[, n_codebooks]) int32.
    prefix_embeds: (B, P, D) from the stubbed modality frontend."""
    x = _embed_tokens(params, cfg, tokens)
    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
    b, s, d = x.shape
    x = hint(x, "data", None, None)
    positions = jnp.arange(s)
    plan = layer_plan(cfg)

    def super_block(x, slices):
        a_slc, d_slc, m_slc = slices
        for i, (is_moe, kind) in enumerate(plan):
            x, _ = _block(_layer_slice(plan, i, a_slc, d_slc, m_slc), x,
                          cfg, positions, is_moe, kind=kind)
        return x, None

    if cfg.remat:
        super_block = jax.checkpoint(
            super_block, policy=jax.checkpoint_policies.nothing_saveable)

    x, _ = L.scan_layers(lambda c, sl: super_block(c, sl), x,
                         _super_stacks(params, cfg, plan), cfg.unroll)
    x = L.rms_norm(x, params["final_norm"])
    if cfg.n_codebooks:
        logits = jnp.einsum("bsd,ndv->bsnv", x, params["lm_head"])
    else:
        logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"])
    return logits


# ------------------------------------------------------------------ decode
def cache_len(cfg: ModelConfig, max_len: int, kind: str = "full") -> int:
    if cfg.mixed_attention:
        return (min(2 * cfg.sliding_window, max_len) if kind == "window"
                else max_len)
    if cfg.sliding_window is not None:
        return min(cfg.sliding_window, max_len)
    return max_len


def prefill_chunk(cfg: ModelConfig, max_len: int) -> int:
    """Longest block one prefill call may write: it must not wrap a
    ring (nor, with mixed kinds, exceed the window)."""
    if cfg.mixed_attention:
        return min(cfg.sliding_window, max_len)
    return cache_len(cfg, max_len)


def cache_specs(cfg: ModelConfig, batch: int, max_len: int) -> Dict:
    """Shapes of the decode cache: one K and one V stack per attention
    kind (``kv_keys``), over that kind's layers."""
    dt = jnp.dtype(cfg.dtype)
    out = {}
    for kind in kinds_in_plan(cfg):
        shp = (layers_of_kind(cfg, kind), batch, cfg.n_kv_heads,
               cache_len(cfg, max_len, kind), cfg.head_dim)
        for key in kv_keys(cfg, kind):
            out[key] = jax.ShapeDtypeStruct(shp, dt)
    return out


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=None) -> Dict:
    return {k: jnp.zeros(s.shape, dtype or s.dtype)
            for k, s in cache_specs(cfg, batch, max_len).items()}


def decode_step(params: Params, cfg: ModelConfig, cache: Dict,
                tokens: jax.Array, index: jax.Array,
                moe_impl: str = "capacity"):
    """One decode step.  tokens: (B, S[, n_codebooks]); index: scalar
    current position (number of tokens already in the cache).

    ``S > 1`` is block decode -- the whole-prompt prefill path: the S
    tokens are written to the cache contiguously at ``index`` and
    attend causally among themselves and over the cache.  The block
    must not wrap a ring buffer (``index % C + S <= C``) nor exceed
    ``prefill_chunk``; serving callers chunk prompts at that bound.
    ``moe_impl`` as in ``_block``.
    """
    x = _embed_tokens(params, cfg, tokens)
    s = x.shape[1]
    positions = index + jnp.arange(s, dtype=jnp.int32)
    plan = layer_plan(cfg)
    n_super = cfg.n_layers // len(plan)
    kinds = kinds_in_plan(cfg)

    def super_block(carry, slices):
        x = carry
        a_slc, d_slc, m_slc, kv = slices
        new = {kind: ([], []) for kind in kinds}
        for i, (is_moe, kind) in enumerate(plan):
            j = len(new[kind][0])
            kc, vc = kv[kind]
            sl = _layer_slice(plan, i, a_slc, d_slc, m_slc)
            if is_moe:
                sl.update(experts)
            x, (nk, nv) = _block(sl, x, cfg, positions, is_moe,
                                 kv_cache=(kc[j], vc[j]),
                                 cache_index=index, kind=kind,
                                 moe_impl=moe_impl)
            new[kind][0].append(nk)
            new[kind][1].append(nv)
        return x, {kind: (jnp.stack(ks), jnp.stack(vs))
                   for kind, (ks, vs) in new.items()}

    def per_super(t):
        return t.reshape((n_super, -1) + t.shape[1:])

    whole = moe_impl == "kernel"
    stacks = _super_stacks(params, cfg, plan, whole)
    experts = expert_stacks(params, whole)
    kv = {kind: tuple(per_super(cache[key]) for key in kv_keys(cfg, kind))
          for kind in kinds}
    x, new_kv = L.scan_layers(super_block, x, stacks + (kv,), cfg.unroll)
    x = L.rms_norm(x, params["final_norm"])
    if cfg.n_codebooks:
        logits = jnp.einsum("bsd,ndv->bsnv", x, params["lm_head"])
    else:
        logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"])
    new_cache = {}
    for kind in kinds:
        for key, t in zip(kv_keys(cfg, kind), new_kv[kind]):
            new_cache[key] = t.reshape(cache[key].shape)
    return logits, new_cache
