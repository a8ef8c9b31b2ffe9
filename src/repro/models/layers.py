"""Core transformer layers: RMSNorm, RoPE, (blockwise) attention, SwiGLU.

Attention for training/prefill is *blockwise with online softmax* (a pure
jnp twin of the Pallas flash kernel): memory is O(S * chunk), never
O(S^2), which is what lets prefill_32k lower/compile within HBM.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.models.builder import Leaf

NEG_INF = -1e30


# ----------------------------------------------------------------- norms
def rmsnorm(x, weight, eps=1e-6):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    out = x * jax.lax.rsqrt(var + eps) * (1.0 + weight.astype(jnp.float32))
    return out.astype(dtype)


# ------------------------------------------------------------------ rope
def rope(x, positions, theta=10_000.0):
    """x: (..., S, H, D) rotated at absolute ``positions`` (..., S)."""
    d = x.shape[-1]
    half = d // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (..., S, half)
    cos = jnp.cos(angles)[..., None, :]  # (..., S, 1, half)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ------------------------------------------------------------- attention
def _softcap(scores, cap):
    if cap and cap > 0.0:
        return cap * jnp.tanh(scores / cap)
    return scores


def blockwise_attention(q, k, v, *, causal=True, window=0, softcap=0.0,
                        q_offset=0, q_chunk=512, kv_chunk=512):
    """Online-softmax attention.

    q: (B, Sq, H, D); k: (B, Sk, KH, D), v: (B, Sk, KH, Dv) with
    H = KH * G (``Dv`` may differ from ``D``, as in latent attention).
    ``window`` > 0 limits attention to the last ``window`` keys (sliding
    window, inclusive of self).  ``q_offset``: absolute position of q[0]
    relative to k[0] (for chunked prefill; 0 for plain self-attention).
    Returns (B, Sq, H, D).
    """
    B, Sq, H, D = q.shape
    _, Sk, KH, _ = k.shape
    Dv = v.shape[-1]
    G = H // KH
    scale = D ** -0.5

    def _pick(S, c):  # largest divisor of S that is <= c
        c = min(c, S)
        while S % c:
            c -= 1
        return c

    q_chunk = _pick(Sq, q_chunk)
    kv_chunk = _pick(Sk, kv_chunk)
    nq, nk = Sq // q_chunk, Sk // kv_chunk

    qc = q.reshape(B, nq, q_chunk, KH, G, D)
    kc = k.reshape(B, nk, kv_chunk, KH, D)
    vc = v.reshape(B, nk, kv_chunk, KH, Dv)

    def q_step(_, qi):
        qblk = qc[:, qi]  # (B, qc, KH, G, D)
        qpos = q_offset + qi * q_chunk + jnp.arange(q_chunk)

        @jax.checkpoint  # flash-style: recompute scores/probs in backward
        def kv_step(carry, ki):
            acc, m, l = carry
            kblk, vblk = kc[:, ki], vc[:, ki]
            kpos = ki * kv_chunk + jnp.arange(kv_chunk)
            s = jnp.einsum("bqhgd,bkhd->bhgqk", qblk, kblk,
                           preferred_element_type=jnp.float32) * scale
            s = _softcap(s, softcap)
            mask = jnp.ones((q_chunk, kv_chunk), bool)
            if causal:
                mask &= kpos[None, :] <= qpos[:, None]
            if window:
                mask &= kpos[None, :] > qpos[:, None] - window
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + p.sum(axis=-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bhgqk,bkhd->bhgqd", p, vblk,
                preferred_element_type=jnp.float32)
            return (acc_new, m_new, l_new), None

        acc0 = jnp.zeros((B, KH, G, q_chunk, Dv), jnp.float32)
        m0 = jnp.full((B, KH, G, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, KH, G, q_chunk), jnp.float32)
        (acc, m, l), _ = jax.lax.scan(kv_step, (acc0, m0, l0),
                                      jnp.arange(nk))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return None, out.astype(q.dtype)  # (B, KH, G, qc, D)

    _, outs = jax.lax.scan(jax.checkpoint(q_step), None, jnp.arange(nq))
    # outs: (nq, B, KH, G, qc, Dv) -> (B, Sq, H, Dv)
    out = jnp.moveaxis(outs, 0, 1).reshape(B, nq, H, q_chunk, Dv)
    out = out.transpose(0, 1, 3, 2, 4).reshape(B, Sq, H, Dv)
    return out


def decode_attention(q, k_cache, v_cache, pos, *, window=0, softcap=0.0):
    """Single-token attention against a cache.

    q: (B, 1, H, D); caches: (B, cap, KH, D); pos: int32 scalar or (B,)
    vector — number of tokens already in the cache *including* the one
    just written at ``pos % cap`` (ring) or ``pos`` (linear).  A vector
    ``pos`` gives every batch row its own decode position (continuous
    batching: co-batched requests at different depths).  Entries with
    absolute index > pos or <= pos - window are masked.
    """
    B, cap, KH, D = k_cache.shape
    H = q.shape[2]
    G = H // KH
    scale = D ** -0.5
    qh = q.reshape(B, KH, G, D)
    s = jnp.einsum("bhgd,bkhd->bhgk", qh, k_cache,
                   preferred_element_type=jnp.float32) * scale
    s = _softcap(s, softcap)
    slot = jnp.arange(cap)
    if jnp.ndim(pos):                       # per-row positions: (B, cap)
        p_ = pos[:, None]
        if window:
            absidx = p_ - ((p_ - slot[None, :]) % cap)
            valid = (absidx >= 0) & (absidx <= p_) & (absidx > p_ - window)
        else:
            valid = slot[None, :] <= p_
        s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    else:
        if window:  # ring buffer: absolute index of slot i
            absidx = pos - ((pos - slot) % cap)
            valid = (absidx >= 0) & (absidx <= pos) & (absidx > pos - window)
        else:
            valid = slot <= pos
        s = jnp.where(valid[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgk,bkhd->bhgd", p.astype(v_cache.dtype), v_cache)
    return out.reshape(B, 1, H, D)


# ----------------------------------------------------------------- MLP
def swiglu(x, w_gate, w_up, w_down, shard=None):
    h = jax.nn.silu(x @ w_gate) * (x @ w_up)
    if shard is not None:
        h = shard(h, "batch", "seq", "ff")
    return h @ w_down


# ------------------------------------------------------- declarations
def attn_decl(cfg) -> dict:
    d, qd, kvd, hd = cfg.d_model, cfg.q_dim, cfg.kv_dim, cfg.resolved_head_dim
    decl = {
        "wq": Leaf((d, qd), ("embed", "q_dim")),
        "wk": Leaf((d, kvd), ("embed", "kv_dim")),
        "wv": Leaf((d, kvd), ("embed", "kv_dim")),
        "wo": Leaf((qd, d), ("q_dim", "embed")),
    }
    if cfg.qkv_bias:
        decl["bq"] = Leaf((qd,), ("q_dim",), "zeros")
        decl["bk"] = Leaf((kvd,), ("kv_dim",), "zeros")
        decl["bv"] = Leaf((kvd,), ("kv_dim",), "zeros")
    if cfg.qk_norm:
        decl["q_norm"] = Leaf((hd,), ("head_dim",), "zeros")
        decl["k_norm"] = Leaf((hd,), ("head_dim",), "zeros")
    return decl


def mla_decl(cfg) -> dict:
    """Latent attention without a query LoRA: ``wq`` gives each head's
    query (``qk_nope_head_dim`` + ``qk_rope_head_dim``); ``wkva`` gives
    the latent (``kv_lora_rank``, normed by ``kva_norm``) and one rotary
    key shared by all heads; ``wkvb`` expands the latent to each head's
    key part (``qk_nope_head_dim``) and value (``v_head_dim``)."""
    d, H = cfg.d_model, cfg.num_heads
    nope, rope_d = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    c, dv = cfg.kv_lora_rank, cfg.v_head_dim
    return {
        "wq": Leaf((d, H * (nope + rope_d)), ("embed", "q_dim")),
        "wkva": Leaf((d, c + rope_d), ("embed", None)),
        "kva_norm": Leaf((c,), (None,), "zeros"),
        "wkvb": Leaf((c, H * (nope + dv)), (None, "q_dim")),
        "wo": Leaf((H * dv, d), ("q_dim", "embed")),
    }


def mlp_decl(cfg) -> dict:
    d, f = cfg.d_model, cfg.d_ff
    return {
        "w_gate": Leaf((d, f), ("embed", "ff")),
        "w_up": Leaf((d, f), ("embed", "ff")),
        "w_down": Leaf((f, d), ("ff", "embed")),
    }


# -------------------------------------------------------------- apply
def attn_qkv(params, x, positions, cfg):
    B, S, _ = x.shape
    hd = cfg.resolved_head_dim
    q = x @ params["wq"]
    k = x @ params["wk"]
    v = x @ params["wv"]
    if cfg.qkv_bias:
        q = q + params["bq"]
        k = k + params["bk"]
        v = v + params["bv"]
    q = q.reshape(B, S, cfg.num_heads, hd)
    k = k.reshape(B, S, cfg.num_kv_heads, hd)
    v = v.reshape(B, S, cfg.num_kv_heads, hd)
    if cfg.qk_norm:
        q = rmsnorm(q, params["q_norm"], cfg.norm_eps)
        k = rmsnorm(k, params["k_norm"], cfg.norm_eps)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    return q, k, v


def attn_train(params, x, cfg, *, window=0, causal=True, shard=None,
               q_chunk=512, kv_chunk=512):
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    q, k, v = attn_qkv(params, x, positions, cfg)
    # note: no explicit q/k/v constraints here — GSPMD propagates the head
    # sharding from the (q_dim/kv_dim)-sharded projection weights, which
    # handles GQA counts that don't divide the model axis.
    out = blockwise_attention(q, k, v, causal=causal, window=window,
                              softcap=cfg.attn_logit_softcap,
                              q_chunk=q_chunk, kv_chunk=kv_chunk)
    return out.reshape(B, S, cfg.q_dim) @ params["wo"]


def _quantize_kv(t):
    """t: (B, 1, KH, D) -> (int8 values, (B, 1, KH) f32 scales)."""
    scale = jnp.max(jnp.abs(t.astype(jnp.float32)), axis=-1) / 127.0
    scale = jnp.maximum(scale, 1e-8)
    q = jnp.clip(jnp.round(t.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale


def attn_decode(params, x, cache, pos, cfg, *, window=0, shard=None,
                mask=None, layer=None):
    """One-token decode. cache: {"k": (B,cap,KH,D), "v": ...} (+ optional
    int8 "k_scale"/"v_scale" when cfg.kv_cache_dtype == "int8").

    ``pos`` is an int32 scalar (every row at the same depth — the
    batch-synchronous path) or a (B,) vector (continuous batching: each
    row writes/reads its own cache slot).  ``mask`` (B,) bool: rows where
    it is False write nothing (their row's update is dropped), so the
    cache is updated in place, one row per writing slot.  ``layer``: the
    cache leaves are stacked over layers; this layer writes at
    ``[layer, row, slot]`` and reads its own slice.  Returns (out,
    new_cache), ``new_cache`` shaped like ``cache``.
    """
    B = x.shape[0]
    if mask is not None:
        pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    vec = jnp.ndim(pos) > 0
    positions = (jnp.reshape(pos, (B, 1)).astype(jnp.int32) if vec
                 else jnp.full((B, 1), pos, jnp.int32))
    q, k, v = attn_qkv(params, x, positions, cfg)
    cap = cache["k"].shape[-3]
    slot = (pos % cap) if window else jnp.minimum(pos, cap - 1)
    kv_seq_ax = "cache_seq" if not window else "kv_seq"
    lead = () if layer is None else (layer,)

    if vec:
        rows = jnp.arange(B)
        if mask is not None:
            slot = jnp.where(mask, slot, cap)      # out of range: dropped

        def put(buf, val):           # per-row scatter: row b writes slot[b]
            return buf.at[lead + (rows, slot)].set(val[:, 0], mode="drop")
    else:
        def put(buf, val):
            start = lead + (0, slot) + (0,) * (val.ndim - 2)
            return jax.lax.dynamic_update_slice(
                buf, val.reshape((1,) * len(lead) + val.shape), start)

    def read(buf):                   # this layer's (B, cap, ...) slice
        return buf if layer is None else buf[layer]

    with jax.named_scope("kv_write"):
        if "k_scale" in cache:   # §Perf iteration 4: int8 cache halves reads
            kq, ks = _quantize_kv(k)
            vq, vs = _quantize_kv(v)
            new_cache = {"k": put(cache["k"], kq), "v": put(cache["v"], vq),
                         "k_scale": put(cache["k_scale"], ks),
                         "v_scale": put(cache["v_scale"], vs)}
        else:
            new_cache = {"k": put(cache["k"], k), "v": put(cache["v"], v)}
    if "k_scale" in cache:
        k_cache = (read(new_cache["k"]).astype(jnp.float32)
                   * read(new_cache["k_scale"])[..., None]).astype(x.dtype)
        v_cache = (read(new_cache["v"]).astype(jnp.float32)
                   * read(new_cache["v_scale"])[..., None]).astype(x.dtype)
    else:
        k_cache, v_cache = read(new_cache["k"]), read(new_cache["v"])
    if shard is not None:
        k_cache = shard(k_cache, "batch", kv_seq_ax, "kv_heads", "head_dim")
        v_cache = shard(v_cache, "batch", kv_seq_ax, "kv_heads", "head_dim")
    out = decode_attention(q, k_cache, v_cache, pos, window=window,
                           softcap=cfg.attn_logit_softcap)
    out = out.reshape(B, 1, cfg.q_dim) @ params["wo"]
    return out, new_cache


# --------------------------------------------------- latent attention
def _mla_project(params, x, positions, cfg):
    """Queries, normed latents and rotated shared keys of ``x`` (B, S, d)
    at ``positions`` (B, S): q_nope (B,S,H,nope), q_pe (B,S,H,rope),
    c (B,S,kv_lora_rank), k_pe (B,S,rope)."""
    B, S, _ = x.shape
    H, nope = cfg.num_heads, cfg.qk_nope_head_dim
    q = (x @ params["wq"]).reshape(B, S, H, -1)
    kva = x @ params["wkva"]
    c = rmsnorm(kva[..., :cfg.kv_lora_rank], params["kva_norm"],
                cfg.norm_eps)
    k_pe = rope(kva[..., None, cfg.kv_lora_rank:], positions,
                cfg.rope_theta)[..., 0, :]
    return (q[..., :nope], rope(q[..., nope:], positions, cfg.rope_theta),
            c, k_pe)


def _mla_up(params, cfg):
    """``wkvb`` split per head: (kv_lora_rank, H, nope) and (.., H, v)."""
    w = params["wkvb"].reshape(cfg.kv_lora_rank, cfg.num_heads, -1)
    return w[..., :cfg.qk_nope_head_dim], w[..., cfg.qk_nope_head_dim:]


def mla_train(params, x, cfg, *, q_chunk=512, kv_chunk=512):
    """Latent attention over a whole sequence, expanded: each head's key
    is [latent up-projection | the shared rotary key], its value the
    latent's up-projection."""
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    q_nope, q_pe, c, k_pe = _mla_project(params, x, positions, cfg)
    w_uk, w_uv = _mla_up(params, cfg)
    k_nope = jnp.einsum("bsc,chn->bshn", c, w_uk)
    v = jnp.einsum("bsc,chv->bshv", c, w_uv)
    H = cfg.num_heads
    q = jnp.concatenate([q_nope, q_pe], -1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe[:, :, None], (B, S, H, k_pe.shape[-1]))],
        -1)
    out = blockwise_attention(q, k, v, causal=True, q_chunk=q_chunk,
                              kv_chunk=kv_chunk)
    return out.reshape(B, S, H * cfg.v_head_dim) @ params["wo"]


def mla_decode(params, x, cache, pos, cfg, *, mask=None, layer=None):
    """One-token latent attention against the latent cache, absorbed:
    the query's key part is taken into the latent space
    (``q_nope . w_uk^T``) and the weighted sum of latents is expanded by
    ``w_uv`` after the softmax, so each cached position is read as its
    ``kv_lora_rank`` latent and its rotary key, never as per-head keys
    and values.  Exactly the expanded form, reassociated.  The core
    (the rotary key's write, scores, softmax, weighted latents) is
    ``ops.mla_decode``: on a TPU one Pallas pass over each slot's live
    rows.

    cache: {"ckv": (B, cap, kv_lora_rank), "kpe": (B, cap, rope)}, with
    a leading layer axis when ``layer`` is given (this layer writes at
    ``[layer, row, slot]``).  ``pos``: (B,) or scalar; ``mask`` (B,)
    bool: rows where it is False write nothing (their index is sent
    out of range and dropped).  Returns (out (B, 1, d), new_cache)."""
    B = x.shape[0]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    q_nope, q_pe, c, k_pe = _mla_project(params, x, pos[:, None], cfg)
    cap = cache["ckv"].shape[-2]
    slot = jnp.minimum(pos, cap - 1)
    if mask is not None:
        slot = jnp.where(mask, slot, cap)          # out of range: dropped
    lead = () if layer is None else (layer,)
    with jax.named_scope("kv_write"):
        ckv = cache["ckv"].at[lead + (jnp.arange(B), slot)].set(
            c[:, 0], mode="drop")
    w_uk, w_uv = _mla_up(params, cfg)
    q_lat = jnp.einsum("bhn,chn->bhc", q_nope[:, 0], w_uk)
    o_lat, kpe = ops.mla_decode(
        q_lat, q_pe[:, 0], k_pe[:, 0], ckv, cache["kpe"], pos, slot,
        scale=(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5,
        layer=layer)
    new_cache = {"ckv": ckv, "kpe": kpe}
    o = jnp.einsum("bhc,chv->bhv", o_lat, w_uv)
    return o.reshape(B, 1, -1) @ params["wo"], new_cache
