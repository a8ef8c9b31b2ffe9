"""Pure-jnp oracles for every Pallas kernel.

These are the ground truth for the per-kernel allclose sweeps AND the
portable fallback used when not running on TPU (CPU tests, GSPMD
dry-run lowering).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


# ------------------------------------------------- redundancy vote
def pairwise_agreement_ref(pub: jax.Array, atol: float = 0.0) -> jax.Array:
    """pub: (E, M, T). Returns (E, M, M) int32 — for each expert e, the
    number of elements on which copies i and j agree (within atol)."""
    diff = jnp.abs(pub[:, :, None, :] - pub[:, None, :, :])
    return (diff <= atol).sum(axis=-1).astype(jnp.int32)


def redundancy_vote_ref(pub: jax.Array, atol: float = 0.0):
    """pub: (E, M, *tail) — expert e's result as published by edge m.

    Replica-level majority vote (paper Step 3): the accepted copy of
    expert e is the one agreeing (on every element) with the largest
    coalition.  Returns (trusted (E, *tail), support (E,) int32).
    """
    E, M = pub.shape[:2]
    flat = pub.reshape(E, M, -1)
    T = flat.shape[-1]
    counts = pairwise_agreement_ref(flat, atol)          # (E, M, M)
    full_agree = (counts == T).astype(jnp.int32)         # exact-copy match
    support_per = full_agree.sum(axis=-1)                # (E, M)
    winner = support_per.argmax(axis=-1)                 # (E,)
    trusted = jnp.take_along_axis(
        flat, winner[:, None, None].astype(jnp.int32), axis=1)[:, 0]
    support = jnp.take_along_axis(support_per, winner[:, None], axis=1)[:, 0]
    return trusted.reshape((E,) + pub.shape[2:]), support


def redundancy_vote_with_flags_ref(pub: jax.Array, atol: float = 0.0):
    """Like redundancy_vote_ref but also returns the per-copy agreement
    flags (E, M): which edge's copy matched the accepted (majority) one —
    the signal the reputation layer consumes (paper §VI-B/D)."""
    E, M = pub.shape[:2]
    flat = pub.reshape(E, M, -1)
    T = flat.shape[-1]
    counts = pairwise_agreement_ref(flat, atol)
    full_agree = (counts == T).astype(jnp.int32)
    support_per = full_agree.sum(axis=-1)
    winner = support_per.argmax(axis=-1)
    trusted = jnp.take_along_axis(
        flat, winner[:, None, None].astype(jnp.int32), axis=1)[:, 0]
    support = jnp.take_along_axis(support_per, winner[:, None], axis=1)[:, 0]
    flags = jnp.take_along_axis(
        full_agree, winner[:, None, None], axis=1)[:, 0]   # (E, M)
    return trusted.reshape((E,) + pub.shape[2:]), support, flags


def redundancy_vote_masked_ref(pub: jax.Array, active: jax.Array,
                               atol: float = 0.0):
    """Vote restricted to ``active`` copies (reputation exclusion,
    paper §VI-D): excluded edges neither count toward majorities nor can
    be elected.  active: (M,) {0,1}.  Returns (trusted, support, flags)."""
    E, M = pub.shape[:2]
    flat = pub.reshape(E, M, -1)
    T = flat.shape[-1]
    counts = pairwise_agreement_ref(flat, atol)
    full_agree = (counts == T).astype(jnp.int32)
    a = active.astype(jnp.int32)
    support_per = (full_agree * a[None, None, :]).sum(axis=-1)   # (E, M)
    score = support_per * a[None, :] - (1 - a[None, :])          # bar excluded
    winner = score.argmax(axis=-1)
    trusted = jnp.take_along_axis(
        flat, winner[:, None, None].astype(jnp.int32), axis=1)[:, 0]
    support = jnp.take_along_axis(support_per, winner[:, None], axis=1)[:, 0]
    flags = jnp.take_along_axis(
        full_agree, winner[:, None, None], axis=1)[:, 0] * a[None, :]
    return trusted.reshape((E,) + pub.shape[2:]), support, flags


# ------------------------------------------------- grouped expert GEMM
def moe_gemm_ref(buf: jax.Array, w: jax.Array) -> jax.Array:
    """buf: (E, C, d), w: (E, d, f) -> (E, C, f)."""
    return jnp.einsum("ecd,edf->ecf", buf, w,
                      preferred_element_type=jnp.float32).astype(buf.dtype)


def moe_mlp_ref(buf, w_gate, w_up, w_down):
    """Full routed-expert SwiGLU: (E,C,d) -> (E,C,d)."""
    h = jax.nn.silu(moe_gemm_ref(buf, w_gate).astype(jnp.float32)) * \
        moe_gemm_ref(buf, w_up).astype(jnp.float32)
    return moe_gemm_ref(h.astype(buf.dtype), w_down)


# ------------------------------------------------- batched audit recompute
def audit_mlp_ref(params, x: jax.Array, gid: jax.Array) -> jax.Array:
    """Grouped gather-MLP oracle: out[s] = mlp(params[gid[s]], x[s]).

    params: stacked {w1 (E,d,h), b1 (E,h), w2 (E,h,o), b2 (E,o)};
    x: (S, C, d); gid: (S,) int32.  This is bit-identical to applying
    the per-expert MLP chunk-by-chunk (the eager audit oracle), which is
    what lets the batched auditor reproduce the executor's leaf digests
    exactly.
    """
    gathered = jax.tree_util.tree_map(lambda a: a[gid], params)

    def one(p, xc):
        h = jax.nn.relu(xc @ p["w1"] + p["b1"])
        return h @ p["w2"] + p["b2"]

    return jax.vmap(one)(gathered, x)


# ------------------------------------------------- flash attention
def attention_ref(q, k, v, *, causal=True, window=0, softcap=0.0):
    """Naive softmax attention oracle. q: (B,Sq,H,D), k/v: (B,Sk,KH,D)."""
    B, Sq, H, D = q.shape
    Sk, KH = k.shape[1], k.shape[2]
    G = H // KH
    qh = q.reshape(B, Sq, KH, G, D)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qh, k,
                   preferred_element_type=jnp.float32) * (D ** -0.5)
    if softcap:
        s = softcap * jnp.tanh(s / softcap)
    qpos = jnp.arange(Sq)[:, None]
    kpos = jnp.arange(Sk)[None, :]
    mask = jnp.ones((Sq, Sk), bool)
    if causal:
        mask &= kpos <= qpos
    if window:
        mask &= kpos > qpos - window
    s = jnp.where(mask[None, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v.dtype), v)
    return out.reshape(B, Sq, H, D)


# ------------------------------------------------- absorbed MLA decode
def mla_decode_ref(q_lat, q_pe, ckv, kpe, pos, scale):
    """Absorbed latent-attention core over the whole cache, rows past
    ``pos`` masked.  q_lat: (B, H, c); q_pe: (B, H, r); ckv: (B, cap, c);
    kpe: (B, cap, r); pos: (B,).  Returns o_lat (B, H, c)."""
    cap = ckv.shape[-2]
    s = (jnp.einsum("bhc,btc->bht", q_lat, ckv,
                    preferred_element_type=jnp.float32)
         + jnp.einsum("bhr,btr->bht", q_pe, kpe,
                      preferred_element_type=jnp.float32))
    s = s * scale
    s = jnp.where(jnp.arange(cap)[None, None, :] <= pos[:, None, None], s,
                  -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bht,btc->bhc", p.astype(ckv.dtype), ckv)


# ------------------------------------------------- SSD scan
def ssd_scan_ref(x, dt, A, Bmat, Cmat, state0):
    """Naive sequential SSM recurrence oracle.

    x: (B,S,H,P), dt: (B,S,H), A: (H,), Bmat/Cmat: (B,S,N),
    state0: (B,H,P,N).  y_t = C_t . h_t,  h_t = exp(dt_t A) h_{t-1}
    + dt_t * x_t (outer) B_t.  Returns (y (B,S,H,P), state)."""
    def step(state, inp):
        xt, dtt, Bt, Ct = inp
        decay = jnp.exp(dtt * A)                         # (B, H)
        ds = jnp.einsum("bh,bhp,bn->bhpn", dtt, xt, Bt)
        state = state * decay[:, :, None, None] + ds
        y = jnp.einsum("bn,bhpn->bhp", Ct, state)
        return state, y

    xs = (jnp.moveaxis(x, 1, 0), jnp.moveaxis(dt, 1, 0),
          jnp.moveaxis(Bmat, 1, 0), jnp.moveaxis(Cmat, 1, 0))
    state, ys = jax.lax.scan(step, state0, xs)
    return jnp.moveaxis(ys, 0, 1), state
