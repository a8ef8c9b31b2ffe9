"""Public jit'd wrappers over the Pallas kernels with pure-jnp fallback.

``backend`` resolution:
- "ref"       : pure jnp oracle (default off-TPU — also what GSPMD
                lowers for the multi-pod dry-run)
- "pallas"    : compiled Pallas kernel (TPU target)
- "interpret" : Pallas kernel body interpreted on CPU (how kernels are
                validated in this container)

Set REPRO_KERNEL_BACKEND to override the default.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import audit_gemm as _ag
from repro.kernels import flash_attention as _fa
from repro.kernels import mla_decode as _mla
from repro.kernels import moe_gemm as _mg
from repro.kernels import redundancy_vote as _rv
from repro.kernels import rglru_scan as _rg
from repro.kernels import ssd_scan as _ssd
from repro.kernels import ref
from repro.kernels.backend import default_backend

__all__ = ["default_backend", "redundancy_vote", "moe_gemm", "audit_mlp",
           "flash_attention", "mla_decode", "mla_read_block", "ssd_scan",
           "rglru_scan"]


# ------------------------------------------------------ redundancy vote
def redundancy_vote(pub: jax.Array, axis: int = 1, *, atol: float = 0.0,
                    backend: str | None = None):
    """Majority vote over redundant copies (paper Step 3).

    pub: (..., M, ...) with the replica axis at ``axis`` and the expert
    axis leading.  Canonical layout (E, M, *tail).  Returns
    (trusted (E, *tail), support (E,))."""
    if axis != 1:
        pub = jnp.moveaxis(pub, axis, 1)
    backend = backend or default_backend()
    if backend == "ref":
        return ref.redundancy_vote_ref(pub, atol)
    E, M = pub.shape[:2]
    flat = pub.reshape(E, M, -1)
    T = flat.shape[-1]
    counts = _rv.pairwise_agreement(
        flat.astype(jnp.float32), atol=atol,
        interpret=(backend == "interpret"))
    pad = (-T) % min(_rv.DEFAULT_TILE, max(T, 1))
    full_agree = (counts == T + pad).astype(jnp.int32)
    support_per = full_agree.sum(axis=-1)
    winner = support_per.argmax(axis=-1)
    trusted = jnp.take_along_axis(
        flat, winner[:, None, None].astype(jnp.int32), axis=1)[:, 0]
    support = jnp.take_along_axis(support_per, winner[:, None], axis=1)[:, 0]
    return trusted.reshape((E,) + pub.shape[2:]), support


# ------------------------------------------------------ grouped GEMM
def moe_gemm(buf, w, *, backend: str | None = None):
    backend = backend or default_backend()
    with jax.named_scope("moe_gemm"):
        if backend == "ref":
            return ref.moe_gemm_ref(buf, w)
        return _mg.moe_gemm(buf, w, interpret=(backend == "interpret"))


# ------------------------------------------------------ batched audit
def audit_mlp(params, x, gid, *, backend: str | None = None):
    """Batched audit recompute: out[s] = mlp(params[gid[s]], x[s]).

    params: stacked {w1,b1,w2,b2} over the expert axis; x: (S, C, d)
    sampled chunks; gid: (S,) int32 expert per sample.  The ref backend
    is bit-identical to the eager per-chunk expert apply (what leaf
    digests are hashed from); the Pallas backend fuses both GEMMs and
    the relu in VMEM (validated allclose in tests/test_kernels.py).
    """
    backend = backend or default_backend()
    with jax.named_scope("audit_mlp"):
        if backend == "ref":
            return ref.audit_mlp_ref(params, x, gid)
        return _ag.audit_mlp(params, x, gid,
                             interpret=(backend == "interpret"))


# ------------------------------------------------------ attention
def flash_attention(q, k, v, *, causal=True, window=0, softcap=0.0,
                    backend: str | None = None):
    """q: (B, Sq, H, D); k/v: (B, Sk, KH, D) — model layout."""
    backend = backend or default_backend()
    if backend == "ref":
        return ref.attention_ref(q, k, v, causal=causal, window=window,
                                 softcap=softcap)
    out = _fa.flash_attention(
        jnp.moveaxis(q, 2, 1), jnp.moveaxis(k, 2, 1), jnp.moveaxis(v, 2, 1),
        causal=causal, window=window, softcap=softcap,
        interpret=(backend == "interpret"))
    return jnp.moveaxis(out, 1, 2)


# ------------------------------------------------------ absorbed MLA decode
def mla_decode(q_lat, q_pe, k_pe, ckv, kpe, pos, slot, *, scale, layer=None,
               backend: str | None = None):
    """One query token per slot against the latent cache, absorbed:
    softmax over rows ``0 .. min(pos, cap - 1)`` of
    ``(q_lat . ckv^T + q_pe . kpe^T) * scale``, times ``ckv``, with this
    step's rotary keys ``k_pe`` (B, r) first written at row ``slot[b]``
    of each slot (``cap``: dropped).

    q_lat: (B, H, c); q_pe: (B, H, r); pos, slot: (B,) int32.  ckv:
    (B, cap, c), this step's latents already written, and kpe:
    (B, cap, r), or with ``layer`` the stacked (L, B, cap, ·) leaves,
    read at ``[layer]``.  The ref backend reads the whole cache; the
    kernel reads each slot's live rows once, and takes the stacked
    leaves whole (never a layer's slice, which would be a copy).
    Returns (o_lat (B, H, c), kpe with the rows written)."""
    backend = backend or default_backend()
    if backend == "ref":
        lead = () if layer is None else (layer,)
        with jax.named_scope("kv_write"):
            kpe = kpe.at[lead + (jnp.arange(k_pe.shape[0]), slot)].set(
                k_pe, mode="drop")
        one = (lambda a: a) if layer is None else (lambda a: a[layer])
        return ref.mla_decode_ref(q_lat, q_pe, one(ckv), one(kpe), pos,
                                  scale), kpe
    stacked = layer is not None
    if not stacked:                    # one layer: a leading axis of 1
        ckv, kpe, layer = ckv[None], kpe[None], 0
    o_lat, kpe = _mla.mla_decode(q_lat, q_pe, k_pe, ckv, kpe, pos, slot,
                                 layer, scale=scale,
                                 interpret=(backend == "interpret"))
    return o_lat, (kpe if stacked else kpe[0])


def mla_read_block(cap: int) -> int:
    """The block of rows ``mla_decode`` reads a ``cap``-row latent cache
    in on the default backend: the kernel's block, or the whole cache
    under the ref backend, which reads every row."""
    return cap if default_backend() == "ref" else _mla.block_rows(cap)


# ------------------------------------------------------ SSD scan
def ssd_scan(x, dt, A, Bmat, Cmat, *, chunk=128, backend: str | None = None):
    backend = backend or default_backend()
    if backend == "ref":
        state0 = jnp.zeros((x.shape[0], x.shape[2], x.shape[3],
                            Bmat.shape[-1]), jnp.float32)
        y, _ = ref.ssd_scan_ref(x.astype(jnp.float32),
                                dt.astype(jnp.float32), A,
                                Bmat.astype(jnp.float32),
                                Cmat.astype(jnp.float32), state0)
        return y
    return _ssd.ssd_scan(x, dt, A, Bmat, Cmat, chunk=chunk,
                         interpret=(backend == "interpret"))


# ------------------------------------------------------ RG-LRU scan
def rglru_scan(a, b, *, backend: str | None = None):
    """h_t = a_t * h_{t-1} + b_t over axis 1; a, b: (B, S, C)."""
    backend = backend or default_backend()
    if backend == "ref":
        from repro.models.rglru import rglru_scan as _ref_scan
        return _ref_scan(a, b)
    return _rg.rglru_scan_pallas(a, b, interpret=(backend == "interpret"))
