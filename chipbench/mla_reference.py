"""Plain reference of a DeepSeek-V3-style model (Moonlight-16B-A3B's
block), for the comparison that decides ``correct``.

Written from the layer equations alone, in the expanded form of latent
attention, with no cache, no batching of requests and no capacity
buckets, over one whole sequence in blocks of queries so that an
8,192-token sequence fits:

    x = embed[tokens]
    for each layer (the leading dense ones, then the MoE ones):
        h = rmsnorm(x) * (1 + norm1)
        [q_nope | q_pe] = h Wq                      per head
        [c | k_pe] = h Wkva;  c = rmsnorm(c) * (1 + kva_norm)
        [k_nope | v] = c Wkvb                       per head
        q_pe, k_pe rotated at their positions       (k_pe: one, shared)
        x += softmax((q_nope.k_nope + q_pe.k_pe) / sqrt(nope + rope),
                     causal) v Wo
        h = rmsnorm(x) * (1 + norm2)
        dense layer: x += SwiGLU(h)
        MoE layer:   s = sigmoid(h Wr) in float32;  idx = top_k(s + b_corr)
                     w = s[idx] / sum(s[idx]) * routed_scaling_factor
                     x += sum_k w_k SwiGLU_idx_k(h) + SwiGLU_shared(h)
    logits = (rmsnorm(x) * (1 + final_norm)) Whead

The expert share is the program's: the router scores all
``num_experts``, the weights are normalized over all k chosen, and only
the chosen experts this chip holds (the first ``held_experts``) add
their part.  Products run at ``highest`` precision in float32.  The
control is the same code in bfloat16 (weights and activations; norms,
router scores and softmax upcast and cast back) at default precision.
The logit-gap comparison is ``lm_reference.readings``.
"""
from __future__ import annotations

import functools
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from lm_reference import PRECISION, _rmsnorm, _rotate, lowered

BUCKETS = (512, 8192)   # one compiled pass serves every chip-sized sample
Q_BLOCK = 512


def _swiglu(h, w, mm, cast):
    return mm("sf,fd->sd", jax.nn.silu(mm("sd,df->sf", h, cast(w["w_gate"])))
              * mm("sd,df->sf", h, cast(w["w_up"])), cast(w["w_down"]))


def _moe(h, m, c, mm, cast):
    """The held experts' part of the routed output, plus the shared
    experts."""
    K, Eh = c["num_experts_per_tok"], c["held_experts"]
    s = jax.nn.sigmoid(mm("sd,de->se", h, cast(m["router"]))
                       .astype(jnp.float32))
    _, idx = jax.lax.top_k(s + m["b_corr"].astype(jnp.float32), K)
    w = jnp.take_along_axis(s, idx, axis=1)
    w = w / w.sum(-1, keepdims=True) * c["routed_scaling_factor"]
    # comb[s, e]: the weight token s gives held expert e (0 if not chosen)
    comb = (w[:, :, None] * (idx[:, :, None] == jnp.arange(Eh))).sum(1)
    g = mm("sd,edf->sef", h, cast(m["w_gate"]))
    u = mm("sd,edf->sef", h, cast(m["w_up"]))
    y = mm("sef,efd->sed", jax.nn.silu(g) * u, cast(m["w_down"]))
    routed = (y.astype(jnp.float32) * comb[:, :, None]).sum(1)
    return routed.astype(h.dtype) + _swiglu(h, m["shared"], mm, cast)


def forward(params, tokens, *, c: Dict, dtype, precision: str = "highest"):
    """tokens: (S,) int32, S a multiple of ``Q_BLOCK`` -> logits (S, V)
    in float32."""
    mm = functools.partial(jnp.einsum, precision=PRECISION[precision])
    cast = lambda a: a.astype(dtype)
    H, eps, theta = c["num_heads"], c["norm_eps"], c["rope_theta"]
    nope, rope, r = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                     c["kv_lora_rank"])
    S = tokens.shape[0]
    pos = jnp.arange(S)
    layers = [(p, "mlp") for p in params.get("leading", [])]
    blk = params["blocks"]["0"]
    layers += [(jax.tree_util.tree_map(lambda a, l=l: a[l], blk), "moe")
               for l in range(c["num_layers"] - len(layers))]
    x = cast(params["embed"])[tokens]
    for p, kind in layers:
        a = p["mla"]
        h = _rmsnorm(x, p["norm1"], eps)
        q = mm("sd,de->se", h, cast(a["wq"])).reshape(S, H, nope + rope)
        kva = mm("sd,de->se", h, cast(a["wkva"]))
        lat = _rmsnorm(kva[:, :r], a["kva_norm"], eps)
        kv = mm("sc,ce->se", lat, cast(a["wkvb"])).reshape(S, H, -1)
        q = jnp.concatenate([q[..., :nope], _rotate(q[..., nope:], pos,
                                                    theta)], -1)
        k_pe = _rotate(kva[:, None, r:], pos, theta)
        k = jnp.concatenate([kv[..., :nope],
                             jnp.broadcast_to(k_pe, (S, H, rope))], -1)
        v = kv[..., nope:]

        def block(i, x=x, q=q, k=k, v=v, p=p, kind=kind):
            rows = i * Q_BLOCK + jnp.arange(Q_BLOCK)
            qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK)
            xb = jax.lax.dynamic_slice_in_dim(x, i * Q_BLOCK, Q_BLOCK)
            s = mm("qhd,khd->hqk", qb, k).astype(jnp.float32) \
                * (nope + rope) ** -0.5
            s = jnp.where(pos[None, None, :] <= rows[None, :, None], s,
                          -jnp.inf)
            o = mm("hqk,khd->qhd", cast(jax.nn.softmax(s, axis=-1)), v)
            xb = xb + mm("se,ed->sd", o.reshape(Q_BLOCK, -1),
                         cast(p["mla"]["wo"]))
            h = _rmsnorm(xb, p["norm2"], eps)
            return xb + (_swiglu(h, p["mlp"], mm, cast) if kind == "mlp"
                         else _moe(h, p["moe"], c, mm, cast))

        x = jax.lax.map(block, jnp.arange(S // Q_BLOCK)).reshape(S, -1)
    x = _rmsnorm(x, params["final_norm"], eps)
    return mm("sd,dv->sv", x, cast(params["lm_head"])).astype(jnp.float32)


def bucket(n: int) -> int:
    for b in BUCKETS:
        if n <= b:
            return b
    raise ValueError(f"sequence of {n} tokens exceeds the largest bucket")


class Reference:
    """Compiled reference passes for one configuration, with the
    ``gaps`` that ``lm_reference.readings`` reads: the served tokens'
    gaps and, per control precision asked for, the gap of the token that
    precision puts first at the same positions."""

    def __init__(self, c: Dict, params):
        self.c = c
        self.params = params
        self._f32 = jax.jit(self._read(jnp.float32, "highest"))
        self._lowfn = jax.jit(self._read(jnp.bfloat16, "default"))
        self._pick = jax.jit(lambda ref, low: ref.max(-1) - jnp.take_along_axis(
            ref, jnp.argmax(low, -1)[:, None], axis=1)[:, 0])
        self._low: Dict[str, Dict] = {}

    def _read(self, dtype, precision):
        c = self.c

        def read(params, tokens, at, served):
            with jax.default_matmul_precision(precision):
                logits = forward(params, tokens, c=c, dtype=dtype,
                                 precision=precision)[at]
            return logits, logits.max(-1) - jnp.take_along_axis(
                logits, served[:, None], axis=1)[:, 0]
        return read

    def gaps(self, prompt: Sequence[int], served: Sequence[int],
             controls: Sequence[str] = ()) -> Dict[str, np.ndarray]:
        """The sequence is padded at its end to a bucket: attention is
        causal, so padding never reaches a read position."""
        prompt, served = np.asarray(prompt), np.asarray(served)
        P, n = len(prompt), len(served)
        seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
        S = bucket(len(seq))
        toks = np.zeros(S, np.int32)
        toks[:len(seq)] = seq
        at = np.full(S, P - 1, np.int32)
        at[:n] = np.arange(P - 1, P - 1 + n)
        want = np.zeros(S, np.int32)
        want[:n] = served
        args = (jnp.asarray(toks), jnp.asarray(at), jnp.asarray(want))
        logits, gap = self._f32(self.params, *args)
        out = {"served": np.asarray(gap)[:n]}
        for kind in controls:
            if kind not in self._low:
                self._low[kind] = lowered(self.params, kind)
            low, _ = self._lowfn(self._low[kind], *args)
            out[kind] = np.asarray(self._pick(logits, low))[:n]
        return out
