"""Plain reference of the decoder-only MoE language model, and the
comparison that decides ``correct`` for a served model.

The forward pass is written from the model's description alone, in
straightforward ``jax.numpy`` over one whole sequence, with no cache,
no batching of requests and no capacity buckets:

    x = embed[tokens]
    for each layer:
        h = rmsnorm(x) * (1 + norm1)
        q, k, v = h Wq, h Wk, h Wv      (rotary positions on q and k)
        x += softmax(q k^T / sqrt(D), causal) v Wo     (k, v shared by
                                         num_heads / num_kv_heads heads)
        h = rmsnorm(x) * (1 + norm2)
        p = softmax(h Wr); the top-k experts, weights renormalized
        x += sum_k w_k * (silu(h Wg_k) * (h Wu_k)) Wd_k
    logits = (rmsnorm(x) * (1 + final_norm)) Whead

Every matrix product runs at the precision the configuration states
(``matmul_precision``: ``default`` is what the program gets from JAX,
one bfloat16 pass on a TPU and exact float32 on a CPU; ``highest``
would be six passes).  The control is the same code in bfloat16
(weights and activations; norms and softmax upcast and cast back), the
step below the float32 the configuration states.

The number compared is the mean, over the served tokens of a sample of
requests, of the gap by which a served token's reference logit lies
below the reference's best logit at its position: 0 where every served
token is the reference's greedy choice.  The widest such gap and the
share of tokens that differ are read too.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

BUCKETS = (256, 512, 1024, 2048, 4096)


def _rmsnorm(x, w, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (y * (1.0 + w.astype(jnp.float32))).astype(x.dtype)


def _rotate(x, pos, theta):
    """Rotary embedding, first half of each head against the second."""
    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * inv            # (S, half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a = x[..., :half].astype(jnp.float32)
    b = x[..., half:].astype(jnp.float32)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           -1).astype(x.dtype)


PRECISION = {"default": jax.lax.Precision.DEFAULT,
             "highest": jax.lax.Precision.HIGHEST}


def forward(params, tokens, *, c: Dict, dtype, precision: str = "default"):
    """tokens: (S,) int32 -> logits (S, V) in float32.  ``precision``:
    of every matrix product, as ``jax.lax.Precision`` names it."""
    mm = functools.partial(jnp.einsum, precision=PRECISION[precision])
    cast = lambda a: a.astype(dtype)
    H, KH, D = c["num_heads"], c["num_kv_heads"], c["head_dim"]
    K, eps, theta = c["num_experts_per_tok"], c["norm_eps"], c["rope_theta"]
    S = tokens.shape[0]
    pos = jnp.arange(S)
    causal = pos[None, :] <= pos[:, None]
    blk = params["blocks"]["0"]
    x = cast(params["embed"])[tokens]
    for l in range(c["num_layers"]):
        a, m = blk["attn"], blk["moe"]
        h = _rmsnorm(x, blk["norm1"][l], eps)
        q = _rotate(mm("sd,de->se", h, cast(a["wq"][l])).reshape(S, H, D),
                    pos, theta)
        k = _rotate(mm("sd,de->se", h, cast(a["wk"][l])).reshape(S, KH, D),
                    pos, theta)
        v = mm("sd,de->se", h, cast(a["wv"][l])).reshape(S, KH, D)
        k = jnp.repeat(k, H // KH, axis=1)
        v = jnp.repeat(v, H // KH, axis=1)
        s = mm("qhd,khd->hqk", q, k).astype(jnp.float32) * D ** -0.5
        s = jnp.where(causal[None], s, -jnp.inf)
        p = cast(jax.nn.softmax(s, axis=-1))
        o = mm("hqk,khd->qhd", p, v).reshape(S, H * D)
        x = x + mm("se,ed->sd", o, cast(a["wo"][l]))
        h = _rmsnorm(x, blk["norm2"][l], eps)
        probs = jax.nn.softmax(
            mm("sd,de->se", h, cast(m["router"][l])).astype(jnp.float32), -1)
        w, idx = jax.lax.top_k(probs, K)
        w = w / w.sum(-1, keepdims=True)
        g = mm("sd,edf->sef", h, cast(m["w_gate"][l]))
        u = mm("sd,edf->sef", h, cast(m["w_up"][l]))
        y = mm("sef,efd->sed", jax.nn.silu(g) * u, cast(m["w_down"][l]))
        y = jnp.take_along_axis(y, idx[:, :, None], axis=1)  # (S, K, d)
        x = x + (y.astype(jnp.float32) * w[:, :, None]).sum(1).astype(dtype)
    x = _rmsnorm(x, params["final_norm"], eps)
    return mm("sd,dv->sv", x, cast(params["lm_head"])).astype(jnp.float32)


def bucket(n: int) -> int:
    for b in BUCKETS:
        if n <= b:
            return b
    raise ValueError(f"sequence of {n} tokens exceeds the largest bucket")


def lowered(params, kind: str):
    """Weights as the control holds them: ``bf16`` rounds them."""
    if kind != "bf16":
        raise ValueError(f"unknown control precision {kind!r}")
    return jax.jit(lambda p: jax.tree_util.tree_map(
        lambda w: w.astype(jnp.bfloat16), p))(params)


class Reference:
    """Compiled reference passes for one configuration.  ``gaps`` reads
    the served tokens' gaps and, for each control precision asked for,
    the gap of the token that precision puts first at the same
    positions."""

    def __init__(self, c: Dict, params):
        self.c = c
        self.params = params
        self._f32 = jax.jit(self._read(jnp.float32, c["matmul_precision"]))
        self._lowfn = jax.jit(self._read(jnp.bfloat16, "default"))
        self._pick = jax.jit(lambda ref, low: ref.max(-1) - jnp.take_along_axis(
            ref, jnp.argmax(low, -1)[:, None], axis=1)[:, 0])
        self._low: Dict[str, Dict] = {}

    def _read(self, dtype, precision):
        c = self.c

        def read(params, tokens, at, served):
            logits = forward(params, tokens, c=c, dtype=dtype,
                             precision=precision)[at]          # (n, V)
            return logits, logits.max(-1) - jnp.take_along_axis(
                logits, served[:, None], axis=1)[:, 0]
        return read

    def gaps(self, prompt: Sequence[int], served: Sequence[int],
             controls: Sequence[str] = ()) -> Dict[str, np.ndarray]:
        """Gaps of the served tokens of one request and, per control
        precision, of the token that precision puts first at the same
        positions.  The sequence is padded at its end to a bucket:
        attention is causal, so padding never reaches a read position."""
        prompt, served = np.asarray(prompt), np.asarray(served)
        P, n = len(prompt), len(served)
        seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
        S = bucket(len(seq))
        toks = np.zeros(S, np.int32)
        toks[:len(seq)] = seq
        # read positions and served tokens padded to the bucket too, so
        # one program serves every request of the bucket
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


def readings(ref: Reference, sample: List[Dict],
             controls: Sequence[str] = ()) -> Dict[str, Dict[str, float]]:
    """Over a sample of requests (each with ``prompt`` and ``served``):
    per series (the served tokens, then each control), the widest gap,
    the mean gap and the share of positions whose token is not the
    reference's first choice."""
    per: Dict[str, List[np.ndarray]] = {}
    for r in sample:
        for k, v in ref.gaps(r["prompt"], r["served"], controls).items():
            per.setdefault(k, []).append(v)
    out = {}
    for k, parts in per.items():
        g = np.concatenate(parts)
        out[k] = {"widest": float(g.max()), "mean": float(g.mean()),
                  "mismatch": float((g > 0).mean()), "tokens": int(g.size)}
    return out
