"""Unified train/prefill/decode step builders for every architecture.

``make_step(cfg, kind)`` returns (step_fn, describe) where step_fn's
signature depends on kind:

- kind="train":   (params, opt_state, batch)      -> (params, opt_state, metrics)
- kind="prefill": (params, batch)                 -> logits
- kind="decode":  (params, caches, batch)         -> (next_token, caches)

The same functions are jitted for CPU-scale runs (mesh=None) and lowered
against ShapeDtypeStructs for the multi-pod dry-run.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.trusted_moe import make_trust
from repro.models import encdec
from repro.models import transformer as tfm
from repro.models.config import ModelConfig
from repro.optim import adamw
from repro.sharding import Sharder, logical_rules


def at_precision(cfg: ModelConfig, fn):
    """``fn`` with its matrix products at ``cfg.matmul_precision``
    (JAX's default precision, one bfloat16 pass on a TPU: ``fn`` as
    it is)."""
    if cfg.matmul_precision == "default":
        return fn

    @functools.wraps(fn)
    def at(*args):
        with jax.default_matmul_precision(cfg.matmul_precision):
            return fn(*args)
    return at


def product_dtype(cfg: ModelConfig, platform: str):
    """The type at which ``cfg``'s matrix products read a weight on
    ``platform`` (a JAX platform name): bfloat16 where its precision is
    one bfloat16 pass (``default`` or ``bfloat16`` on a TPU), else None,
    the weight as it is given (``high`` and ``highest`` read float32
    through several passes; the CPU computes ``default`` in float32)."""
    if platform == "tpu" and cfg.matmul_precision in ("default", "bfloat16"):
        return jnp.bfloat16
    return None


def at_product_width(params, dtype):
    """``params`` with every matrix product's weight
    (``tfm.is_product_weight``) that is wider than ``dtype`` rounded to
    it, once, in one compiled call; every other leaf is the caller's
    own array, and the caller's tree is left as it was.  ``dtype`` None
    keeps the tree.  Returns (tree, paths of the rounded leaves)."""
    if dtype is None:
        return params, []
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    bits = jnp.finfo(dtype).bits
    narrow = [i for i, (path, a) in enumerate(flat)
              if tfm.is_product_weight(path)
              and jnp.finfo(a.dtype).bits > bits]
    rounded = jax.jit(lambda xs: [x.astype(dtype) for x in xs])(
        [flat[i][1] for i in narrow])
    leaves = [a for _, a in flat]
    for i, a in zip(narrow, rounded):
        leaves[i] = a
    return (jax.tree_util.tree_unflatten(treedef, leaves),
            [flat[i][0] for i in narrow])


def model_forward(params, batch, cfg: ModelConfig, shard=None, trust=None,
                  remat=True, unroll=False):
    """Dispatch on architecture family.  Returns (logits, aux, labels)."""
    if cfg.is_encoder_decoder:
        logits, aux = encdec.forward_train(params, batch["frames"],
                                           batch["tokens"], cfg, shard=shard,
                                           remat=remat, unroll=unroll)
        return logits, aux, batch.get("labels")
    prefix = batch.get("patches")
    logits, aux = tfm.forward_train(params, batch["tokens"], cfg,
                                    shard=shard, trust=trust,
                                    prefix_embeds=prefix, remat=remat,
                                    unroll=unroll)
    labels = batch.get("labels")
    if prefix is not None and labels is not None:
        # VLM: no loss on the image-prefix region
        ignore = jnp.full(prefix.shape[:2], -1, jnp.int32)
        labels = jnp.concatenate([ignore, labels], axis=1)
    return logits, aux, labels


def make_train_step(cfg: ModelConfig, opt_cfg: adamw.AdamWConfig,
                    mesh=None, attack=None, remat=True, unroll=False):
    shard = Sharder(mesh, logical_rules(mesh, cfg), fsdp=True,
                    attack=attack) if mesh is not None else None
    trust = None
    if cfg.redundancy.mode != "off" and mesh is not None:
        expert_sharded = (cfg.num_experts % mesh.devices.shape[-1] == 0)
        trust = make_trust(mesh, cfg.redundancy, expert_sharded, attack)

    def loss_and_grad(params, mb):
        def loss_fn(p):
            logits, aux, labels = model_forward(p, mb, cfg, shard, trust,
                                                remat, unroll)
            loss = tfm.lm_loss(logits, labels) + aux
            return loss, aux
        return jax.value_and_grad(loss_fn, has_aux=True)(params)

    K = max(cfg.train_microbatches, 1)

    def train_step(params, opt_state, batch):
        if K == 1:
            (loss, aux), grads = loss_and_grad(params, batch)
        else:
            # gradient accumulation: scan over K microbatches (activation
            # memory / K; f32 grad accumulator shards like the params)
            micro = jax.tree_util.tree_map(
                lambda x: x.reshape((K, x.shape[0] // K) + x.shape[1:]),
                batch)
            acc0 = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)

            def mb_step(acc, mb):
                acc_g, acc_loss, acc_aux = acc
                (loss, aux), grads = loss_and_grad(params, mb)
                acc_g = jax.tree_util.tree_map(
                    lambda a, g: a + g.astype(jnp.float32) / K, acc_g, grads)
                return (acc_g, acc_loss + loss / K, acc_aux + aux / K), None

            (grads, loss, aux), _ = jax.lax.scan(
                mb_step, (acc0, jnp.zeros((), jnp.float32),
                          jnp.zeros((), jnp.float32)), micro)
        params, opt_state, om = adamw.update(opt_cfg, grads, opt_state,
                                             params)
        metrics = {"loss": loss, "aux_loss": aux, **om}
        return params, opt_state, metrics

    return at_precision(cfg, train_step)


def make_prefill_step(cfg: ModelConfig, mesh=None, unroll=False):
    from repro.sharding import use_fsdp
    shard = Sharder(mesh, logical_rules(mesh, cfg),
                    fsdp=use_fsdp(cfg, "prefill",
                                  mesh.devices.shape[-1])) \
        if mesh is not None else None

    def prefill_step(params, batch):
        logits, _aux, _ = model_forward(params, batch, cfg, shard,
                                        trust=None, remat=False,
                                        unroll=unroll)
        return logits[:, -1:].argmax(axis=-1)

    return at_precision(cfg, prefill_step)


def make_decode_step(cfg: ModelConfig, mesh=None, unroll=False):
    """The batch may carry ``pos`` as a scalar (every row at the same depth)
    or a (B,) vector, and an optional (B,) bool ``active`` mask: inactive
    rows run the padded compute but leave their caches untouched — the
    fixed-shape contract continuous batching compiles once against."""
    from repro.sharding import use_fsdp
    shard = Sharder(mesh, logical_rules(mesh, cfg),
                    fsdp=use_fsdp(cfg, "decode",
                                  mesh.devices.shape[-1])) \
        if mesh is not None else None

    def decode_step(params, caches, batch):
        tokens, pos = batch["tokens"], batch["pos"]
        active = batch.get("active")
        if cfg.is_encoder_decoder:
            if active is not None:
                raise NotImplementedError(
                    "active-slot masking targets decoder-only archs")
            logits, caches = encdec.forward_decode(params, caches, tokens,
                                                   pos, cfg, shard=shard,
                                                   unroll=unroll)
        else:
            logits, caches, *_ = tfm.forward_decode(
                params, caches, tokens, pos, cfg, shard=shard,
                unroll=unroll, write_mask=active)
        return logits[:, -1].argmax(axis=-1), caches

    return at_precision(cfg, decode_step)


def make_serve_chunk_step(cfg: ModelConfig, mesh=None, unroll=False):
    """Fused serving macro-step for the engine: one compiled call runs C
    engine ticks (``tfm.forward_serve_chunk`` — a ``lax.scan`` of masked
    greedy decode micro-steps) in which prefilling slots chunk-consume
    their prompts while decoding slots keep generating autoregressively.
    Long prompts cost ceil(len/C) dispatches instead of len, in-flight
    decode is never stalled behind a token-by-token prompt feed, and
    per-call overhead amortizes over the chunk.

    batch: ``tokens`` (B, C) int32, ``start`` (B,) int32 (last generated
    token per slot), ``pos`` (B,) int32, ``lengths`` (B,) int32 (prompt
    columns consumed), ``adv`` (B,) int32 (micro-steps the slot advances
    at all; 0 = idle padding).  Returns (out_tokens (C, B), caches),
    plus, with an expert share (``cfg.held_experts``), the assignments
    routed to held experts."""
    from repro.sharding import use_fsdp
    if cfg.is_encoder_decoder:
        raise NotImplementedError("serve chunk drives decoder-only archs")
    shard = Sharder(mesh, logical_rules(mesh, cfg),
                    fsdp=use_fsdp(cfg, "decode",
                                  mesh.devices.shape[-1])) \
        if mesh is not None else None

    def serve_chunk_step(params, caches, batch):
        return tfm.forward_serve_chunk(
            params, caches, batch["tokens"], batch["start"], batch["pos"],
            batch["lengths"], batch["adv"], cfg, shard=shard,
            unroll=unroll)

    return at_precision(cfg, serve_chunk_step)


def make_step(cfg: ModelConfig, kind: str, mesh=None,
              opt_cfg: Optional[adamw.AdamWConfig] = None, remat=True,
              unroll=False):
    if kind == "train":
        return make_train_step(cfg, opt_cfg or adamw.AdamWConfig(), mesh,
                               remat=remat, unroll=unroll)
    if kind == "prefill":
        return make_prefill_step(cfg, mesh, unroll=unroll)
    if kind == "decode":
        return make_decode_step(cfg, mesh, unroll=unroll)
    raise ValueError(kind)


# ------------------------------------------------------- federated edge
def make_fed_local_step(num_experts: int, top_k: int, lr: float,
                        apply_all):
    """Jitted local SGD update for one federated edge (``repro.fed``).

    The edge runs the full-bank dense MoE forward (gate top-k mixture
    over ``apply_all``'s (N, B, C) outputs) but its gradient is masked
    to the experts it OWNS: unowned experts receive exactly zero update,
    so the edge's published delta is zero (and chunk-dedups away) off
    its expert subset.  The gate is trained by every edge.

    Returns ``step(params, x, y, owned) -> (params, loss)`` where
    ``params = {"gate", "experts"}``, ``x`` is (B, in_dim), ``y`` (B,)
    int labels and ``owned`` a float (N,) ownership mask.
    """
    from repro.core import experts as ex

    def moe_loss(params, x, y):
        logits = ex.gate_apply(params["gate"], x)
        w, _ = ex.sparse_gate_weights(logits, top_k)
        outs = apply_all(params["experts"], x)        # (N, B, C)
        mix = jnp.einsum("bn,nbc->bc", w, outs)
        logp = jax.nn.log_softmax(mix)
        return -jnp.take_along_axis(logp, y[:, None], axis=1).mean()

    def local_step(params, x, y, owned):
        loss, grads = jax.value_and_grad(moe_loss)(params, x, y)

        def mask_expert(g):
            shape = (num_experts,) + (1,) * (g.ndim - 1)
            return g * owned.reshape(shape)

        new = {
            "gate": jax.tree_util.tree_map(
                lambda p, g: p - lr * g, params["gate"], grads["gate"]),
            "experts": jax.tree_util.tree_map(
                lambda p, g: p - lr * mask_expert(g),
                params["experts"], grads["experts"]),
        }
        return new, loss

    return jax.jit(local_step)
