"""Decoder-only model: scan-over-layers stack handling every layer kind
(attn / local_attn / rglru / ssm) x (dense / moe / none) MLP.

Parameters, KV-caches and inputs are all declared with
``repro.models.builder`` so they materialize identically as real arrays
(tests), ShapeDtypeStructs (dry-run) and PartitionSpecs (pjit).
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
import numpy as np

from repro.models import moe as moe_lib
from repro.models import rglru as rglru_lib
from repro.models import ssm as ssm_lib
from repro.models.builder import Leaf, stack
from repro.models.config import LayerSpec, ModelConfig
from repro.models.layers import (attn_decl, attn_decode, attn_train,
                                 mla_decl, mla_decode, mla_train, mlp_decl,
                                 product, rmsnorm, swiglu)


# ------------------------------------------------------------- decls
def layer_decl(spec: LayerSpec, cfg: ModelConfig) -> dict:
    decl = {"norm1": Leaf((cfg.d_model,), ("embed",), "zeros")}
    if spec.kind in ("attn", "local_attn"):
        decl["attn"] = attn_decl(cfg)
    elif spec.kind == "mla":
        decl["mla"] = mla_decl(cfg)
    elif spec.kind == "rglru":
        decl["rglru"] = rglru_lib.rglru_decl(cfg)
    elif spec.kind == "ssm":
        decl["ssm"] = ssm_lib.ssm_decl(cfg)
    else:
        raise ValueError(spec.kind)
    if spec.mlp != "none":
        decl["norm2"] = Leaf((cfg.d_model,), ("embed",), "zeros")
        decl["moe" if spec.mlp == "moe" else "mlp"] = (
            moe_lib.moe_decl(cfg) if spec.mlp == "moe" else mlp_decl(cfg))
    return decl


def model_decl(cfg: ModelConfig) -> dict:
    nb = cfg.resolved_num_blocks
    decl = {
        "embed": Leaf((cfg.padded_vocab, cfg.d_model), ("vocab", "embed"),
                      scale=0.02),
        "final_norm": Leaf((cfg.d_model,), ("embed",), "zeros"),
        "blocks": {str(i): stack(layer_decl(s, cfg), nb)
                   for i, s in enumerate(cfg.block_pattern)},
    }
    if cfg.remainder:
        decl["remainder"] = [layer_decl(s, cfg) for s in cfg.remainder]
    if cfg.leading:
        decl["leading"] = [layer_decl(s, cfg) for s in cfg.leading]
    if not cfg.tie_embeddings:
        decl["lm_head"] = Leaf((cfg.d_model, cfg.padded_vocab),
                               ("embed", "vocab"), scale=0.02)
    return decl


def _attn_cache_decl(cfg: ModelConfig, batch: int, cache_len: int,
                     window: int) -> dict:
    cap = min(window, cache_len) if window else cache_len
    seq_ax = "kv_seq" if window else "cache_seq"
    shape = (batch, cap, cfg.num_kv_heads, cfg.resolved_head_dim)
    axes = ("batch", seq_ax, "kv_heads", "head_dim")
    if cfg.kv_cache_dtype == "int8":
        # §Perf iteration 4: absmax-quantized cache + per-slot-head scales
        sshape = (batch, cap, cfg.num_kv_heads)
        saxes = ("batch", seq_ax, "kv_heads")
        return {"k": Leaf(shape, axes, "zeros", dtype="int8"),
                "v": Leaf(shape, axes, "zeros", dtype="int8"),
                "k_scale": Leaf(sshape, saxes, "zeros", dtype="float32"),
                "v_scale": Leaf(sshape, saxes, "zeros", dtype="float32")}
    return {"k": Leaf(shape, axes, "zeros"), "v": Leaf(shape, axes, "zeros")}


def _layer_cache_decl(spec: LayerSpec, cfg: ModelConfig, batch: int,
                      cache_len: int) -> dict:
    if spec.kind == "attn":
        return _attn_cache_decl(cfg, batch, cache_len, 0)
    if spec.kind == "local_attn":
        return _attn_cache_decl(cfg, batch, cache_len, cfg.sliding_window)
    if spec.kind == "mla":
        # the latent (after its norm) and the rotated shared key of each
        # position: what absorbed decode reads, no per-head keys/values
        axes = ("batch", "cache_seq", None)
        return {"ckv": Leaf((batch, cache_len, cfg.kv_lora_rank), axes,
                            "zeros"),
                "kpe": Leaf((batch, cache_len, cfg.qk_rope_head_dim), axes,
                            "zeros")}
    if spec.kind == "rglru":
        inner = cfg.rglru_expand * cfg.d_model
        return {
            "h": Leaf((batch, inner), ("batch", "rglru_inner"), "zeros"),
            "conv": Leaf((batch, cfg.ssm_conv_width - 1, inner),
                         ("batch", "conv", "rglru_inner"), "zeros"),
        }
    if spec.kind == "ssm":
        H, P, N = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
        convdim = cfg.ssm_inner + 2 * N
        return {
            "state": Leaf((batch, H, P, N),
                          ("batch", "ssm_heads", None, "state"), "zeros"),
            "conv": Leaf((batch, cfg.ssm_conv_width - 1, convdim),
                         ("batch", "conv", None), "zeros"),
        }
    raise ValueError(spec.kind)


def cache_decl(cfg: ModelConfig, batch: int, cache_len: int) -> dict:
    nb = cfg.resolved_num_blocks
    decl = {"blocks": {str(i): stack(_layer_cache_decl(s, cfg, batch, cache_len), nb)
                       for i, s in enumerate(cfg.block_pattern)}}
    if cfg.remainder:
        decl["remainder"] = [_layer_cache_decl(s, cfg, batch, cache_len)
                             for s in cfg.remainder]
    if cfg.leading:
        decl["leading"] = [_layer_cache_decl(s, cfg, batch, cache_len)
                           for s in cfg.leading]
    return decl


# cache and parameter lists of layers outside the scanned stack
UNSTACKED = ("leading", "remainder")

# leaves read only as a matrix product's weight, by the group that holds
# them (``shared``: a MoE layer's shared experts).  The embedding is a
# row gather (and a tied head reads it too), and the norm scales,
# biases and the router's selection bias ``b_corr`` act elementwise
PRODUCT_WEIGHTS = {"attn": ("wq", "wk", "wv", "wo"),
                   "mla": ("wq", "wkva", "wkvb", "wo"),
                   "moe": ("router", "w_gate", "w_up", "w_down"),
                   "shared": ("w_gate", "w_up", "w_down"),
                   "mlp": ("w_gate", "w_up", "w_down")}


def is_product_weight(path) -> bool:
    """Whether the leaf at ``path`` (a key path of ``model_decl``'s tree,
    as ``jax.tree_util`` gives it) is a matrix product's weight."""
    names = [k.key for k in path if isinstance(k, jax.tree_util.DictKey)]
    if names == ["lm_head"]:
        return True
    return (len(names) >= 2
            and names[-1] in PRODUCT_WEIGHTS.get(names[-2], ()))


# ------------------------------------------- block-granular KV paging
def check_kv_pageable(cfg: ModelConfig) -> None:
    """KV paging (``repro.storage.kv``) addresses cache ROWS by absolute
    position, which the full-attention and latent cache layouts
    guarantee: local_attn caches are capped ring windows and rglru/ssm
    carry recurrent state that is not row-addressable.  Raises for
    those."""
    for spec in cfg.all_layers:
        if spec.kind not in ("attn", "mla"):
            raise ValueError(
                f"kv_storage needs all-'attn'/'mla' layers (row-addressable "
                f"caches); config has a {spec.kind!r} layer")


def slice_kv_block(caches, slot: int, start: int, end: int) -> dict:
    """Copy one slot's cache rows [start, end) out of every layer's KV
    leaves, as host numpy arrays — the pytree a sealed KV block stores.
    Stacked block caches carry a leading layer axis (batch is axis 1);
    leading and remainder caches lead with batch."""
    block = {"blocks": jax.tree_util.tree_map(
        lambda a: np.asarray(a[:, slot, start:end]), caches["blocks"])}
    for part in UNSTACKED:
        if part in caches:
            block[part] = jax.tree_util.tree_map(
                lambda a: np.asarray(a[slot, start:end]), caches[part])
    return block


def restore_kv_block(caches, slot: int, start: int, block: dict) -> dict:
    """Functional inverse of ``slice_kv_block``: write a fetched block's
    rows back into one slot at ``start``.  Returns the new cache tree."""
    new = {"blocks": jax.tree_util.tree_map(
        lambda a, b: a.at[:, slot, start:start + b.shape[1]].set(
            jnp.asarray(b, a.dtype)),
        caches["blocks"], block["blocks"])}
    for part in UNSTACKED:
        if part in caches:
            new[part] = jax.tree_util.tree_map(
                lambda a, b: a.at[slot, start:start + b.shape[0]].set(
                    jnp.asarray(b, a.dtype)),
                caches[part], block[part])
    return new


# ------------------------------------------------------------- apply
def scan_or_unroll(body, carry, xs, unroll: bool):
    """lax.scan, or a Python loop over the leading axis (``unroll=True``).

    The dry-run unrolls the layer stack because XLA's cost_analysis
    counts a while-loop body once — unrolling yields correct per-layer
    FLOPs/bytes/collective accounting (inner chunk scans are corrected
    analytically in launch/roofline.py)."""
    if not unroll:
        return jax.lax.scan(body, carry, xs)
    nb = jax.tree_util.tree_leaves(xs)[0].shape[0]
    ys = []
    for i in range(nb):
        sl = jax.tree_util.tree_map(lambda a: a[i], xs)
        carry, y = body(carry, sl)
        ys.append(y)
    if ys and ys[0] is not None:
        ys = jax.tree_util.tree_map(lambda *a: jnp.stack(a), *ys)
    else:
        ys = None
    return carry, ys


def _layer_train(spec: LayerSpec, p, x, cfg, shard, trust, chunks):
    h = rmsnorm(x, p["norm1"], cfg.norm_eps)
    if spec.kind in ("attn", "local_attn"):
        window = cfg.sliding_window if spec.kind == "local_attn" else 0
        y = attn_train(p["attn"], h, cfg, window=window, shard=shard,
                       q_chunk=chunks[0], kv_chunk=chunks[1])
    elif spec.kind == "mla":
        y = mla_train(p["mla"], h, cfg, q_chunk=chunks[0],
                      kv_chunk=chunks[1])
    elif spec.kind == "rglru":
        y = rglru_lib.rglru_train(p["rglru"], h, cfg, shard=shard)
    elif spec.kind == "ssm":
        y = ssm_lib.ssm_train(p["ssm"], h, cfg, shard=shard)
    x = x + y
    if shard is not None:
        x = shard(x, "batch", "seq", "embed")
    aux = jnp.zeros((), jnp.float32)
    if spec.mlp != "none":
        h = rmsnorm(x, p["norm2"], cfg.norm_eps)
        if spec.mlp == "moe":
            if (cfg.moe_impl == "ep" and shard is not None
                    and shard.mesh is not None):
                from repro.models.moe_ep import moe_mlp_ep
                y, aux = moe_mlp_ep(p["moe"], h, cfg, shard.mesh,
                                    shard.rules, fsdp=shard.fsdp,
                                    attack=shard.attack)
            else:
                y, aux = moe_lib.moe_mlp(p["moe"], h, cfg, shard=shard,
                                         trust=trust)
        else:
            y = swiglu(h, p["mlp"]["w_gate"], p["mlp"]["w_up"],
                       p["mlp"]["w_down"], shard=shard)
        x = x + y
        if shard is not None:
            x = shard(x, "batch", "seq", "embed")
    return x, aux


def forward_train(params, tokens, cfg: ModelConfig, *, shard=None,
                  trust=None, prefix_embeds=None, remat=True,
                  q_chunk=512, kv_chunk=512, unroll=False):
    """tokens: (B, S_text) int32; prefix_embeds: optional (B, P, d) stub
    modality embeddings prepended to the sequence (VLM early fusion).
    Returns (logits (B, S, V), aux_loss)."""
    x = jnp.take(params["embed"], tokens, axis=0)
    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
    if shard is not None:
        x = shard(x, "batch", "seq", "embed")
    chunks = (q_chunk, kv_chunk)
    aux0 = jnp.zeros((), jnp.float32)
    for i, spec in enumerate(cfg.leading):
        x, a = _layer_train(spec, params["leading"][i], x, cfg, shard, trust,
                            chunks)
        aux0 = aux0 + a

    def body(carry, blk):
        x, aux = carry
        for i, spec in enumerate(cfg.block_pattern):
            x, a = _layer_train(spec, blk[str(i)], x, cfg, shard, trust,
                                chunks)
            aux = aux + a
        return (x, aux), None

    if remat:
        body = jax.checkpoint(body)
    (x, aux), _ = scan_or_unroll(body, (x, aux0), params["blocks"], unroll)
    for i, spec in enumerate(cfg.remainder):
        x, a = _layer_train(spec, params["remainder"][i], x, cfg, shard,
                            trust, chunks)
        aux = aux + a
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    head = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
    logits = product(x, head)
    if shard is not None:
        logits = shard(logits, "batch", "seq", "vocab")
    return logits, aux


def _mask_rows(mask, new, old):
    """Row-select a cache leaf: rows where ``mask`` is False keep their
    old value (the slot is not advancing this step)."""
    m = mask.reshape((-1,) + (1,) * (new.ndim - 1))
    return jnp.where(m, new, old)


def _layer_decode(spec: LayerSpec, p, cache, x, pos, cfg, shard,
                  write_mask=None, layer=None):
    """One layer of a decode step.  ``layer``: ``cache`` is the block
    stack's cache (leading layer axis) and this layer's state sits at
    ``[layer]``; it is written there, so the stack updates in place.

    Returns (x, new_cache, held): ``held``, with an expert share, the
    assignments that landed on held experts over the writing rows, else
    None."""
    # named scopes land in each HLO op's ``op_name``, so the profiler's
    # device ops can be told apart by the layer part that made them
    attn = spec.kind in ("attn", "local_attn")
    with jax.named_scope("attention" if attn else spec.kind):
        h = rmsnorm(x, p["norm1"], cfg.norm_eps)
        if spec.kind == "mla":
            y, new_cache = mla_decode(p["mla"], h, cache, pos, cfg,
                                      mask=write_mask, layer=layer)
        elif attn:
            # inactive slots (not decoding this step / past their prefill
            # length) write no KV row: the mask drops their update
            window = cfg.sliding_window if spec.kind == "local_attn" else 0
            y, new_cache = attn_decode(p["attn"], h, cache, pos, cfg,
                                       window=window, shard=shard,
                                       mask=write_mask, layer=layer)
        else:
            own = (cache if layer is None else
                   jax.tree_util.tree_map(lambda a: a[layer], cache))
            decode = (rglru_lib.rglru_decode if spec.kind == "rglru"
                      else ssm_lib.ssm_decode)
            y, new_cache = decode(p[spec.kind], h, own, cfg, shard=shard)
            with jax.named_scope("kv_write"):
                if write_mask is not None:
                    # inactive slots keep their recurrent state
                    new_cache = jax.tree_util.tree_map(
                        lambda n, o: _mask_rows(write_mask, n, o),
                        new_cache, own)
                if layer is not None:
                    new_cache = jax.tree_util.tree_map(
                        lambda a, n: a.at[layer].set(n), cache, new_cache)
    x = x + y
    held = None
    if spec.mlp != "none":
        with jax.named_scope("moe" if spec.mlp == "moe" else "mlp"):
            h = rmsnorm(x, p["norm2"], cfg.norm_eps)
            if spec.mlp == "moe" and cfg.expert_share:
                y, _, held = moe_lib.moe_mlp(p["moe"], h, cfg, shard=shard,
                                             return_held=True)
                if write_mask is not None:
                    held = held * write_mask[:, None]
                held = held.sum()
            elif spec.mlp == "moe":
                y, _ = moe_lib.moe_mlp(p["moe"], h, cfg, shard=shard)
            else:
                y = swiglu(h, p["mlp"]["w_gate"], p["mlp"]["w_up"],
                           p["mlp"]["w_down"], shard=shard)
            x = x + y
    return x, new_cache, held


def forward_decode(params, caches, tokens, pos, cfg: ModelConfig, *,
                   shard=None, unroll=False, write_mask=None):
    """One decode step.  tokens: (B, 1); pos: int32 scalar (all rows at
    the same absolute position — the batch-synchronous path) or (B,)
    vector (continuous batching: per-slot positions).  Returns
    (logits (B, 1, V), new_caches) — plus, with an expert share
    (``cfg.held_experts``), the int32 number of assignments the writing
    rows routed to held experts, over every MoE layer.

    ``write_mask`` (B,) bool: rows where it is False run the (padded)
    compute but leave their KV rows and recurrent state untouched — the
    fixed-shape active-slot mask that lets one compiled step serve any
    batch occupancy without recompilation."""
    x = jnp.take(params["embed"], tokens, axis=0)
    if shard is not None:
        x = shard(x, "batch", "seq", "embed")
    helds, new_caches = [], {}

    def unstacked(part, x):
        new_caches[part] = []
        for i, spec in enumerate(getattr(cfg, part)):
            x, nc, h = _layer_decode(spec, params[part][i], caches[part][i],
                                     x, pos, cfg, shard,
                                     write_mask=write_mask)
            new_caches[part].append(nc)
            if h is not None:
                helds.append(h)
        return x

    if cfg.leading:
        x = unstacked("leading", x)

    # the stacked block cache rides in the layer scan's carry and each
    # layer writes its rows at [layer, ...]: nothing rebuilds the stack
    def body(carry, inp):
        x, cch = carry
        blk, layer = inp
        cch = dict(cch)
        hs = []
        for i, spec in enumerate(cfg.block_pattern):
            x, cch[str(i)], h = _layer_decode(
                spec, blk[str(i)], cch[str(i)], x, pos, cfg, shard,
                write_mask=write_mask, layer=layer)
            if h is not None:
                hs.append(h)
        return (x, cch), (sum(hs) if hs else None)

    (x, new_block_caches), blk_held = scan_or_unroll(
        body, (x, caches["blocks"]),
        (params["blocks"], jnp.arange(cfg.resolved_num_blocks)), unroll)
    if blk_held is not None:
        helds.append(blk_held.sum())
    new_caches["blocks"] = new_block_caches
    if cfg.remainder:
        x = unstacked("remainder", x)
    with jax.named_scope("head"):
        x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
        head = (params["embed"].T if cfg.tie_embeddings
                else params["lm_head"])
        logits = product(x, head)
    if cfg.expert_share:
        return (logits, new_caches,
                sum(helds) if helds else jnp.zeros((), jnp.int32))
    return logits, new_caches


def forward_serve_chunk(params, caches, tokens, start, pos, lengths, adv,
                        cfg: ModelConfig, *, shard=None, unroll=False):
    """Fused serving macro-step: ``C`` engine ticks in ONE compiled call
    (a ``lax.scan`` of masked greedy decode micro-steps), advancing
    every batch slot one position per micro-step — prefilling slots
    consume prompt tokens while decoding slots keep generating
    autoregressively, so a long prompt is chunked through without ever
    stalling in-flight decode, and the per-call Python/dispatch overhead
    amortizes over the whole chunk.

    tokens: (B, C) int32 — slot b's next prompt tokens, left-aligned and
    zero-padded past ``lengths[b]``; start: (B,) int32 — the last token
    slot b generated (fed at the first micro-step past its prompt; 0 if
    none); pos: (B,) int32 — slot b's absolute position at micro-step 0;
    lengths: (B,) int32 in [0, C] — how many prompt columns slot b
    consumes; adv: (B,) int32 in [0, C] — how many micro-steps slot b
    advances at all (its cache writes are masked from step ``adv[b]``
    on; 0 = idle slot, pure padding).

    Micro-step t feeds ``tokens[:, t]`` where ``t < lengths``, else each
    slot's previous greedy output (carried across the scan, seeded from
    ``start``) — so a slot whose prompt ends inside the chunk hands off
    to generation mid-scan with no host round-trip.

    Returns ``(out_tokens (C, B), new_caches)``, plus ``held`` with an
    expert share: ``out_tokens[t, b]`` is slot b's greedy next token
    after micro-step t — a generated token iff the slot was at or past
    its prompt boundary there (the host emits exactly those); ``held``
    the assignments routed to held experts, over layers and
    micro-steps."""
    B, C = tokens.shape
    pos = jnp.asarray(pos, jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)
    adv = jnp.asarray(adv, jnp.int32)

    def micro(carry, xt):
        caches, cur = carry
        tok, t = xt                              # (B,), scalar step index
        feed = jnp.where(t < lengths, tok, cur)
        logits, caches, *held = forward_decode(
            params, caches, feed[:, None], pos + t, cfg, shard=shard,
            unroll=unroll, write_mask=t < adv)
        nxt = logits[:, -1].argmax(axis=-1).astype(jnp.int32)
        return (caches, nxt), (nxt, held)

    (caches, _), (outs, held) = jax.lax.scan(
        micro, (caches, jnp.asarray(start, jnp.int32)),
        (tokens.T, jnp.arange(C)))
    return (outs, caches, *(h.sum(axis=0) for h in held))


def lm_loss(logits, labels, mask=None):
    """Cross-entropy; labels: (B, S) int32, positions with label < 0 are
    ignored (e.g. the VLM image-prefix region).

    Written vocab-sharding-friendly: logsumexp + one-hot contraction both
    reduce over the (model-sharded) vocab axis via psum — no all-gather of
    the logits, no full-vocab gather."""
    valid = labels >= 0 if mask is None else mask & (labels >= 0)
    labels = jnp.maximum(labels, 0)
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    onehot = (jnp.arange(logits.shape[-1])[None, None, :] ==
              labels[..., None])
    picked = jnp.sum(jnp.where(onehot, logits, 0.0), axis=-1)
    ll = picked - lse
    denom = jnp.maximum(valid.sum(), 1)
    return -(ll * valid).sum() / denom
