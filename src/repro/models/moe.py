"""Token-choice top-k sparsely-gated MoE layer (GShard-style) with
*group-wise* capacity dispatch, shared experts, and a load-balance
auxiliary loss.

Grouping: each batch row is a dispatch group (batch is the data-sharded
axis), so the capacity cumsum runs over S*k positions *within* a row —
independent across data shards, no cross-device serialization.  Tokens
are scattered into a per-group per-expert capacity buffer
(B, E, C, d), run through the grouped expert GEMM (the Pallas
``moe_gemm`` kernel on TPU; jnp einsum oracle elsewhere), and combined
back with their gate weights.

The B-MoE trust mechanism (redundant execution + consensus vote) wraps
the routed-expert output buffer — see ``repro.core.trusted_moe``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.builder import Leaf


def moe_decl(cfg) -> dict:
    """The router scores all ``num_experts``; the expert banks hold only
    the held experts (all of them unless ``cfg.held_experts``), in the
    order ``held_experts`` lists them."""
    E, d, f = cfg.resolved_padded_experts, cfg.d_model, cfg.moe_d_ff
    Eh = len(cfg.resolved_held_experts)
    decl = {
        "router": Leaf((d, E), ("embed", "experts"), scale=0.02),
        "w_gate": Leaf((Eh, d, f), ("experts", "embed", "moe_ff")),
        "w_up": Leaf((Eh, d, f), ("experts", "embed", "moe_ff")),
        "w_down": Leaf((Eh, f, d), ("experts", "moe_ff", "embed")),
    }
    if cfg.router_scoring == "sigmoid":
        decl["b_corr"] = Leaf((E,), ("experts",), "zeros")
    if cfg.num_shared_experts:
        sf = cfg.num_shared_experts * f
        decl["shared"] = {
            "w_gate": Leaf((d, sf), ("embed", "ff")),
            "w_up": Leaf((d, sf), ("embed", "ff")),
            "w_down": Leaf((sf, d), ("ff", "embed")),
        }
    return decl


def capacity_for(cfg, tokens_per_group: int) -> int:
    cap = max(int(cfg.capacity_factor * tokens_per_group *
                  cfg.num_experts_per_tok / cfg.num_experts), 1)
    cap = min(-(-cap // 8) * 8, tokens_per_group * cfg.num_experts_per_tok)
    return max(cap, 1)


def capacity_positions(expert_id, num_experts: int, capacity: int):
    """Capacity-bucket slot assignment — the shared dispatch machinery.

    ``expert_id``: (G, P) int — expert chosen at each of P dispatch
    positions, independently per group G (a batch row here; the single
    all-batch group in the B-MoE system's sparse dispatch).  Returns
    ``(position, keep, onehot)``: ``position[g, p]`` counts earlier
    same-expert assignments within the group (the slot in that expert's
    capacity bucket), ``keep = position < capacity`` marks assignments
    that fit, and ``onehot`` is the (G, P, E) int32 assignment tensor the
    positions were computed from (returned so callers needing per-expert
    statistics — the router aux loss — don't rebuild it).  Overflowing
    assignments are *dropped*, never mis-routed.
    """
    onehot = jax.nn.one_hot(expert_id, num_experts, dtype=jnp.int32)
    pos_all = jnp.cumsum(onehot, axis=1) - onehot       # (G, P, E)
    position = (pos_all * onehot).sum(-1)
    return position, position < capacity, onehot


def route(logits, k: int, capacity: int, num_real: int = 0, *,
          scoring: str = "softmax", bias=None, scale: float = 1.0):
    """logits: (B, S, E).  Per-row top-k routing with capacity buckets.

    ``num_real`` < E masks the padded experts (expert-axis padding for
    even model-axis sharding) out of the softmax/top-k.

    ``scoring="softmax"``: the top k of the softmax.  ``"sigmoid"``
    (DeepSeek-V3): scores ``sigmoid(logits)`` in float32, the top k of
    ``scores + bias`` chosen (``bias`` (E,) steers selection only) and
    weighted by their scores.  The k weights are renormalized to sum 1;
    ``scale`` multiplies them after.

    Returns weights (B,S,k), expert_id (B,S,k), position (B,S,k),
    keep (B,S,k) and the GShard load-balance aux loss."""
    B, S, E = logits.shape
    if num_real and num_real < E:
        pad_mask = jnp.arange(E) >= num_real
        logits = jnp.where(pad_mask[None, None, :], -1e30, logits)
    if scoring == "softmax":
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        weights, expert_id = jax.lax.top_k(probs, k)
        weights = weights / jnp.maximum(weights.sum(-1, keepdims=True), 1e-9)
    else:
        scores = jax.nn.sigmoid(logits.astype(jnp.float32))
        choice = scores if bias is None else scores + bias.astype(jnp.float32)
        _, expert_id = jax.lax.top_k(choice, k)
        weights = jnp.take_along_axis(scores, expert_id, axis=-1)
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-20)
        # the load-balance statistic reads each token's scores as shares
        probs = scores / scores.sum(-1, keepdims=True)
    if scale != 1.0:
        weights = weights * scale

    position, keep, onehot = capacity_positions(
        expert_id.reshape(B, S * k), E, capacity)
    position = position.reshape(B, S, k)
    keep = keep.reshape(B, S, k)

    frac_tokens = onehot.sum(axis=(0, 1)).astype(jnp.float32) / (B * S * k)
    frac_probs = probs.mean(axis=(0, 1))
    aux = E * jnp.sum(frac_tokens * frac_probs)
    return weights, expert_id, position, keep, aux


def route_masked(logits, k: int, capacity: int, num_real: int = 0,
                 valid=None):
    """``route`` for a token axis that may carry pad rows, returning
    psum-able load-balance statistics instead of a local scalar aux.

    ``valid``: (B, S) bool (None = every row real).  Pad rows route to
    the out-of-range sentinel expert E whose one-hot row is all-zero —
    they occupy no capacity slot, carry zero gate weight, and a scatter
    at expert index E is out-of-bounds (dropped), so padding adds no
    wire bytes and no expert FLOPs.

    Returns weights (B,S,k), expert_id (B,S,k), position (B,S,k),
    keep (B,S,k) and ``(tok_counts (E,), prob_sums (E,), n_valid ())``.
    A sharded caller psums the statistics over its token shards and
    forms the aux loss over the EXACT global batch::

        aux = E * sum(counts / (T * k) * probs / T),  T = n_valid

    which with ``valid=None`` on one shard reduces bitwise to
    ``route``'s aux (same sums, same order)."""
    B, S, E = logits.shape
    if num_real and num_real < E:
        pad_mask = jnp.arange(E) >= num_real
        logits = jnp.where(pad_mask[None, None, :], -1e30, logits)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    weights, expert_id = jax.lax.top_k(probs, k)
    weights = weights / jnp.maximum(weights.sum(-1, keepdims=True), 1e-9)
    if valid is not None:
        expert_id = jnp.where(valid[:, :, None], expert_id, E)
        weights = weights * valid[:, :, None].astype(weights.dtype)

    position, keep, onehot = capacity_positions(
        expert_id.reshape(B, S * k), E, capacity)
    position = position.reshape(B, S, k)
    keep = keep.reshape(B, S, k)
    if valid is not None:
        # a pad row's zero one-hot lands at position 0 (< capacity)
        keep = keep & valid[:, :, None]

    tok_counts = onehot.sum(axis=(0, 1)).astype(jnp.float32)
    if valid is None:
        prob_sums = probs.sum(axis=(0, 1))
        n_valid = jnp.float32(B * S)
    else:
        prob_sums = (probs * valid[:, :, None].astype(probs.dtype)
                     ).sum(axis=(0, 1))
        n_valid = valid.sum().astype(jnp.float32)
    return weights, expert_id, position, keep, (tok_counts, prob_sums,
                                                n_valid)


def grouped_mlp(buf, w_gate, w_up, w_down, shard=None):
    """buf: (B, E, C, d) -> (B, E, C, d) through each expert's SwiGLU.

    On TPU this is the ``moe_gemm`` Pallas kernel (B folded into the
    grid); the einsums below are its exact oracle and the GSPMD path
    used for dry-run lowering."""
    h = jax.nn.silu(jnp.einsum("becd,edf->becf", buf, w_gate)) * \
        jnp.einsum("becd,edf->becf", buf, w_up)
    if shard is not None:
        h = shard(h, "batch", "experts", None, "moe_ff")
    return jnp.einsum("becf,efd->becd", h, w_down)


def moe_mlp(params, x, cfg, shard=None, trust=None, return_stats=False,
            return_held=False):
    """x: (B, S, d) -> (B, S, d), plus aux loss.

    With an expert share (``cfg.held_experts``) the router scores and
    chooses over all experts and weights the chosen k as usual; only the
    assignments to held experts are dispatched (capacity buffers for the
    held experts alone), so ``y`` is the held experts' weighted part plus
    the shared experts: what this device adds to the layer.

    ``trust``: optional hook applied to the routed-expert output buffer —
    the B-MoE redundancy + consensus vote.

    ``return_stats``: also return the per-expert routed-token counts
    ``(E,)`` (drops included — a dropped assignment still computed its
    bucket, so its expert's parameters were needed).  This is the gate
    statistic the serving engine's edge cache feeds its EMA prefetcher
    with; default off so existing (y, aux) call sites are untouched.

    ``return_held``: also return, per token (B, S), how many of its
    assignments landed on held experts and were computed."""
    B, S, d = x.shape
    k = cfg.num_experts_per_tok
    E = cfg.resolved_padded_experts
    C = capacity_for(cfg, S)

    logits = jnp.einsum("bsd,de->bse", x, params["router"])
    weights, expert_id, position, keep, aux = route(
        logits, k, C, cfg.num_experts, scoring=cfg.router_scoring,
        bias=params.get("b_corr"), scale=cfg.routed_scaling_factor)
    counts = (jnp.zeros(E, jnp.int32).at[expert_id.reshape(-1)].add(1)
              if return_stats else None)
    if cfg.expert_share:
        # global expert -> its index in the held bank; absent experts map
        # past the bank and their assignments are not dispatched
        held = cfg.resolved_held_experts
        local = np.full(E, len(held), np.int32)
        local[list(held)] = np.arange(len(held))
        expert_id = jnp.asarray(local)[expert_id]
        keep = keep & (expert_id < len(held))
        expert_id = jnp.minimum(expert_id, len(held) - 1)
        E = len(held)

    # ---- dispatch: per-row scatter into (B, E, C, d) capacity buffers
    row = jnp.broadcast_to(jnp.arange(B)[:, None], (B, S * k))
    eid = expert_id.reshape(B, S * k)
    pos = jnp.where(keep, position, C - 1).reshape(B, S * k)  # clamp
    gath = jnp.repeat(x, k, axis=1) * keep.reshape(B, S * k, 1).astype(x.dtype)
    buf = jnp.zeros((B, E, C, d), x.dtype).at[row, eid, pos].add(gath)
    if shard is not None:
        buf = shard(buf, "batch", "experts", None, "embed")

    out_buf = grouped_mlp(buf, params["w_gate"], params["w_up"],
                          params["w_down"], shard=shard)
    if trust is not None:  # B-MoE consensus on per-expert outputs
        # the vote needs concrete (fully-reduced) buffer values
        if shard is not None:
            out_buf = shard(out_buf, "batch", "experts", None, "embed")
        out_buf = trust(out_buf)
    # NOTE: no sharding constraint on out_buf otherwise — under expert-TP
    # (moe_ff sharded) the buffer is a partial sum, and the combine below
    # is linear in it, so XLA can defer the psum to the (B, S, d) output
    # (~E*C/S x fewer reduced bytes; §Perf iteration 2)

    # ---- combine: gather back and weight
    yk = out_buf[row, eid, pos]                          # (B, S*k, d)
    wk = (weights * keep).reshape(B, S * k, 1).astype(x.dtype)
    y = (yk * wk).reshape(B, S, k, d).sum(axis=2)

    if cfg.num_shared_experts:
        sp = params["shared"]
        y = y + (jax.nn.silu(x @ sp["w_gate"]) * (x @ sp["w_up"])) @ sp["w_down"]
    out = (y, aux * cfg.router_aux_weight)
    if return_stats:
        out += (counts,)
    if return_held:
        out += (keep.sum(-1, dtype=jnp.int32),)
    return out
