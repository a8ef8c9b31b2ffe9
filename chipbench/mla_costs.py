"""Operations and bytes a DeepSeek-V3-style model's serve step requires,
computed from shapes (the numerators of the ``.reasoning`` cell's
rooflines and utilization): what the algorithm needs, not what the
program happens to do.

Attention is counted in its absorbed form, which is what decode runs:
per live cached position and head, the score against the latent and the
rotary key (``kv_lora_rank + qk_rope_head_dim`` multiply-adds) and the
weighted sum of latents (``kv_lora_rank``); the per-head up-projections
``w_uk``, ``w_uv`` are ``wkvb``'s parameters, used once per token.
"""
from __future__ import annotations

from typing import Dict, Iterable, Tuple

from lm_weights import product_width, stored_width
from mla_weights import leaves, shapes

ATTN = ("wq", "wkva", "wkvb", "wo")
ELEMENTWISE = ("final_norm", "norm1", "norm2", "kva_norm", "b_corr")
EXPERTS = ("w_gate", "w_up", "w_down")


def latent_row_bytes(c: Dict, itemsize: int = 4) -> int:
    """Bytes of one cached position (latent and rotary key), over all
    layers."""
    return (c["num_layers"] * (c["kv_lora_rank"] + c["qk_rope_head_dim"])
            * itemsize)


def attn_params(c: Dict) -> int:
    """Parameters of one layer's attention products."""
    d, H = c["d_model"], c["num_heads"]
    nope, rope, r, dv = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                         c["kv_lora_rank"], c["v_head_dim"])
    return (d * H * (nope + rope) + d * (r + rope) + r * H * (nope + dv)
            + H * dv * d)


def attn_key_flops(c: Dict) -> int:
    """FLOPs of one token's absorbed attention per live cached position,
    over all layers."""
    r, rope = c["kv_lora_rank"], c["qk_rope_head_dim"]
    return 2 * c["num_layers"] * c["num_heads"] * (2 * r + rope)


def expert_flops(c: Dict) -> int:
    """FLOPs of one routed token-expert assignment in one layer."""
    return 2 * 3 * c["d_model"] * c["moe_d_ff"]


def dense_token_flops(c: Dict) -> int:
    """FLOPs of one token outside attention over the cache and outside
    the routed experts: attention products, dense MLPs, routers, shared
    experts and the head."""
    d, n_lead = c["d_model"], c["leading_dense"]
    n_moe = c["num_layers"] - n_lead
    per_moe = d * c["num_experts"] + 3 * d * c["num_shared_experts"] \
        * c["moe_d_ff"]
    return 2 * (c["num_layers"] * attn_params(c) + n_lead * 3 * d * c["d_ff"]
                + n_moe * per_moe + d * c["vocab_size"])


def runs_flops(c: Dict, runs: Iterable[Tuple[int, int]],
               held_per_token: float) -> float:
    """Forward FLOPs of the tokens at the contiguous runs ``(start,
    stop)`` of positions, each at its position, with ``held_per_token``
    routed assignments to held experts per token (all MoE layers)."""
    per_tok = dense_token_flops(c) + held_per_token * expert_flops(c)
    total = 0.0
    for a, b in runs:
        n = b - a
        total += per_tok * n + attn_key_flops(c) * (a + 1 + b) * n / 2
    return total


def runs_latent_bytes(c: Dict, runs, itemsize: int = 4) -> int:
    """Latent rows read (``position + 1`` per token) and the row written,
    summed over the contiguous runs of positions."""
    row = latent_row_bytes(c, itemsize)
    return sum(row * ((a + 2 + b + 1) * (b - a) // 2) for a, b in runs)


def weight_bytes(c: Dict, ticks: int, rows: int, only=None) -> int:
    """Least HBM bytes of weights ``ticks`` micro-steps read, which
    gather ``rows`` embedding rows in all: every product's weight once
    per micro-step at ``product_width`` (the held experts whole), norm
    scales and the selection bias once per micro-step and the gathered
    rows at ``stored_width``.  ``only``: leaf names to count (the
    attention products alone, say)."""
    stored, product = stored_width(c), product_width(c)
    per_tick, row = 0, 0
    for path, (shape, _) in leaves(shapes(c)):
        n = 1
        for s in shape:
            n *= s
        if path[-1] == "embed":
            row = shape[-1] * stored
        elif only is None or path[-1] in only:
            per_tick += n * (stored if path[-1] in ELEMENTWISE else product)
    return ticks * per_tick + (0 if only else rows * row)


def serve_step_bytes(c: Dict, ticks: int, runs) -> int:
    rows = sum(b - a for a, b in runs)
    return weight_bytes(c, ticks, rows) + runs_latent_bytes(c, runs)


def mla_step_bytes(c: Dict, ticks: int, runs) -> int:
    """The attention's part: Wq, Wkva, Wkvb, Wo of every layer at their
    product width each micro-step, and the latent rows."""
    return weight_bytes(c, ticks, 0, only=ATTN) + runs_latent_bytes(c, runs)


def mla_step_flops(c: Dict, runs) -> float:
    """The attention's part: its projections and absorbed scores and
    values over the live rows."""
    per_tok = 2 * c["num_layers"] * attn_params(c)
    return sum(per_tok * (b - a) + attn_key_flops(c) * (a + 1 + b) * (b - a)
               / 2 for a, b in runs)
