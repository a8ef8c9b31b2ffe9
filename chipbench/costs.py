"""Operations and bytes the work requires, computed from shapes.

These are the numerators of the benchmark's rooflines and utilizations:
what the algorithm needs, not what the program happens to do.  Padding,
masked rows and recomputation are not counted.
"""
from __future__ import annotations

from typing import Dict

from lm_weights import param_count, streamed_bytes


def kv_row_bytes(c: Dict, itemsize: int = 4) -> int:
    """Bytes of one cached position, keys and values, over all layers."""
    return 2 * c["num_layers"] * c["num_kv_heads"] * c["head_dim"] * itemsize


def kv_cache_bytes(c: Dict, slots: int, cache_len: int,
                   itemsize: int = 4) -> int:
    return slots * cache_len * kv_row_bytes(c, itemsize)


def token_flops(c: Dict, position: int) -> int:
    """Forward FLOPs of one token at absolute ``position`` (0-based):
    two per multiply-add of every matrix product it takes part in, plus
    attention scores and the weighted sum over ``position + 1`` keys."""
    _, active = param_count(c)
    attn = 4 * c["num_layers"] * c["num_heads"] * c["head_dim"] * (position + 1)
    return 2 * active + attn


def positions_flops(c: Dict, positions) -> int:
    """``token_flops`` summed over an iterable of positions, in closed
    form per contiguous run ``(start, stop)``."""
    _, active = param_count(c)
    per_key = 4 * c["num_layers"] * c["num_heads"] * c["head_dim"]
    total = 0
    for a, b in positions:
        n = b - a
        total += 2 * active * n + per_key * (a + 1 + b) * n // 2
    return total


def serve_microstep_bytes(c: Dict, positions, itemsize: int = 4) -> int:
    """HBM bytes one micro-step of the serve step must move: the weights
    as ``lm_weights.streamed_bytes`` counts them (each product's weight
    at the width its products read it, the busy slots' embedding rows,
    the norm scales), each busy slot's cached keys and values up to its
    position, and the one row it writes.  ``positions``: the position
    each busy slot processes in this micro-step."""
    row = kv_row_bytes(c, itemsize)
    return (streamed_bytes(c, 1, len(positions))
            + sum((p + 2) * row for p in positions))


def runs_kv_bytes(c: Dict, positions, itemsize: int = 4) -> int:
    """The KV part of ``serve_microstep_bytes`` summed over contiguous
    runs ``(start, stop)`` of processed positions."""
    row = kv_row_bytes(c, itemsize)
    return sum(row * ((a + 2 + b + 1) * (b - a) // 2) for a, b in positions)


def serve_step_bytes(c: Dict, ticks: int, positions,
                     itemsize: int = 4) -> int:
    """``serve_microstep_bytes`` summed over a macro-step of ``ticks``
    micro-steps whose busy slots process the contiguous runs ``(start,
    stop)`` of positions, in closed form."""
    rows = sum(b - a for a, b in positions)
    return (streamed_bytes(c, ticks, rows)
            + runs_kv_bytes(c, positions, itemsize))


def round_sample_flops(c: Dict) -> int:
    """FLOPs one training sample of a B-MoE round requires: the gate and
    its top-k experts' two-layer MLP forward, and twice that backward."""
    d, N, K = c["in_dim"], c["num_experts"], c["top_k"]
    H, C = c["hidden"], c["num_classes"]
    forward = 2 * d * N + K * (2 * d * H + 2 * H * C)
    return 3 * forward


def moe_gemm_cost(experts: int, rows: int, d_in: int, d_out: int,
                  itemsize: int = 4):
    """(FLOPs, HBM bytes) of one grouped expert GEMM, ``moe_gemm``: each
    of ``experts`` multiplies its (rows, d_in) buffer by its (d_in,
    d_out) weight; the buffers and weights are read once and the
    outputs written once."""
    flops = 2 * experts * rows * d_in * d_out
    moved = experts * (rows * d_in + d_in * d_out + rows * d_out)
    return flops, itemsize * moved
