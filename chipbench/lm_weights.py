"""Seeded weights for a decoder-only MoE language model, made on the
device in one compiled call.

The tree has the layout the serving engine reads (the layers stacked
on a leading axis under ``blocks/0``); the values come from this file
alone, so the plain reference never takes anything the program made.
Matrices are normal with standard deviation 1/sqrt(fan-in); the
embedding, the router and the head 0.02; the norm scales 0.1, so that
the reference has to apply them.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from traffic import rng_for


def shapes(c: Dict) -> Dict:
    """Nested dict of (shape, std) per leaf for configuration ``c``."""
    L, d, V = c["num_layers"], c["d_model"], c["vocab_size"]
    H, KH, D = c["num_heads"], c["num_kv_heads"], c["head_dim"]
    E, f = c["num_experts"], c["moe_d_ff"]
    fan = lambda n: n ** -0.5
    return {
        "embed": ((V, d), 0.02),
        "final_norm": ((d,), 0.1),
        "lm_head": ((d, V), 0.02),
        "blocks": {"0": {
            "norm1": ((L, d), 0.1),
            "norm2": ((L, d), 0.1),
            "attn": {"wq": ((L, d, H * D), fan(d)),
                     "wk": ((L, d, KH * D), fan(d)),
                     "wv": ((L, d, KH * D), fan(d)),
                     "wo": ((L, H * D, d), fan(H * D))},
            "moe": {"router": ((L, d, E), 0.02),
                    "w_gate": ((L, E, d, f), fan(d)),
                    "w_up": ((L, E, d, f), fan(d)),
                    "w_down": ((L, E, f, d), fan(f))},
        }},
    }


def _leaves(tree, prefix=()) -> list:
    if isinstance(tree, dict):
        return [x for k in sorted(tree) for x in _leaves(tree[k], prefix + (k,))]
    return [(prefix, tree)]


# bytes of one element of each type a configuration's ``torch_dtype``
# may name
DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2}

# the narrowest width, in bytes, at which a matrix product at each of
# JAX's precisions (by either of its names) reads an operand on TPU: one
# bfloat16 pass reads bfloat16; three passes read a float32 split into
# two bfloat16 halves; "highest" reads float32
PRECISION_BYTES = {"default": 2, "bfloat16": 2, "high": 4,
                   "tensorfloat32": 4, "highest": 4, "float32": 4}

# leaves that feed no matrix product: the embedding is a row lookup,
# the norm scales multiply elementwise
GATHERED = "embed"
ELEMENTWISE = ("final_norm", "norm1", "norm2")


def stored_width(c: Dict) -> int:
    """Bytes of one stored weight, from the file's ``torch_dtype``."""
    return DTYPE_BYTES[c["torch_dtype"]]


def product_width(c: Dict) -> int:
    """Bytes at which the matrix products read a weight: the file's
    ``matmul_precision`` reads it no wider than it is stored."""
    if c["matmul_precision"] not in PRECISION_BYTES:
        raise KeyError(f"no operand width for matmul_precision "
                       f"{c['matmul_precision']!r}; known: "
                       f"{sorted(PRECISION_BYTES)}")
    return min(stored_width(c), PRECISION_BYTES[c["matmul_precision"]])


def weight_bytes(c: Dict) -> int:
    """The weights' stored size: what they hold of the device's memory."""
    return sum(int(np.prod(s)) for _, (s, _) in _leaves(shapes(c))) \
        * stored_width(c)


def streamed_bytes(c: Dict, ticks: int, rows: int) -> int:
    """Least HBM bytes of weights that ``ticks`` micro-steps of the serve
    step read, which gather ``rows`` embedding rows in all (one per busy
    slot and micro-step): every matrix product's weight once per
    micro-step at ``product_width`` (a conversion to it need not repeat,
    since the weights do not change), the norm scales once per micro-step
    and the gathered rows, both at ``stored_width``."""
    stored, product = stored_width(c), product_width(c)
    per_tick = 0
    for path, (shape, _) in _leaves(shapes(c)):
        if path[-1] == GATHERED:
            row = shape[-1] * stored
        else:
            per_tick += int(np.prod(shape)) * (
                stored if path[-1] in ELEMENTWISE else product)
    return ticks * per_tick + rows * row


def key_for(seed: int) -> jax.Array:
    s = rng_for(seed, 0).integers(0, 2**31 - 1, size=2)
    return jax.random.fold_in(jax.random.PRNGKey(int(s[0])), int(s[1]))


def make(c: Dict, seed: int, dtype=jnp.float32) -> Dict:
    """All weights of ``c`` from ``seed``, in one jitted program."""
    leaves = _leaves(shapes(c))

    def build(key):
        out: Dict = {}
        for i, (path, (shape, std)) in enumerate(leaves):
            val = (jax.random.normal(jax.random.fold_in(key, i), shape,
                                     jnp.float32) * std).astype(dtype)
            node = out
            for k in path[:-1]:
                node = node.setdefault(k, {})
            node[path[-1]] = val
        return out

    return jax.jit(build)(key_for(seed))


def param_count(c: Dict) -> Tuple[int, int]:
    """(all parameters, parameters in the matrix products of one token):
    attention projections, router, the top-k experts of each layer, and
    the head; the embedding is a row lookup and the norms are not
    matrix products."""
    L, d, V = c["num_layers"], c["d_model"], c["vocab_size"]
    H, KH, D = c["num_heads"], c["num_kv_heads"], c["head_dim"]
    E, K, f = c["num_experts"], c["num_experts_per_tok"], c["moe_d_ff"]
    total = sum(int(np.prod(s)) for _, (s, _) in _leaves(shapes(c)))
    attn = d * H * D * 2 + d * KH * D * 2
    active = L * (attn + d * E + K * 3 * d * f) + d * V
    return total, active
