"""Seeded weights for a DeepSeek-V3-style model (latent attention, one
or more leading dense layers, then MoE layers with a sigmoid router and
shared experts), made on the device in one compiled call.

The tree has the layout the serving engine reads: the leading layers
in a list under ``leading``, the MoE layers stacked on a leading axis
under ``blocks/0``, the routed expert banks holding only the experts
this chip holds.  The values come from this file alone, so the plain
reference never takes anything the program made.  Matrices are normal
with standard deviation 1/sqrt(fan-in); the embedding, the router and
the head 0.02; the norm scales 0.1, so that the reference has to apply
them; the router's selection bias ``b_corr`` 0.05, so that it changes
some selections.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from lm_weights import key_for

B_CORR_STD = 0.05


def _attn(c: Dict, lead=()) -> Dict:
    d, H = c["d_model"], c["num_heads"]
    nope, rope, r, dv = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                         c["kv_lora_rank"], c["v_head_dim"])
    fan = lambda n: n ** -0.5
    return {"wq": (lead + (d, H * (nope + rope)), fan(d)),
            "wkva": (lead + (d, r + rope), fan(d)),
            "kva_norm": (lead + (r,), 0.1),
            "wkvb": (lead + (r, H * (nope + dv)), fan(r)),
            "wo": (lead + (H * dv, d), fan(H * dv))}


def _mlp(d: int, f: int, lead=()) -> Dict:
    return {"w_gate": (lead + (d, f), d ** -0.5),
            "w_up": (lead + (d, f), d ** -0.5),
            "w_down": (lead + (f, d), f ** -0.5)}


def shapes(c: Dict) -> Dict:
    """Nested dict of (shape, std) per leaf for configuration ``c``."""
    d, V = c["d_model"], c["vocab_size"]
    n_lead = c["leading_dense"]
    L = c["num_layers"] - n_lead
    E, Eh, f = c["num_experts"], c["held_experts"], c["moe_d_ff"]
    moe = {"router": ((L, d, E), 0.02), "b_corr": ((L, E), B_CORR_STD),
           **_mlp(d, f, (L, Eh)),
           "shared": _mlp(d, c["num_shared_experts"] * f, (L,))}
    return {
        "embed": ((V, d), 0.02),
        "final_norm": ((d,), 0.1),
        "lm_head": ((d, V), 0.02),
        "leading": {str(i): {"norm1": ((d,), 0.1), "norm2": ((d,), 0.1),
                             "mla": _attn(c), "mlp": _mlp(d, c["d_ff"])}
                    for i in range(n_lead)},
        "blocks": {"0": {"norm1": ((L, d), 0.1), "norm2": ((L, d), 0.1),
                         "mla": _attn(c, (L,)), "moe": moe}},
    }


def leaves(tree, prefix=()) -> list:
    """(path, (shape, std)) of every leaf, in a fixed order."""
    if isinstance(tree, dict):
        return [x for k in sorted(tree) for x in leaves(tree[k], prefix + (k,))]
    return [(prefix, tree)]


def make(c: Dict, seed: int, dtype=jnp.float32) -> Dict:
    """All weights of ``c`` from ``seed``, in one jitted program."""
    spec = leaves(shapes(c))

    def build(key):
        out: Dict = {}
        for i, (path, (shape, std)) in enumerate(spec):
            val = (jax.random.normal(jax.random.fold_in(key, i), shape,
                                     jnp.float32) * std).astype(dtype)
            node = out
            for k in path[:-1]:
                node = node.setdefault(k, {})
            node[path[-1]] = val
        if "leading" in out:
            out["leading"] = [out["leading"][str(i)]
                              for i in range(len(out["leading"]))]
        return out

    return jax.jit(build)(key_for(seed))

