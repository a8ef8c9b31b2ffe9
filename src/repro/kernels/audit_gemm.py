"""Pallas TPU kernel: grouped gather-GEMM for batched audit recompute.

Audit recompute evaluates the paper's 2-layer MLP expert on every
sampled (expert, chunk) pair of a round commitment.  The eager auditor
dispatches one apply per pair; this kernel takes the whole padded batch
of sampled chunks (S, C, d) plus a per-sample group index and fuses the
full expert — relu(x @ w1[g] + b1[g]) @ w2[g] + b2[g] — in one pass:
layer-1 partial products accumulate over the contraction dim in an f32
VMEM scratch block, and the epilogue (bias, relu, layer-2 GEMM, bias)
runs when the last d-block lands, so the hidden activations never leave
VMEM.  Expert weights are gathered per sample with a scalar-prefetched
index (``PrefetchScalarGridSpec``), the same mechanism a
capacity-bucketed MoE dispatch uses — duplicate group ids are fine and
simply re-stream the same weight block.

Validated on CPU with interpret=True against ``ref.audit_mlp_ref``
(tests/test_kernels.py); the compiled path targets the MXU with the
feature dims padded to lane multiples by the wrapper.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import resolve_interpret


def _audit_mlp_kernel(gid_ref, x_ref, w1_ref, b1_ref, w2_ref, b2_ref,
                      o_ref, h_ref):
    del gid_ref                      # consumed by the index_maps
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    h_ref[...] += jnp.dot(x_ref[0], w1_ref[0],
                          preferred_element_type=jnp.float32)

    @pl.when(k == pl.num_programs(1) - 1)
    def _epilogue():
        h = jnp.maximum(h_ref[...] + b1_ref[0], 0.0)
        o_ref[0] = (jnp.dot(h, w2_ref[0], preferred_element_type=jnp.float32)
                    + b2_ref[0])


def _pad_axis(x, axis: int, mult: int):
    p = (-x.shape[axis]) % mult
    if p:
        pads = [(0, 0)] * x.ndim
        pads[axis] = (0, p)
        x = jnp.pad(x, pads)
    return x


def audit_mlp(params, x: jax.Array, gid: jax.Array, *, block_d: int = 256,
              interpret: bool | None = None) -> jax.Array:
    """Fused grouped 2-layer MLP: out[s] = mlp(params[gid[s]], x[s]).

    params: dict with stacked ``w1 (E, d, h)``, ``b1 (E, h)``,
    ``w2 (E, h, o)``, ``b2 (E, o)``; x: (S, C, d) padded sample chunks;
    gid: (S,) int32 expert index per sample.  Returns (S, C, o) f32.
    """
    interpret = resolve_interpret(interpret)
    w1, b1, w2, b2 = params["w1"], params["b1"], params["w2"], params["b2"]
    S, C, d = x.shape
    o = w2.shape[-1]
    block_d = min(block_d, d)

    xp = _pad_axis(_pad_axis(x, 1, 8), 2, block_d)
    w1p = _pad_axis(w1, 1, block_d)
    w2p = _pad_axis(w2, 2, 128)
    # biases ride as (E, 1, .) so each block's last two dims equal the
    # array's (Mosaic tiles the last two dims by (8, 128) or full size)
    b1r = b1[:, None, :]
    b2r = _pad_axis(b2, 1, 128)[:, None, :]
    Cp, dp = xp.shape[1], xp.shape[2]
    h = w1.shape[-1]
    op = w2p.shape[-1]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(S, dp // block_d),
        in_specs=[
            pl.BlockSpec((1, Cp, block_d), lambda s, k, gid: (s, 0, k)),
            pl.BlockSpec((1, block_d, h), lambda s, k, gid: (gid[s], k, 0)),
            pl.BlockSpec((1, 1, h), lambda s, k, gid: (gid[s], 0, 0)),
            pl.BlockSpec((1, h, op), lambda s, k, gid: (gid[s], 0, 0)),
            pl.BlockSpec((1, 1, op), lambda s, k, gid: (gid[s], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, Cp, op), lambda s, k, gid: (s, 0, 0)),
        scratch_shapes=[pltpu.VMEM((Cp, h), jnp.float32)],
    )
    out = pl.pallas_call(
        _audit_mlp_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, Cp, op), jnp.float32),
        interpret=interpret,
    )(gid.astype(jnp.int32), xp, w1p, b1r, w2p, b2r)
    return out[:, :C, :o]
