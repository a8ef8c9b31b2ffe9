"""Pallas TPU kernel: RG-LRU linear recurrence h_t = a_t*h_{t-1} + b_t
(RecurrentGemma, arXiv:2402.19427).

Layout: (B, S, C).  Grid (B, C/Ct, S/Sq) with the sequence dimension
innermost: the carried hidden state (Ct lanes) lives in VMEM scratch
across sequence chunks; within a chunk the recurrence runs as a
``fori_loop`` over rows on the VPU (8x128 lanes).  This is the
TPU-native shape of the scan: lanes parallel, time sequential —
vs the log-depth associative scan used on the jnp path
(``models.rglru.rglru_scan``), which is the oracle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.backend import resolve_interpret


def _rglru_kernel(a_ref, b_ref, y_ref, h_ref, *, Sq):
    s = pl.program_id(2)

    @pl.when(s == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    a = a_ref[0]                                       # (Sq, Ct)
    b = b_ref[0]

    def body(i, h):
        h = a[i] * h + b[i]
        y_ref[0, pl.ds(i, 1), :] = h[None]
        return h

    h_ref[...] = jax.lax.fori_loop(0, Sq, body, h_ref[...])


def rglru_scan_pallas(a, b, *, seq_block=128, chan_block=256,
                      interpret=None):
    """a, b: (B, S, C) f32 -> h: (B, S, C)."""
    interpret = resolve_interpret(interpret)
    B, S, C = a.shape
    Sq = min(seq_block, S)
    Ct = min(chan_block, C)
    if S % Sq or C % Ct:
        raise ValueError(f"S={S} % {Sq} or C={C} % {Ct} != 0")
    from jax.experimental.pallas import tpu as pltpu

    grid = (B, C // Ct, S // Sq)
    return pl.pallas_call(
        functools.partial(_rglru_kernel, Sq=Sq),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, Sq, Ct), lambda bi, ci, si: (bi, si, ci)),
            pl.BlockSpec((1, Sq, Ct), lambda bi, ci, si: (bi, si, ci)),
        ],
        out_specs=pl.BlockSpec((1, Sq, Ct), lambda bi, ci, si: (bi, si, ci)),
        out_shape=jax.ShapeDtypeStruct((B, S, C), jnp.float32),
        scratch_shapes=[pltpu.VMEM((Ct,), jnp.float32)],
        interpret=interpret,
    )(a.astype(jnp.float32), b.astype(jnp.float32))
