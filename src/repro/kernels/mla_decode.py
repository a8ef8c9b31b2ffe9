"""Pallas TPU kernel: absorbed latent-attention decode over each slot's
live latent rows.

One query token per slot against the latent cache: scores
``q_lat . ckv^T + q_pe . kpe^T`` (scaled), a softmax over rows
``0 .. min(pos, cap - 1)``, and the weighted sum of latents, all in ONE
pass that reads each live row once.  Rows past a slot's position are
never read, except up to the end of its last block, where they are
masked.

The grid runs over slots.  The caches stay in HBM (``memory_space=ANY``)
as the stacked (L, B, cap, ·) leaves: the layer is a scalar picked inside
the kernel, so the caller never slices (and so copies) a layer's cache.
Each slot loops over its own ``min(pos, cap - 1) // blk + 1`` blocks,
double-buffered by manual DMA; the next slot's first block is fetched
while the current slot's last one is computed, so the DMA engine never
waits on a slot boundary.  Both products take the float32 cache as
stored at full float32 precision (``HIGHEST``) and accumulate in
float32; the online softmax is float32.

The rotary keys go in as (L, B, r, cap): the TPU stores a 64-wide minor
axis transposed, so that view is the buffer as it lies, not a copy.  The
kernel also writes this step's rotary key of each slot, in place (the
cache is aliased to the output): a scatter of one row into the
transposed buffer is laid out by XLA with the row contiguous outside the
layer scan, which would copy a whole layer's keys each step to hand them
to the kernel.  The write reads, patches and writes back the (r, 128)
tile that holds the row, behind the slot's reads; the blocks read take
the new row from the input, so they never wait on it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import resolve_interpret

NEG_INF = -1e30
BLOCK_ROWS = 512        # latent rows per DMA block: 1 MB of a 512-wide cache
TILE = 128              # lanes of the rotary-key tile a write patches
PRECISION = jax.lax.Precision.HIGHEST


def block_rows(cap: int) -> int:
    """The block the kernel reads a ``cap``-row cache in."""
    blk = min(BLOCK_ROWS, cap)
    if cap % blk:
        raise ValueError(f"cache length {cap} is not a multiple of the "
                         f"{blk}-row block")
    return blk


def _kernel(layer_ref, pos_ref, slot_ref, q_lat_ref, q_pe_ref, new_ref,
            ckv_hbm, kpe_hbm, o_ref, kpe_out, ckv_buf, kpe_buf, tile_buf,
            sems, tile_sems, base_ref, *, blk, tile, cap, scale):
    b = pl.program_id(0)
    last = pl.num_programs(0) - 1
    layer = layer_ref[0]

    def copies(i, j, buf):
        rows = pl.ds(pl.multiple_of(j * blk, blk), blk)
        return (pltpu.make_async_copy(ckv_hbm.at[layer, i, rows],
                                      ckv_buf.at[buf], sems.at[0, buf]),
                pltpu.make_async_copy(kpe_hbm.at[layer, i, :, rows],
                                      kpe_buf.at[buf], sems.at[1, buf]))

    def start(i, j, buf):
        for c in copies(i, j, buf):
            c.start()

    def tile_copy(i, write):
        """Slot ``i``'s rotary-key tile holding its written row: HBM to
        VMEM, or back with ``write``."""
        lanes = pl.ds(pl.multiple_of(
            jnp.minimum(slot_ref[i], cap - 1) // tile * tile, tile), tile)
        hbm, vmem = kpe_out.at[layer, i, :, lanes], tile_buf.at[i % 2]
        src, dst = (vmem, hbm) if write else (hbm, vmem)
        return pltpu.make_async_copy(src, dst,
                                     tile_sems.at[int(write), i % 2])

    @pl.when(b == 0)
    def _first():
        base_ref[0] = 0
        start(0, 0, 0)

    slot = slot_ref[b]                 # the row written; cap: none
    writes = slot < cap

    @pl.when(writes)
    def _read_tile():
        tile_copy(b, False).start()

    pos = pos_ref[b]
    nb = jnp.minimum(pos, cap - 1) // blk + 1        # the live rows' blocks
    base = base_ref[0]                 # blocks of the slots before this one
    q_lat = q_lat_ref[0].astype(jnp.float32)          # (H, c)
    q_pe = q_pe_ref[0].astype(jnp.float32)            # (H, r)
    new = new_ref[0].astype(jnp.float32)              # (r, 1)
    H, r = q_pe.shape

    def body(j, carry):
        m, l, acc = carry
        buf = (base + j) % 2

        @pl.when(j + 1 < nb)
        def _next_block():
            start(b, j + 1, 1 - buf)

        @pl.when((j + 1 == nb) & (b < last))
        def _next_slot():
            start(b + 1, 0, 1 - buf)

        for c in copies(b, j, buf):
            c.wait()
        k = ckv_buf[buf].astype(jnp.float32)          # (blk, c)
        col = j * blk + jax.lax.broadcasted_iota(jnp.int32, (r, blk), 1)
        kp = jnp.where(col == slot, new, kpe_buf[buf].astype(jnp.float32))
        s = (jax.lax.dot_general(q_lat, k, (((1,), (1,)), ((), ())),
                                 precision=PRECISION,
                                 preferred_element_type=jnp.float32)
             + jnp.dot(q_pe, kp, precision=PRECISION,
                       preferred_element_type=jnp.float32))
        s = s * scale
        row = j * blk + jax.lax.broadcasted_iota(jnp.int32, (H, blk), 1)
        s = jnp.where(row <= pos, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l = alpha * l + p.sum(axis=-1, keepdims=True)
        acc = alpha * acc + jnp.dot(p, k, precision=PRECISION,
                                    preferred_element_type=jnp.float32)
        return m_new, l, acc

    m0 = jnp.full((H, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((H, 1), jnp.float32)
    acc0 = jnp.zeros(q_lat.shape, jnp.float32)
    _, l, acc = jax.lax.fori_loop(0, nb, body, (m0, l0, acc0))
    o_ref[0] = (acc / l).astype(o_ref.dtype)
    base_ref[0] = base + nb

    # the previous slot's tile write has landed before its buffer is
    # reused by the next slot's read
    @pl.when((b > 0) & (slot_ref[jnp.maximum(b - 1, 0)] < cap))
    def _previous_written():
        tile_copy(b - 1, True).wait()

    @pl.when(writes)
    def _write_tile():
        tile_copy(b, False).wait()
        lane = jax.lax.broadcasted_iota(jnp.int32, (r, tile), 1)
        cur = tile_buf[b % 2]
        tile_buf[b % 2] = jnp.where(lane == slot % tile,
                                    new.astype(cur.dtype), cur)
        tile_copy(b, True).start()

    @pl.when(writes & (b == last))
    def _last_written():
        tile_copy(b, True).wait()


def mla_decode(q_lat, q_pe, k_pe, ckv, kpe, pos, slot, layer, *, scale,
               interpret: bool | None = None):
    """q_lat: (B, H, c); q_pe: (B, H, r); k_pe: (B, r), this step's
    rotary keys; ckv: (L, B, cap, c) and kpe: (L, B, cap, r), the stacked
    caches, read at ``[layer]``; pos: (B,) int32; slot: (B,) int32 in
    [0, cap], the row slot b writes ``k_pe[b]`` at (``cap``: it writes
    nothing); layer: int32 scalar.  Returns (o_lat (B, H, c) float32,
    kpe with the rows written): per slot the softmax over rows
    ``0 .. min(pos, cap - 1)`` of the scaled scores, times the latents."""
    interpret = resolve_interpret(interpret)
    B, H, c = q_lat.shape
    r = q_pe.shape[-1]
    cap = ckv.shape[-2]
    blk = block_rows(cap)
    tile = min(TILE, blk)
    per_slot = lambda b, *_: (b, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B,),
        in_specs=[pl.BlockSpec((1, H, c), per_slot),
                  pl.BlockSpec((1, H, r), per_slot),
                  pl.BlockSpec((1, r, 1), per_slot),
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[pl.BlockSpec((1, H, c), per_slot),
                   pl.BlockSpec(memory_space=pl.ANY)],
        scratch_shapes=[pltpu.VMEM((2, blk, c), ckv.dtype),
                        pltpu.VMEM((2, r, blk), kpe.dtype),
                        pltpu.VMEM((2, r, tile), kpe.dtype),
                        pltpu.SemaphoreType.DMA((2, 2)),
                        pltpu.SemaphoreType.DMA((2, 2)),
                        pltpu.SMEM((1,), jnp.int32)])
    kernel = functools.partial(_kernel, blk=blk, tile=tile, cap=cap,
                               scale=scale)
    kpe_t = jnp.swapaxes(kpe, -1, -2)                 # (L, B, r, cap)
    o_lat, kpe_t = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=(jax.ShapeDtypeStruct((B, H, c), jnp.float32),
                   jax.ShapeDtypeStruct(kpe_t.shape, kpe_t.dtype)),
        input_output_aliases={7: 1},                  # kpe_t, in place
        # slots run in order: each prefetches the next one's first block
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="mla_decode",
    )(jnp.reshape(jnp.asarray(layer, jnp.int32), (1,)),
      jnp.asarray(pos, jnp.int32), jnp.asarray(slot, jnp.int32), q_lat,
      q_pe, k_pe[..., None], ckv, kpe_t)
    return o_lat, jnp.swapaxes(kpe_t, -1, -2)
