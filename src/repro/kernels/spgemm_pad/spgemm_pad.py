"""SpGEMM hash-pad Pallas TPU kernel — numeric phase of sparse×sparse A@B.

The paper's NeuraMem accumulates SpGEMM partial products in a HashPad: each
pp is hashed by its output tag into an on-chip line, merged on tag match,
and the line is **evicted the moment its row completes** (rolling eviction,
C3).  The TPU adaptation keeps the same dataflow but moves every
data-dependent decision to plan time (``sparse.spgemm.symbolic``):

* **multiply stage** — A sits in PR-2's operand-deduplicated chunk layout
  (``pack_dedup_chunks``): a dense ``(block_rows, width)`` coefficient tile
  per chunk, one lane per distinct A column.  B's rows were hash-scattered
  host-side into a chunk-contiguous **slab**: lane ``u`` of chunk ``k``
  holds B row ``u_cols[k,u]`` with every value at bucket
  ``high_bits(col·γ_b)`` of the block's reseeded hash.  Per grid step the
  kernel lands exactly one coefficient tile and one ``(width, h_tile)``
  slab tile by async DMA — the same two-copy pipeline as the Gustavson
  SpMM kernel's ``gather="stream"`` path;
* **accumulate stage** — one MXU matmul folds the whole chunk into a
  ``(block_rows, h_tile)`` **VMEM hash-pad scratch tile**: bucket h of pad
  row r accumulates every pp whose output column hashes to h.  The
  symbolic phase chose γ_b so the bucket map is injective on each row's
  output column set — the CAM tag-match resolved at plan time, so the pad
  needs no probe loop;
* **rolling eviction** — chunks of one output block are consecutive;
  ``first[k]`` overwrites the pad on block entry (re-arming it without a
  zero-fill pass) and ``evict[k]`` — set on each block's last chunk, i.e.
  at row completion — copies the pad to the output tile routed by
  ``out_block[k]``.  Peak on-chip state is one pad tile + one landing
  slab tile, never the interim bloat (paper Table 1).

Grid = ``(h_tiles, n_chunks)``: the pad axis is tiled like the SpMM
kernel's feature axis; the chunk axis is innermost so the pad stays
resident across a block's chunks.  out_block/first/evict are
scalar-prefetched to SMEM; the output BlockSpec index map reads
``out_block[k]``.  Compiled on a TPU, interpreted elsewhere
(``core.platform``); validated on CPU against ref.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import platform

MAX_SINGLE_TILE_H = 512  # auto h_tile: one pad tile up to this lane count


def _kernel(ob_smem, first_smem, evict_smem, a_hbm, slab_hbm, y_ref,
            a_ref, land_ref, pad_ref, sems, *, block_rows: int, width: int,
            h_tile: int):
    j = pl.program_id(0)
    k = pl.program_id(1)
    a_cp = pltpu.make_async_copy(
        a_hbm.at[pl.dslice(k * block_rows, block_rows), :], a_ref,
        sems.at[0])
    a_cp.start()
    land_cp = pltpu.make_async_copy(
        slab_hbm.at[pl.dslice(k * width, width),
                    pl.dslice(j * h_tile, h_tile)], land_ref, sems.at[1])
    land_cp.start()
    a_cp.wait()
    land_cp.wait()
    # accumulate stage: the coefficient tile routes every partial product to
    # its (row, bucket) cell of the hash pad in one MXU matmul
    contrib = jax.lax.dot(a_ref[...], land_ref[...],
                          preferred_element_type=jnp.float32)
    is_first = first_smem[k] != 0
    pad_ref[...] = jnp.where(is_first, contrib, pad_ref[...] + contrib)

    @pl.when(evict_smem[k] != 0)
    def _evict():                       # rolling eviction at row completion
        y_ref[...] = pad_ref[...]


def _auto_h_tile(h: int) -> int:
    return h if h <= MAX_SINGLE_TILE_H else MAX_SINGLE_TILE_H


@platform.resolves_interpret
@functools.partial(jax.jit, static_argnames=("block_rows", "n_blocks",
                                             "pad_width", "h_tile",
                                             "interpret"))
def spgemm_hashpad(out_block: jax.Array, first: jax.Array, evict: jax.Array,
                   a: jax.Array, slab: jax.Array, *, block_rows: int,
                   n_blocks: int, pad_width: int, h_tile: int | None = None,
                   interpret: bool) -> jax.Array:
    """C_pad = fold(A_tiles @ slab) over chunks → (n_blocks·block_rows, H).

    out_block/first/evict: (n_chunks,) int32; a: (n_chunks·block_rows,
    width) f32 coefficient tiles; slab: (n_chunks·width, pad_width) f32
    hashed B rows.  Output row r holds row r's hash pad; the caller
    gathers C's nnz back out via the plan's (out_row, out_bucket) map.
    """
    n_chunks = out_block.shape[0]
    width = slab.shape[0] // n_chunks
    if h_tile is None:
        h_tile = _auto_h_tile(pad_width)
    if pad_width % h_tile:
        raise ValueError(f"h_tile {h_tile} must divide pad_width {pad_width}")
    h_tiles = pad_width // h_tile
    out_shape = jax.ShapeDtypeStruct((n_blocks * block_rows, pad_width),
                                     jnp.float32)
    # pad-tile axis outer, chunk axis inner: chunks of one output block stay
    # consecutive, so the pad scratch survives until its eviction step
    out_spec = pl.BlockSpec((block_rows, h_tile),
                            lambda j, k, ob, fi, ev: (ob[k], j))
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,          # out_block, first, evict
        grid=(h_tiles, n_chunks),
        in_specs=[any_spec, any_spec],  # a, slab (HBM)
        out_specs=out_spec,
        scratch_shapes=[
            pltpu.VMEM((block_rows, width), a.dtype),      # coeff tile
            pltpu.VMEM((width, h_tile), slab.dtype),       # landing slab
            pltpu.VMEM((block_rows, h_tile), jnp.float32),  # hash pad
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    kernel = functools.partial(_kernel, block_rows=block_rows, width=width,
                               h_tile=h_tile)
    return pl.pallas_call(kernel, grid_spec=grid_spec, out_shape=out_shape,
                          interpret=interpret)(
        out_block, first, evict, a, slab)


# ---------------------------------------------------------------------------
# int8 quantized-tile mode (pallas_q8) — same hash-pad dataflow, int8 DMA
# ---------------------------------------------------------------------------
#
# Both operands of chunk k — its coefficient tile AND its slab rows — carry
# one per-chunk symmetric scale (``repro.sparse.quantize``), so the whole
# MXU fold rescales with a single scalar multiply before accumulating into
# the f32 pad.  int8 magnitudes ≤ 127 keep every chunk sum < 2²⁴, so the f32
# accumulation inside the dot is exact; HBM → VMEM traffic is ¼ of f32.


def _kernel_q8(ob_smem, first_smem, evict_smem, ascale_smem, bscale_smem,
               a_hbm, slab_hbm, y_ref, a_ref, land_ref, pad_ref, sems, *,
               block_rows: int, width: int, h_tile: int):
    j = pl.program_id(0)
    k = pl.program_id(1)
    a_cp = pltpu.make_async_copy(
        a_hbm.at[pl.dslice(k * block_rows, block_rows), :], a_ref,
        sems.at[0])
    a_cp.start()
    land_cp = pltpu.make_async_copy(
        slab_hbm.at[pl.dslice(k * width, width),
                    pl.dslice(j * h_tile, h_tile)], land_ref, sems.at[1])
    land_cp.start()
    a_cp.wait()
    land_cp.wait()
    contrib = jax.lax.dot(a_ref[...].astype(jnp.float32),
                          land_ref[...].astype(jnp.float32),
                          preferred_element_type=jnp.float32)
    contrib = contrib * (ascale_smem[k] * bscale_smem[k])
    is_first = first_smem[k] != 0
    pad_ref[...] = jnp.where(is_first, contrib, pad_ref[...] + contrib)

    @pl.when(evict_smem[k] != 0)
    def _evict():
        y_ref[...] = pad_ref[...]


@platform.resolves_interpret
@functools.partial(jax.jit, static_argnames=("block_rows", "n_blocks",
                                             "pad_width", "h_tile",
                                             "interpret"))
def spgemm_hashpad_q8(out_block: jax.Array, first: jax.Array,
                      evict: jax.Array, a_q8: jax.Array, a_scale: jax.Array,
                      slab_q8: jax.Array, slab_scale: jax.Array, *,
                      block_rows: int, n_blocks: int, pad_width: int,
                      h_tile: int | None = None,
                      interpret: bool) -> jax.Array:
    """int8-operand hash-pad SpGEMM: C_pad ≈ fold(A_tiles @ slab), f32 out.

    a_q8: (n_chunks·block_rows, width) int8 with a_scale (n_chunks,) f32;
    slab_q8: (n_chunks·width, pad_width) int8 with slab_scale (n_chunks,)
    f32 — both scales per dedup chunk, rescaled at the pad accumulate.
    """
    n_chunks = out_block.shape[0]
    width = slab_q8.shape[0] // n_chunks
    if h_tile is None:
        h_tile = _auto_h_tile(pad_width)
    if pad_width % h_tile:
        raise ValueError(f"h_tile {h_tile} must divide pad_width {pad_width}")
    h_tiles = pad_width // h_tile
    out_shape = jax.ShapeDtypeStruct((n_blocks * block_rows, pad_width),
                                     jnp.float32)
    out_spec = pl.BlockSpec((block_rows, h_tile),
                            lambda j, k, ob, fi, ev, sa, sb: (ob[k], j))
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,   # out_block, first, evict, a_scale, b_scale
        grid=(h_tiles, n_chunks),
        in_specs=[any_spec, any_spec],  # a_q8, slab_q8 (HBM)
        out_specs=out_spec,
        scratch_shapes=[
            pltpu.VMEM((block_rows, width), jnp.int8),      # coeff tile
            pltpu.VMEM((width, h_tile), jnp.int8),          # landing slab
            pltpu.VMEM((block_rows, h_tile), jnp.float32),  # hash pad
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    kernel = functools.partial(_kernel_q8, block_rows=block_rows,
                               width=width, h_tile=h_tile)
    return pl.pallas_call(kernel, grid_spec=grid_spec, out_shape=out_shape,
                          interpret=interpret)(
        out_block, first, evict, a_scale.astype(jnp.float32),
        slab_scale.astype(jnp.float32), a_q8, slab_q8)
