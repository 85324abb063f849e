"""Gustavson SpMM Pallas TPU kernel — the paper's MMH4/HACC pipeline as a
VMEM-tiled gather–multiply–accumulate with rolling eviction.

TPU adaptation of the NeuraChip dataflow (DESIGN.md §2.1), operating on the
operand-deduplicated chunk layout (``repro.sparse.graph.pack_dedup_chunks``):

* **multiply stage** (NeuraCore ≙ MMH4): a chunk's distinct source rows of X
  are brought into a ``(width, d_tile)`` VMEM landing buffer.  Under
  ``gather="dma"`` the kernel row-gathers them straight from X in HBM — in
  waves of ``group`` rows, one ``pltpu.make_async_copy`` + semaphore per
  landing lane, every wave in flight before the first wait (a pipeline as
  deep as the landing buffer).  Under ``gather="stream"`` the operands were
  pre-gathered by one vectorized XLA gather into a chunk-contiguous slab,
  and the kernel lands each chunk's slab with a single strided DMA.  Each
  edge value sits in a dense ``(block_rows, width)`` **coefficient tile** —
  the chunk's stacked one-hot matrices — so the whole chunk folds in one
  MXU matmul: ``contrib = A_chunk @ landing``;
* **accumulate stage** (NeuraMem ≙ HACC): the coefficient tile routes every
  partial product to its destination sublane of the ``(block_rows, d_tile)``
  output tile — the HashPad analogue.  The CAM tag match degenerated into
  the tile's row index at pack time (edges host-sorted by destination row);
* **rolling eviction**: ``remaining[k]`` (the distinct-operand counter)
  bounds the DMA wave loop; once the chunk's last operand lands and folds,
  the tile is evicted.  Oversized blocks were split into several chunks at
  pack time — later chunks *revisit* their output block and accumulate into
  the still-resident tile (``first[k]`` selects overwrite vs accumulate), so
  one power-law hub row never inflates every block's padding.

Layout: grid = ``(d_tiles, n_chunks)`` — the feature axis is tiled so D never
has to fit one VMEM lane-width and large-D models get grid parallelism; the
chunk axis is innermost so chunks of one output block stay consecutive and
the revisited output tile stays resident.  u_cols/remaining/out_block/first
live in SMEM via scalar prefetch — the *output* BlockSpec index map reads
``out_block`` to route each chunk's tile.  The coefficient tiles and X (or
the streamed operand slab) stay in ANY/HBM and are fetched by explicit DMA:
exactly one ``(block_rows, width)`` tile and one chunk's operands per grid
step — never whole arrays (the old layout re-copied the full vals array
every step: O(n_blocks²·nnz_pad) operand traffic).

Compiled for the TPU (the v5e compile rehearsals in
``tests/test_tpu_compile.py``); interpreted on CPU against ref.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import platform

DEFAULT_GROUP = 8        # landing-buffer rows per DMA wave (MMH4 lane count)
MAX_SINGLE_TILE_D = 1024  # auto d_tile: keep one feature tile up to this width
LANES = 128              # TPU lane tile: compiled chunk widths / d_tiles
# one v5e TensorCore's scalar memory, which the scalar-prefetched chunk
# tables share with the compiler's own scalars (hence the reserve)
SMEM_BYTES = 1 << 20
SMEM_RESERVE = 64 << 10


def tables_fit_smem(u_cols) -> bool:
    """Whether a layout's chunk tables fit the SMEM that the compiled kernel
    prefetches them into: ``u_cols`` and at most four 32-bit scalars a
    chunk (``remaining``, ``out_block``, ``first`` and, in ``pallas_q8``,
    the chunk's scale).  The interpreter has no SMEM to fill."""
    if platform.interpret_mode():
        return True
    words = u_cols.size + 4 * u_cols.shape[0]
    return 4 * words <= SMEM_BYTES - SMEM_RESERVE


def _fold(a_ref, first_smem, y_ref, land, k):
    """Accumulate stage: one MXU matmul folds the whole chunk; revisits of
    the same output block accumulate into the still-resident tile."""
    contrib = jax.lax.dot(a_ref[...].astype(land.dtype), land,
                          preferred_element_type=jnp.float32)
    contrib = contrib.astype(y_ref.dtype)
    is_first = first_smem[k] != 0
    y_ref[...] = jnp.where(is_first, contrib, y_ref[...] + contrib)


def _start_a_tile(a_hbm, a_ref, sem, k, block_rows):
    return pltpu.make_async_copy(
        a_hbm.at[pl.dslice(k * block_rows, block_rows), :], a_ref, sem)


def _gather_waves(u_cols_smem, rem_smem, x_hbm, land_ref, sems, j, k, *,
                  group: int, d_tiles: int):
    """Multiply stage: row-gather chunk ``k``'s operands (feature tile
    ``j``) into the landing buffer, every wave in flight before the first
    wait — the pipeline is as deep as the landing buffer.  ``x_hbm`` is the
    ``(N·d_tiles, d_tile)`` row-tile view of X: one operand row-tile is one
    whole row of it, the only single-row DMA Mosaic accepts."""
    n_waves = (rem_smem[k] + group - 1) // group   # rolling-eviction counter
    # zero the landing buffer: lanes no DMA wave touches must fold as exact
    # zeros (the coefficient tile is zero there, but 0·garbage could be NaN)
    land_ref[...] = jnp.zeros_like(land_ref)

    def wave_copies(w):
        return [pltpu.make_async_copy(
                    x_hbm.at[u_cols_smem[k, w * group + t] * d_tiles + j],
                    land_ref.at[w * group + t], sems.at[1 + w * group + t])
                for t in range(group)]

    def start_wave(w, _):
        for c in wave_copies(w):
            c.start()
        return 0

    def wait_wave(w, _):
        for c in wave_copies(w):
            c.wait()
        return 0

    jax.lax.fori_loop(0, n_waves, start_wave, 0)
    jax.lax.fori_loop(0, n_waves, wait_wave, 0)


def _kernel_dma(u_cols_smem, rem_smem, ob_smem, first_smem, a_hbm, x_hbm,
                y_ref, a_ref, land_ref, sems, *, block_rows: int, group: int,
                d_tiles: int):
    j = pl.program_id(0)
    k = pl.program_id(1)
    a_cp = _start_a_tile(a_hbm, a_ref, sems.at[0], k, block_rows)
    a_cp.start()
    _gather_waves(u_cols_smem, rem_smem, x_hbm, land_ref, sems, j, k,
                  group=group, d_tiles=d_tiles)
    a_cp.wait()
    _fold(a_ref, first_smem, y_ref, land_ref[...], k)


def _kernel_stream(u_cols_smem, rem_smem, ob_smem, first_smem, a_hbm,
                   land_hbm, y_ref, a_ref, land_ref, sems, *,
                   block_rows: int, width: int, d_tile: int):
    j = pl.program_id(0)
    k = pl.program_id(1)
    a_cp = _start_a_tile(a_hbm, a_ref, sems.at[0], k, block_rows)
    a_cp.start()
    land_cp = pltpu.make_async_copy(
        land_hbm.at[pl.dslice(k * width, width),
                    pl.dslice(j * d_tile, d_tile)], land_ref, sems.at[1])
    land_cp.start()
    a_cp.wait()
    land_cp.wait()
    _fold(a_ref, first_smem, y_ref, land_ref[...], k)


def _auto_d_tile(d: int, interpret: bool | None = None) -> int:
    """The fewest feature tiles of at most MAX_SINGLE_TILE_D lanes.
    Compiled, each is a multiple of 128 — Mosaic refuses a DMA column slice
    of any other width, even one as wide as the array — so D=602 runs as one
    640-lane tile.  The interpreter takes any width: one tile is exactly D,
    a split rounds to 8 lanes."""
    n_tiles = -(-d // MAX_SINGLE_TILE_D)
    interpreted = platform.interpret_mode(interpret)
    if interpreted and n_tiles == 1:
        return d
    unit = 8 if interpreted else LANES
    per_tile = -(-d // n_tiles)
    return -(-per_tile // unit) * unit


def _resolve(dtype, d: int, width: int, d_tile, gather: str, interpret):
    """Concrete launch choices, made outside jit so a compiled kernel is
    keyed by them and never by ``None``/``"auto"``.

    ``"auto"`` gathers by DMA on a TPU for 32-bit operands.  A packed dtype
    tiles as (16, 128) (bf16) or (32, 128) (int8), so one operand row is
    not a whole tile row and Mosaic refuses its DMA: those stream.  The
    compiled DMA path lands 128-lane feature tiles (a wider one-row DMA is
    refused), and compiled chunks must be whole 128-lane tiles wide — the
    packer emits them so on a TPU (``sparse.graph.chunk_width_multiple``).
    """
    interpret = platform.interpret_mode(interpret)
    if not interpret and width % LANES:
        raise ValueError(
            f"chunk width {width} is not a multiple of {LANES}: the "
            f"compiled kernel needs whole lane tiles (pack with "
            f"width_multiple={LANES})")
    if gather == "auto":
        gather = ("dma" if platform.on_tpu()
                  and jnp.dtype(dtype).itemsize == 4 else "stream")
    if d_tile is None:
        d_tile = (LANES if gather == "dma" and not interpret
                  else _auto_d_tile(d, interpret))
    return d_tile, gather, interpret


def _pad_operands(u_cols, a, x, *, group: int, d_tile: int, gather: str):
    """Pad the chunk width to whole DMA waves of ``group`` (``"dma"``) and
    X to whole feature tiles.  Under ``gather="dma"`` X is returned as its
    ``(N·d_tiles, d_tile)`` row-tile view."""
    width = u_cols.shape[1]
    lane_pad = (-width) % group if gather == "dma" else 0
    if lane_pad:
        u_cols = jnp.pad(u_cols, ((0, 0), (0, lane_pad)))
        a = jnp.pad(a, ((0, 0), (0, lane_pad)))
    n, d = x.shape
    d_pad = (-d) % d_tile
    if d_pad:
        x = jnp.pad(x, ((0, 0), (0, d_pad)))
    d_tiles = (d + d_pad) // d_tile
    if gather == "dma":
        x = x.reshape(n * d_tiles, d_tile)
    else:
        # multiply-stage gather hoisted to one vectorized XLA gather; each
        # chunk's operand slab is contiguous → one strided DMA per step
        x = jnp.take(x, u_cols.reshape(-1), axis=0)
    return u_cols, a, x, d_tiles


def spmm_dedup_chunks(u_cols: jax.Array, remaining: jax.Array,
                      out_block: jax.Array, first: jax.Array, a: jax.Array,
                      x: jax.Array, *, block_rows: int, n_blocks: int,
                      group: int = DEFAULT_GROUP, d_tile: int | None = None,
                      gather: str = "auto",
                      interpret: bool | None = None) -> jax.Array:
    """Chunked-dedup Gustavson SpMM:  y = A @ X on the packed layout.

    u_cols: (n_chunks, width) int32; remaining/out_block/first: (n_chunks,)
    int32; a: (n_chunks·block_rows, width) f32; x: (N, D) →
    (n_blocks·block_rows, D) in ``x.dtype`` (f32 accumulation per chunk).

    ``gather="dma"`` row-gathers X inside the kernel (explicit async copies —
    the TPU path, no operand materialization); ``"stream"`` pre-gathers the
    operands with one vectorized XLA gather and slab-DMAs each chunk (the
    fast path under interpret, where per-row copy emulation dominates);
    ``"auto"`` picks by backend and dtype (``_resolve``).  ``interpret``
    defaults to the platform policy (``core.platform.interpret_mode``).
    """
    d_tile, gather, interpret = _resolve(x.dtype, x.shape[1], u_cols.shape[1],
                                         d_tile, gather, interpret)
    return _spmm_dedup_chunks(u_cols, remaining, out_block, first, a, x,
                              block_rows=block_rows, n_blocks=n_blocks,
                              group=group, d_tile=d_tile, gather=gather,
                              interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block_rows", "n_blocks",
                                             "group", "d_tile", "gather",
                                             "interpret"))
def _spmm_dedup_chunks(u_cols, remaining, out_block, first, a, x, *,
                       block_rows: int, n_blocks: int, group: int,
                       d_tile: int, gather: str, interpret: bool):
    d = x.shape[1]
    u_cols, a, operand, d_tiles = _pad_operands(
        u_cols, a, x, group=group, d_tile=d_tile, gather=gather)
    n_chunks, width = u_cols.shape
    out_shape = jax.ShapeDtypeStruct((n_blocks * block_rows,
                                      d_tiles * d_tile), x.dtype)
    # grid: feature tiles outer, chunks inner — chunks of one output block
    # stay consecutive, so the revisited output tile is still resident
    out_spec = pl.BlockSpec((block_rows, d_tile),
                            lambda j, k, uc, re, ob, fi: (ob[k], j))
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    if gather == "dma":
        kernel = functools.partial(_kernel_dma, block_rows=block_rows,
                                   group=group, d_tiles=d_tiles)
        n_sems = 1 + width
    else:
        kernel = functools.partial(_kernel_stream, block_rows=block_rows,
                                   width=width, d_tile=d_tile)
        n_sems = 2
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,        # u_cols, remaining, out_block, first
        grid=(d_tiles, n_chunks),
        in_specs=[any_spec, any_spec],   # a, X row tiles / operand slab
        out_specs=out_spec,
        scratch_shapes=[
            pltpu.VMEM((block_rows, width), a.dtype),   # coeff tile
            pltpu.VMEM((width, d_tile), x.dtype),       # landing buffer
            pltpu.SemaphoreType.DMA((n_sems,)),
        ],
    )
    y = pl.pallas_call(kernel, grid_spec=grid_spec, out_shape=out_shape,
                       interpret=interpret)(
        u_cols, remaining, out_block, first, a, operand)
    return y[:, :d] if d_tiles * d_tile != d else y


# ---------------------------------------------------------------------------
# int8 quantized-tile mode (pallas_q8) — same dataflow, 4× fewer operand bytes
# ---------------------------------------------------------------------------
#
# The coefficient tiles and the X operands move through HBM/DMA/VMEM as int8
# (per-chunk scale for A, per-feature-tile scale for X — see
# ``repro.sparse.quantize``).  The fold upcasts to f32 *inside* the MXU
# matmul: int8 magnitudes ≤ 127 make every partial product and every chunk
# sum (< 127·127·width < 2²⁴) exactly representable, so f32 accumulation is
# bit-identical to an int32 accumulate.  Both scales are constant over one
# grid step's contraction, so dequantization is a single scalar multiply of
# the contribution at fold time — rescale-at-eviction, not per-element.


def _fold_q8(a_ref, first_smem, ascale_smem, xscale_smem, y_ref, land, j, k):
    contrib = jax.lax.dot(a_ref[...].astype(jnp.float32),
                          land.astype(jnp.float32),
                          preferred_element_type=jnp.float32)
    contrib = contrib * (ascale_smem[k] * xscale_smem[j])
    is_first = first_smem[k] != 0
    y_ref[...] = jnp.where(is_first, contrib, y_ref[...] + contrib)


def _kernel_dma_q8(u_cols_smem, rem_smem, ob_smem, first_smem, ascale_smem,
                   xscale_smem, a_hbm, x_hbm, y_ref, a_ref, land_ref, sems, *,
                   block_rows: int, group: int, d_tiles: int):
    j = pl.program_id(0)
    k = pl.program_id(1)
    a_cp = _start_a_tile(a_hbm, a_ref, sems.at[0], k, block_rows)
    a_cp.start()
    _gather_waves(u_cols_smem, rem_smem, x_hbm, land_ref, sems, j, k,
                  group=group, d_tiles=d_tiles)
    a_cp.wait()
    _fold_q8(a_ref, first_smem, ascale_smem, xscale_smem, y_ref,
             land_ref[...], j, k)


def _kernel_stream_q8(u_cols_smem, rem_smem, ob_smem, first_smem, ascale_smem,
                      xscale_smem, a_hbm, land_hbm, y_ref, a_ref, land_ref,
                      sems, *, block_rows: int, width: int, d_tile: int):
    j = pl.program_id(0)
    k = pl.program_id(1)
    a_cp = _start_a_tile(a_hbm, a_ref, sems.at[0], k, block_rows)
    a_cp.start()
    land_cp = pltpu.make_async_copy(
        land_hbm.at[pl.dslice(k * width, width),
                    pl.dslice(j * d_tile, d_tile)], land_ref, sems.at[1])
    land_cp.start()
    a_cp.wait()
    land_cp.wait()
    _fold_q8(a_ref, first_smem, ascale_smem, xscale_smem, y_ref,
             land_ref[...], j, k)


def spmm_dedup_chunks_q8(u_cols: jax.Array, remaining: jax.Array,
                         out_block: jax.Array, first: jax.Array,
                         a_q8: jax.Array, a_scale: jax.Array,
                         x_q8: jax.Array, x_scale: jax.Array, *,
                         block_rows: int, n_blocks: int,
                         group: int = DEFAULT_GROUP,
                         d_tile: int | None = None, gather: str = "auto",
                         interpret: bool | None = None) -> jax.Array:
    """int8-operand Gustavson SpMM:  y ≈ A @ X, f32 output.

    a_q8: (n_chunks·block_rows, width) int8 with a_scale (n_chunks,) f32;
    x_q8: (N, D) int8 with x_scale (ceil(D/d_tile),) f32 — ``d_tile`` MUST
    match the tile width the scales were computed with
    (``quantize_feature_tiles(x, d_tile)``; ``None`` means
    ``_auto_d_tile(D, interpret)``), else the rescale is wrong.
    Output is always f32 (cross-chunk accumulation of rescaled folds).
    """
    d = x_q8.shape[1]
    if d_tile is None:
        d_tile = _auto_d_tile(d, interpret)
    d_tile, gather, interpret = _resolve(x_q8.dtype, d, u_cols.shape[1],
                                         d_tile, gather, interpret)
    d_tiles = -(-d // d_tile)
    if x_scale.shape[0] != d_tiles:
        raise ValueError(
            f"x_scale has {x_scale.shape[0]} tiles for d_tiles={d_tiles}; "
            f"quantize with the same d_tile the kernel runs with")
    return _spmm_dedup_chunks_q8(
        u_cols, remaining, out_block, first, a_q8, a_scale, x_q8, x_scale,
        block_rows=block_rows, n_blocks=n_blocks, group=group, d_tile=d_tile,
        gather=gather, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block_rows", "n_blocks",
                                             "group", "d_tile", "gather",
                                             "interpret"))
def _spmm_dedup_chunks_q8(u_cols, remaining, out_block, first, a_q8, a_scale,
                          x_q8, x_scale, *, block_rows: int, n_blocks: int,
                          group: int, d_tile: int, gather: str,
                          interpret: bool):
    d = x_q8.shape[1]
    u_cols, a_q8, operand, d_tiles = _pad_operands(
        u_cols, a_q8, x_q8, group=group, d_tile=d_tile, gather=gather)
    n_chunks, width = u_cols.shape
    out_shape = jax.ShapeDtypeStruct((n_blocks * block_rows,
                                      d_tiles * d_tile), jnp.float32)
    out_spec = pl.BlockSpec((block_rows, d_tile),
                            lambda j, k, uc, re, ob, fi, sa, sx: (ob[k], j))
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    if gather == "dma":
        kernel = functools.partial(_kernel_dma_q8, block_rows=block_rows,
                                   group=group, d_tiles=d_tiles)
        n_sems = 1 + width
    else:
        kernel = functools.partial(_kernel_stream_q8, block_rows=block_rows,
                                   width=width, d_tile=d_tile)
        n_sems = 2
    grid_spec = pltpu.PrefetchScalarGridSpec(
        # u_cols, remaining, out_block, first, a_scale, x_scale
        num_scalar_prefetch=6,
        grid=(d_tiles, n_chunks),
        in_specs=[any_spec, any_spec],           # a_q8, X / operand slab
        out_specs=out_spec,
        scratch_shapes=[
            pltpu.VMEM((block_rows, width), jnp.int8),   # coeff tile
            pltpu.VMEM((width, d_tile), jnp.int8),       # landing buffer
            pltpu.SemaphoreType.DMA((n_sems,)),
        ],
    )
    y = pl.pallas_call(kernel, grid_spec=grid_spec, out_shape=out_shape,
                       interpret=interpret)(
        u_cols, remaining, out_block, first,
        a_scale.astype(jnp.float32), x_scale.astype(jnp.float32),
        a_q8, operand)
    return y[:, :d] if d_tiles * d_tile != d else y


def spmm_blocked_ell(cols, row_local, vals, remaining, x,
                     block_rows: int = 8, interpret: bool | None = None,
                     group: int = DEFAULT_GROUP, d_tile: int | None = None,
                     gather: str = "auto") -> jax.Array:
    """Per-lane blocked-ELL compatibility entry (host-side inputs only).

    Repacks the lane layout into dedup chunks (host, per call — use the plan
    layer to pack once) and runs the kernel.  Kept so existing call sites and
    the ref oracle's layout contract stay valid.
    """
    import numpy as np
    cols = np.asarray(cols)
    row_local = np.asarray(row_local)
    vals = np.asarray(vals)
    remaining = np.asarray(remaining)
    n_blocks, nnz_pad = cols.shape
    lane = np.arange(nnz_pad)[None, :]
    live = lane < remaining[:, None]
    b_idx = np.nonzero(live)[0]
    rows_g = row_local[live] + b_idx * block_rows
    from repro.sparse.graph import pack_dedup_chunks
    ch = pack_dedup_chunks(rows_g, cols[live], vals[live],
                           n_blocks * block_rows, int(x.shape[0]),
                           block_rows=block_rows)
    return spmm_dedup_chunks(
        jnp.asarray(ch.u_cols), jnp.asarray(ch.remaining),
        jnp.asarray(ch.out_block), jnp.asarray(ch.first), jnp.asarray(ch.a),
        x, block_rows=block_rows, n_blocks=n_blocks, group=group,
        d_tile=d_tile, gather=gather, interpret=interpret)
