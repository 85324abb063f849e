"""EmbeddingBag Pallas kernel — DLRM's hot path as a NeuraChip-style pipeline.

out[b, f] = Σ_m  table[ids[b, f, m]]

Identical dataflow to the Gustavson kernel (EmbeddingBag ≡ SpMM with a
one-hot-bag adjacency): ids are scalar-prefetched, table rows are DMA'd from
HBM into double-buffered slots (multiply stage), and the bag reduction folds
into a VMEM accumulator (accumulate stage) that is evicted once the bag
completes — a bag is a one-row HashPad line whose counter is the bag size.

Grid: one step per (batch-tile); each step walks F·M lookups for
``batch_tile`` samples and writes a (batch_tile, F·D) tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import platform

N_SLOTS = 2


def _kernel(ids_smem, table_hbm, out_ref, acc_ref, slot_ref, sems,
            *, batch_tile: int, n_fields: int, bag: int):
    t = pl.program_id(0)
    n_look = batch_tile * n_fields * bag

    def idx(i):
        # i enumerates (sample, field, m) row-major within this tile
        return ids_smem[t, i // (n_fields * bag),
                        (i // bag) % n_fields, i % bag]

    def start(i):
        pltpu.make_async_copy(table_hbm.at[idx(i)], slot_ref.at[i % N_SLOTS],
                              sems.at[i % N_SLOTS]).start()

    start(0)

    def body(i, _):
        s = i % N_SLOTS
        pltpu.make_async_copy(table_hbm.at[idx(i)], slot_ref.at[s],
                              sems.at[s]).wait()

        @pl.when(i + 1 < n_look)
        def _():
            start(i + 1)

        b_loc = i // (n_fields * bag)
        f = (i // bag) % n_fields
        m = i % bag

        @pl.when(m == 0)                      # fresh bag → reset accumulator
        def _():
            acc_ref[pl.ds(0, 1), :] = jnp.zeros_like(slot_ref[s, :])[None]

        acc_ref[pl.ds(0, 1), :] = (acc_ref[pl.ds(0, 1), :]
                                   + slot_ref[s, :][None])

        @pl.when(m == bag - 1)                # bag complete → evict
        def _():
            d = slot_ref.shape[1]
            out_ref[pl.ds(b_loc, 1), pl.ds(f * d, d)] = acc_ref[
                pl.ds(0, 1), :]
        return 0

    jax.lax.fori_loop(0, n_look, body, 0)


@platform.resolves_interpret
@functools.partial(jax.jit, static_argnames=("batch_tile", "interpret"))
def embedding_bag(ids: jax.Array, table: jax.Array, batch_tile: int = 8,
                  *, interpret: bool) -> jax.Array:
    """ids: (B, F, M) int32 (B % batch_tile == 0); table: (V, D).
    → (B, F·D) f32 (reshape to (B, F, D) outside)."""
    b, f, m = ids.shape
    assert b % batch_tile == 0
    n_tiles = b // batch_tile
    d = table.shape[1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_tiles,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((batch_tile, f * d), lambda t, *_: (t, 0)),
        scratch_shapes=[
            pltpu.VMEM((1, d), jnp.float32),
            pltpu.VMEM((N_SLOTS, d), jnp.float32),
            pltpu.SemaphoreType.DMA((N_SLOTS,)),
        ],
    )
    kernel = functools.partial(_kernel, batch_tile=batch_tile, n_fields=f,
                               bag=m)
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, f * d), jnp.float32),
        interpret=interpret,
    )(ids.reshape(n_tiles, batch_tile, f, m), table)
