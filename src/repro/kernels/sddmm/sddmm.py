"""SDDMM Pallas kernel — per-edge row-pair dot products (GAT score stage).

score[e] = Σ_d  X[src[e], d] · Y[dst[e], d]

Same NeuraCore-style decoupled gather as the Gustavson kernel: src/dst indices
are scalar-prefetched to SMEM, the two operand rows are DMA'd from HBM into
double-buffered VMEM slots, and the dot is one VPU reduction per edge.  Edges
are processed in blocks of ``edge_block`` per grid step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import platform

N_SLOTS = 2


def _kernel(src_smem, dst_smem, x_hbm, y_hbm, out_ref,
            xs_ref, ys_ref, sems_x, sems_y, *, edge_block: int):
    b = pl.program_id(0)

    def start(i):
        s = i % N_SLOTS
        pltpu.make_async_copy(x_hbm.at[src_smem[b, i]], xs_ref.at[s],
                              sems_x.at[s]).start()
        pltpu.make_async_copy(y_hbm.at[dst_smem[b, i]], ys_ref.at[s],
                              sems_y.at[s]).start()

    start(0)

    def body(i, _):
        s = i % N_SLOTS
        pltpu.make_async_copy(x_hbm.at[src_smem[b, i]], xs_ref.at[s],
                              sems_x.at[s]).wait()
        pltpu.make_async_copy(y_hbm.at[dst_smem[b, i]], ys_ref.at[s],
                              sems_y.at[s]).wait()

        @pl.when(i + 1 < edge_block)
        def _():
            start(i + 1)

        dot = jnp.sum(xs_ref[s, :] * ys_ref[s, :])
        out_ref[pl.ds(i, 1)] = dot[None]
        return 0

    jax.lax.fori_loop(0, edge_block, body, 0)


@platform.resolves_interpret
@functools.partial(jax.jit, static_argnames=("edge_block", "interpret"))
def sddmm(src: jax.Array, dst: jax.Array, x: jax.Array, y: jax.Array,
          edge_block: int = 256, *, interpret: bool) -> jax.Array:
    """src/dst: (E,) int32 (E % edge_block == 0); x/y: (N, D).  → (E,) f32."""
    e = src.shape[0]
    assert e % edge_block == 0
    n_blocks = e // edge_block
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_blocks,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((edge_block,), lambda b, *_: (b,)),
        scratch_shapes=[
            pltpu.VMEM((N_SLOTS, x.shape[1]), jnp.float32),
            pltpu.VMEM((N_SLOTS, y.shape[1]), jnp.float32),
            pltpu.SemaphoreType.DMA((N_SLOTS,)),
            pltpu.SemaphoreType.DMA((N_SLOTS,)),
        ],
    )
    kernel = functools.partial(_kernel, edge_block=edge_block)
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((e,), jnp.float32),
        interpret=interpret,
    )(src.reshape(n_blocks, edge_block), dst.reshape(n_blocks, edge_block),
      x, y)
