"""Public wrapper for the SpGEMM hash-pad kernel.

Compiled on TPU, interpret elsewhere (same policy as the Gustavson SpMM
kernel).  No custom VJP: the SpGEMM numeric phase computes graph *structure
values* (Â², coarsened adjacency) once at plan/setup time, outside any
gradient tape — the training path differentiates through the downstream
SpMM, not through the structure precomputation.
"""
from __future__ import annotations

import jax

from repro.kernels.spgemm_pad.ref import spgemm_hashpad_ref
from repro.kernels.spgemm_pad.spgemm_pad import (spgemm_hashpad,
                                                 spgemm_hashpad_q8)


def hashpad_accumulate(out_block, first, evict, a, slab, *, block_rows: int,
                       n_blocks: int, pad_width: int,
                       h_tile: int | None = None, interpret=None,
                       use_kernel: bool = True) -> jax.Array:
    """(n_blocks·block_rows, pad_width) hash-pad accumulation of A@B."""
    if not use_kernel:
        return spgemm_hashpad_ref(out_block, a, slab, block_rows, n_blocks,
                                  pad_width)
    return spgemm_hashpad(out_block, first, evict, a, slab,
                          block_rows=block_rows, n_blocks=n_blocks,
                          pad_width=pad_width, h_tile=h_tile,
                          interpret=interpret)


def hashpad_accumulate_q8(out_block, first, evict, a_q8, a_scale, slab_q8,
                          slab_scale, *, block_rows: int, n_blocks: int,
                          pad_width: int, h_tile: int | None = None,
                          interpret=None) -> jax.Array:
    """int8-operand hash-pad accumulation (pallas_q8 SpGEMM executor)."""
    return spgemm_hashpad_q8(out_block, first, evict, a_q8, a_scale,
                             slab_q8, slab_scale, block_rows=block_rows,
                             n_blocks=n_blocks, pad_width=pad_width,
                             h_tile=h_tile,
                             interpret=interpret)
