"""Jit'd public wrapper for the EmbeddingBag kernel."""
from __future__ import annotations

import jax

from repro.kernels.embedding_bag.embedding_bag import embedding_bag
from repro.kernels.embedding_bag.ref import embedding_bag_ref


def lookup(ids, table, batch_tile: int = 8, use_kernel: bool = True):
    """ids (B, F, M) → (B, F, D)."""
    b, f, _ = ids.shape
    if use_kernel:
        out = embedding_bag(ids, table, batch_tile=batch_tile)
    else:
        out = embedding_bag_ref(ids, table)
    return out.reshape(b, f, -1)
