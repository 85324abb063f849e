"""Jit'd public wrapper for the SDDMM kernel."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.sddmm.ref import sddmm_ref
from repro.kernels.sddmm.sddmm import sddmm


def edge_scores(src, dst, x, y, edge_block: int = 256, use_kernel: bool = True):
    e = src.shape[0]
    pad = (-e) % edge_block
    if pad:
        src = jnp.pad(src, (0, pad))
        dst = jnp.pad(dst, (0, pad))
    if use_kernel:
        out = sddmm(src, dst, x, y, edge_block=edge_block)
    else:
        out = sddmm_ref(src, dst, x, y)
    return out[:e]
