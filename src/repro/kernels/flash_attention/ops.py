"""Jit'd wrapper: (B, S, H, hd) GQA attention → flash kernel layout."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.flash_attention import flash_attention
from repro.kernels.flash_attention.ref import causal_attention_ref


def mha_causal(q, k, v, block_q: int = 256, block_k: int = 256,
               use_kernel: bool = True):
    """q: (B, S, H, hd); k/v: (B, S, KV, hd) → (B, S, H, hd)."""
    b, s, h, hd = q.shape
    g = h // k.shape[2]
    if g > 1:
        k = jnp.repeat(k, g, axis=2)
        v = jnp.repeat(v, g, axis=2)
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, s, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(b * h, s, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(b * h, s, hd)
    if use_kernel:
        of = flash_attention(qf, kf, vf, block_q=block_q, block_k=block_k)
    else:
        of = causal_attention_ref(qf, kf, vf)
    return of.reshape(b, h, s, hd).transpose(0, 2, 1, 3)
