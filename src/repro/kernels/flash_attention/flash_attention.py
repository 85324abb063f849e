"""Causal flash attention Pallas TPU kernel (fwd) — the LM archs' prefill
hot path (not a paper contribution; see DESIGN.md §2.1).

Classic two-level blocking: grid = (batch·heads, q_blocks); the kv loop runs
inside the kernel with the online-softmax running (m, l, acc) state held in
VMEM scratch — the same "fold partial results the moment they are complete"
discipline as the paper's rolling eviction, applied to softmax partials.
Causal masking skips fully-masked kv blocks via ``pl.when`` on the block
index, so the kernel does the ~S²/2 useful work rather than S².
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import platform


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
            *, block_q: int, block_k: int, seq_len: int, scale: float):
    qi = pl.program_id(1)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, -1e30)
    l_ref[...] = jnp.zeros_like(l_ref)
    n_kb = seq_len // block_k

    q = q_ref[0].astype(jnp.float32) * scale            # (block_q, d)
    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_k), 0)

    def kv_block(ki, _):
        @pl.when(ki * block_k <= qi * block_q + block_q - 1)   # causal skip
        def _():
            k = k_ref[0, pl.ds(ki * block_k, block_k), :].astype(
                jnp.float32)
            v = v_ref[0, pl.ds(ki * block_k, block_k), :].astype(
                jnp.float32)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            k_pos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, -1e30)
            m_prev = m_ref[:, 0]
            m_new = jnp.maximum(m_prev, s.max(axis=1))
            p = jnp.exp(s - m_new[:, None])
            corr = jnp.exp(m_prev - m_new)
            l_ref[:, 0] = l_ref[:, 0] * corr + p.sum(axis=1)
            acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[:, 0] = m_new
        return 0

    jax.lax.fori_loop(0, n_kb, kv_block, 0)
    o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[:, 0], 1e-30)[:, None]
                ).astype(o_ref.dtype)


@platform.resolves_interpret
@functools.partial(jax.jit,
                   static_argnames=("block_q", "block_k", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    block_q: int = 256, block_k: int = 256,
                    *, interpret: bool) -> jax.Array:
    """q/k/v: (BH, S, d) — batch and heads pre-flattened, kv pre-repeated to
    full heads (GQA repeat happens in the caller).  Causal.  → (BH, S, d)."""
    bh, s, d = q.shape
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    assert s % block_q == 0 and s % block_k == 0
    scale = 1.0 / math.sqrt(d)
    kernel = functools.partial(_kernel, block_q=block_q, block_k=block_k,
                               seq_len=s, scale=scale)
    return pl.pallas_call(
        kernel,
        grid=(bh, s // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, s, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, s, d), lambda b, i: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
