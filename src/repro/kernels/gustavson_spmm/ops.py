"""Public wrappers for the Pallas Gustavson SpMM kernel.

``spmm`` — COO → dedup-chunk layout → kernel, packing host-side once per
call.  ``spmm_dedup_grad`` — the kernel with a custom VJP so it is usable as
a production *training* path: the forward pass runs the Pallas pipeline and
the backward pass runs **the same Pallas kernel** on the transpose chunk
layout (dX = Aᵀ·dY — no plain-JAX segment reduction anywhere), while the
coefficient-tile cotangent dA comes from the grouped operand gather the
forward already performs (dA[k] = dY_block(k) · landing(k)ᵀ).  Gradients for
traced edge values (GAT attention) flow through the device scatter that
builds the coefficient tiles, outside this op.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import platform
from repro.kernels.gustavson_spmm.gustavson_spmm import (
    _auto_d_tile, spmm_dedup_chunks, spmm_dedup_chunks_q8)


def _float0_zeros(a: jax.Array):
    """Cotangent for integer-valued primals (JAX convention: float0)."""
    return np.zeros(a.shape, dtype=jax.dtypes.float0)


# statics = (block_rows, n_blocks, n_t_blocks, group, d_tile, gather,
#            interpret)
@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _spmm_dedup_ad(statics, u_cols, remaining, out_block, first, a,
                   t_u_cols, t_remaining, t_out_block, t_first, a_t, x):
    block_rows, n_blocks, _, group, d_tile, gather, interpret = statics
    return spmm_dedup_chunks(u_cols, remaining, out_block, first, a, x,
                             block_rows=block_rows, n_blocks=n_blocks,
                             group=group, d_tile=d_tile, gather=gather,
                             interpret=interpret)


def _ad_fwd(statics, u_cols, remaining, out_block, first, a,
            t_u_cols, t_remaining, t_out_block, t_first, a_t, x):
    y = _spmm_dedup_ad(statics, u_cols, remaining, out_block, first, a,
                       t_u_cols, t_remaining, t_out_block, t_first, a_t, x)
    return y, (u_cols, remaining, out_block, first,
               t_u_cols, t_remaining, t_out_block, t_first, a_t, x)


def _ad_bwd(statics, res, dy):
    (u_cols, remaining, out_block, first,
     t_u_cols, t_remaining, t_out_block, t_first, a_t, x) = res
    block_rows, n_blocks, n_t_blocks, group, d_tile, gather, interp = statics
    # dX = Aᵀ·dY through the same Pallas kernel on the transpose layout
    dx_full = spmm_dedup_chunks(t_u_cols, t_remaining, t_out_block, t_first,
                                a_t, dy, block_rows=block_rows,
                                n_blocks=n_t_blocks, group=group,
                                d_tile=d_tile, gather=gather,
                                interpret=interp)
    dx = dx_full[: x.shape[0]].astype(x.dtype)
    # dA[k] = dY_block(k) · landingᵀ(k) — the forward's operand gather again
    n_chunks, width = u_cols.shape
    d = x.shape[1]
    land = jnp.take(x, u_cols.reshape(-1), axis=0).astype(jnp.float32)
    land = land.reshape(n_chunks, width, d)
    dyb = jnp.take(dy.reshape(n_blocks, block_rows, d), out_block, axis=0)
    da = jnp.einsum("krd,kud->kru", dyb.astype(jnp.float32), land)
    da = da.reshape(n_chunks * block_rows, width)
    # a_t does not enter the primal value — its cotangent is exactly zero
    # (traced edge values reach it through the scatter outside this op)
    return (_float0_zeros(u_cols), _float0_zeros(remaining),
            _float0_zeros(out_block), _float0_zeros(first), da,
            _float0_zeros(t_u_cols), _float0_zeros(t_remaining),
            _float0_zeros(t_out_block), _float0_zeros(t_first),
            jnp.zeros_like(a_t), dx)


_spmm_dedup_ad.defvjp(_ad_fwd, _ad_bwd)


def spmm_dedup_grad(u_cols, remaining, out_block, first, a,
                    t_u_cols, t_remaining, t_out_block, t_first, a_t, x, *,
                    block_rows: int, n_blocks: int, n_t_blocks: int,
                    group: int = 8, d_tile=None, gather: str = "auto",
                    interpret=None):
    """Differentiable chunked-dedup SpMM (grads flow to ``a``, ``a_t`` —
    i.e. to edge values through the coefficient scatters — and ``x``)."""
    statics = (block_rows, n_blocks, n_t_blocks, group, d_tile, gather,
               platform.interpret_mode(interpret))
    return _spmm_dedup_ad(statics, u_cols, remaining, out_block, first, a,
                          t_u_cols, t_remaining, t_out_block, t_first, a_t,
                          x)


# ---------------------------------------------------------------------------
# pallas_q8: straight-through custom VJP — int8 forward, f32 backward
# ---------------------------------------------------------------------------
#
# The forward pass runs the int8-operand kernel (quantizing X per feature
# tile in-trace; the coefficient tiles arrive pre-quantized or are quantized
# here from the f32 tiles).  The backward pass is the straight-through
# estimator: the f32 machinery of ``_ad_bwd`` unchanged — dX through the f32
# transpose-layout kernel, dA from the f32 operand gather — so only the
# incoming cotangent (which saw the quantized forward value) carries
# quantization error, never the gradient operators themselves.

@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _spmm_dedup_q8_ad(statics, u_cols, remaining, out_block, first, a,
                      a_q8, a_scale, t_u_cols, t_remaining, t_out_block,
                      t_first, a_t, x):
    block_rows, n_blocks, _, group, d_tile, gather, interpret = statics
    from repro.sparse.quantize import quantize_feature_tiles
    dt = d_tile if d_tile is not None else _auto_d_tile(x.shape[1],
                                                        interpret)
    x_q8, x_scale = quantize_feature_tiles(x, dt)
    y = spmm_dedup_chunks_q8(u_cols, remaining, out_block, first, a_q8,
                             a_scale, x_q8, x_scale, block_rows=block_rows,
                             n_blocks=n_blocks, group=group, d_tile=dt,
                             gather=gather, interpret=interpret)
    return y.astype(x.dtype)


def _q8_ad_fwd(statics, u_cols, remaining, out_block, first, a, a_q8,
               a_scale, t_u_cols, t_remaining, t_out_block, t_first, a_t, x):
    y = _spmm_dedup_q8_ad(statics, u_cols, remaining, out_block, first, a,
                          a_q8, a_scale, t_u_cols, t_remaining, t_out_block,
                          t_first, a_t, x)
    return y, (u_cols, remaining, out_block, first,
               t_u_cols, t_remaining, t_out_block, t_first, a_t, x,
               a_q8, a_scale)


def _q8_ad_bwd(statics, res, dy):
    (u_cols, remaining, out_block, first,
     t_u_cols, t_remaining, t_out_block, t_first, a_t, x,
     a_q8, a_scale) = res
    grads = _ad_bwd(statics, (u_cols, remaining, out_block, first,
                              t_u_cols, t_remaining, t_out_block, t_first,
                              a_t, x), dy)
    (d_uc, d_rem, d_ob, d_first, da,
     d_tuc, d_trem, d_tob, d_tfirst, da_t, dx) = grads
    return (d_uc, d_rem, d_ob, d_first, da,
            _float0_zeros(a_q8), jnp.zeros_like(a_scale),
            d_tuc, d_trem, d_tob, d_tfirst, da_t, dx)


_spmm_dedup_q8_ad.defvjp(_q8_ad_fwd, _q8_ad_bwd)


def spmm_dedup_grad_q8(u_cols, remaining, out_block, first, a,
                       t_u_cols, t_remaining, t_out_block, t_first, a_t,
                       x, *, a_q8=None, a_scale=None, block_rows: int,
                       n_blocks: int, n_t_blocks: int, group: int = 8,
                       d_tile=None, gather: str = "auto", interpret=None):
    """Differentiable int8-operand SpMM (straight-through gradients).

    ``a_q8``/``a_scale`` may be baked plan-time tiles; when ``None`` the f32
    tiles ``a`` are quantized per chunk in-trace (for traced edge values).
    """
    if a_q8 is None:
        from repro.sparse.quantize import quantize_chunk_tiles
        a_q8, a_scale = quantize_chunk_tiles(a, u_cols.shape[0])
    statics = (block_rows, n_blocks, n_t_blocks, group, d_tile, gather,
               platform.interpret_mode(interpret))
    return _spmm_dedup_q8_ad(statics, u_cols, remaining, out_block, first,
                             a, a_q8, a_scale, t_u_cols, t_remaining,
                             t_out_block, t_first, a_t, x)


def spmm(rows: np.ndarray, cols: np.ndarray, vals: np.ndarray, x,
         n_rows: int, block_rows: int = 8, use_kernel: bool = True):
    """Y = A @ X.  Packs once (host), then runs the Pallas kernel (compiled
    on TPU, interpret elsewhere).  Returns (n_rows, D) — padding stripped."""
    from repro.kernels.gustavson_spmm.ref import spmm_dedup_chunks_ref
    from repro.sparse.graph import pack_dedup_chunks
    ch = pack_dedup_chunks(rows, cols, vals, n_rows, int(x.shape[0]),
                           block_rows=block_rows)
    args = (jnp.asarray(ch.u_cols), jnp.asarray(ch.remaining),
            jnp.asarray(ch.out_block), jnp.asarray(ch.first),
            jnp.asarray(ch.a))
    n_blocks = ch.n_blocks
    if use_kernel:
        y = spmm_dedup_chunks(*args, x, block_rows=block_rows,
                              n_blocks=n_blocks)
    else:
        y = spmm_dedup_chunks_ref(args[0], args[2], args[4], x,
                                  block_rows, n_blocks)
    return y[:n_rows]
