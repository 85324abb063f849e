"""Per-tile symmetric int8 quantization for the Pallas kernel operands.

The ``pallas_q8`` fast path (DESIGN.md §12) moves the Gustavson kernels'
coefficient tiles and feature/slab operands as int8 — 4× fewer HBM bytes
than f32, which is the whole NeuraChip bandwidth argument — and rescales
inside the kernel at fold time.  This module owns the quantization scheme
so the plan layer, both kernels, the backends, and the parity gates agree
on one contract:

* **coefficient tiles / B slab** — one scale per *dedup chunk* (the tile a
  single grid step lands): ``scale_a[k] = max|A_tile_k| / 127``.  Constant
  over the whole MXU contraction of chunk ``k``, so it factors out of the
  matmul exactly;
* **feature rows** — one scale per *feature tile* (the ``d_tile``-wide
  column block a grid step covers): ``scale_x[j] = max|X[:, jd:(j+1)d]| /
  127``.  Per-row scales would vary along the contraction axis and could
  not be factored out; per-column-tile scales are constant over both the
  contraction and the tile's output columns;
* all-zero tiles quantize with ``scale = 1.0`` (exact zeros; the same
  ``scale == 0`` guard as ``optim.compression.quantize_int8``);
* the kernels fold ``int8 × int8`` products with **f32 accumulation**:
  int8 magnitudes are ≤ 127, every product ≤ 16129 and every chunk sum ≤
  127·127·width < 2²⁴, all exactly representable in f32 — so the f32 MXU
  accumulation is bit-identical to an int32 accumulate, and the only
  inexactness in the whole path is the quantization rounding itself.

That last property is what makes the **scale-derived error bound** below
rigorous: with per-entry rounding errors ≤ scale/2 and magnitudes ≤
127·scale, each partial product deviates by at most ``127·s_a·s_x`` and a
row of output block ``b`` (feature tile ``j``) by at most

    bound(b, j) = Σ_{k: out_block[k]=b} terms_k · 127 · s_a[k] · s_x[j]

(``terms_k`` = live lanes of chunk ``k``).  ``aggregate_q8_bound`` /
``spgemm_q8_bound`` evaluate the max over (b, j); the quantized parity
tests assert the measured deviation stays under it — the
quantization-aware analogue of the f32 paths' 1e-4 gate, which stays
untouched.  A model forward composes per-layer error through
nonlinearities, so model-level and served q8 outputs are held to a
measured envelope instead (DESIGN.md §12).
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array

Q8_MAX = 127.0


class QuantizedFeatures(NamedTuple):
    """Resident pre-quantized features: int8 rows + per-feature-tile scales.

    The inference operating point of the ``pallas_q8`` backend — features
    quantize ONCE (at load/store time, ``quantize_features``) instead of
    per aggregate call, so the fast path pays only the int8 gather + the
    kernel.  A NamedTuple is a pytree, so it passes through ``jax.jit``
    boundaries like an array; the backend validates the scale vector's
    length against the kernel's feature-tile count (the d_tile the scales
    were computed with must match the plan's).
    """

    q8: Array          # (N, D) int8
    scale: Array       # (ceil(D / d_tile),) f32


def quantize_features(x: Array, d_tile: int) -> "QuantizedFeatures":
    """One-time feature quantization for the resident fast path — the
    ``d_tile`` must be the kernel's (``plan.ell_d_tile``, or
    ``kernels.gustavson_spmm._auto_d_tile(D)`` when the plan defers)."""
    q8, scale = quantize_feature_tiles(x, d_tile)
    return QuantizedFeatures(q8=q8, scale=scale)


def _safe_scale(maxabs: Array) -> Array:
    """maxabs/127 with the all-zero guard: a zero tile quantizes with
    scale 1.0 so dequantization returns exact zeros (no denormal blow-up)."""
    scale = maxabs / Q8_MAX
    return jnp.where(scale == 0, 1.0, scale).astype(jnp.float32)


def quantize_chunk_tiles(a: Array, n_chunks: int) -> Tuple[Array, Array]:
    """Per-chunk symmetric int8 quantization of a chunk-stacked 2-D layout.

    ``a`` is ``(n_chunks · rows_per_chunk, width)`` — the Gustavson
    coefficient tiles (``rows_per_chunk = block_rows``) or the SpGEMM
    hashed slab (``rows_per_chunk = width``).  Returns ``(q8, scale)`` with
    ``q8`` int8 of the same shape and ``scale`` of shape ``(n_chunks,)``.
    Trace-safe (used in-jit by ``plan_with_values`` and the traced-vals
    backends) and exact for already-quantized values.
    """
    a = jnp.asarray(a, jnp.float32)
    if n_chunks == 0:           # empty layout (no valid edges): nothing to do
        return (jnp.zeros(a.shape, jnp.int8),
                jnp.zeros((0,), jnp.float32))
    tiles = a.reshape(n_chunks, -1)
    scale = _safe_scale(jnp.max(jnp.abs(tiles), axis=1))
    q = jnp.clip(jnp.round(tiles / scale[:, None]), -Q8_MAX, Q8_MAX)
    if not isinstance(scale, jax.core.Tracer):
        # concrete (plan-time) quantization only — the in-jit re-quantize
        # path carries tracers, which must not touch host bookkeeping
        from repro.sparse.stats import record_count, record_value
        record_count("q8.tile_quants")
        record_value("q8.scale_max", float(jnp.max(scale)))
        record_value("q8.scale_mean", float(jnp.mean(scale)))
    return q.reshape(a.shape).astype(jnp.int8), scale


def quantize_feature_tiles(x: Array, d_tile: int) -> Tuple[Array, Array]:
    """Per-feature-tile symmetric int8 quantization of ``x (N, D)``.

    One scale per ``d_tile``-wide column block (the kernel's grid-j tile),
    so the scale is constant across the MXU contraction.  Returns
    ``(x_q8 (N, D) int8, scale (ceil(D/d_tile),) f32)``.
    """
    x = jnp.asarray(x, jnp.float32)
    n, d = x.shape
    d_tile = int(d_tile)
    pad = (-d) % d_tile
    xp = jnp.pad(x, ((0, 0), (0, pad))) if pad else x
    d_tiles = (d + pad) // d_tile
    blocks = xp.reshape(n, d_tiles, d_tile)
    scale = _safe_scale(jnp.max(jnp.abs(blocks), axis=(0, 2)))
    per_col = jnp.repeat(scale, d_tile)[:d]
    q = jnp.clip(jnp.round(x / per_col[None, :]), -Q8_MAX, Q8_MAX)
    return q.astype(jnp.int8), scale


def aggregate_q8_bound(remaining, out_block, n_blocks: int,
                       a_scale, x_scale) -> float:
    """Worst-case |y_q8 − y_f32| over the aggregate output (host numpy).

    Per-term deviation ≤ 127·s_a[k]·s_x[j]; a row of output block ``b``
    accumulates ``remaining[k]`` live terms from every chunk routed to it.
    """
    rem = np.asarray(remaining, np.float64)
    ob = np.asarray(out_block, np.int64)
    sa = np.asarray(a_scale, np.float64)
    per_block = np.bincount(ob, weights=rem * sa, minlength=int(n_blocks))
    sx_max = float(np.max(np.asarray(x_scale, np.float64), initial=0.0))
    return float(Q8_MAX * per_block.max(initial=0.0) * sx_max)


def aggregate_q8_bound_for(plan, x) -> float:
    """``aggregate_q8_bound`` for one (plan, f32 features) pair: the
    features quantize with the kernel's feature tile, as the backend
    does."""
    from repro.kernels.gustavson_spmm.gustavson_spmm import _auto_d_tile
    dt = plan.ell_d_tile or _auto_d_tile(x.shape[1])
    _, x_scale = quantize_feature_tiles(x, dt)
    return aggregate_q8_bound(plan.ell_remaining, plan.ell_out_block,
                              plan.n_blocks, plan.ell_a_scale, x_scale)


def spgemm_q8_bound(width: int, out_block, n_blocks: int,
                    a_scale, b_scale) -> float:
    """Worst-case |c_q8 − c_f32| over the SpGEMM output (host numpy).

    Each chunk contributes ≤ ``width`` partial products per output cell;
    per-term deviation ≤ 127·s_a[k]·s_b[k] (both operands of chunk ``k``
    share its scales).
    """
    ob = np.asarray(out_block, np.int64)
    sa = np.asarray(a_scale, np.float64)
    sb = np.asarray(b_scale, np.float64)
    per_block = np.bincount(ob, weights=sa * sb, minlength=int(n_blocks))
    return float(Q8_MAX * float(width) * per_block.max(initial=0.0))


def q8_gate(dev: float, bound: float, slack: float = 0.01,
            atol: float = 1e-6) -> bool:
    """The quantized parity predicate: measured deviation within the
    scale-derived bound (+1% f32-rounding slack).  NaN devs fail."""
    return bool(dev <= bound * (1.0 + slack) + atol)
