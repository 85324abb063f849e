"""Unified sparse-backend engine — one aggregation API, four executors.

Every sparse aggregation in the repo goes through one call signature,

    aggregate(plan, vals, x) -> y          # y[r] = Σ_e vals[e]·x[cols[e]]
    accumulate(plan, messages) -> y        # y[r] = Σ_e messages[e]

dispatched over a registry of interchangeable executors:

* ``dense``       — one-shot gather + segment-sum (XLA scatter; baseline);
* ``chunked``     — rolling-eviction waves (paper C3): partial products are
                    produced and folded in fixed-size chunks so the interim
                    working set is O(chunk·D), not O(nnz·D);
* ``pallas``      — the blocked-ELL Gustavson TPU kernel (paper's MMH4/HACC
                    pipeline; DESIGN.md §2.1), with a custom VJP so it is a
                    training path, not a test fixture;
* ``distributed`` — DRHM row-ownership + all-gather shard_map schedule
                    (paper C1+C2 at pod scale; DESIGN.md §4).

``vals`` may be ``None`` (use the plan's precomputed edge weights — GCN
normalization, GIN's implicit 1.0) or a traced (E,) array (GAT attention);
either way padding lanes contribute nothing.  ``accumulate`` is the
NeuraMem half alone, for models whose multiply stage is vector-valued
(SchNet continuous filters, DimeNet triplet contributions); the ``pallas``
executor falls back to the chunked schedule there — the kernel's multiply
stage is scalar-per-nnz by construction (DESIGN.md §3.3).

True sparse×sparse SpGEMM (sparse output — the paper's headline workload)
has its own registry under the same discipline:

    spgemm(plan, a_vals, b_vals) -> c_vals   # C = A@B on the plan's
                                             # symbolic structure

over ``dense`` (size-guarded densify oracle) / ``reference``
(rolling-eviction waves) / ``pallas`` (hash-pad kernel) executors, with the
plan built once by ``repro.sparse.spgemm.make_spgemm_plan`` (DESIGN.md §9).

Models never import ``repro.core.spgemm`` directly: they take a
``backend="dense"|"chunked"|"pallas"|"distributed"`` name, resolved here.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro.core import spgemm as core_spgemm
from repro.sparse.plan import (ALL_BACKENDS, AggregationPlan,
                               BackendPlanError)

Array = jax.Array

__all__ = ["Backend", "BACKENDS", "ALL_BACKENDS", "BackendPlanError",
           "register_backend", "get_backend", "aggregate", "accumulate",
           "SpgemmBackend", "SPGEMM_BACKENDS", "ALL_SPGEMM_BACKENDS",
           "register_spgemm_backend", "get_spgemm_backend", "spgemm"]

ALL_SPGEMM_BACKENDS = ("dense", "reference", "pallas", "pallas_q8")


@dataclasses.dataclass(frozen=True)
class Backend:
    """A registered executor: full decoupled SpMM + accumulate-only entry."""

    name: str
    aggregate: Callable[[AggregationPlan, Optional[Array], Array], Array]
    accumulate: Callable[[AggregationPlan, Array], Array]


BACKENDS: Dict[str, Backend] = {}


def register_backend(backend: Backend) -> Backend:
    BACKENDS[backend.name] = backend
    return backend


def get_backend(name: str) -> Backend:
    try:
        return BACKENDS[name]
    except KeyError:
        raise KeyError(f"unknown sparse backend {name!r}; registered: "
                       f"{sorted(BACKENDS)}") from None


def aggregate(plan: AggregationPlan, vals: Optional[Array], x: Array,
              backend: str = "dense") -> Array:
    """y[r] = Σ_{e: rows[e]=r} vals[e] · x[cols[e]] on the named executor.

    ``x`` may be a ``sparse.quantize.QuantizedFeatures`` (resident int8
    rows) on the ``pallas_q8`` executor — the inference fast path."""
    n_x = x.q8.shape[0] if hasattr(x, "q8") else x.shape[0]
    if n_x != plan.n_rows:
        # JAX gathers clip out-of-bounds indices, so a mismatched plan would
        # return silently-wrong values instead of erroring — catch it here.
        raise ValueError(
            f"x has {n_x} rows but the plan was built for "
            f"n_rows={plan.n_rows} (padded node count incl. ghost row)")
    return get_backend(backend).aggregate(plan, vals, x)


def accumulate(plan: AggregationPlan, messages: Array,
               backend: str = "dense") -> Array:
    """y[r] = Σ_{e: rows[e]=r} messages[e] on the named executor."""
    if messages.shape[0] != plan.rows.shape[0]:
        raise ValueError(
            f"messages has {messages.shape[0]} entries but the plan holds "
            f"{plan.rows.shape[0]} (padded) edges")
    return get_backend(backend).accumulate(plan, messages)


# ---------------------------------------------------------------------------
# SpGEMM registry (sparse × sparse, sparse output — DESIGN.md §9)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SpgemmBackend:
    """A registered SpGEMM executor: (plan, a_vals, b_vals) → c_vals."""

    name: str
    spgemm: Callable


SPGEMM_BACKENDS: Dict[str, SpgemmBackend] = {}


def register_spgemm_backend(backend: SpgemmBackend) -> SpgemmBackend:
    SPGEMM_BACKENDS[backend.name] = backend
    return backend


def get_spgemm_backend(name: str) -> SpgemmBackend:
    if name not in SPGEMM_BACKENDS:
        # executors live in the spgemm subsystem; importing it registers
        # them (kept lazy — backend.py must not depend on the kernels)
        import repro.sparse.spgemm.numeric  # noqa: F401
    try:
        return SPGEMM_BACKENDS[name]
    except KeyError:
        raise KeyError(f"unknown spgemm backend {name!r}; registered: "
                       f"{sorted(SPGEMM_BACKENDS)}") from None


def spgemm(plan, a_vals: Optional[Array] = None,
           b_vals: Optional[Array] = None,
           backend: str = "reference") -> Array:
    """c_vals of C = A@B on the plan's symbolic structure (row-major CSR
    order — ``plan.c_row``/``plan.c_col``).  ``a_vals``/``b_vals`` override
    the plan's baked values; ``None`` uses them (structure is plan state,
    values are data)."""
    for nm, v, nnz in (("a_vals", a_vals, plan.nnz_a),
                       ("b_vals", b_vals, plan.nnz_b)):
        if v is not None and v.shape[0] != nnz:
            raise ValueError(f"{nm} has {v.shape[0]} entries but the plan "
                             f"holds {nnz} nonzeros")
    return get_spgemm_backend(backend).spgemm(plan, a_vals, b_vals)


# ---------------------------------------------------------------------------
# Shared helpers
# ---------------------------------------------------------------------------

def _edge_vals(plan: AggregationPlan, vals: Optional[Array],
               dtype) -> Array:
    """Per-edge scalars with the padding contract enforced."""
    if vals is None:
        return plan.base_vals.astype(dtype)
    return jnp.where(plan.valid, vals, 0).astype(dtype)


def _mask_messages(plan: AggregationPlan, messages: Array) -> Array:
    shape = (-1,) + (1,) * (messages.ndim - 1)
    return jnp.where(plan.valid.reshape(shape), messages, 0)


# ---------------------------------------------------------------------------
# dense — one-shot gather + segment-sum
# ---------------------------------------------------------------------------

def _dense_aggregate(plan, vals, x):
    pp = jnp.take(x, plan.cols, axis=0)
    pp = pp * _edge_vals(plan, vals, pp.dtype)[:, None]
    return jax.ops.segment_sum(pp, plan.rows, num_segments=plan.n_rows)


def _dense_accumulate(plan, messages):
    return jax.ops.segment_sum(_mask_messages(plan, messages), plan.rows,
                               num_segments=plan.n_rows)


register_backend(Backend("dense", _dense_aggregate, _dense_accumulate))


# ---------------------------------------------------------------------------
# chunked — rolling-eviction waves (paper C3)
# ---------------------------------------------------------------------------

def _chunked_aggregate(plan, vals, x):
    v = _edge_vals(plan, vals, x.dtype)
    return core_spgemm.spmm_chunked(plan.rows, plan.cols, v, x, plan.n_rows,
                                    chunk=plan.chunk)


def _chunked_accumulate(plan, messages):
    return core_spgemm.segment_sum_chunked(plan.rows,
                                           _mask_messages(plan, messages),
                                           plan.n_rows, chunk=plan.chunk)


register_backend(Backend("chunked", _chunked_aggregate, _chunked_accumulate))


# ---------------------------------------------------------------------------
# pallas — blocked-ELL Gustavson kernel (compiled on TPU, interpret elsewhere)
# ---------------------------------------------------------------------------

def _coeff_tiles(plan, vals, a_base, slots):
    """Coefficient tiles for traced edge values: scatter-add straight into
    the 2-D ``(n_chunks·block_rows, width)`` layout (duplicate edges share a
    cell — add, not set; OOB slots of padding edges drop)."""
    width = a_base.shape[1]
    v = jnp.where(plan.valid, vals, 0).astype(jnp.float32)
    return jnp.zeros_like(a_base).at[slots // width, slots % width].add(
        v, mode="drop")


def _pallas_aggregate(plan, vals, x):
    from repro.kernels.gustavson_spmm import ops as gops
    plan.require("ell", "pallas")
    if vals is None:
        a, a_t = plan.ell_a, plan.ell_t_a
    else:
        a = _coeff_tiles(plan, vals, plan.ell_a, plan.ell_slots)
        a_t = _coeff_tiles(plan, vals, plan.ell_t_a, plan.ell_t_slots)
    # bf16 stays bf16: the kernel lands operands in x.dtype, accumulates in
    # f32, and evicts tiles back in x.dtype — no full-array upcast here
    y = gops.spmm_dedup_grad(
        plan.ell_u_cols, plan.ell_remaining, plan.ell_out_block,
        plan.ell_first, a,
        plan.ell_t_u_cols, plan.ell_t_remaining, plan.ell_t_out_block,
        plan.ell_t_first, a_t, x,
        block_rows=plan.block_rows, n_blocks=plan.n_blocks,
        n_t_blocks=plan.n_t_blocks, group=plan.ell_group,
        d_tile=plan.ell_d_tile)
    return y[: plan.n_rows]


def _pallas_accumulate(plan, messages):
    # The kernel's multiply stage is scalar-per-nnz; vector-valued messages
    # use the chunked rolling-eviction schedule instead (DESIGN.md §3.3).
    return _chunked_accumulate(plan, messages)


register_backend(Backend("pallas", _pallas_aggregate, _pallas_accumulate))


# ---------------------------------------------------------------------------
# pallas_q8 — int8 quantized-tile Gustavson kernel (DESIGN.md §12)
# ---------------------------------------------------------------------------

def _pallas_q8_aggregate(plan, vals, x):
    from repro.kernels.gustavson_spmm import ops as gops
    from repro.kernels.gustavson_spmm.gustavson_spmm import (
        _auto_d_tile, spmm_dedup_chunks_q8)
    from repro.sparse.quantize import QuantizedFeatures, quantize_chunk_tiles
    plan.require("ell", "pallas_q8")
    a_q8 = a_scale = None
    if vals is None:
        a, a_t = plan.ell_a, plan.ell_t_a
        # plan-time baked int8 tiles when the plan carries them; otherwise
        # (plan built for `pallas` only) quantize the f32 tiles in-trace
        a_q8, a_scale = plan.ell_a_q8, plan.ell_a_scale
    else:
        a = _coeff_tiles(plan, vals, plan.ell_a, plan.ell_slots)
        a_t = _coeff_tiles(plan, vals, plan.ell_t_a, plan.ell_t_slots)
    if a_q8 is None:
        a_q8, a_scale = quantize_chunk_tiles(a, plan.ell_u_cols.shape[0])
    if isinstance(x, QuantizedFeatures):
        # resident fast path: features were quantized once at store time —
        # inference-only (no VJP; there is no f32 X to differentiate)
        dt = plan.ell_d_tile or _auto_d_tile(x.q8.shape[1])
        d_tiles = -(-x.q8.shape[1] // dt)
        if x.scale.shape[0] != d_tiles:
            raise ValueError(
                f"QuantizedFeatures carries {x.scale.shape[0]} feature-tile "
                f"scales but the plan's kernel uses d_tile={dt} "
                f"({d_tiles} tiles) — re-quantize with the plan's d_tile")
        y = spmm_dedup_chunks_q8(
            plan.ell_u_cols, plan.ell_remaining, plan.ell_out_block,
            plan.ell_first, a_q8, a_scale, x.q8, x.scale,
            block_rows=plan.block_rows, n_blocks=plan.n_blocks,
            group=plan.ell_group, d_tile=plan.ell_d_tile)
        return y[: plan.n_rows]
    # X quantizes per feature tile inside the op (the scales must be computed
    # with the kernel's own d_tile); output returns in x.dtype
    y = gops.spmm_dedup_grad_q8(
        plan.ell_u_cols, plan.ell_remaining, plan.ell_out_block,
        plan.ell_first, a,
        plan.ell_t_u_cols, plan.ell_t_remaining, plan.ell_t_out_block,
        plan.ell_t_first, a_t, x,
        a_q8=a_q8, a_scale=a_scale,
        block_rows=plan.block_rows, n_blocks=plan.n_blocks,
        n_t_blocks=plan.n_t_blocks, group=plan.ell_group,
        d_tile=plan.ell_d_tile)
    return y[: plan.n_rows]


register_backend(Backend("pallas_q8", _pallas_q8_aggregate,
                         _pallas_accumulate))


# ---------------------------------------------------------------------------
# distributed — DRHM row ownership + all-gather shard_map (paper C1+C2)
# ---------------------------------------------------------------------------

def _dist_edge_vals(plan, vals):
    if vals is None:
        return plan.dist_vals
    v = jnp.where(plan.valid, vals, 0).astype(jnp.float32)
    flat = jnp.zeros((plan.dist_rows_local.shape[0],), jnp.float32)
    return flat.at[plan.dist_slots].set(v, mode="drop")


def _dist_permute_in(plan, x):
    pad = plan.dist_n_pad - x.shape[0]
    x_pad = jnp.concatenate(
        [x, jnp.zeros((pad,) + x.shape[1:], x.dtype)]) if pad else x
    return jnp.take(x_pad, plan.dist_inv_perm, axis=0)


def _dist_permute_out(plan, y_perm, dtype):
    return jnp.take(y_perm, plan.dist_perm[: plan.n_rows], axis=0
                    ).astype(dtype)


def _distributed_aggregate(plan, vals, x):
    from repro.core import distributed
    plan.require("dist", "distributed")
    v = _dist_edge_vals(plan, vals)
    x_perm = _dist_permute_in(plan, x.astype(jnp.float32))
    fn = distributed.make_allgather_spmm_dims(plan.mesh, plan.rows_per_shard,
                                              data_axis="data",
                                              model_axis=None)
    y_perm = fn(x_perm, plan.dist_rows_local, plan.dist_cols_perm, v)
    return _dist_permute_out(plan, y_perm, x.dtype)


def _distributed_accumulate(plan, messages):
    from repro.core import distributed
    plan.require("dist", "distributed")
    m = _mask_messages(plan, messages).astype(jnp.float32)
    flat = jnp.zeros((plan.dist_rows_local.shape[0],) + m.shape[1:],
                     jnp.float32)
    m_dist = flat.at[plan.dist_slots].set(m, mode="drop")
    fn = distributed.make_owner_accumulate(plan.mesh, plan.rows_per_shard,
                                           data_axis="data")
    y_perm = fn(m_dist, plan.dist_rows_local)
    return _dist_permute_out(plan, y_perm, messages.dtype)


register_backend(Backend("distributed", _distributed_aggregate,
                         _distributed_accumulate))
