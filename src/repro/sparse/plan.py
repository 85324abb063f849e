"""Host-side aggregation plans — one layout precomputation per graph.

NeuraChip's decoupling of Gustavson's multiply and accumulate stages (paper
C1) is only an architectural property if every executor can sit behind the
same call.  The **plan** is the piece that makes that true: for a fixed graph
it precomputes, once, every layout the backend registry
(``repro.sparse.backend``) might dispatch to:

* padded COO (``rows``/``cols``/``base_vals``/``valid``) — the ``dense``
  segment-sum executor and the ``chunked`` rolling-eviction executor;
* operand-deduplicated chunk layout (``ell_*``, via ``pack_dedup_chunks``) —
  the ``pallas`` Gustavson kernel — **twice**: the forward matrix and its
  transpose (``ell_t_*``), so the kernel's backward pass (dX = Aᵀ·dY) runs
  through the same Pallas pipeline.  Per-edge ``ell_slots``/``ell_t_slots``
  let *traced* edge values (e.g. GAT attention weights) be scatter-added
  into the coefficient tiles on device;
* DRHM shard plan (``dist_*``, via ``plan_distributed_spmm``) — the
  ``distributed`` all-gather executor, again with scatter slots.

``cached_plan_from_graph`` adds an LRU cache keyed on graph identity +
backend set + layout parameters, so repeated step builds against a static
graph stop re-packing layouts host-side.

``AggregationPlan`` is registered as a pytree (arrays are leaves, layout
sizes / the mesh are static aux data), so plans pass through ``jax.jit``
boundaries and can hold either concrete host-built arrays or tracers
(``edge_plan`` builds a COO-only plan from traced edge arrays inside a model
forward — enough for ``dense``/``chunked``; ``pallas``/``distributed`` need a
host-built ``make_plan``).

Conventions (same as everywhere else in the repo): ``rows`` are *receivers*
(the accumulating side), ``cols`` are *senders*; ``n_rows`` is the padded
node count **including** the ghost row, i.e. ``x.shape[0]``; padding edges
carry ``valid == False`` and contribute nothing.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array

ALL_BACKENDS = ("dense", "chunked", "pallas", "pallas_q8", "distributed")


class BackendPlanError(ValueError):
    """A backend was asked to run on a plan missing its layout section."""


@dataclasses.dataclass(frozen=True)
class AggregationPlan:
    """Precomputed per-graph layouts for every registered executor."""

    # --- static layout sizes (pytree aux data) ---
    n_rows: int                      # padded node count incl. ghost row
    chunk: int = 8192                # rolling-eviction wave size
    block_rows: int = 8              # output-block rows (pallas layout)
    n_blocks: int = 0                # forward output blocks (pallas)
    n_t_blocks: int = 0              # transpose output blocks (pallas bwd)
    ell_group: int = 8               # DMA-wave width (rows per wave)
    ell_d_tile: Optional[int] = None  # feature-tile width (None → auto)
    n_shards: int = 0
    rows_per_shard: int = 0
    edges_per_shard: int = 0
    mesh: Optional[object] = None    # jax Mesh (hashable) for `distributed`

    # --- COO section (always present; may hold tracers) ---
    rows: Optional[Array] = None       # (E_pad,) int32 — receivers
    cols: Optional[Array] = None       # (E_pad,) int32 — senders
    valid: Optional[Array] = None      # (E_pad,) bool
    base_vals: Optional[Array] = None  # (E_pad,) f32 — weight·valid

    # --- dedup-chunk section (`pallas`; see graph.pack_dedup_chunks) ---
    ell_u_cols: Optional[Array] = None     # (n_chunks, width) int32
    ell_remaining: Optional[Array] = None  # (n_chunks,) int32
    ell_out_block: Optional[Array] = None  # (n_chunks,) int32
    ell_first: Optional[Array] = None      # (n_chunks,) int32
    ell_a: Optional[Array] = None          # (n_chunks·block_rows, width) f32
    ell_slots: Optional[Array] = None      # (E_pad,) int32; OOB ⇒ dropped
    # transpose mirror — the kernelized backward's layout
    ell_t_u_cols: Optional[Array] = None
    ell_t_remaining: Optional[Array] = None
    ell_t_out_block: Optional[Array] = None
    ell_t_first: Optional[Array] = None
    ell_t_a: Optional[Array] = None
    ell_t_slots: Optional[Array] = None
    # int8 quantized coefficient tiles (`pallas_q8`): per-dedup-chunk
    # symmetric scales, baked plan-time from the f32 tiles (DESIGN.md §12).
    # Only the forward tiles are quantized — the straight-through backward
    # runs the f32 transpose layout
    ell_a_q8: Optional[Array] = None       # (n_chunks·block_rows, width) int8
    ell_a_scale: Optional[Array] = None    # (n_chunks,) f32

    # --- DRHM shard section (`distributed`) ---
    dist_rows_local: Optional[Array] = None  # (S*e_per,) int32
    dist_cols_perm: Optional[Array] = None   # (S*e_per,) int32
    dist_vals: Optional[Array] = None        # (S*e_per,) f32
    dist_slots: Optional[Array] = None       # (E_pad,) int32; OOB ⇒ dropped
    dist_perm: Optional[Array] = None        # (n_pad,) int32: row → slot
    dist_inv_perm: Optional[Array] = None    # (n_pad,) int32: slot → row

    def has(self, section: str) -> bool:
        if section == "ell":
            return self.ell_u_cols is not None
        if section == "dist":
            return self.dist_rows_local is not None and self.mesh is not None
        return self.rows is not None

    def require(self, section: str, backend: str) -> None:
        if not self.has(section):
            raise BackendPlanError(
                f"backend {backend!r} needs the {section!r} plan section; "
                f"build the plan with make_plan(..., backends=({backend!r},"
                f" ...)) — inline edge_plan() covers only dense/chunked")

    @property
    def dist_n_pad(self) -> int:
        return self.n_shards * self.rows_per_shard


_LEAF_FIELDS = (
    "rows", "cols", "valid", "base_vals",
    "ell_u_cols", "ell_remaining", "ell_out_block", "ell_first", "ell_a",
    "ell_slots",
    "ell_t_u_cols", "ell_t_remaining", "ell_t_out_block", "ell_t_first",
    "ell_t_a", "ell_t_slots",
    "ell_a_q8", "ell_a_scale",
    "dist_rows_local", "dist_cols_perm", "dist_vals", "dist_slots",
    "dist_perm", "dist_inv_perm",
)
_AUX_FIELDS = ("n_rows", "chunk", "block_rows", "n_blocks", "n_t_blocks",
               "ell_group", "ell_d_tile",
               "n_shards", "rows_per_shard", "edges_per_shard", "mesh")


def _plan_flatten(p: AggregationPlan):
    return (tuple(getattr(p, f) for f in _LEAF_FIELDS),
            tuple(getattr(p, f) for f in _AUX_FIELDS))


def _plan_unflatten(aux, leaves):
    return AggregationPlan(**dict(zip(_AUX_FIELDS, aux)),
                           **dict(zip(_LEAF_FIELDS, leaves)))


jax.tree_util.register_pytree_node(AggregationPlan, _plan_flatten,
                                   _plan_unflatten)


# ---------------------------------------------------------------------------
# Builders
# ---------------------------------------------------------------------------

def edge_plan(senders: Array, receivers: Array, n_rows: int,
              edge_weight: Optional[Array] = None,
              edge_valid: Optional[Array] = None,
              chunk: int = 8192) -> AggregationPlan:
    """Trace-safe COO-only plan — what models build inline when no host plan
    was provided.  Supports the ``dense`` and ``chunked`` executors."""
    senders = jnp.asarray(senders)
    receivers = jnp.asarray(receivers)
    if edge_valid is None:
        valid = jnp.ones(senders.shape, bool)
    else:
        valid = jnp.asarray(edge_valid)
    if edge_weight is None:
        base = valid.astype(jnp.float32)
    else:
        base = jnp.where(valid, jnp.asarray(edge_weight), 0.0)
        base = base.astype(jnp.float32)
    return AggregationPlan(n_rows=int(n_rows), chunk=chunk, rows=receivers,
                           cols=senders, valid=valid, base_vals=base)


def make_plan(senders: np.ndarray, receivers: np.ndarray, n_rows: int,
              edge_weight: Optional[np.ndarray] = None,
              edge_valid: Optional[np.ndarray] = None, *,
              backends: Sequence[str] = ("dense", "chunked"),
              chunk: int = 8192, block_rows: int = 8, width_cap: int = 128,
              width_multiple: Optional[int] = None, group: int = 8,
              d_tile: Optional[int] = None,
              mesh=None, gamma: int = 0x9E3779B1,
              edge_pad_multiple: int = 8) -> AggregationPlan:
    """Host-side plan: precompute every layout in ``backends`` once.

    Only valid edges enter the pallas/distributed layouts; invalid (padding)
    edges get an out-of-bounds scatter slot, so traced per-edge values on
    padding lanes are dropped by construction.  ``block_rows`` is the least
    output-block height: compiled, a layout whose chunk tables would not
    fit the kernel's SMEM packs blocks twice as tall until they do.
    """
    for b in backends:
        if b not in ALL_BACKENDS:
            raise KeyError(f"unknown backend {b!r}; have {ALL_BACKENDS}")
    s = np.asarray(senders, np.int32)
    r = np.asarray(receivers, np.int32)
    e = s.shape[0]
    valid = (np.ones(e, bool) if edge_valid is None
             else np.asarray(edge_valid, bool))
    w = (np.ones(e, np.float32) if edge_weight is None
         else np.asarray(edge_weight, np.float32))
    base = np.where(valid, w, 0.0).astype(np.float32)
    vidx = np.nonzero(valid)[0]
    kw = dict(n_rows=int(n_rows), chunk=chunk,
              rows=jnp.asarray(r), cols=jnp.asarray(s),
              valid=jnp.asarray(valid), base_vals=jnp.asarray(base))

    if "pallas" in backends or "pallas_q8" in backends:
        from repro.kernels.gustavson_spmm.gustavson_spmm import (
            tables_fit_smem)
        from repro.sparse.graph import pack_dedup_chunks
        from repro.sparse.stats import record_count, record_value
        while True:
            pack_kw = dict(block_rows=block_rows, width_cap=width_cap,
                           width_multiple=width_multiple)
            # forward (A) and transpose (Aᵀ — the kernelized backward's
            # layout); the matrix is square over the padded node space, so
            # the transpose is the same packer with the roles swapped
            fwd = pack_dedup_chunks(r[vidx], s[vidx], base[vidx],
                                    int(n_rows), int(n_rows), **pack_kw)
            tr = pack_dedup_chunks(s[vidx], r[vidx], base[vidx],
                                   int(n_rows), int(n_rows), **pack_kw)
            # the compiled kernel holds a layout's chunk tables in SMEM;
            # where they would not fit, taller row blocks need fewer chunks
            if block_rows >= n_rows or (tables_fit_smem(fwd.u_cols)
                                        and tables_fit_smem(tr.u_cols)):
                break
            block_rows *= 2
        record_count("plan.dedup_packs", 2)
        record_value("plan.chunk_width", fwd.u_cols.shape[1])
        record_value("plan.n_chunks", fwd.u_cols.shape[0])
        # hub splits: chunks minted beyond one-per-output-block — a high-
        # degree (hub) receiver block's operand set overflowing its tile
        record_value("plan.hub_splits",
                     int(fwd.u_cols.shape[0] - np.unique(fwd.out_block).size))
        slots = np.full(e, fwd.a.size, np.int32)
        slots[vidx] = fwd.slots
        t_slots = np.full(e, tr.a.size, np.int32)
        t_slots[vidx] = tr.slots
        kw.update(block_rows=block_rows, n_blocks=fwd.n_blocks,
                  n_t_blocks=tr.n_blocks, ell_group=group, ell_d_tile=d_tile,
                  ell_u_cols=jnp.asarray(fwd.u_cols),
                  ell_remaining=jnp.asarray(fwd.remaining),
                  ell_out_block=jnp.asarray(fwd.out_block),
                  ell_first=jnp.asarray(fwd.first),
                  ell_a=jnp.asarray(fwd.a),
                  ell_slots=jnp.asarray(slots),
                  ell_t_u_cols=jnp.asarray(tr.u_cols),
                  ell_t_remaining=jnp.asarray(tr.remaining),
                  ell_t_out_block=jnp.asarray(tr.out_block),
                  ell_t_first=jnp.asarray(tr.first),
                  ell_t_a=jnp.asarray(tr.a),
                  ell_t_slots=jnp.asarray(t_slots))
        if "pallas_q8" in backends:
            # bake the int8 tiles for the default-values path; traced edge
            # values re-quantize in-jit (plan_with_values / the backend)
            from repro.sparse.quantize import quantize_chunk_tiles
            a_q8, a_scale = quantize_chunk_tiles(kw["ell_a"],
                                                 fwd.u_cols.shape[0])
            kw.update(ell_a_q8=a_q8, ell_a_scale=a_scale)

    if "distributed" in backends:
        from repro.core.distributed import plan_distributed_spmm
        if mesh is None:
            mesh = jax.make_mesh((jax.device_count(),), ("data",),
                                 axis_types=(jax.sharding.AxisType.Auto,))
        n_shards = int(mesh.shape["data"])
        dp = plan_distributed_spmm(r[vidx], s[vidx], base[vidx], int(n_rows),
                                   n_shards=n_shards, gamma=gamma,
                                   edge_pad_multiple=edge_pad_multiple)
        slots = np.full(e, dp.n_shards * dp.edges_per_shard, np.int32)
        slots[vidx] = dp.slots
        kw.update(mesh=mesh, n_shards=dp.n_shards,
                  rows_per_shard=dp.rows_per_shard,
                  edges_per_shard=dp.edges_per_shard,
                  dist_rows_local=jnp.asarray(dp.rows_local),
                  dist_cols_perm=jnp.asarray(dp.cols_perm),
                  dist_vals=jnp.asarray(dp.vals),
                  dist_slots=jnp.asarray(slots),
                  dist_perm=jnp.asarray(dp.perm.astype(np.int32)),
                  dist_inv_perm=jnp.asarray(dp.inv_perm.astype(np.int32)))

    return AggregationPlan(**kw)


def plan_with_values(plan: AggregationPlan,
                     edge_weight: Optional[Array] = None,
                     edge_valid: Optional[Array] = None) -> AggregationPlan:
    """Trace-safe re-valuation of a static-structure plan.

    Shape-bucketed serving (DESIGN.md §10) builds ONE host plan per bucket
    — the sampler's slot arithmetic makes every request's sender/receiver
    indices identical — and only the per-edge weights/validity differ per
    request.  This swaps those in *inside jit*: the COO ``base_vals``, the
    pallas coefficient tiles (scatter-added through the plan's slot maps,
    forward and transpose), and the distributed per-lane values are rebuilt
    from the traced arrays; every layout index stays the host-packed static
    data.  Edges invalid in the NEW mask contribute zero on every backend.

    The plan must have been built with all edges valid (so its slot maps
    cover every edge); parallel duplicate edges share a coefficient cell and
    their weights sum, matching segment-sum semantics.
    """
    valid = plan.valid if edge_valid is None else jnp.asarray(edge_valid)
    if edge_weight is None:
        base = valid.astype(jnp.float32)
    else:
        base = jnp.where(valid, jnp.asarray(edge_weight), 0.0)
        base = base.astype(jnp.float32)
    kw = dict(valid=valid, base_vals=base)
    if plan.ell_u_cols is not None:
        for pre in ("ell_", "ell_t_"):
            a_base = getattr(plan, pre + "a")
            slots = getattr(plan, pre + "slots")
            width = a_base.shape[1]
            kw[pre + "a"] = jnp.zeros_like(a_base).at[
                slots // width, slots % width].add(base, mode="drop")
        if plan.ell_a_q8 is not None:
            from repro.sparse.quantize import quantize_chunk_tiles
            a_q8, a_scale = quantize_chunk_tiles(
                kw["ell_a"], plan.ell_u_cols.shape[0])
            kw.update(ell_a_q8=a_q8, ell_a_scale=a_scale)
    if plan.dist_rows_local is not None:
        flat = jnp.zeros((plan.dist_rows_local.shape[0],), jnp.float32)
        kw["dist_vals"] = flat.at[plan.dist_slots].set(base, mode="drop")
    return dataclasses.replace(plan, **kw)


# ---------------------------------------------------------------------------
# Feature-shard plan — the serving cluster's sharded-residency layout
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class FeatureShardPlan:
    """DRHM row-sharded residency for a resident feature table (serving
    cluster, DESIGN.md §11): lane ``i`` of ``n_lanes`` owns permuted row
    slots ``[i·R, (i+1)·R)``.  Because the DRHM permutation is a bijection,
    every lane holds exactly ``R = n_pad / n_lanes`` rows — exact balance,
    independent of which nodes are popular.

    ``perm`` maps a *padded* row id (ghost row included, id ``n_rows-1``) to
    its permuted slot; the halo-exchange gather uses it to translate a
    sampled subgraph's global node ids into slots of the sharded table."""

    n_rows: int                  # padded row count incl. ghost row
    n_lanes: int
    n_pad: int                   # permuted slot count (n_lanes-divisible)
    gamma: int
    perm: np.ndarray             # (n_pad,) row id -> permuted slot
    inv_perm: np.ndarray         # (n_pad,) permuted slot -> row id

    @property
    def rows_per_lane(self) -> int:
        return self.n_pad // self.n_lanes

    def owner_of(self, row_ids: np.ndarray) -> np.ndarray:
        return self.perm[row_ids] // self.rows_per_lane

    def permute_table(self, table: np.ndarray) -> np.ndarray:
        """Lay a host feature table (ghost row last) out in permuted slot
        order; pad slots (beyond ``n_rows``) are zero, like the ghost row."""
        out = np.zeros((self.n_pad,) + table.shape[1:], table.dtype)
        out[self.perm[:table.shape[0]]] = table
        return out


def plan_feature_sharding(n_rows: int, n_lanes: int,
                          gamma: int = 0x9E3779B1) -> FeatureShardPlan:
    """DRHM shard plan for a resident feature table of ``n_rows`` rows
    (ghost row included) over ``n_lanes`` serving lanes."""
    from repro.core import drhm
    sp = drhm.plan_row_sharding(n_rows, n_lanes, gamma)
    return FeatureShardPlan(n_rows=n_rows, n_lanes=n_lanes, n_pad=sp.n_pad,
                            gamma=sp.gamma, perm=sp.perm,
                            inv_perm=sp.inv_perm)


def plan_from_graph(g, *, n_rows: Optional[int] = None,
                    **kwargs) -> AggregationPlan:
    """Plan for a padded ``Graph``.  ``n_rows`` defaults to ``n_nodes + 1``
    (the ghost-row convention: features carry one extra padding row)."""
    n = int(n_rows) if n_rows is not None else g.n_nodes + 1
    return make_plan(np.asarray(g.senders), np.asarray(g.receivers), n,
                     edge_weight=(None if g.edge_weight is None
                                  else np.asarray(g.edge_weight)),
                     edge_valid=np.asarray(g.edge_valid), **kwargs)


# ---------------------------------------------------------------------------
# Plan cache — repeated step builds on a static graph re-pack nothing
# ---------------------------------------------------------------------------

PLAN_CACHE_MAXSIZE = 8

# key → (graph, plan); insertion order = LRU order.  The entry keeps a strong
# reference to the keying graph so the id()s in the key cannot be recycled
# while the entry lives; lookups re-verify identity with `is`.
_PLAN_CACHE: "dict[tuple, tuple]" = {}
_PLAN_CACHE_STATS = {"hits": 0, "misses": 0}


def _freeze_kwargs(kwargs):
    def _freeze(v):
        if isinstance(v, (list, tuple)):
            return tuple(_freeze(x) for x in v)
        return v
    return tuple(sorted((k, _freeze(v)) for k, v in kwargs.items()))


def _graph_key(g, n_rows, kwargs):
    ids = tuple(None if a is None else id(a)
                for a in (g.senders, g.receivers, g.edge_weight,
                          g.edge_valid))
    return ids + (g.n_nodes, n_rows, _freeze_kwargs(kwargs))


def _same_graph(a, b) -> bool:
    return (a.senders is b.senders and a.receivers is b.receivers
            and a.edge_weight is b.edge_weight
            and a.edge_valid is b.edge_valid)


def cached_plan_from_graph(g, *, n_rows: Optional[int] = None,
                           maxsize: int = None, **kwargs) -> AggregationPlan:
    """``plan_from_graph`` with an LRU cache keyed on graph identity (the
    exact array objects), backend set, and layout parameters.

    Host-side packing (blocked-ELL dedup chunks, DRHM shards) is O(E) python
    work per call — a static graph trained for thousands of steps must pay
    it once, not once per step-builder invocation.
    """
    maxsize = PLAN_CACHE_MAXSIZE if maxsize is None else maxsize
    key = _graph_key(g, n_rows, kwargs)
    entry = _PLAN_CACHE.get(key)
    if entry is not None and _same_graph(entry[0], g):
        _PLAN_CACHE_STATS["hits"] += 1
        plan = entry[1]
        # refresh LRU position
        del _PLAN_CACHE[key]
        _PLAN_CACHE[key] = entry
        return plan
    _PLAN_CACHE_STATS["misses"] += 1
    plan = plan_from_graph(g, n_rows=n_rows, **kwargs)
    _PLAN_CACHE[key] = (g, plan)
    while len(_PLAN_CACHE) > max(int(maxsize), 0):
        _PLAN_CACHE.pop(next(iter(_PLAN_CACHE)))
    return plan


def plan_cache_info() -> dict:
    return dict(_PLAN_CACHE_STATS, size=len(_PLAN_CACHE))


def plan_cache_clear() -> None:
    _PLAN_CACHE.clear()
    _PLAN_CACHE_STATS.update(hits=0, misses=0)
