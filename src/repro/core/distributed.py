"""Pod-scale decoupled SpMM — DRHM row ownership + two-stage dataflow (C1+C2)
with an optional ring-pipelined rolling-eviction schedule (C3 + overlap).

Layouts (all planned host-side, once per graph):

* Node features X are stored in DRHM-permuted row order and sharded
  ``P('data', 'model')`` → device (i, j) holds row-slots [i·R, (i+1)·R) of the
  permuted order and feature block j.  Because the DRHM permutation is a
  bijection, every device owns exactly R rows — *exact* balance, independent of
  the graph's sparsity pattern (paper §2.4 "sparsity agnostic", strengthened).
* Edges are grouped by the owner of their *destination* row (the accumulating
  device — NeuraMem analogue) and padded to equal per-owner counts; the
  destination index is pre-localized to the owner's slot space.

Dataflow per step (``allgather`` variant — paper-faithful):
  1. all-gather X row-shards along 'data'  (multiply-stage operand fetch ≙ the
     NeuraCores streaming matrix B rows from HBM),
  2. local gather·scale → partial products   (NeuraCore),
  3. local segment-sum into owned row block  (NeuraMem; no partial product ever
     crosses the network — accumulation locality is total).

``ring`` variant (beyond-paper): X blocks circulate around the 'data' ring via
ppermute; edges are additionally grouped by *source* block — shape
(owner, src_block, e_blk) — so each hop folds exactly its chunk immediately
(rolling eviction) while the next block is in flight (compute/comm overlap).
DRHM hashes *both* endpoints, so the (owner × src_block) histogram is doubly
balanced and the per-cell padding e_blk stays ≈ E/P² · (1+ε).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import drhm
from repro.sparse.graph import round_up

Array = jax.Array


# ---------------------------------------------------------------------------
# Host-side planning
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class DistSpmmPlan:
    """Device-ready, DRHM-balanced edge partition for a fixed graph."""

    n_shards: int
    rows_per_shard: int          # R — row slots per data shard (padded)
    edges_per_shard: int         # equal per-shard edge count (padded)
    # all-gather layout: flat (n_shards * edges_per_shard,) — shard i owns slice i
    rows_local: np.ndarray       # destination slot within owner shard
    cols_perm: np.ndarray        # source row in *permuted* global order
    vals: np.ndarray             # edge weights (0 ⇒ padding lane)
    perm: np.ndarray             # global row id -> permuted slot
    inv_perm: np.ndarray
    # ring layout: (n_shards, n_shards, e_blk) [owner, src_block, lane]
    ring_rows: Optional[np.ndarray] = None   # dest slot within owner
    ring_cols: Optional[np.ndarray] = None   # source slot within src block
    ring_vals: Optional[np.ndarray] = None
    # slot of input edge i in the flat (n_shards * edges_per_shard) layout —
    # lets callers scatter traced edge values into the owner-grouped order.
    slots: Optional[np.ndarray] = None       # (E,) int32

    @property
    def n_pad(self) -> int:
        return self.n_shards * self.rows_per_shard

    @property
    def e_blk(self) -> int:
        return 0 if self.ring_rows is None else self.ring_rows.shape[2]


def plan_distributed_spmm(rows: np.ndarray, cols: np.ndarray,
                          vals: Optional[np.ndarray], n_nodes: int,
                          n_shards: int, gamma: int = 0x9E3779B1,
                          ring: bool = False,
                          edge_pad_multiple: int = 8) -> DistSpmmPlan:
    """Group edges by DRHM owner of their destination row (+ source block for
    the ring schedule), localize indices, pad to equal counts."""
    shard_plan = drhm.plan_row_sharding(n_nodes, n_shards, gamma)
    perm, n_pad = shard_plan.perm, shard_plan.n_pad
    r_per = n_pad // n_shards

    dest_slot = perm[rows]                       # permuted destination slot
    src_slot = perm[cols]                        # permuted source slot
    owner = dest_slot // r_per
    src_block = src_slot // r_per
    v = np.ones(rows.shape[0], np.float32) if vals is None else vals.astype(np.float32)

    order = np.argsort(owner, kind="stable")
    d_s, s_s, v_s, o_s = dest_slot[order], src_slot[order], v[order], owner[order]

    counts = np.bincount(o_s, minlength=n_shards)
    e_per = int(round_up(max(int(counts.max(initial=1)), 1), edge_pad_multiple))
    rows_l = np.zeros((n_shards, e_per), np.int32)
    cols_p = np.zeros((n_shards, e_per), np.int32)
    vals_p = np.zeros((n_shards, e_per), np.float32)
    starts = np.zeros(n_shards + 1, np.int64)
    np.cumsum(counts, out=starts[1:])
    slots = np.zeros(rows.shape[0], np.int32)
    for s in range(n_shards):
        lo, hi = starts[s], starts[s + 1]
        k = hi - lo
        rows_l[s, :k] = d_s[lo:hi] % r_per
        cols_p[s, :k] = s_s[lo:hi]
        vals_p[s, :k] = v_s[lo:hi]
        slots[order[lo:hi]] = s * e_per + np.arange(k, dtype=np.int32)

    ring_rows = ring_cols = ring_vals = None
    if ring:
        cell = owner * n_shards + src_block
        corder = np.argsort(cell, kind="stable")
        d_c, s_c, v_c = dest_slot[corder], src_slot[corder], v[corder]
        cell_counts = np.bincount(cell[corder], minlength=n_shards * n_shards)
        e_blk = int(round_up(max(int(cell_counts.max(initial=1)), 1),
                             edge_pad_multiple))
        ring_rows = np.zeros((n_shards, n_shards, e_blk), np.int32)
        ring_cols = np.zeros((n_shards, n_shards, e_blk), np.int32)
        ring_vals = np.zeros((n_shards, n_shards, e_blk), np.float32)
        cstarts = np.zeros(n_shards * n_shards + 1, np.int64)
        np.cumsum(cell_counts, out=cstarts[1:])
        for c in range(n_shards * n_shards):
            lo, hi = cstarts[c], cstarts[c + 1]
            k = hi - lo
            ow, sb = divmod(c, n_shards)
            ring_rows[ow, sb, :k] = d_c[lo:hi] % r_per
            ring_cols[ow, sb, :k] = s_c[lo:hi] % r_per
            ring_vals[ow, sb, :k] = v_c[lo:hi]

    return DistSpmmPlan(
        n_shards=n_shards, rows_per_shard=r_per, edges_per_shard=e_per,
        rows_local=rows_l.reshape(-1), cols_perm=cols_p.reshape(-1),
        vals=vals_p.reshape(-1), perm=perm, inv_perm=shard_plan.inv_perm,
        ring_rows=ring_rows, ring_cols=ring_cols, ring_vals=ring_vals,
        slots=slots,
    )


def permute_features(x: np.ndarray, plan: DistSpmmPlan) -> np.ndarray:
    """Host-side: lay out node features in DRHM-permuted order (padded)."""
    n, d = x.shape
    out = np.zeros((plan.n_pad, d), x.dtype)
    out[plan.perm[:n]] = x
    return out


def unpermute_features(xp: np.ndarray, plan: DistSpmmPlan, n_nodes: int):
    return xp[plan.perm[:n_nodes]]


# ---------------------------------------------------------------------------
# Device-side SpMM factories (shard_map)
# ---------------------------------------------------------------------------

def make_allgather_spmm(mesh, plan: DistSpmmPlan, data_axis="data",
                        model_axis="model"):
    return make_allgather_spmm_dims(mesh, plan.rows_per_shard, data_axis,
                                    model_axis)


def make_allgather_spmm_dims(mesh, rows_per_shard: int, data_axis="data",
                             model_axis="model"):
    """Paper-faithful distributed decoupled SpMM (shape-only factory — usable
    from the dry-run where no concrete plan exists).

    Returned fn: (x_perm, rows_local, cols_perm, vals) -> y
    x_perm: (n_pad, D) P(data, model); edge arrays (n_shards*e_per,) P(data);
    y: (n_pad, D) P(data, model).  ``data_axis`` may be a tuple of mesh axes;
    ``model_axis`` may be None (features replicated).
    """
    r_per = rows_per_shard

    def local_fn(x_loc, rows_l, cols_p, vals):
        # stage 0: operand fetch (HBM stream analogue)
        x_full = jax.lax.all_gather(x_loc, data_axis, axis=0, tiled=True)
        # stage 1: NeuraCore — partial products
        pp = jnp.take(x_full, cols_p, axis=0) * vals[:, None].astype(x_full.dtype)
        # stage 2: NeuraMem — local accumulate into owned row block
        return jax.ops.segment_sum(pp, rows_l, num_segments=r_per)

    return jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(data_axis, model_axis), P(data_axis), P(data_axis), P(data_axis)),
        out_specs=P(data_axis, model_axis),
    )


def make_halo_gather(mesh, n_ghost_slot: int, data_axis="data"):
    """Halo exchange for sharded serving (DESIGN.md §11): each lane holds a
    DRHM-permuted row shard of the resident feature table and one sampled
    subgraph's node ids; boundary rows (owned by other lanes) arrive through
    the same stage-0 operand fetch the distributed SpMM uses (all-gather
    along the lane axis), then each lane gathers exactly its subgraph's
    rows.  The gather is a pure row copy, so sharded residency is *bitwise*
    identical to replicated residency — the cluster parity contract.

    Note the memory shape: sharding bounds what each lane stores AT REST
    (R = n_pad/L rows); the all-gather still materializes the full table
    *transiently* during the exchange, so peak working memory matches
    replicated mode.  A selective exchange (ship only each lane's
    requested boundary rows, e.g. ragged all-to-all) is the follow-up that
    makes peak memory O(R + batch); the call signature here is already
    shaped for that swap.

    Returned fn: ``(x_perm, perm, node_ids) -> x_batch``
    x_perm: (n_pad, D) P(lane) — permuted, sharded feature table;
    perm: (n_rows,) replicated row→slot map; node_ids: (L, n) P(lane),
    ``-1`` ⇒ the ghost slot ``perm[n_ghost_slot]``; x_batch: (L, n, D) P(lane).
    """

    def local_fn(x_loc, perm, node_ids):
        x_full = jax.lax.all_gather(x_loc, data_axis, axis=0, tiled=True)
        ridx = jnp.where(node_ids[0] >= 0, node_ids[0],
                         n_ghost_slot).astype(jnp.int32)
        return jnp.take(x_full, jnp.take(perm, ridx), axis=0)[None]

    return jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(data_axis), P(), P(data_axis)),
        out_specs=P(data_axis),
    )


def make_owner_accumulate(mesh, rows_per_shard: int, data_axis="data"):
    """Accumulate-only distributed stage: per-edge messages are already
    formed (vector-valued multiply stage ran upstream) and grouped by the
    DRHM owner of their destination row, so each shard folds its slice
    locally — no partial product crosses the network.

    Returned fn: (messages, rows_local) -> y_perm
    messages: (n_shards*e_per, D) P(data); rows_local: (n_shards*e_per,)
    P(data); y_perm: (n_pad, D) P(data).
    """
    r_per = rows_per_shard

    def local_fn(m_loc, rows_l):
        return jax.ops.segment_sum(m_loc, rows_l, num_segments=r_per)

    return jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(data_axis), P(data_axis)),
        out_specs=P(data_axis),
    )


def make_ring_spmm(mesh, plan: DistSpmmPlan, data_axis="data",
                   model_axis="model"):
    assert plan.ring_rows is not None, "plan must be built with ring=True"
    return make_ring_spmm_dims(mesh, plan.rows_per_shard, plan.n_shards,
                               data_axis, model_axis)


def make_ring_spmm_dims(mesh, rows_per_shard: int, n_shards: int,
                        data_axis="data", model_axis="model"):
    """Ring-pipelined rolling-eviction SpMM (beyond-paper §Perf lever).

    Returned fn: (x_perm, ring_rows, ring_cols, ring_vals) -> y
    x_perm: (n_pad, D) P(data, model); ring arrays (n_sh, n_sh, e_blk) with
    dim0 sharded P(data); y: (n_pad, D) P(data, model).
    """
    r_per = rows_per_shard
    n_sh = n_shards

    def local_fn(x_loc, r_rows, r_cols, r_vals):
        # local shapes: x_loc (r_per, d_loc); ring arrays (1, n_sh, e_blk)
        r_rows, r_cols, r_vals = r_rows[0], r_cols[0], r_vals[0]
        me = jax.lax.axis_index(data_axis)
        perm_pairs = [(i, (i + 1) % n_sh) for i in range(n_sh)]

        def hop(t, carry):
            acc, blk = carry
            src_blk = (me - t) % n_sh          # block currently held
            rows_t = jax.lax.dynamic_index_in_dim(r_rows, src_blk, 0, False)
            cols_t = jax.lax.dynamic_index_in_dim(r_cols, src_blk, 0, False)
            vals_t = jax.lax.dynamic_index_in_dim(r_vals, src_blk, 0, False)
            pp = jnp.take(blk, cols_t, axis=0) * vals_t[:, None].astype(blk.dtype)
            acc = acc + jax.ops.segment_sum(pp, rows_t, num_segments=r_per)
            blk = jax.lax.ppermute(blk, data_axis, perm_pairs)
            return (acc, blk)

        acc0 = jnp.zeros((r_per, x_loc.shape[1]), x_loc.dtype)
        # The carried block is device-varying (ppermute output); mark the
        # freshly-created accumulator the same way so loop carry types match.
        vary_axes = (data_axis if isinstance(data_axis, tuple)
                     else (data_axis,))
        if model_axis:
            vary_axes = vary_axes + (model_axis,)
        acc0 = jax.lax.pcast(acc0, vary_axes, to="varying")
        acc, _ = jax.lax.fori_loop(0, n_sh, hop, (acc0, x_loc))
        return acc

    return jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(data_axis, model_axis), P(data_axis, None, None),
                  P(data_axis, None, None), P(data_axis, None, None)),
        out_specs=P(data_axis, model_axis),
    )
