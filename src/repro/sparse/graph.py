"""Graph containers and host-side format conversions.

The device-side representation is always a padded COO edge list (senders,
receivers, optional values, valid mask) — the only layout segment reductions
need.  Host-side we additionally keep CSR for the neighbor sampler and the
blocked-ELL packer used by the Gustavson Pallas kernel.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array


class Graph(NamedTuple):
    """Padded device-side COO graph.

    senders/receivers: (E_pad,) int32.  Padding edges have both set to
    ``n_nodes`` (a ghost row) and ``edge_valid == False``.
    """

    senders: Array
    receivers: Array
    n_nodes: int          # static (python int) — number of real nodes
    edge_valid: Array     # (E_pad,) bool
    edge_weight: Optional[Array] = None  # (E_pad,) float or None


def pad_to(x: np.ndarray, size: int, fill) -> np.ndarray:
    if x.shape[0] == size:
        return x
    pad = np.full((size - x.shape[0],) + x.shape[1:], fill, dtype=x.dtype)
    return np.concatenate([x, pad], axis=0)


def round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def make_graph(senders: np.ndarray, receivers: np.ndarray, n_nodes: int,
               edge_weight: Optional[np.ndarray] = None,
               pad_multiple: int = 128) -> Graph:
    """Build a padded Graph from raw COO arrays (host-side)."""
    e = senders.shape[0]
    e_pad = round_up(max(e, 1), pad_multiple)
    valid = np.zeros((e_pad,), dtype=bool)
    valid[:e] = True
    s = pad_to(senders.astype(np.int32), e_pad, n_nodes)
    r = pad_to(receivers.astype(np.int32), e_pad, n_nodes)
    w = None
    if edge_weight is not None:
        w = pad_to(edge_weight.astype(np.float32), e_pad, 0.0)
    return Graph(
        senders=jnp.asarray(s),
        receivers=jnp.asarray(r),
        n_nodes=n_nodes,
        edge_valid=jnp.asarray(valid),
        edge_weight=None if w is None else jnp.asarray(w),
    )


def coo_to_csr(senders: np.ndarray, receivers: np.ndarray, n_nodes: int):
    """Host-side CSR build (rows = receivers — aggregation viewpoint)."""
    order = np.argsort(receivers, kind="stable")
    s_sorted = senders[order]
    r_sorted = receivers[order]
    indptr = np.zeros(n_nodes + 1, dtype=np.int64)
    np.cumsum(np.bincount(r_sorted, minlength=n_nodes), out=indptr[1:])
    return indptr, s_sorted.astype(np.int32), order


def coarsen_graph(g: Graph, clusters: np.ndarray, n_clusters: int,
                  backend: str = "reference",
                  pad_multiple: int = 128) -> Graph:
    """Coarse graph  A_c = Pᵀ A P  via two rectangular SpGEMMs.

    ``clusters[i]`` assigns node i to one of ``n_clusters`` super-nodes; P
    is the (n × n_c) one-hot assignment matrix, so ``A_c[a, b]`` sums the
    weight of every original edge from cluster b into cluster a — the
    standard contraction step of multilevel partitioners, here an exercise
    of the sparse-output SpGEMM engine (DESIGN.md §9) on rectangular
    operands: structure comes from the symbolic phase, the second product's
    B-values are the first product's (device-computed) outputs.
    """
    from repro.sparse import backend as sb
    from repro.sparse.spgemm import make_spgemm_plan
    clusters = np.asarray(clusters, np.int64)
    valid = np.asarray(g.edge_valid)
    s = np.asarray(g.senders)[valid]
    r = np.asarray(g.receivers)[valid]
    w = (np.ones(s.size, np.float32) if g.edge_weight is None
         else np.asarray(g.edge_weight)[valid])
    n = int(g.n_nodes)
    nodes = np.arange(n, dtype=np.int64)
    # M = A @ P  (n × n_c): A[r, s] = w, P[i, clusters[i]] = 1
    plan_m = make_spgemm_plan(r, s, n, nodes, clusters, n, n_clusters,
                              a_vals=w, executors=(backend,))
    m_vals = sb.spgemm(plan_m, backend=backend)
    # A_c = Pᵀ @ M  (n_c × n_c): Pᵀ[clusters[i], i] = 1; M's structure is
    # host-known from the first plan, its values flow in per call
    plan_c = make_spgemm_plan(clusters, nodes, n_clusters,
                              np.asarray(plan_m.c_row),
                              np.asarray(plan_m.c_col), n, n_clusters,
                              executors=(backend,))
    c_vals = sb.spgemm(plan_c, None, m_vals, backend=backend)
    return make_graph(np.asarray(plan_c.c_col).astype(np.int32),
                      np.asarray(plan_c.c_row).astype(np.int32),
                      int(n_clusters),
                      edge_weight=np.asarray(c_vals, np.float32),
                      pad_multiple=pad_multiple)


def sym_norm_weights(senders: np.ndarray, receivers: np.ndarray, n_nodes: int,
                     add_self_loops: bool = True):
    """GCN symmetric normalization  D^-1/2 (A+I) D^-1/2  — host-side."""
    if add_self_loops:
        loops = np.arange(n_nodes, dtype=senders.dtype)
        senders = np.concatenate([senders, loops])
        receivers = np.concatenate([receivers, loops])
    deg = np.zeros(n_nodes, dtype=np.float64)
    np.add.at(deg, receivers, 1.0)
    dinv = 1.0 / np.sqrt(np.maximum(deg, 1.0))
    w = dinv[senders] * dinv[receivers]
    return senders, receivers, w.astype(np.float32)


@dataclasses.dataclass(frozen=True)
class BlockedELL:
    """Blocked-ELL packing of a sparse matrix for the Gustavson Pallas kernel.

    Rows are grouped into blocks of ``block_rows``; each block stores a padded
    nnz list (cols, vals, local row index within the block) of length
    ``nnz_pad`` (the max nnz over blocks, rounded to ``nnz_multiple``).
    ``remaining`` is the per-block rolling-eviction counter: the number of real
    partial products the block must absorb before its accumulator tile can be
    evicted to HBM.
    """

    cols: np.ndarray       # (n_blocks, nnz_pad) int32 — column index per edge
    row_local: np.ndarray  # (n_blocks, nnz_pad) int32 — row within block
    vals: np.ndarray       # (n_blocks, nnz_pad) float32 (0 for padding)
    remaining: np.ndarray  # (n_blocks,) int32 — eviction counters
    n_rows: int
    n_cols: int
    block_rows: int
    # slot of input edge i in the flattened (n_blocks * nnz_pad) layout —
    # lets callers scatter *traced* edge values (e.g. attention weights)
    # into the packed layout on device.
    slots: Optional[np.ndarray] = None  # (E,) int32

    @property
    def n_blocks(self) -> int:
        return self.cols.shape[0]

    @property
    def nnz_pad(self) -> int:
        return self.cols.shape[1]


def pack_blocked_ell(rows: np.ndarray, cols: np.ndarray, vals: np.ndarray,
                     n_rows: int, n_cols: int, block_rows: int = 8,
                     nnz_multiple: int = 128) -> BlockedELL:
    """Pack COO (rows, cols, vals) into BlockedELL (host-side, done once)."""
    n_blocks = round_up(n_rows, block_rows) // block_rows
    order = np.argsort(rows, kind="stable")
    rows, cols, vals = rows[order], cols[order], vals[order]
    blk = rows // block_rows
    counts = np.zeros(n_blocks, dtype=np.int64)
    np.add.at(counts, blk, 1)
    nnz_pad = int(round_up(max(int(counts.max(initial=1)), 1), nnz_multiple))
    out_cols = np.zeros((n_blocks, nnz_pad), dtype=np.int32)
    out_rloc = np.zeros((n_blocks, nnz_pad), dtype=np.int32)
    out_vals = np.zeros((n_blocks, nnz_pad), dtype=np.float32)
    # bucket-fill
    starts = np.zeros(n_blocks + 1, dtype=np.int64)
    np.cumsum(counts, out=starts[1:])
    slots = np.zeros(rows.shape[0], dtype=np.int32)
    for b in range(n_blocks):
        lo, hi = starts[b], starts[b + 1]
        k = hi - lo
        out_cols[b, :k] = cols[lo:hi]
        out_rloc[b, :k] = rows[lo:hi] - b * block_rows
        out_vals[b, :k] = vals[lo:hi]
        slots[order[lo:hi]] = b * nnz_pad + np.arange(k, dtype=np.int32)
    return BlockedELL(
        cols=out_cols, row_local=out_rloc, vals=out_vals,
        remaining=counts.astype(np.int32), n_rows=n_rows, n_cols=n_cols,
        block_rows=block_rows, slots=slots,
    )


@dataclasses.dataclass(frozen=True)
class DedupChunks:
    """Operand-deduplicated chunked blocked-ELL for the Gustavson kernel.

    Rows are grouped into output blocks of ``block_rows``; each block's nnz
    are **deduplicated by source row** (one landing-buffer lane per distinct
    operand — NeuraChip's operand-reuse, killing redundant gather traffic)
    and split into **chunks** of at most ``width`` distinct operands, so one
    pathological row (a power-law hub in the transpose) never inflates every
    block's padding.  A chunk carries:

    * ``u_cols[k]``   — the distinct source-row ids (padded with 0);
    * ``a[k·BR:(k+1)·BR]`` — a dense ``(block_rows, width)`` coefficient tile:
      ``a[r, u] = Σ vals`` over the chunk's nnz with local row ``r`` and
      operand ``u`` (the stacked one-hot matrices of the grouped multiply);
    * ``remaining[k]`` — the rolling-eviction counter (# real operands);
    * ``out_block[k]`` — which output block the chunk folds into; chunks of
      one block are consecutive, ``first[k]`` marks the first (overwrite vs
      accumulate on revisit).  Every output block owns ≥ 1 chunk, so even
      empty blocks evict a (zero) tile.

    ``slots[i]`` maps input edge *i* to its cell in the flattened ``a`` so
    traced edge values (GAT attention) can be **scatter-added** into the
    coefficient tiles on device; excluded edges get an out-of-bounds slot.
    """

    u_cols: np.ndarray     # (n_chunks, width) int32 — distinct operand rows
    a: np.ndarray          # (n_chunks·block_rows, width) f32 — coeff tiles
    remaining: np.ndarray  # (n_chunks,) int32 — eviction counters
    out_block: np.ndarray  # (n_chunks,) int32 — destination output block
    first: np.ndarray      # (n_chunks,) int32 — 1 ⇔ first chunk of its block
    n_rows: int
    n_cols: int
    block_rows: int
    slots: Optional[np.ndarray] = None  # (E,) int32 into a.reshape(-1)

    @property
    def n_chunks(self) -> int:
        return self.u_cols.shape[0]

    @property
    def width(self) -> int:
        return self.u_cols.shape[1]

    @property
    def n_blocks(self) -> int:
        return round_up(self.n_rows, self.block_rows) // self.block_rows


def chunk_block_edges(b: int, idx: np.ndarray, rows: np.ndarray,
                      cols: np.ndarray, block_rows: int,
                      width_cap: int) -> list:
    """Dedup + chunk one output block's edge set (host-side).

    ``idx`` indexes the canonical edge arrays, already restricted to rows
    of block ``b`` in canonical (stable row-sorted) order.  Returns the
    block's chunk tuples ``(block, u_ids, edge_idx, rloc, uidx)`` — at
    least one (possibly empty) chunk, so empty blocks still evict a zero
    tile.  Both the cold packer and the incremental delta re-packer
    (``sparse/delta.py``) call this helper, which is what guarantees a
    dirty-block rebuild is chunk-identical to a cold re-pack.
    """
    if idx.size == 0:
        return [(b, np.empty(0, np.int64), idx,
                 np.empty(0, np.int64), np.empty(0, np.int64))]
    u_ids, uinv = np.unique(cols[idx], return_inverse=True)
    chunks = []
    for lo in range(0, u_ids.size, width_cap):
        hi = min(lo + width_cap, u_ids.size)
        sel = (uinv >= lo) & (uinv < hi)
        chunks.append((b, u_ids[lo:hi], idx[sel],
                       rows[idx[sel]] - b * block_rows, uinv[sel] - lo))
    return chunks


def chunk_width_multiple(width_multiple: Optional[int] = None) -> int:
    """What chunk widths round to: whole 128-lane tiles where the Gustavson
    kernel compiles for the chip (Mosaic slices its coefficient tile and
    landing buffer only at that width), 16 under the interpreter, where a
    narrow chunk costs less.  An explicit multiple wins."""
    if width_multiple is not None:
        return int(width_multiple)
    from repro.core import platform   # repro.core imports this module
    from repro.kernels.gustavson_spmm.gustavson_spmm import LANES
    return 16 if platform.interpret_mode() else LANES


def assemble_dedup_chunks(per_block: list, vals: np.ndarray, n_edges: int,
                          n_rows: int, n_cols: int, block_rows: int,
                          width_multiple: Optional[int] = None
                          ) -> DedupChunks:
    """Assemble per-block chunk tuples (from :func:`chunk_block_edges`)
    into the flat DedupChunks arrays.  ``width`` adapts to the graph: the
    max distinct-operand count over chunks, rounded to
    ``chunk_width_multiple(width_multiple)`` — balanced graphs get narrow
    tiles, hub-heavy ones get more chunks.
    """
    width = int(round_up(max(1, max((c[1].size for chunks in per_block
                                     for c in chunks), default=1)),
                         chunk_width_multiple(width_multiple)))
    n_chunks = sum(len(c) for c in per_block)
    u_cols = np.zeros((n_chunks, width), np.int32)
    a = np.zeros((n_chunks * block_rows, width), np.float32)
    remaining = np.zeros(n_chunks, np.int32)
    out_block = np.zeros(n_chunks, np.int32)
    first = np.zeros(n_chunks, np.int32)
    slots = np.full(n_edges, n_chunks * block_rows * width,
                    np.int32)  # OOB default
    k = 0
    for chunks in per_block:
        for i, (b, u_ids, idx, rloc, uidx) in enumerate(chunks):
            u_cols[k, :u_ids.size] = u_ids
            remaining[k] = u_ids.size
            out_block[k] = b
            first[k] = int(i == 0)
            cell = (k * block_rows + rloc) * width + uidx
            np.add.at(a.reshape(-1), cell, vals[idx])
            slots[idx] = cell
            k += 1
    return DedupChunks(u_cols=u_cols, a=a, remaining=remaining,
                       out_block=out_block, first=first, n_rows=n_rows,
                       n_cols=n_cols, block_rows=block_rows, slots=slots)


def pack_dedup_chunks(rows: np.ndarray, cols: np.ndarray, vals: np.ndarray,
                      n_rows: int, n_cols: int, block_rows: int = 8,
                      width_cap: int = 128,
                      width_multiple: Optional[int] = None) -> DedupChunks:
    """Pack COO into DedupChunks (host-side, once per graph).

    ``width`` adapts to the graph: the max distinct-operand count over
    chunks after capping at ``width_cap``, rounded to ``width_multiple`` —
    balanced graphs get narrow tiles, hub-heavy ones get more chunks.
    """
    rows = np.asarray(rows, np.int64)
    cols = np.asarray(cols, np.int64)
    vals = np.asarray(vals, np.float32)
    e = rows.shape[0]
    n_blocks = round_up(n_rows, block_rows) // block_rows
    order = np.argsort(rows, kind="stable")
    blk_sorted = rows[order] // block_rows

    # per block: dedup operands, split into runs of ≤ width_cap distinct
    starts = np.zeros(n_blocks + 1, np.int64)
    np.add.at(starts, blk_sorted + 1, 1)
    starts = np.cumsum(starts)
    per_block = [chunk_block_edges(b, order[starts[b]:starts[b + 1]],
                                   rows, cols, block_rows, width_cap)
                 for b in range(n_blocks)]
    return assemble_dedup_chunks(per_block, vals, e, n_rows, n_cols,
                                 block_rows, width_multiple)
