"""Incremental plan re-packing for streaming graph mutation (DESIGN.md §16).

The serving stack freezes the resident graph at startup; production graphs
mutate continuously.  A cold ``plan_from_graph`` re-pack is O(E log E) host
work plus a *python loop over output blocks* (``pack_dedup_chunks``) — far
too slow to sit on a mutation stream.  This module maintains every layout
the plan layer packs **incrementally**:

* **CSR** (both orientations — receiver-sorted for the serving sampler and
  the forward dedup-chunk layout, sender-sorted for the transpose/backward
  layout) via vectorized ``np.insert``/``np.delete`` at end-of-row
  positions.  Canonical edge order is "original order minus deletes, with
  inserts appended", so the incremental CSR is **bitwise identical** to
  ``coo_to_csr`` over the compacted edge arrays (stable sort ties break on
  canonical position; appended inserts have the largest positions in their
  row).
* **Dedup-chunk layouts** by re-chunking only *dirty* output blocks (blocks
  that lost or gained an edge) through the same per-block chunking rule as
  the cold packer, then reassembling the flat chunk arrays with fully
  vectorized numpy — no python loop over blocks.  Clean blocks reuse their
  cached operand tables.

Parity contract (property-tested in ``tests/test_delta.py`` and gated by
``benchmarks/cluster_bench.py --mutation``): after any interleaving of
inserts/deletes + flushes, ``plan()`` is *structurally bitwise* equal to a
cold ``plan_from_graph`` over the compacted edge arrays — CSR, ``u_cols``,
``remaining``, ``out_block``, ``first``, chunk width, and slot maps — and
the coefficient tiles ``a`` match bitwise as well, because per-cell
accumulation order (block-major canonical) is identical in both packers.
Aggregate outputs therefore agree to float32 exactness; the public gate is
≤ 1e-5 to stay robust to backend reduction-order differences.

The bounded-staleness *policy* (when a flush must happen) lives with the
serving stream in ``repro.serve.live``; this module is the mechanism.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from repro.sparse.graph import (DedupChunks, Graph, chunk_width_multiple,
                                make_graph, pad_to, round_up)
from repro.sparse.stats import record_count, record_value

DELTA_BACKENDS = ("dense", "chunked", "pallas", "pallas_q8")


class DeltaGraphError(ValueError):
    """A mutation the delta state cannot apply (unknown edge, bad ids) or a
    plan section it cannot maintain incrementally (``distributed``)."""


class _LayoutState:
    """One orientation's incrementally-maintained CSR + dedup-chunk state.

    ``rows`` is the blocked/accumulating side (receivers for the forward
    layout, senders for the transpose), ``cols`` the operand side.  All
    per-position arrays are kept in CSR (block-major canonical) order and
    edited with the same ``np.delete``/``np.insert`` so they never drift
    from ``order``.
    """

    def __init__(self, rows: np.ndarray, cols: np.ndarray, n_rows: int,
                 block_rows: int, width_cap: int):
        self.n_rows = int(n_rows)
        self.n_cols = int(n_rows)          # square over the padded node space
        self.block_rows = int(block_rows)
        self.width_cap = int(width_cap)
        self.n_blocks = round_up(self.n_rows, self.block_rows) \
            // self.block_rows
        rows = np.asarray(rows, np.int64)
        cols = np.asarray(cols, np.int64)
        self.order = np.argsort(rows, kind="stable")     # csr pos → canonical
        self.sorted_cols = cols[self.order].astype(np.int32)
        indptr = np.zeros(self.n_rows + 1, np.int64)
        np.add.at(indptr, rows + 1, 1)
        self.indptr = np.cumsum(indptr)
        # global per-block operand dedup, vectorized: unique (block, col)
        # pairs in block-major order reproduce each block's np.unique
        blk_e = rows[self.order] // self.block_rows
        comb = blk_e * np.int64(self.n_cols) + self.sorted_cols
        uc, uinv = np.unique(comb, return_inverse=True)
        self.u_all = (uc % self.n_cols).astype(np.int32)
        counts_u = np.bincount(uc // self.n_cols, minlength=self.n_blocks)
        self.u_ptr = np.zeros(self.n_blocks + 1, np.int64)
        np.cumsum(counts_u, out=self.u_ptr[1:])
        local = uinv - self.u_ptr[blk_e]
        self.uidx = local % self.width_cap          # operand slot in chunk
        self.chunk_in_block = local // self.width_cap

    # -- mutation ------------------------------------------------------------
    def apply(self, del_can: np.ndarray, del_rows: np.ndarray,
              ins_rows: np.ndarray, ins_cols: np.ndarray,
              e_old: int) -> int:
        """Apply one flushed batch.  ``del_can`` are sorted canonical edge
        indices (into the pre-flush arrays); inserts are appended in order.
        Returns the number of dirty blocks re-chunked."""
        if del_can.size:
            mark = np.zeros(e_old, bool)
            mark[del_can] = True
            del_pos = np.nonzero(mark[self.order])[0]
            self.order = np.delete(self.order, del_pos)
            self.order -= np.searchsorted(del_can, self.order)
            self.sorted_cols = np.delete(self.sorted_cols, del_pos)
            self.uidx = np.delete(self.uidx, del_pos)
            self.chunk_in_block = np.delete(self.chunk_in_block, del_pos)
            delta = np.zeros(self.n_rows + 1, np.int64)
            np.subtract.at(delta, del_rows + 1, 1)
            self.indptr = self.indptr + np.cumsum(delta)
        if ins_rows.size:
            # canonical ids follow buffer order (inserts append), but the
            # CSR edit must place them row-major: two inserts into different
            # rows can share one numeric end-of-row position when the rows
            # between them are empty, and np.insert breaks that tie by list
            # order — so sort by row (stable: same-row inserts keep buffer
            # order, matching canonical order within the row)
            by_row = np.argsort(ins_rows, kind="stable")
            pos = self.indptr[ins_rows[by_row] + 1]  # end-of-row, post-del
            new_ids = (e_old - del_can.size) + np.arange(ins_rows.size)
            self.order = np.insert(self.order, pos, new_ids[by_row])
            ins_cols = ins_cols[by_row]
            self.sorted_cols = np.insert(self.sorted_cols, pos,
                                         ins_cols.astype(np.int32))
            self.uidx = np.insert(self.uidx, pos, 0)
            self.chunk_in_block = np.insert(self.chunk_in_block, pos, 0)
            delta = np.zeros(self.n_rows + 1, np.int64)
            np.add.at(delta, ins_rows + 1, 1)
            self.indptr = self.indptr + np.cumsum(delta)
        touched = np.concatenate([del_rows, ins_rows])
        if touched.size == 0:
            return 0
        dirty = np.unique(touched // self.block_rows)
        self._rechunk(dirty)
        return int(dirty.size)

    def _rechunk(self, dirty: np.ndarray) -> None:
        """Re-dedup + re-chunk the dirty blocks through the cold packer's
        chunking rule (chunk j of a block covers unique-operand ranks
        ``[j·cap, (j+1)·cap)``), splicing their operand tables into
        ``u_all`` while every clean block's cache is reused untouched."""
        br, cap = self.block_rows, self.width_cap
        old_ptr = self.u_ptr
        counts = np.diff(old_ptr).copy()
        # one global unique over all dirty blocks' (block, col) pairs —
        # block-major sorted, so it reproduces each block's own np.unique
        lo_e = self.indptr[dirty * br]
        hi_e = self.indptr[np.minimum((dirty + 1) * br, self.n_rows)]
        sizes = hi_e - lo_e
        pos = (np.repeat(lo_e - np.concatenate([[0], np.cumsum(sizes)[:-1]]),
                         sizes) + np.arange(int(sizes.sum())))
        blk_d = np.repeat(dirty, sizes)
        comb = blk_d * np.int64(self.n_cols) + self.sorted_cols[pos]
        uc, uinv = np.unique(comb, return_inverse=True)
        blk_of_u = uc // self.n_cols
        j_of_u = np.searchsorted(dirty, blk_of_u)
        counts_d = np.bincount(j_of_u, minlength=dirty.size)
        ptr_d = np.zeros(dirty.size + 1, np.int64)
        np.cumsum(counts_d, out=ptr_d[1:])
        local = uinv - ptr_d[np.searchsorted(dirty, blk_d)]
        self.uidx[pos] = local % cap
        self.chunk_in_block[pos] = local // cap
        u_new = (uc % self.n_cols).astype(np.int32)
        pieces: List[np.ndarray] = []
        prev_u = 0
        for j, b in enumerate(dirty.tolist()):
            pieces.append(self.u_all[prev_u:old_ptr[b]])
            pieces.append(u_new[ptr_d[j]:ptr_d[j + 1]])
            prev_u = int(old_ptr[b + 1])
            counts[b] = counts_d[j]
        pieces.append(self.u_all[prev_u:])
        self.u_all = np.concatenate(pieces)
        self.u_ptr = np.zeros(self.n_blocks + 1, np.int64)
        np.cumsum(counts, out=self.u_ptr[1:])

    # -- assembly ------------------------------------------------------------
    def chunk_layout(self) -> Tuple[np.ndarray, int]:
        """(chunks-per-block, total chunks) from the cached operand counts
        — every block owns ≥ 1 chunk, even empty ones."""
        counts_u = np.diff(self.u_ptr)
        nch = np.maximum(1, -(-counts_u // self.width_cap))
        return nch, int(nch.sum())

    def assemble(self, vals: np.ndarray,
                 width_multiple: Optional[int] = None) -> DedupChunks:
        """Materialize the flat DedupChunks arrays — all vectorized; no
        python loop over blocks.  Bitwise-matches ``pack_dedup_chunks``
        over the canonical edge arrays (per-cell accumulation order is
        block-major canonical in both)."""
        br, cap = self.block_rows, self.width_cap
        counts_u = np.diff(self.u_ptr)
        nch, n_chunks = self.chunk_layout()
        width = int(round_up(max(1, min(int(counts_u.max(initial=0)), cap)),
                             chunk_width_multiple(width_multiple)))
        chunk_start = np.zeros(self.n_blocks + 1, np.int64)
        np.cumsum(nch, out=chunk_start[1:])
        blk_of_u = np.repeat(np.arange(self.n_blocks), counts_u)
        local_u = np.arange(self.u_all.size) - self.u_ptr[blk_of_u]
        u_gchunk = chunk_start[blk_of_u] + local_u // cap
        u_cols = np.zeros((n_chunks, width), np.int32)
        u_cols[u_gchunk, local_u % cap] = self.u_all
        remaining = np.bincount(u_gchunk,
                                minlength=n_chunks).astype(np.int32)
        out_block = np.repeat(np.arange(self.n_blocks, dtype=np.int32), nch)
        first = np.zeros(n_chunks, np.int32)
        first[chunk_start[:-1]] = 1
        rows_per_pos = np.repeat(np.arange(self.n_rows, dtype=np.int64),
                                 np.diff(self.indptr))
        blk_e = rows_per_pos // br
        gchunk_e = chunk_start[blk_e] + self.chunk_in_block
        cell = ((gchunk_e * br + (rows_per_pos - blk_e * br)) * width
                + self.uidx)
        a = np.zeros(n_chunks * br * width, np.float32)
        np.add.at(a, cell, np.asarray(vals, np.float32)[self.order])
        slots = np.full(self.order.size, n_chunks * br * width, np.int32)
        slots[self.order] = cell
        return DedupChunks(u_cols=u_cols, a=a.reshape(n_chunks * br, width),
                           remaining=remaining, out_block=out_block,
                           first=first, n_rows=self.n_rows,
                           n_cols=self.n_cols, block_rows=br, slots=slots)


@dataclasses.dataclass
class FlushResult:
    """What one flush did — surfaced to telemetry and the mutation bench."""

    epoch: int
    inserted: int
    deleted: int
    dirty_blocks: int          # across both layout orientations
    clean_blocks: int
    n_edges: int


class DeltaGraphState:
    """The mutable resident graph: canonical edge arrays + incrementally
    maintained CSRs and dedup-chunk layouts, with buffered edge mutations
    applied in epoch batches by :meth:`flush`.

    Canonical order is *original edges minus deletes, inserts appended* —
    exactly what a cold re-pack of the compacted arrays would see, which is
    what makes the incremental layouts bitwise-comparable to
    ``plan_from_graph`` at every epoch boundary.
    """

    def __init__(self, senders: np.ndarray, receivers: np.ndarray,
                 n_nodes: int, weights: Optional[np.ndarray] = None, *,
                 block_rows: int = 8, width_cap: int = 128,
                 width_multiple: Optional[int] = None):
        self.n_nodes = int(n_nodes)
        self.n_rows = self.n_nodes + 1            # ghost-row convention
        self.block_rows = int(block_rows)
        self.width_cap = int(width_cap)
        self.width_multiple = chunk_width_multiple(width_multiple)
        self._s = np.asarray(senders, np.int64).copy()
        self._r = np.asarray(receivers, np.int64).copy()
        if np.any((self._s < 0) | (self._s >= self.n_nodes) |
                  (self._r < 0) | (self._r >= self.n_nodes)):
            raise DeltaGraphError("edge endpoints out of range")
        self._w = (np.ones(self._s.size, np.float32) if weights is None
                   else np.asarray(weights, np.float32).copy())
        if self._w.shape != self._s.shape:
            raise DeltaGraphError("weights shape mismatch")
        # forward layout: rows = receivers (the aggregation viewpoint, and
        # the serving sampler's CSR); transpose layout: rows = senders
        self._fwd = _LayoutState(self._r, self._s, self.n_rows,
                                 block_rows, width_cap)
        self._tr = _LayoutState(self._s, self._r, self.n_rows,
                                block_rows, width_cap)
        self.epoch = 0
        self._pend_ins: List[Tuple[int, int, float]] = []
        self._pend_del: List[Tuple[int, int]] = []

    # -- buffered mutations --------------------------------------------------
    @property
    def n_edges(self) -> int:
        return int(self._s.size)

    @property
    def pending(self) -> int:
        return len(self._pend_ins) + len(self._pend_del)

    def insert_edge(self, sender: int, receiver: int,
                    weight: float = 1.0) -> None:
        s, r = int(sender), int(receiver)
        if not (0 <= s < self.n_nodes and 0 <= r < self.n_nodes):
            raise DeltaGraphError(f"edge ({s}, {r}) out of range")
        self._pend_ins.append((s, r, float(weight)))

    def delete_edge(self, sender: int, receiver: int) -> None:
        """Delete one ``(sender, receiver)`` edge.  A pending insert of the
        same pair is cancelled instead; otherwise the *last* matching
        canonical edge is removed at the next flush.  Raises if no such
        edge exists in the post-buffer graph."""
        s, r = int(sender), int(receiver)
        for i in range(len(self._pend_ins) - 1, -1, -1):
            if self._pend_ins[i][0] == s and self._pend_ins[i][1] == r:
                del self._pend_ins[i]
                return
        have = int(np.count_nonzero((self._s == s) & (self._r == r)))
        booked = sum(1 for d in self._pend_del if d == (s, r))
        if booked >= have:
            raise DeltaGraphError(f"edge ({s}, {r}) not present")
        self._pend_del.append((s, r))

    # -- epoch boundary ------------------------------------------------------
    def flush(self) -> FlushResult:
        """Apply the buffered batch: compact canonical arrays, delta-update
        both CSRs and both dedup-chunk layouts, bump the epoch."""
        ins = self._pend_ins
        dels = self._pend_del
        self._pend_ins, self._pend_del = [], []
        e_old = self._s.size
        # resolve deletes to canonical indices (last matching occurrence)
        del_idx: List[int] = []
        taken = set()
        for s, r in dels:
            cand = np.nonzero((self._s == s) & (self._r == r))[0]
            hit = next((int(i) for i in cand[::-1] if int(i) not in taken),
                       None)
            if hit is None:        # unreachable via delete_edge's booking
                raise DeltaGraphError(f"edge ({s}, {r}) not present")
            taken.add(hit)
            del_idx.append(hit)
        del_can = np.sort(np.asarray(del_idx, np.int64))
        ins_s = np.asarray([i[0] for i in ins], np.int64)
        ins_r = np.asarray([i[1] for i in ins], np.int64)
        ins_w = np.asarray([i[2] for i in ins], np.float32)
        dirty = self._fwd.apply(del_can, self._r[del_can], ins_r, ins_s,
                                e_old)
        dirty += self._tr.apply(del_can, self._s[del_can], ins_s, ins_r,
                                e_old)
        keep = np.ones(e_old, bool)
        keep[del_can] = False
        self._s = np.concatenate([self._s[keep], ins_s])
        self._r = np.concatenate([self._r[keep], ins_r])
        self._w = np.concatenate([self._w[keep], ins_w])
        self.epoch += 1
        record_count("delta.flushes", 1)
        record_count("delta.edges_inserted", ins_s.size)
        record_count("delta.edges_deleted", del_can.size)
        record_count("delta.dirty_blocks", dirty)
        total_blocks = self._fwd.n_blocks + self._tr.n_blocks
        record_value("delta.clean_block_frac",
                     1.0 - dirty / max(1, total_blocks))
        return FlushResult(epoch=self.epoch, inserted=int(ins_s.size),
                           deleted=int(del_can.size), dirty_blocks=dirty,
                           clean_blocks=total_blocks - dirty,
                           n_edges=self.n_edges)

    # -- views ---------------------------------------------------------------
    def csr(self) -> Tuple[np.ndarray, np.ndarray]:
        """Serving CSR (receiver-sorted), same convention as
        ``coo_to_csr(senders, receivers, n_nodes)`` — bitwise identical to
        a cold build over the canonical arrays."""
        return (self._fwd.indptr[:self.n_nodes + 1].copy(),
                self._fwd.sorted_cols.copy())

    def graph(self, pad_multiple: int = 128) -> Graph:
        """The compacted canonical graph as a padded device Graph — the
        cold re-pack reference at this epoch."""
        return make_graph(self._s.astype(np.int32),
                          self._r.astype(np.int32), self.n_nodes,
                          edge_weight=self._w, pad_multiple=pad_multiple)

    def chunk_stats(self) -> dict:
        """Forward-layout chunk stats, matching what ``make_plan`` records
        (``plan.n_chunks`` / ``plan.chunk_width`` / ``plan.hub_splits``)."""
        counts_u = np.diff(self._fwd.u_ptr)
        _, n_chunks = self._fwd.chunk_layout()
        width = int(round_up(max(1, min(int(counts_u.max(initial=0)),
                                        self.width_cap)),
                             self.width_multiple))
        return {"n_chunks": n_chunks, "chunk_width": width,
                "hub_splits": n_chunks - self._fwd.n_blocks,
                "n_edges": self.n_edges, "epoch": self.epoch}

    def repack(self) -> Tuple[DedupChunks, DedupChunks]:
        """Host-side incremental re-pack at the current epoch: the forward
        and transpose DedupChunks layouts, assembled from cached clean
        blocks + the re-chunked dirty ones.  This is the delta side of the
        ``delta_repack_speedup`` bench comparison (device upload is
        identical either way and excluded from both)."""
        return (self._fwd.assemble(self._w, self.width_multiple),
                self._tr.assemble(self._w, self.width_multiple))

    def cold_repack(self) -> Tuple[DedupChunks, DedupChunks]:
        """What a cold re-pack of the canonical arrays costs host-side
        (CSR sort + both dedup-chunk packs) — the baseline the incremental
        path is measured against, and its parity reference."""
        from repro.sparse.graph import coo_to_csr, pack_dedup_chunks
        coo_to_csr(self._s, self._r, self.n_nodes)
        kw = dict(block_rows=self.block_rows, width_cap=self.width_cap,
                  width_multiple=self.width_multiple)
        fwd = pack_dedup_chunks(self._r, self._s, self._w, self.n_rows,
                                self.n_rows, **kw)
        tr = pack_dedup_chunks(self._s, self._r, self._w, self.n_rows,
                               self.n_rows, **kw)
        return fwd, tr

    def plan(self, *, backends: Sequence[str] = ("dense", "chunked",
                                                 "pallas"),
             chunk: int = 8192, group: int = 8,
             d_tile: Optional[int] = None,
             pad_multiple: int = 128):
        """The incremental ``AggregationPlan`` at this epoch — equal to
        ``plan_from_graph(self.graph(), backends=...)`` without re-packing
        clean blocks.  The ``distributed`` section has no delta path (its
        DRHM shard layout re-permutes globally); request a cold plan."""
        from repro.sparse.plan import AggregationPlan
        for b in backends:
            if b not in DELTA_BACKENDS:
                raise DeltaGraphError(
                    f"backend {b!r} has no incremental re-pack; build a "
                    f"cold plan via plan_from_graph (have {DELTA_BACKENDS})")
        e = self.n_edges
        e_pad = round_up(max(e, 1), pad_multiple)
        s_p = pad_to(self._s.astype(np.int32), e_pad, self.n_nodes)
        r_p = pad_to(self._r.astype(np.int32), e_pad, self.n_nodes)
        valid = np.zeros(e_pad, bool)
        valid[:e] = True
        base = np.zeros(e_pad, np.float32)
        base[:e] = self._w
        kw = dict(n_rows=self.n_rows, chunk=chunk, rows=jnp.asarray(r_p),
                  cols=jnp.asarray(s_p), valid=jnp.asarray(valid),
                  base_vals=jnp.asarray(base))
        if "pallas" in backends or "pallas_q8" in backends:
            fwd, tr = self.repack()
            record_count("delta.incremental_repacks", 2)
            slots = np.full(e_pad, fwd.a.size, np.int32)
            slots[:e] = fwd.slots
            t_slots = np.full(e_pad, tr.a.size, np.int32)
            t_slots[:e] = tr.slots
            kw.update(block_rows=self.block_rows, n_blocks=fwd.n_blocks,
                      n_t_blocks=tr.n_blocks, ell_group=group,
                      ell_d_tile=d_tile,
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
                from repro.sparse.quantize import quantize_chunk_tiles
                a_q8, a_scale = quantize_chunk_tiles(
                    kw["ell_a"], fwd.u_cols.shape[0])
                kw.update(ell_a_q8=a_q8, ell_a_scale=a_scale)
        return AggregationPlan(**kw)

    def cold_plan(self, *, backends: Sequence[str] = ("dense", "chunked",
                                                      "pallas"), **kwargs):
        """The cold re-pack reference: ``plan_from_graph`` over the
        compacted canonical arrays (what the incremental plan must match
        at every epoch boundary) — also the mutation bench's baseline."""
        from repro.sparse.plan import plan_from_graph
        return plan_from_graph(self.graph(), backends=backends, **kwargs)


def plans_match(pa, pb, *, tol: float = 1e-5) -> Tuple[bool, dict]:
    """Structural + numeric parity between two plans over the same graph
    (the epoch-boundary check).  Structure (CSR-derived layouts, chunk
    tables, slot maps) must be bitwise; coefficient tiles within ``tol``
    (measured bitwise in practice — same per-cell accumulation order)."""
    detail: dict = {}
    ok = True

    def _arr(p, f):
        v = getattr(p, f)
        return None if v is None else np.asarray(v)

    for f in ("rows", "cols", "valid", "ell_u_cols", "ell_remaining",
              "ell_out_block", "ell_first", "ell_slots", "ell_t_u_cols",
              "ell_t_remaining", "ell_t_out_block", "ell_t_first",
              "ell_t_slots"):
        a, b = _arr(pa, f), _arr(pb, f)
        same = ((a is None and b is None)
                or (a is not None and b is not None
                    and a.shape == b.shape and bool(np.array_equal(a, b))))
        detail[f] = bool(same)
        ok = ok and same
    for f in ("base_vals", "ell_a", "ell_t_a"):
        a, b = _arr(pa, f), _arr(pb, f)
        if a is None and b is None:
            dev = 0.0
        elif a is None or b is None or a.shape != b.shape:
            dev = float("inf")
        else:
            dev = float(np.max(np.abs(a - b))) if a.size else 0.0
        detail[f + "_dev"] = dev
        ok = ok and dev <= tol
    detail["n_rows"] = pa.n_rows == pb.n_rows
    ok = ok and detail["n_rows"]
    return ok, detail


def chunks_match(ca, cb, *, tol: float = 1e-5) -> Tuple[bool, dict]:
    """Host-side ``DedupChunks`` parity (the cheap epoch-boundary check the
    serving graph stream runs before installing a mutated layout): chunk
    tables and slot maps bitwise, coefficient tiles within ``tol``."""
    detail: dict = {}
    ok = True
    for f in ("u_cols", "remaining", "out_block", "first", "slots"):
        a, b = np.asarray(getattr(ca, f)), np.asarray(getattr(cb, f))
        same = a.shape == b.shape and bool(np.array_equal(a, b))
        detail[f] = same
        ok = ok and same
    a, b = np.asarray(ca.a), np.asarray(cb.a)
    dev = (float(np.max(np.abs(a - b)))
           if a.shape == b.shape and a.size else
           (0.0 if a.shape == b.shape else float("inf")))
    detail["a_dev"] = dev
    ok = ok and dev <= tol
    detail["n_blocks"] = ca.n_blocks == cb.n_blocks
    return ok and detail["n_blocks"], detail
