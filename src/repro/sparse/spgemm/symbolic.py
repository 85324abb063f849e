"""SpGEMM symbolic phase — output structure, interim-pp maps, hash-pad
layout (host-side, once per matrix pair).

NeuraChip's headline workload is sparse×sparse SpGEMM (A·A² on
SuiteSparse/SNAP graphs): the output C = A@B is itself sparse, its structure
is *data-dependent*, and the interim partial products bloat far beyond
nnz(C) (paper Table 1, Eq. 1).  On the ASIC the structure is discovered on
the fly by the HashPad's tag-match; in JAX every shape must be static, so we
split the paper's pipeline the way production SpGEMM libraries do:

* **symbolic phase** (this module) — host-side numpy.  One vectorized CSR
  walk expands every Gustavson partial product ``(a_nnz e, b_nnz f)`` and
  merges them into the exact output structure: CSR layout of C, the
  pp → output-slot map the reference executor folds over, and the bloat
  statistics (pp_interim / nnz_out — validated against
  ``neurasim.model.stats_from_coo``).  A **hash-dedup variant**
  (``hash_dedup_row_nnz``) discovers the same per-row counts the way the
  HashPad does — insert tags into a bounded pad with linear probing —
  and reports the collision/probe counts the analytic path cannot see.

* **hash-pad layout** — the numeric Pallas kernel accumulates partial
  products into a ``(block_rows, pad_width)`` VMEM pad per output row
  block; bucket = the high bits of ``col · γ_b`` (the full-width variant of
  ``core.drhm.drhm_hash`` — one reseeded odd multiplier per row block).
  The symbolic phase *searches* γ_b per block — reseeding, DRHM-style,
  until the bucket map is injective on every row's output column set — so
  the kernel needs no CAM tag match at all: collisions are resolved at
  plan time, not probe time.  If some block cannot be seeded at the current
  ``pad_width``, the pad grows ×2 and the search restarts (the software
  analogue of HashPad overflow).

``make_spgemm_plan`` packages all of it — plus the A-side dedup-chunk
coefficient tiles (PR 2's ``pack_dedup_chunks``) and the B-side hashed slab
scatter map — into a pytree-registered ``SpgemmPlan`` the numeric executors
(``repro.sparse.spgemm.numeric``) consume.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.eviction import bloat_percent
from repro.sparse.stats import record_count, record_value

__all__ = ["SpgemmSymbolic", "SpgemmPlan", "symbolic", "make_spgemm_plan",
           "hash_bucket", "hash_dedup_row_nnz", "find_block_gammas",
           "ALL_SPGEMM_EXECUTORS"]

MAX_PP_INT32 = (1 << 31) - 1


# ---------------------------------------------------------------------------
# Hash-pad bucket map (full-width DRHM-style multiplicative hash)
# ---------------------------------------------------------------------------

def hash_bucket(cols: np.ndarray, gamma, pad_width: int) -> np.ndarray:
    """Bucket of each output column: high bits of ``col · γ  mod 2³²``.

    ``core.drhm.drhm_hash`` masks the tag to its low k bits (the paper's
    Eq. 3 operand); output columns exceed 2¹⁶, so the pad uses the
    full-width product — an odd γ is bijective mod 2³², leaving truncation
    to ``log2(pad_width)`` bits as the only collision source, which the
    per-block reseed search removes entirely.  ``pad_width`` must be a
    power of two.
    """
    g = np.asarray(gamma, dtype=np.uint64)      # scalar or per-element γ
    prod = (cols.astype(np.uint64) * g) & np.uint64(0xFFFFFFFF)
    shift = 32 - int(pad_width).bit_length() + 1
    return (prod >> np.uint64(shift)).astype(np.int64)


def _next_pow2(x: int) -> int:
    return 1 << max(int(x) - 1, 0).bit_length() if x > 1 else 1


def _odd_gammas(rng: np.random.Generator, k: int) -> np.ndarray:
    return (rng.integers(1, 2 ** 30, size=k, dtype=np.int64) * 2 + 1).astype(
        np.uint32)


def find_block_gammas(c_indptr: np.ndarray, c_cols: np.ndarray, n_rows: int,
                      block_rows: int, pad_width: int, max_reseeds: int = 8,
                      seed: int = 0
                      ) -> Tuple[Optional[np.ndarray], int, int]:
    """Per-block γ such that buckets are injective on every row's column set.

    Returns (gammas | None, reseeds, collisions): ``None`` means some block
    failed after ``max_reseeds`` draws — the caller grows the pad.  Rows of
    one block share a γ (the pad tile is evicted per block); the paper
    reseeds per row, we reseed per 8-row tile — noted in DESIGN.md §9.
    """
    n_blocks = max(1, -(-n_rows // block_rows))
    rng = np.random.default_rng(seed)
    gammas = np.zeros(n_blocks, np.uint32)
    reseeds = 0
    collisions = 0
    for b in range(n_blocks):
        lo, hi = b * block_rows, min((b + 1) * block_rows, n_rows)
        sets = [c_cols[c_indptr[i]:c_indptr[i + 1]] for i in range(lo, hi)
                if c_indptr[i + 1] - c_indptr[i] > 1]
        found = False
        for g in _odd_gammas(rng, max_reseeds):
            coll = 0
            for s in sets:
                coll += s.size - np.unique(hash_bucket(s, g, pad_width)).size
            if coll == 0:
                gammas[b] = g
                found = True
                break
            reseeds += 1
            collisions += coll
        if not found:
            return None, reseeds, collisions
    return gammas, reseeds, collisions


def hash_dedup_row_nnz(pp_row: np.ndarray, pp_col: np.ndarray, n_rows: int,
                       pad_width: int, seed: int = 0):
    """Per-row output nnz discovered the HashPad way: linear-probe insertion
    of each partial product's column tag into a ``pad_width`` table, one
    fresh γ per row (the paper's per-row reseed).  Exact — dedup by tag
    equality, probing past occupied mismatching lines — and, unlike the
    merge variant, it *measures* collision behaviour.

    Returns (row_nnz, stats) with stats = {"probes", "occupancy_peak"}.
    O(pp) python — small/medium workloads only (tests, sweep stats).
    """
    assert pad_width == _next_pow2(pad_width)
    order = np.argsort(pp_row, kind="stable")
    rows_s, cols_s = pp_row[order], pp_col[order]
    starts = np.searchsorted(rows_s, np.arange(n_rows + 1))
    gammas = _odd_gammas(np.random.default_rng(seed), n_rows)
    row_nnz = np.zeros(n_rows, np.int64)
    probes = 0
    occupancy_peak = 0
    for i in range(n_rows):
        cols_i = cols_s[starts[i]:starts[i + 1]]
        if cols_i.size == 0:
            continue
        keys = np.full(pad_width, -1, np.int64)
        buckets = hash_bucket(cols_i, gammas[i], pad_width)
        placed = 0
        for col, b in zip(cols_i.tolist(), buckets.tolist()):
            steps = 0
            while keys[b] not in (-1, col):        # occupied by another tag
                probes += 1
                steps += 1
                if steps >= pad_width:             # every line holds another
                    raise ValueError(              # distinct tag ⇒ overflow
                        f"row {i} overflows the {pad_width}-line pad")
                b = (b + 1) % pad_width
            if keys[b] == -1:
                keys[b] = col
                placed += 1
        row_nnz[i] = placed
        occupancy_peak = max(occupancy_peak, placed)
    record_count("hashpad.rows", int(n_rows))
    record_count("hashpad.probes", int(probes))
    record_value("hashpad.occupancy_peak", occupancy_peak / pad_width)
    return row_nnz, {"probes": probes, "occupancy_peak": occupancy_peak}


# ---------------------------------------------------------------------------
# Merge-based symbolic phase (the exact structure the numeric phases fill)
# ---------------------------------------------------------------------------

def _b_csr(b_rows: np.ndarray, b_cols: np.ndarray, n_inner: int):
    """CSR view of B: (order, cols_sorted, deg, indptr) — the one layout
    both the pp expansion and the slab scatter walk over (stable sort, so
    the two consumers index identical positions)."""
    order = np.argsort(b_rows, kind="stable")
    deg = np.bincount(b_rows, minlength=n_inner)
    indptr = np.zeros(n_inner + 1, np.int64)
    np.cumsum(deg, out=indptr[1:])
    return order, b_cols[order], deg, indptr


def _expand_b_rows(keys: np.ndarray, deg: np.ndarray, indptr: np.ndarray):
    """Positions (into the CSR order) of every nnz of B rows ``keys``,
    concatenated — the vectorized Gustavson expansion.  → (pos, lens,
    total)."""
    lens = deg[keys]
    total = int(lens.sum())
    starts = np.repeat(indptr[keys], lens)
    offs = np.arange(total, dtype=np.int64) \
        - np.repeat(np.cumsum(lens) - lens, lens)
    return starts + offs, lens, total

@dataclasses.dataclass(frozen=True)
class SpgemmSymbolic:
    """Host-side symbolic result for C = A@B (all numpy)."""

    n_rows: int             # rows of A and C
    n_inner: int            # cols of A == rows of B
    n_cols: int             # cols of B and C
    nnz_a: int
    nnz_b: int
    c_indptr: np.ndarray    # (n_rows+1,) int64 — CSR row pointers of C
    c_row: np.ndarray       # (nnz_out,) row-major sorted
    c_col: np.ndarray       # (nnz_out,)
    pp_a: np.ndarray        # (pp_interim,) index into A's nnz per pp
    pp_b: np.ndarray        # (pp_interim,) index into B's nnz per pp
    pp_slot: np.ndarray     # (pp_interim,) output slot each pp folds into
    # B's CSR view (the expansion walked it once — consumers reuse it
    # instead of re-sorting; see _b_csr)
    b_order: Optional[np.ndarray] = None
    b_cols_sorted: Optional[np.ndarray] = None
    b_deg: Optional[np.ndarray] = None
    b_indptr: Optional[np.ndarray] = None

    @property
    def nnz_out(self) -> int:
        return self.c_row.size

    @property
    def pp_interim(self) -> int:
        return self.pp_a.size

    @property
    def row_nnz(self) -> np.ndarray:
        return np.diff(self.c_indptr)

    @property
    def bloat_pct(self) -> float:
        return bloat_percent(self.pp_interim, self.nnz_out)


def symbolic(a_rows: np.ndarray, a_cols: np.ndarray, n_rows: int,
             b_rows: np.ndarray, b_cols: np.ndarray, n_inner: int,
             n_cols: Optional[int] = None) -> SpgemmSymbolic:
    """Exact Gustavson symbolic phase: one vectorized CSR walk.

    Expands every partial product ``A[i,k]·B[k,j]`` (the paper's interim
    set — Eq. 1's numerator) and merges by output coordinate.  Same
    expansion as ``neurasim.model.stats_from_coo``, but the maps are kept:
    ``pp_a``/``pp_b``/``pp_slot`` are what the numeric reference executor
    folds over in rolling-eviction waves.
    """
    a_rows = np.asarray(a_rows, np.int64)
    a_cols = np.asarray(a_cols, np.int64)
    b_rows = np.asarray(b_rows, np.int64)
    b_cols = np.asarray(b_cols, np.int64)
    n_cols = int(n_cols) if n_cols is not None else int(n_inner)
    if a_rows.size and int(a_rows.max()) >= n_rows:
        raise ValueError("a_rows exceed n_rows")
    if a_cols.size and int(a_cols.max()) >= n_inner:
        raise ValueError("a_cols exceed the inner dimension")
    if b_rows.size and int(b_rows.max()) >= n_inner:
        raise ValueError("b_rows exceed the inner dimension")
    if b_cols.size and int(b_cols.max()) >= n_cols:
        raise ValueError("b_cols exceed n_cols")

    b_order, b_cols_sorted, deg_b, b_indptr = _b_csr(b_rows, b_cols, n_inner)
    b_pos, lens, total = _expand_b_rows(a_cols, deg_b, b_indptr)
    if total > MAX_PP_INT32:
        raise ValueError(f"{total} interim partial products overflow int32 "
                         "slot maps; shard the matrix first")
    pp_a = np.repeat(np.arange(a_rows.size, dtype=np.int64), lens)
    pp_b = b_order[b_pos]
    pp_row = a_rows[pp_a]
    pp_col = b_cols_sorted[b_pos]

    keys = pp_row * np.int64(n_cols) + pp_col
    uniq, pp_slot = np.unique(keys, return_inverse=True)
    c_row = (uniq // n_cols).astype(np.int64)
    c_col = (uniq % n_cols).astype(np.int64)
    c_indptr = np.searchsorted(c_row, np.arange(n_rows + 1))
    return SpgemmSymbolic(
        n_rows=int(n_rows), n_inner=int(n_inner), n_cols=n_cols,
        nnz_a=int(a_rows.size), nnz_b=int(b_rows.size),
        c_indptr=c_indptr, c_row=c_row, c_col=c_col,
        pp_a=pp_a, pp_b=pp_b, pp_slot=pp_slot.astype(np.int64),
        b_order=b_order, b_cols_sorted=b_cols_sorted, b_deg=deg_b,
        b_indptr=b_indptr)


# ---------------------------------------------------------------------------
# SpgemmPlan — the device-side package (pytree, like sparse.plan)
# ---------------------------------------------------------------------------

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class SpgemmPlan:
    """Precomputed layouts for every SpGEMM executor (see numeric.py).

    Mirrors ``sparse.plan.AggregationPlan``: arrays are pytree leaves,
    sizes are static aux data, so plans pass through ``jax.jit``.
    Structure is baked at plan time; *values* (``a_vals``/``b_vals``) may be
    swapped per call — ``None`` uses the baked ``a_base``/``b_base``.
    """

    # --- static layout sizes (pytree aux data) ---
    n_rows: int
    n_inner: int
    n_cols: int
    nnz_a: int
    nnz_b: int
    nnz_out: int
    pp_interim: int          # Eq.-1 interim partial products (exact)
    pp_dedup: int            # slab entries after operand dedup (≤ pp_interim)
    pad_width: int           # hash-pad lanes per output row (power of two)
    block_rows: int
    n_blocks: int
    n_chunks: int
    width: int               # distinct operands per chunk (A-side layout)
    chunk: int               # reference executor's rolling-eviction wave
    n_waves: int
    reseeds: int             # γ draws burned by the injectivity search
    collisions: int          # bucket collisions seen during the search
    pad_growths: int         # ×2 pad expansions before every block seeded

    # --- COO inputs (structure; values are the *_base defaults) ---
    a_rows: Optional[Array] = None     # (nnz_a,) int32
    a_cols: Optional[Array] = None     # (nnz_a,) int32
    a_base: Optional[Array] = None     # (nnz_a,) f32
    b_rows: Optional[Array] = None     # (nnz_b,) int32
    b_cols: Optional[Array] = None     # (nnz_b,) int32
    b_base: Optional[Array] = None     # (nnz_b,) f32

    # --- symbolic output structure ---
    c_indptr: Optional[Array] = None   # (n_rows+1,) int32
    c_row: Optional[Array] = None      # (nnz_out,) int32
    c_col: Optional[Array] = None      # (nnz_out,) int32

    # --- reference executor: pp maps, padded to a chunk multiple ---
    pp_a: Optional[Array] = None       # (n_waves·chunk,) int32
    pp_b: Optional[Array] = None       # (n_waves·chunk,) int32
    pp_slot: Optional[Array] = None    # (n_waves·chunk,) int32; pad ⇒ ghost

    # --- pallas executor: A coefficient tiles + hashed B slab + gather ---
    ell_u_cols: Optional[Array] = None    # (n_chunks, width) int32
    ell_a: Optional[Array] = None         # (n_chunks·block_rows, width) f32
    ell_out_block: Optional[Array] = None  # (n_chunks,) int32
    ell_first: Optional[Array] = None     # (n_chunks,) int32
    ell_evict: Optional[Array] = None     # (n_chunks,) int32 — row completion
    ell_slots: Optional[Array] = None     # (nnz_a,) int32 into ell_a flat
    slab_row: Optional[Array] = None      # (pp_dedup,) int32 — slab lane
    slab_col: Optional[Array] = None      # (pp_dedup,) int32 — pad bucket
    slab_src: Optional[Array] = None      # (pp_dedup,) int32 into b vals
    out_row: Optional[Array] = None       # (nnz_out,) int32 into c_pad rows
    out_bucket: Optional[Array] = None    # (nnz_out,) int32 into pad lanes
    gammas: Optional[Array] = None        # (n_blocks,) uint32 — per-block γ

    # --- pallas_q8 executor: baked int8 tiles + quantized hashed slab -----
    # (per-chunk symmetric scales; the default-values fast path skips the
    # runtime slab scatter entirely — see numeric._pallas_q8_spgemm)
    ell_a_q8: Optional[Array] = None      # (n_chunks·block_rows, width) int8
    ell_a_scale: Optional[Array] = None   # (n_chunks,) f32
    slab_q8: Optional[Array] = None       # (n_chunks·width, pad_width) int8
    slab_scale: Optional[Array] = None    # (n_chunks,) f32

    @property
    def bloat_pct(self) -> float:
        return bloat_percent(self.pp_interim, self.nnz_out)

    @property
    def peak_live_pp(self) -> dict:
        """Live interim partial products per schedule — the Fig-15 contrast:
        ``barrier`` holds the whole bloat, ``rolling`` one wave, ``hashpad``
        one resident pad tile + one landing slab tile."""
        return {
            "barrier": self.pp_interim,
            "rolling": min(self.chunk, self.pp_interim),
            "hashpad": (self.block_rows + self.width) * self.pad_width,
        }


_SP_LEAF_FIELDS = (
    "a_rows", "a_cols", "a_base", "b_rows", "b_cols", "b_base",
    "c_indptr", "c_row", "c_col", "pp_a", "pp_b", "pp_slot",
    "ell_u_cols", "ell_a", "ell_out_block", "ell_first", "ell_evict",
    "ell_slots", "slab_row", "slab_col", "slab_src", "out_row", "out_bucket",
    "gammas", "ell_a_q8", "ell_a_scale", "slab_q8", "slab_scale",
)
_SP_AUX_FIELDS = (
    "n_rows", "n_inner", "n_cols", "nnz_a", "nnz_b", "nnz_out",
    "pp_interim", "pp_dedup", "pad_width", "block_rows", "n_blocks",
    "n_chunks", "width", "chunk", "n_waves", "reseeds", "collisions",
    "pad_growths",
)


def _sp_flatten(p: SpgemmPlan):
    return (tuple(getattr(p, f) for f in _SP_LEAF_FIELDS),
            tuple(getattr(p, f) for f in _SP_AUX_FIELDS))


def _sp_unflatten(aux, leaves):
    return SpgemmPlan(**dict(zip(_SP_AUX_FIELDS, aux)),
                      **dict(zip(_SP_LEAF_FIELDS, leaves)))


jax.tree_util.register_pytree_node(SpgemmPlan, _sp_flatten, _sp_unflatten)


# ---------------------------------------------------------------------------
# Plan builder
# ---------------------------------------------------------------------------

def _i32(x) -> Array:
    return jnp.asarray(np.asarray(x, np.int32))


ALL_SPGEMM_EXECUTORS = ("dense", "reference", "pallas", "pallas_q8")


def make_spgemm_plan(a_rows: np.ndarray, a_cols: np.ndarray, n_rows: int,
                     b_rows: np.ndarray, b_cols: np.ndarray, n_inner: int,
                     n_cols: Optional[int] = None, *,
                     a_vals: Optional[np.ndarray] = None,
                     b_vals: Optional[np.ndarray] = None,
                     executors: Sequence[str] = ALL_SPGEMM_EXECUTORS,
                     block_rows: int = 8, width_cap: int = 128,
                     width_multiple: Optional[int] = None,
                     chunk: int = 8192,
                     pad_slack: float = 2.0, max_reseeds: int = 8,
                     max_pad_width: int = 1 << 16,
                     seed: int = 0) -> SpgemmPlan:
    """Symbolic phase + the requested numeric layouts, packaged once.

    A is (n_rows × n_inner), B is (n_inner × n_cols), both COO; ``*_vals``
    default to implicit 1.0 (unweighted adjacency).  Builds the exact
    output CSR structure (always — the ``dense`` oracle needs nothing
    more), plus, per ``executors`` (mirroring ``make_plan``'s
    ``backends=``):

    * ``reference`` — the chunk-padded pp → slot wave maps
      (O(pp_interim) host+device memory — the Table-1 bloat itself);
    * ``pallas`` — the hash-pad layout: A packed into PR-2 dedup-chunk
      coefficient tiles, per-block γ found by reseeded search, B's rows
      hashed into a per-chunk slab scatter map, and the pad → C gather.
    """
    for ex in executors:
        if ex not in ALL_SPGEMM_EXECUTORS:
            raise KeyError(f"unknown spgemm executor {ex!r}; have "
                           f"{ALL_SPGEMM_EXECUTORS}")
    a_rows = np.asarray(a_rows, np.int64)
    a_cols = np.asarray(a_cols, np.int64)
    b_rows = np.asarray(b_rows, np.int64)
    b_cols = np.asarray(b_cols, np.int64)
    av = (np.ones(a_rows.size, np.float32) if a_vals is None
          else np.asarray(a_vals, np.float32))
    bv = (np.ones(b_rows.size, np.float32) if b_vals is None
          else np.asarray(b_vals, np.float32))
    sym = symbolic(a_rows, a_cols, n_rows, b_rows, b_cols, n_inner, n_cols)
    pp = sym.pp_interim
    kw = dict(
        n_rows=sym.n_rows, n_inner=sym.n_inner, n_cols=sym.n_cols,
        nnz_a=sym.nnz_a, nnz_b=sym.nnz_b, nnz_out=sym.nnz_out,
        pp_interim=pp,
        a_rows=_i32(a_rows), a_cols=_i32(a_cols), a_base=jnp.asarray(av),
        b_rows=_i32(b_rows), b_cols=_i32(b_cols), b_base=jnp.asarray(bv),
        c_indptr=_i32(sym.c_indptr), c_row=_i32(sym.c_row),
        c_col=_i32(sym.c_col),
        pp_dedup=0, pad_width=0, block_rows=int(block_rows), n_blocks=0,
        n_chunks=0, width=0, chunk=max(1, min(int(chunk), max(pp, 1))),
        n_waves=0, reseeds=0, collisions=0, pad_growths=0)

    if "reference" in executors:
        # pp → slot maps padded to a wave multiple (ghost slot for padding)
        chunk_eff = kw["chunk"]
        n_waves = -(-pp // chunk_eff) if pp else 0
        pp_pad = n_waves * chunk_eff
        pp_a = np.zeros(pp_pad, np.int64)
        pp_b = np.zeros(pp_pad, np.int64)
        pp_slot = np.full(pp_pad, sym.nnz_out, np.int64)
        pp_a[:pp], pp_b[:pp], pp_slot[:pp] = sym.pp_a, sym.pp_b, sym.pp_slot
        kw.update(n_waves=int(n_waves), pp_a=_i32(pp_a), pp_b=_i32(pp_b),
                  pp_slot=_i32(pp_slot))

    if "pallas" in executors or "pallas_q8" in executors:
        # --- A coefficient tiles (PR-2 packer) ----------------------------
        from repro.sparse.graph import pack_dedup_chunks
        ch = pack_dedup_chunks(a_rows, a_cols, av, int(n_rows),
                               int(n_inner), block_rows=block_rows,
                               width_cap=width_cap,
                               width_multiple=width_multiple)
        n_chunks, width = ch.u_cols.shape
        evict = np.ones(n_chunks, np.int32)
        evict[:-1] = (ch.out_block[1:] != ch.out_block[:-1]).astype(np.int32)

        # --- per-block γ: reseed until injective, grow the pad on failure -
        max_row = int(sym.row_nnz.max(initial=0))
        pad_width = _next_pow2(max(int(max_row * pad_slack), 8))
        growths = 0
        reseeds = 0      # accumulated across pad growths — the full search
        collisions = 0
        while True:
            gammas, att_reseeds, att_collisions = find_block_gammas(
                sym.c_indptr, sym.c_col, int(n_rows), block_rows, pad_width,
                max_reseeds=max_reseeds, seed=seed + growths)
            reseeds += att_reseeds
            collisions += att_collisions
            if gammas is not None:
                break
            pad_width *= 2
            growths += 1
            if pad_width > max_pad_width:
                raise ValueError(
                    f"no injective bucket map below pad_width="
                    f"{max_pad_width}; raise max_pad_width or shard the "
                    "rows")

        # --- hashed B slab: one scatter map entry per dedup'd pp ----------
        lane_live = np.arange(width)[None, :] < ch.remaining[:, None]
        lane_flat = (np.arange(n_chunks)[:, None] * width
                     + np.arange(width)[None, :])[lane_live]
        ks = ch.u_cols[lane_live].astype(np.int64)      # B row per lane
        g_lane = np.repeat(gammas[ch.out_block], ch.remaining)
        b_pos, lens, total = _expand_b_rows(ks, sym.b_deg, sym.b_indptr)
        slab_src = sym.b_order[b_pos]
        slab_row = np.repeat(lane_flat, lens)
        slab_col = hash_bucket(sym.b_cols_sorted[b_pos],
                               np.repeat(g_lane, lens), pad_width)

        # --- pad → C gather -----------------------------------------------
        out_bucket = hash_bucket(sym.c_col,
                                 gammas[sym.c_row // block_rows], pad_width)
        record_count("spgemm.plans")
        record_count("spgemm.reseeds", reseeds)
        record_count("spgemm.collisions", collisions)
        record_count("spgemm.pad_growths", growths)
        record_value("spgemm.pad_width", pad_width)
        record_value("spgemm.pad_occupancy", max_row / pad_width)
        record_value("spgemm.bloat_pct", sym.bloat_pct)
        record_value("spgemm.chunk_width", width)
        kw.update(
            pp_dedup=int(total), pad_width=int(pad_width),
            n_blocks=int(ch.n_blocks), n_chunks=int(n_chunks),
            width=int(width), reseeds=int(reseeds),
            collisions=int(collisions), pad_growths=int(growths),
            ell_u_cols=jnp.asarray(ch.u_cols), ell_a=jnp.asarray(ch.a),
            ell_out_block=jnp.asarray(ch.out_block),
            ell_first=jnp.asarray(ch.first), ell_evict=jnp.asarray(evict),
            ell_slots=jnp.asarray(ch.slots),
            slab_row=_i32(slab_row), slab_col=_i32(slab_col),
            slab_src=_i32(slab_src),
            out_row=_i32(sym.c_row), out_bucket=_i32(out_bucket),
            gammas=jnp.asarray(gammas))

        if "pallas_q8" in executors:
            # bake the int8 layouts for the default-values path: quantized
            # A tiles AND the fully-materialized quantized slab — the q8
            # executor then skips the runtime slab scatter the f32 path
            # pays every call (structure is plan state, values are data)
            from repro.sparse.quantize import quantize_chunk_tiles
            a_q8, a_scale = quantize_chunk_tiles(kw["ell_a"], int(n_chunks))
            slab_f32 = np.zeros((n_chunks * width, pad_width), np.float32)
            np.add.at(slab_f32, (slab_row, slab_col), bv[slab_src])
            slab_q8, slab_scale = quantize_chunk_tiles(
                jnp.asarray(slab_f32), int(n_chunks))
            kw.update(ell_a_q8=a_q8, ell_a_scale=a_scale,
                      slab_q8=slab_q8, slab_scale=slab_scale)

    return SpgemmPlan(**kw)
