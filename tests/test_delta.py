"""Streaming graph deltas: incremental re-pack parity vs cold packing.

The live-mutation contract (DESIGN.md §16): after ANY interleaving of edge
inserts/deletes and epoch flushes, the incrementally maintained CSR and
dedup-chunk layouts are plan-equal to a cold ``plan_from_graph`` over the
compacted edge arrays — structure bitwise, aggregates within 1e-5 — and the
dedup-chunk stats (chunk count, width, hub splits) agree exactly.

Property tests run under real ``hypothesis`` when installed, else the
deterministic shim."""
import numpy as np
import pytest

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:                                       # pragma: no cover
    from _hypothesis_shim import given, settings, st

import jax.numpy as jnp

from repro.sparse import backend as sb
from repro.sparse.delta import (DeltaGraphError, DeltaGraphState,
                                chunks_match, plans_match)
from repro.sparse.graph import coo_to_csr

N = 24          # node count: small enough that collisions/hubs are common


def _seed_graph(seed, e=64):
    rng = np.random.default_rng(seed)
    s = rng.integers(0, N, e)
    r = rng.integers(0, N, e)
    w = rng.normal(size=e).astype(np.float32)
    return s, r, w, rng


def _assert_cold_parity(d: DeltaGraphState, seed=0):
    # CSR bitwise vs a cold sort of the compacted canonical arrays
    indptr, indices = d.csr()
    ci, cc, _ = coo_to_csr(d._s, d._r, d.n_nodes)
    np.testing.assert_array_equal(indptr, ci)
    np.testing.assert_array_equal(indices, cc)
    # chunk layouts bitwise vs a cold pack
    for inc, cold in zip(d.repack(), d.cold_repack()):
        ok, detail = chunks_match(inc, cold)
        assert ok, detail
    # full plan parity + aggregate parity through a real executor
    pa, pb = d.plan(), d.cold_plan()
    ok, detail = plans_match(pa, pb)
    assert ok, detail
    rng = np.random.default_rng(seed + 999)
    x = jnp.asarray(rng.normal(size=(pa.n_rows, 8)).astype(np.float32))
    for be in ("chunked", "pallas"):
        ya = np.asarray(sb.aggregate(pa, None, x, backend=be))
        yb = np.asarray(sb.aggregate(pb, None, x, backend=be))
        np.testing.assert_allclose(ya, yb, atol=1e-5)
    # stats the plan records must agree with make_plan's view
    stats = d.chunk_stats()
    fwd_cold = d.cold_repack()[0]
    assert stats["n_chunks"] == fwd_cold.u_cols.shape[0]
    assert stats["chunk_width"] == fwd_cold.u_cols.shape[1]


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 10_000),
       st.lists(st.sampled_from(["ins", "del", "flush"]),
                min_size=4, max_size=40))
def test_random_interleaving_matches_cold_pack(seed, script):
    s, r, w, rng = _seed_graph(seed)
    d = DeltaGraphState(s, r, N, weights=w)
    for op in script:
        if op == "ins":
            d.insert_edge(int(rng.integers(0, N)), int(rng.integers(0, N)),
                          float(rng.normal()))
        elif op == "del" and d.n_edges + d.pending > 1:
            # delete a live edge (range-validated booking raises on absent)
            k = int(rng.integers(0, d._s.size))
            try:
                d.delete_edge(int(d._s[k]), int(d._r[k]))
            except DeltaGraphError:
                pass          # every copy already booked for deletion
        else:
            d.flush()
    d.flush()
    _assert_cold_parity(d, seed)


def test_empty_delta_flush_is_identity():
    s, r, w, _ = _seed_graph(3)
    d = DeltaGraphState(s, r, N, weights=w)
    before = d.csr()
    res = d.flush()                       # nothing buffered
    assert (res.inserted, res.deleted, res.dirty_blocks) == (0, 0, 0)
    assert res.epoch == 1
    after = d.csr()
    np.testing.assert_array_equal(before[0], after[0])
    np.testing.assert_array_equal(before[1], after[1])
    _assert_cold_parity(d)


def test_delete_all_edges_of_a_row():
    s, r, w, _ = _seed_graph(5, e=48)
    d = DeltaGraphState(s, r, N, weights=w)
    row = int(r[0])                        # receiver row = CSR row
    for k in np.nonzero(r == row)[0]:
        d.delete_edge(int(s[k]), int(r[k]))
    d.flush()
    indptr, _ = d.csr()
    assert indptr[row + 1] - indptr[row] == 0
    _assert_cold_parity(d)


def test_delete_every_edge_then_rebuild():
    s, r, w, rng = _seed_graph(7, e=20)
    d = DeltaGraphState(s, r, N, weights=w)
    for k in range(s.size):
        d.delete_edge(int(s[k]), int(r[k]))
    d.flush()
    assert d.n_edges == 0
    _assert_cold_parity(d)
    for _ in range(16):
        d.insert_edge(int(rng.integers(0, N)), int(rng.integers(0, N)))
    d.flush()
    assert d.n_edges == 16
    _assert_cold_parity(d)


def test_delete_absent_edge_raises_and_leaves_state_clean():
    d = DeltaGraphState(np.array([0, 1]), np.array([1, 2]), 4)
    with pytest.raises(DeltaGraphError):
        d.delete_edge(3, 3)
    d.delete_edge(0, 1)
    with pytest.raises(DeltaGraphError):
        d.delete_edge(0, 1)                # only copy already booked
    assert d.pending == 1
    d.flush()
    assert d.n_edges == 1
    _assert_cold_parity(d)


def test_insert_cancelled_by_delete_before_flush():
    d = DeltaGraphState(np.array([0]), np.array([1]), 4)
    d.insert_edge(2, 3)
    d.delete_edge(2, 3)                    # cancels the pending insert
    assert d.pending == 0
    d.flush()
    assert d.n_edges == 1
    _assert_cold_parity(d)


def test_out_of_range_mutations_rejected():
    d = DeltaGraphState(np.array([0]), np.array([1]), 4)
    with pytest.raises(DeltaGraphError):
        d.insert_edge(4, 0)
    with pytest.raises(DeltaGraphError):
        d.insert_edge(0, -1)


def test_distributed_backend_has_no_delta_path():
    s, r, w, _ = _seed_graph(11)
    d = DeltaGraphState(s, r, N, weights=w)
    with pytest.raises(DeltaGraphError):
        d.plan(backends=("dense", "distributed"))


def test_incremental_beats_cold_on_sparse_deltas():
    """Small deltas on a big graph re-chunk only the dirty blocks, and over
    several epochs of inserts and deletes the incremental layouts stay
    bitwise equal to a cold pack."""
    rng = np.random.default_rng(0)
    n, e = 4096, 60_000
    d = DeltaGraphState(rng.integers(0, n, e), rng.integers(0, n, e), n)
    for _ in range(3):
        for _ in range(32):
            d.insert_edge(int(rng.integers(0, n)), int(rng.integers(0, n)))
        for _ in range(10):
            k = int(rng.integers(0, d._s.size))
            try:
                d.delete_edge(int(d._s[k]), int(d._r[k]))
            except DeltaGraphError:
                pass               # every copy of that edge already booked
        res = d.flush()
        assert res.dirty_blocks < res.clean_blocks
        for inc, cold in zip(d.repack(), d.cold_repack()):
            ok, detail = chunks_match(inc, cold)
            assert ok, detail
    ok, detail = plans_match(d.plan(), d.cold_plan())
    assert ok, detail
