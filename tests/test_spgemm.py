"""Decoupled SpGEMM (paper C1), rolling eviction (C3), and the
sparse-output SpGEMM engine (symbolic + numeric phases, DESIGN.md §9)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:  # deterministic fallback; requirements-dev.txt has the real one
    from _hypothesis_shim import given, settings, st

from repro.core import eviction, spgemm
from repro.sparse import backend as sb
from repro.sparse.spgemm import (hash_bucket, hash_dedup_row_nnz,
                                 make_spgemm_plan, symbolic, two_hop_graph)


def _dense_ref(rows, cols, vals, x, n):
    d = np.zeros((n, n), np.float32)
    np.add.at(d, (rows, cols), vals)
    return d @ x


@given(st.integers(4, 60), st.integers(1, 300), st.integers(1, 32),
       st.integers(0, 2**31 - 1))
@settings(max_examples=30, deadline=None)
def test_decoupled_spmm_matches_dense(n, e, d, seed):
    rng = np.random.default_rng(seed)
    rows = rng.integers(0, n, e)
    cols = rng.integers(0, n, e)
    vals = rng.normal(size=e).astype(np.float32)
    x = rng.normal(size=(n, d)).astype(np.float32)
    y = spgemm.spmm(jnp.asarray(rows), jnp.asarray(cols), jnp.asarray(vals),
                    jnp.asarray(x), n)
    np.testing.assert_allclose(np.asarray(y), _dense_ref(rows, cols, vals, x, n),
                               rtol=2e-4, atol=2e-4)


@given(st.integers(0, 2**31 - 1), st.sampled_from([16, 64, 128]))
@settings(max_examples=15, deadline=None)
def test_rolling_eviction_equals_full(seed, chunk):
    """C3 invariant: chunked accumulation == one-shot accumulation."""
    rng = np.random.default_rng(seed)
    n, e, d = 40, 512, 8
    rows = jnp.asarray(rng.integers(0, n, e))
    cols = jnp.asarray(rng.integers(0, n, e))
    vals = jnp.asarray(rng.normal(size=e).astype(np.float32))
    x = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    full = spgemm.spmm(rows, cols, vals, x, n)
    chunked = spgemm.spmm_chunked(rows, cols, vals, x, n, chunk=chunk)
    np.testing.assert_allclose(np.asarray(full), np.asarray(chunked),
                               rtol=1e-5, atol=1e-5)


def test_masked_padding_contributes_nothing():
    rng = np.random.default_rng(0)
    n, e, d = 20, 100, 4
    rows = rng.integers(0, n, e)
    cols = rng.integers(0, n, e)
    vals = rng.normal(size=e).astype(np.float32)
    x = rng.normal(size=(n, d)).astype(np.float32)
    valid = np.ones(e, bool)
    valid[50:] = False
    y = spgemm.spmm_masked(jnp.asarray(rows), jnp.asarray(cols),
                           jnp.asarray(vals), jnp.asarray(x), n,
                           jnp.asarray(valid))
    ref = _dense_ref(rows[:50], cols[:50], vals[:50], x, n)
    np.testing.assert_allclose(np.asarray(y), ref, rtol=1e-5, atol=1e-5)


def test_bloat_percent_eq1():
    """Paper Eq. (1) on a hand-checkable case."""
    assert eviction.bloat_percent(100, 50) == 100.0
    assert eviction.bloat_percent(50, 50) == 0.0


def test_interim_pp_and_output_nnz_tiny():
    # A = [[1,1],[0,1]] (COO), A@A: pp = row-wise expansion count
    rows = np.array([0, 0, 1])
    cols = np.array([0, 1, 1])
    pp = eviction.interim_pp_count(cols, np.bincount(rows, minlength=2))
    # row0 of A references B rows 0 (2 nnz) and 1 (1 nnz); row1 → B row 1
    assert pp == 2 + 1 + 1
    nnz = eviction.output_nnz(rows, cols, rows, cols, 2, 2)
    assert nnz == 3  # [[1,2],[0,1]]
    # the historical core.spgemm entry delegates to the same count
    assert spgemm.interim_partial_products(
        cols, np.bincount(rows, minlength=2)) == pp


def test_spgemm_via_dense_size_guard():
    """The densifying oracle refuses anything beyond tiny sizes."""
    a = jnp.zeros((1,), jnp.int32)
    v = jnp.ones((1,), jnp.float32)
    with pytest.raises(ValueError, match="sparse-output engine"):
        spgemm.spgemm_via_dense(a, a, v, 1, a, a, v, 1 << 13, 1 << 13)


# ---------------------------------------------------------------------------
# Sparse-output SpGEMM engine: symbolic phase
# ---------------------------------------------------------------------------

def _coo(rng, n_rows, n_cols, e):
    return (rng.integers(0, n_rows, e), rng.integers(0, n_cols, e),
            rng.normal(size=e).astype(np.float32))


def _dense_of(rows, cols, vals, n_rows, n_cols):
    d = np.zeros((n_rows, n_cols), np.float32)
    np.add.at(d, (rows, cols), vals)
    return d


@given(st.integers(2, 40), st.integers(2, 40), st.integers(2, 40),
       st.integers(0, 200), st.integers(0, 200), st.integers(0, 2**31 - 1))
@settings(max_examples=25, deadline=None)
def test_symbolic_structure_matches_dense_oracle(n, m, k, ea, eb, seed):
    """Property: symbolic row-nnz / structure == the boolean dense product,
    on fully random rectangular operands (including empty ones)."""
    rng = np.random.default_rng(seed)
    ar, ac, _ = _coo(rng, n, m, ea)
    br, bc, _ = _coo(rng, m, k, eb)
    sym = symbolic(ar, ac, n, br, bc, m, k)
    a = _dense_of(ar, ac, np.ones(ea, np.float32), n, m) > 0
    b = _dense_of(br, bc, np.ones(eb, np.float32), m, k) > 0
    c = a.astype(np.int64) @ b.astype(np.int64) > 0
    assert sym.nnz_out == int(c.sum())
    np.testing.assert_array_equal(sym.row_nnz, c.sum(1))
    assert c[sym.c_row, sym.c_col].all()
    # Eq.-1 interim count agrees with both existing implementations
    deg_b = np.bincount(br, minlength=m)
    assert sym.pp_interim == eviction.interim_pp_count(ac, deg_b)


def test_symbolic_matches_dense_on_powerlaw():
    from repro.data.synthetic import powerlaw_graph
    s, r = powerlaw_graph(300, 1800, seed=11)
    sym = symbolic(r, s, 300, r, s, 300)
    a = _dense_of(r, s, np.ones(r.size, np.float32), 300, 300) > 0
    c = a.astype(np.int64) @ a.astype(np.int64) > 0
    assert sym.nnz_out == int(c.sum())
    np.testing.assert_array_equal(sym.row_nnz, c.sum(1))


def test_symbolic_pp_matches_neurasim_walk():
    """Engine-measured stats == the independent NeuraSim Eq.-1 walk."""
    from repro.data.synthetic import powerlaw_graph
    from repro.neurasim.model import stats_from_coo
    s, r = powerlaw_graph(256, 1024, seed=4)
    sym = symbolic(s, r, 256, s, r, 256)
    w = stats_from_coo(s.astype(np.int64), r.astype(np.int64), 256)
    assert sym.pp_interim == w.pp_interim
    assert sym.nnz_out == w.nnz_out


@given(st.integers(0, 2**31 - 1), st.sampled_from([64, 128, 256]))
@settings(max_examples=10, deadline=None)
def test_hash_dedup_variant_matches_merge(seed, pad_width):
    """The HashPad-style linear-probe dedup discovers the same per-row
    output nnz as the merge (np.unique) symbolic phase."""
    rng = np.random.default_rng(seed)
    ar, ac, _ = _coo(rng, 24, 24, 120)
    br, bc, _ = _coo(rng, 24, 24, 120)
    sym = symbolic(ar, ac, 24, br, bc, 24)
    pp_row = sym.c_row[sym.pp_slot]
    pp_col = sym.c_col[sym.pp_slot]
    row_nnz, stats = hash_dedup_row_nnz(pp_row, pp_col, 24, pad_width,
                                        seed=seed)
    np.testing.assert_array_equal(row_nnz, sym.row_nnz)
    assert stats["occupancy_peak"] <= pad_width


def test_hash_dedup_high_bloat_row():
    """A row whose pp count exceeds the pad but whose *distinct* tags fit
    (the paper's high-bloat regime) must dedup fine; only a row with more
    distinct tags than pad lines overflows."""
    pp_row = np.zeros(70, np.int64)
    pp_col = np.arange(70, dtype=np.int64) % 10       # 70 pps, 10 distinct
    row_nnz, _ = hash_dedup_row_nnz(pp_row, pp_col, 1, 64)
    assert row_nnz[0] == 10
    with pytest.raises(ValueError, match="overflows"):
        hash_dedup_row_nnz(np.zeros(70, np.int64),
                           np.arange(70, dtype=np.int64), 1, 64)


def test_hash_bucket_reseed_at_adversarial_stride():
    """Columns sharing a power-of-two stride (the degenerate case for
    low-k-bit hashing) still get an injective per-block bucket map, and
    every executor stays exact."""
    n_cols = 16 << 16
    ar = np.zeros(16, np.int64)
    ac = np.arange(16, dtype=np.int64)
    br = np.arange(16, dtype=np.int64)
    bc = np.arange(16, dtype=np.int64) << 16      # stride 2^16 columns
    plan = make_spgemm_plan(ar, ac, 4, br, bc, 16, n_cols)
    assert plan.nnz_out == 16
    gammas = np.asarray(plan.gammas)
    assert (gammas % 2 == 1).all()                # odd ⇒ bijective mod 2^32
    buckets = hash_bucket(np.asarray(plan.c_col), gammas[0], plan.pad_width)
    assert np.unique(buckets).size == 16          # injective on the row set
    for name in sb.ALL_SPGEMM_BACKENDS:
        np.testing.assert_allclose(
            np.asarray(sb.spgemm(plan, backend=name)), np.ones(16),
            rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# Numeric phase: executor parity against the dense oracle
# ---------------------------------------------------------------------------

def _full_parity(plan, c_vals, dense_c, tol=1e-4):
    """Scatter the sparse result into dense and compare EVERYWHERE — also
    catches mass leaking off the symbolic structure."""
    got = np.zeros_like(dense_c)
    got[np.asarray(plan.c_row), np.asarray(plan.c_col)] = np.asarray(c_vals)
    np.testing.assert_allclose(got, dense_c, rtol=tol, atol=tol)


@pytest.mark.parametrize("backend", ["reference", "pallas"])
def test_spgemm_executor_parity_powerlaw(backend):
    from repro.data.synthetic import powerlaw_graph
    rng = np.random.default_rng(0)
    n = 200
    s, r = powerlaw_graph(n, 1200, seed=9)
    av = rng.normal(size=s.size).astype(np.float32)
    bv = rng.normal(size=s.size).astype(np.float32)
    plan = make_spgemm_plan(r, s, n, r, s, n, a_vals=av, b_vals=bv,
                            chunk=256)
    c = sb.spgemm(plan, backend=backend)
    dense_c = _dense_of(r, s, av, n, n) @ _dense_of(r, s, bv, n, n)
    _full_parity(plan, c, dense_c)
    assert plan.pp_dedup <= plan.pp_interim     # operand reuse never inflates


@pytest.mark.parametrize("backend", sb.ALL_SPGEMM_BACKENDS)
def test_spgemm_rectangular_and_value_override(backend):
    """Structure is plan state, values are data: same plan, fresh values."""
    from repro.sparse import quantize

    rng = np.random.default_rng(3)
    n, m, k = 24, 50, 9
    ar, ac, av = _coo(rng, n, m, 90)
    br, bc, bv = _coo(rng, m, k, 70)
    plan = make_spgemm_plan(ar, ac, n, br, bc, m, k, a_vals=av, b_vals=bv,
                            chunk=64)
    # the quantized executor is exact only up to its scale-derived bound
    # (tests/test_quantized.py gates that bound); f32 executors stay at 1e-4
    tol = 1e-4
    if backend == "pallas_q8":
        tol = 1.01 * float(quantize.spgemm_q8_bound(
            plan.width, plan.ell_out_block, plan.n_blocks,
            plan.ell_a_scale, plan.slab_scale))
    _full_parity(plan, sb.spgemm(plan, backend=backend),
                 _dense_of(ar, ac, av, n, m) @ _dense_of(br, bc, bv, m, k),
                 tol=tol)
    av2 = rng.normal(size=av.size).astype(np.float32)
    c2 = sb.spgemm(plan, jnp.asarray(av2), None, backend=backend)
    _full_parity(plan, c2,
                 _dense_of(ar, ac, av2, n, m) @ _dense_of(br, bc, bv, m, k),
                 tol=tol)


@pytest.mark.parametrize("backend", sb.ALL_SPGEMM_BACKENDS)
def test_spgemm_empty_rows_and_all_zero_output(backend):
    # disjoint support ⇒ nnz_out == 0; executors return a (0,) result
    plan0 = make_spgemm_plan(np.array([0, 1]), np.array([2, 3]), 4,
                             np.array([0, 1]), np.array([0, 1]), 4, 4)
    assert plan0.nnz_out == 0
    assert sb.spgemm(plan0, backend=backend).shape == (0,)
    # fully empty operands
    empty = np.array([], np.int64)
    plan_e = make_spgemm_plan(empty, empty, 6, empty, empty, 6, 6)
    assert plan_e.nnz_out == 0 and plan_e.pp_interim == 0
    assert sb.spgemm(plan_e, backend=backend).shape == (0,)
    # rows of A with no nnz stay empty in C
    ar = np.array([2, 2, 5], np.int64)
    ac = np.array([0, 1, 1], np.int64)
    plan_r = make_spgemm_plan(ar, ac, 8, ar, ac, 8, 8)
    assert (np.diff(np.asarray(plan_r.c_indptr))[[0, 1, 3, 4, 6, 7]] == 0
            ).all()
    c = sb.spgemm(plan_r, backend=backend)
    dense_c = (_dense_of(ar, ac, np.ones(3, np.float32), 8, 8)
               @ _dense_of(ar, ac, np.ones(3, np.float32), 8, 8))
    _full_parity(plan_r, c, dense_c)


def test_spgemm_backend_registry():
    with pytest.raises(KeyError, match="unknown spgemm backend"):
        sb.get_spgemm_backend("nope")
    with pytest.raises(ValueError, match="a_vals"):
        plan = make_spgemm_plan(np.array([0]), np.array([0]), 2,
                                np.array([0]), np.array([0]), 2, 2)
        sb.spgemm(plan, jnp.ones((5,), jnp.float32))


def test_spgemm_plan_lazy_executor_layouts():
    """executors= builds only the requested layouts; running an executor
    whose layout is missing is a loud error, never a silent zero."""
    rng = np.random.default_rng(6)
    ar, ac, av = _coo(rng, 16, 16, 40)
    ref_only = make_spgemm_plan(ar, ac, 16, ar, ac, 16, 16, a_vals=av,
                                b_vals=av, executors=("reference",))
    assert ref_only.ell_a is None and ref_only.pad_width == 0
    dense_c = _dense_of(ar, ac, av, 16, 16) @ _dense_of(ar, ac, av, 16, 16)
    _full_parity(ref_only, sb.spgemm(ref_only, backend="reference"),
                 dense_c)
    _full_parity(ref_only, sb.spgemm(ref_only, backend="dense"), dense_c)
    with pytest.raises(ValueError, match="'pallas' layout"):
        sb.spgemm(ref_only, backend="pallas")
    pallas_only = make_spgemm_plan(ar, ac, 16, ar, ac, 16, 16, a_vals=av,
                                   b_vals=av, executors=("pallas",))
    assert pallas_only.pp_a is None
    _full_parity(pallas_only, sb.spgemm(pallas_only, backend="pallas"),
                 dense_c)
    with pytest.raises(ValueError, match="'reference' layout"):
        sb.spgemm(pallas_only, backend="reference")
    with pytest.raises(KeyError, match="unknown spgemm executor"):
        make_spgemm_plan(ar, ac, 16, ar, ac, 16, 16, executors=("nope",))


# ---------------------------------------------------------------------------
# Â²-powered workloads: two-hop aggregation + graph coarsening
# ---------------------------------------------------------------------------

def test_two_hop_graph_matches_dense_square():
    from repro.data.synthetic import powerlaw_graph
    from repro.sparse.graph import make_graph
    s, r = powerlaw_graph(150, 700, seed=5)
    g = make_graph(s, r, 150)
    g2 = two_hop_graph(g, backend="pallas")
    v = np.asarray(g.edge_valid)
    a = _dense_of(np.asarray(g.receivers)[v], np.asarray(g.senders)[v],
                  np.ones(int(v.sum()), np.float32), 150, 150)
    c = a @ a
    np.fill_diagonal(c, 0.0)                     # drop_self_loops default
    v2 = np.asarray(g2.edge_valid)
    got = _dense_of(np.asarray(g2.receivers)[v2],
                    np.asarray(g2.senders)[v2],
                    np.asarray(g2.edge_weight)[v2], 150, 150)
    np.testing.assert_allclose(got, c, rtol=1e-5, atol=1e-5)


def test_coarsen_graph_matches_dense():
    from repro.data.synthetic import powerlaw_graph
    from repro.sparse.graph import coarsen_graph, make_graph
    rng = np.random.default_rng(8)
    s, r = powerlaw_graph(120, 500, seed=8)
    w = rng.normal(size=s.size).astype(np.float32)
    g = make_graph(s, r, 120, edge_weight=w)
    clusters = rng.integers(0, 7, 120)
    gc = coarsen_graph(g, clusters, 7, backend="reference")
    v = np.asarray(g.edge_valid)
    a = _dense_of(np.asarray(g.receivers)[v], np.asarray(g.senders)[v],
                  np.asarray(g.edge_weight)[v], 120, 120)
    p = np.zeros((120, 7), np.float32)
    p[np.arange(120), clusters] = 1.0
    want = p.T @ a @ p
    vc = np.asarray(gc.edge_valid)
    got = _dense_of(np.asarray(gc.receivers)[vc],
                    np.asarray(gc.senders)[vc],
                    np.asarray(gc.edge_weight)[vc], 7, 7)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("backend", ["dense", "pallas"])
def test_gin_two_hop_trains_through_steps(backend):
    """Acceptance: two_hop mode trains GIN end-to-end through
    launch/steps.py — Â² precomputed once by the SpGEMM engine, every step
    plain SpMM on its plan; the loss must strictly decrease."""
    from repro.data.synthetic import powerlaw_graph
    from repro.launch import steps as steps_mod
    from repro.models.gnn import gin
    from repro.optim import adamw
    from repro.sparse.graph import make_graph
    s, r = powerlaw_graph(80, 320, seed=6)
    g = make_graph(s, r, 80)
    cfg = gin.GINConfig(d_in=6, d_hidden=12, n_classes=2, n_layers=2,
                        two_hop=True)
    params = gin.init_params(jax.random.key(0), cfg)
    rng = np.random.default_rng(1)
    n_pad = 81
    batch = {
        "x": jnp.asarray(rng.normal(size=(n_pad, 6)).astype(np.float32)),
        "senders": g.senders, "receivers": g.receivers,
        "edge_valid": g.edge_valid,
        "graph_ids": jnp.asarray((np.arange(n_pad) % 2).astype(np.int32)),
        "labels": jnp.asarray(np.array([0, 1], np.int32)),
    }
    step = jax.jit(steps_mod.build_gnn_step(
        "gin", cfg, None, {"n_graphs": 2}, adamw.AdamWConfig(lr=1e-3),
        backend=backend, graph=g))
    opt = adamw.init_state(params)
    losses = []
    for _ in range(4):
        params, opt, m = step(params, opt, batch)
        losses.append(float(m["loss"]))
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0]


def test_gin_two_hop_parity_across_backends():
    """The same two-hop step must produce identical losses on every local
    executor (the acceptance 1e-4 band)."""
    from repro.data.synthetic import powerlaw_graph
    from repro.launch import steps as steps_mod
    from repro.models.gnn import gin
    from repro.optim import adamw
    from repro.sparse.graph import make_graph
    s, r = powerlaw_graph(60, 240, seed=2)
    g = make_graph(s, r, 60)
    cfg = gin.GINConfig(d_in=5, d_hidden=8, n_classes=2, n_layers=2,
                        two_hop=True)
    params = gin.init_params(jax.random.key(1), cfg)
    rng = np.random.default_rng(2)
    batch = {
        "x": jnp.asarray(rng.normal(size=(61, 5)).astype(np.float32)),
        "senders": g.senders, "receivers": g.receivers,
        "edge_valid": g.edge_valid,
        "graph_ids": jnp.asarray(np.zeros(61, np.int32)),
        "labels": jnp.asarray(np.array([1], np.int32)),
    }
    losses = {}
    for backend in ("dense", "chunked", "pallas"):
        step = jax.jit(steps_mod.build_gnn_step(
            "gin", cfg, None, {"n_graphs": 1}, adamw.AdamWConfig(lr=1e-3),
            backend=backend, graph=g))
        _, _, m = step(params, adamw.init_state(params), batch)
        losses[backend] = float(m["loss"])
    ref = losses["dense"]
    for backend, loss in losses.items():
        # relative: the loss is ~3e3, where one f32 ulp is 2.4e-4
        assert loss == pytest.approx(ref, rel=1e-6), (backend, losses)


def test_two_hop_rejected_for_edge_value_models():
    from repro.launch import steps as steps_mod
    with pytest.raises(ValueError, match="two_hop"):
        steps_mod.build_gnn_step("gat-cora", object(), None,
                                 {"n_graphs": 1}, backend="dense",
                                 two_hop=True)


def test_two_hop_never_degrades_silently():
    """two_hop without a graph (or with an explicit one-hop plan) must be a
    loud error, never a silent fall-back to one-hop aggregation."""
    from repro.data.synthetic import powerlaw_graph
    from repro.launch import steps as steps_mod
    from repro.models.gnn import gin
    from repro.sparse.graph import make_graph
    from repro.sparse.plan import plan_from_graph
    cfg = gin.GINConfig(two_hop=True)
    with pytest.raises(ValueError, match="needs graph"):
        steps_mod.build_gnn_step("gin", cfg, None, {"n_graphs": 1},
                                 backend="dense")
    s, r = powerlaw_graph(30, 90, seed=0)
    g = make_graph(s, r, 30)
    with pytest.raises(ValueError, match="not plan"):
        steps_mod.build_gnn_step("gin", cfg, None, {"n_graphs": 1},
                                 backend="dense", graph=g,
                                 plan=plan_from_graph(g))


def test_dimenet_two_hop_through_steps():
    """DimeNet's two_hop config routes the Â² plan into the output block."""
    import dataclasses as dc
    from repro.configs.dimenet import reduced
    from repro.launch import steps as steps_mod
    from repro.models.gnn import dimenet
    from repro.optim import adamw
    from repro.sparse.graph import make_graph
    rng = np.random.default_rng(4)
    n, e = 16, 40
    s = rng.integers(0, n, e).astype(np.int32)
    r = (s + 1 + rng.integers(0, n - 1, e).astype(np.int32)) % n
    g = make_graph(s, r, n, pad_multiple=8)
    e_pad = np.asarray(g.senders).shape[0]
    t = e_pad * 2
    batch = {
        "species": jnp.asarray(rng.integers(1, 5, n + 1).astype(np.int32)),
        "pos": jnp.asarray(rng.normal(size=(n + 1, 3)).astype(np.float32)),
        "senders": g.senders, "receivers": g.receivers,
        "edge_valid": g.edge_valid,
        "t_in": jnp.asarray(rng.integers(0, e_pad, t).astype(np.int32)),
        "t_out": jnp.asarray(rng.integers(0, e_pad, t).astype(np.int32)),
        "t_valid": jnp.asarray(np.ones(t, bool)),
        "graph_ids": jnp.asarray(np.zeros(n + 1, np.int32)),
        "targets": jnp.asarray(np.array([0.5], np.float32)),
    }
    losses = {}
    # (cfg.two_hop, explicit two_hop arg): the arg must win over the config
    for case, (cfg_flag, arg) in {"off": (False, None), "cfg": (True, None),
                                  "arg": (False, True)}.items():
        cfg = dc.replace(reduced(), two_hop=cfg_flag)
        step = jax.jit(steps_mod.build_gnn_step(
            "dimenet", cfg, None, {"n_graphs": 1}, adamw.AdamWConfig(),
            backend="dense", graph=g, two_hop=arg))
        params = dimenet.init_params(jax.random.key(0), cfg)
        _, _, m = step(params, adamw.init_state(params), batch)
        losses[case] = float(m["loss"])
    assert all(np.isfinite(v) for v in losses.values())
    assert losses["cfg"] != losses["off"]   # the Â² stage actually fires
    assert losses["arg"] == losses["cfg"]   # explicit arg never a no-op
