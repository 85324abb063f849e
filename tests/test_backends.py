"""Unified sparse-backend engine: executor parity, plan contracts, registry.

Satellite coverage for the backend refactor: ``dense``, ``chunked`` and
``pallas`` (interpret mode) must agree within 1e-4 on GCN/GAT/SAGE forward
passes over random graphs, including empty-row and all-padding edge cases;
``distributed`` parity runs in a subprocess over 8 emulated devices.
"""
import functools
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.sparse import backend as sb
from repro.sparse.plan import (ALL_BACKENDS, BackendPlanError, edge_plan,
                               make_plan)

PARITY_BACKENDS = ("chunked", "pallas")


def _random_plan_inputs(n, e, seed, weighted=True, n_invalid=0):
    rng = np.random.default_rng(seed)
    s = rng.integers(0, n, e)
    r = rng.integers(0, n, e)
    w = rng.normal(size=e).astype(np.float32) if weighted else None
    valid = np.ones(e, bool)
    if n_invalid:
        valid[e - n_invalid:] = False
    return s, r, w, valid, rng


# ---------------------------------------------------------------------------
# Raw aggregate parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", PARITY_BACKENDS)
@pytest.mark.parametrize("weighted", [True, False])
def test_aggregate_parity(backend, weighted):
    n, e, d = 64, 400, 24
    s, r, w, valid, rng = _random_plan_inputs(n, e, 0, weighted, n_invalid=60)
    x = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    plan = make_plan(s, r, n, edge_weight=w, edge_valid=valid,
                     backends=("dense", "chunked", "pallas"), chunk=128)
    ref = sb.aggregate(plan, None, x, backend="dense")
    out = sb.aggregate(plan, None, x, backend=backend)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("backend", PARITY_BACKENDS)
def test_aggregate_traced_vals_parity(backend):
    """Traced per-edge values (the GAT-attention path) route through the
    plan's scatter slots on every executor."""
    n, e, d = 48, 256, 16
    s, r, _, valid, rng = _random_plan_inputs(n, e, 1, False, n_invalid=32)
    x = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    vals = jnp.asarray(rng.normal(size=e).astype(np.float32))
    plan = make_plan(s, r, n, edge_valid=valid,
                     backends=("dense", "chunked", "pallas"), chunk=64)

    @functools.partial(jax.jit, static_argnames=("nm",))
    def agg(v, xx, nm):
        return sb.aggregate(plan, v, xx, backend=nm)

    ref = agg(vals, x, "dense")
    out = agg(vals, x, backend)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_aggregate_empty_rows_and_all_padding():
    """Nodes with no in-edges get zeros; an all-padding edge list yields an
    all-zero result on every local executor."""
    n, e, d = 40, 96, 8
    rng = np.random.default_rng(3)
    s = rng.integers(0, 4, e)          # only rows 0..3 ever receive
    r = rng.integers(0, 4, e)
    x = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    plan = make_plan(s, r, n, backends=("dense", "chunked", "pallas"),
                     chunk=32)
    ref = sb.aggregate(plan, None, x, backend="dense")
    assert float(jnp.abs(ref[4:]).max()) == 0.0
    for backend in PARITY_BACKENDS:
        out = sb.aggregate(plan, None, x, backend=backend)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)

    all_pad = make_plan(s, r, n, edge_valid=np.zeros(e, bool),
                        backends=("dense", "chunked", "pallas"), chunk=32)
    for backend in ("dense",) + PARITY_BACKENDS:
        out = sb.aggregate(all_pad, None, x, backend=backend)
        assert float(jnp.abs(out).max()) == 0.0, backend


@pytest.mark.parametrize("backend", PARITY_BACKENDS)
def test_accumulate_parity(backend):
    n, e, d = 32, 200, 12
    s, r, _, valid, rng = _random_plan_inputs(n, e, 5, False, n_invalid=40)
    msgs = jnp.asarray(rng.normal(size=(e, d)).astype(np.float32))
    plan = make_plan(s, r, n, edge_valid=valid,
                     backends=("dense", "chunked", "pallas"), chunk=64)
    ref = sb.accumulate(plan, msgs, backend="dense")
    out = sb.accumulate(plan, msgs, backend=backend)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# Model-level parity: GCN / GAT / SAGE forward passes
# ---------------------------------------------------------------------------

def _graph_and_plan(n, e, seed, weighted, n_invalid=0):
    from repro.sparse.graph import sym_norm_weights
    rng = np.random.default_rng(seed)
    s = rng.integers(0, n, e)
    r = rng.integers(0, n, e)
    if weighted:
        s, r, w = sym_norm_weights(s, r, n)
    else:
        w = None
    e_tot = s.shape[0]
    valid = np.ones(e_tot, bool)
    if n_invalid:
        valid[e_tot - n_invalid:] = False
    plan = make_plan(s, r, n + 1, edge_weight=w, edge_valid=valid,
                     backends=("dense", "chunked", "pallas"), chunk=128)
    return rng, plan


@pytest.mark.parametrize("backend", PARITY_BACKENDS)
@pytest.mark.parametrize("n_invalid", [0, 37])
def test_gcn_forward_backend_parity(backend, n_invalid):
    from repro.models.gnn import gcn
    cfg = gcn.GCNConfig(d_in=12, d_hidden=8, n_classes=5, n_layers=2)
    rng, plan = _graph_and_plan(50, 200, 0, True, n_invalid)
    x = jnp.asarray(rng.normal(size=(51, cfg.d_in)).astype(np.float32))
    params = gcn.init_params(jax.random.key(0), cfg)
    ref = gcn.forward(params, cfg, x, backend="dense", plan=plan)
    out = gcn.forward(params, cfg, x, backend=backend, plan=plan)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("backend", PARITY_BACKENDS)
def test_gat_forward_backend_parity(backend):
    from repro.models.gnn import gat
    cfg = gat.GATConfig(d_in=10, d_hidden=4, n_heads=2, n_classes=3,
                        n_layers=2)
    rng, plan = _graph_and_plan(40, 150, 1, False, n_invalid=20)
    x = jnp.asarray(rng.normal(size=(41, cfg.d_in)).astype(np.float32))
    params = gat.init_params(jax.random.key(0), cfg)
    ref = gat.forward(params, cfg, x, backend="dense", plan=plan)
    out = gat.forward(params, cfg, x, backend=backend, plan=plan)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("backend", PARITY_BACKENDS)
def test_sage_forward_backend_parity(backend):
    from repro.models.gnn import sage
    cfg = sage.SAGEConfig(d_in=8, d_hidden=6, n_classes=4, n_layers=2)
    rng, plan = _graph_and_plan(36, 120, 2, False, n_invalid=16)
    x = jnp.asarray(rng.normal(size=(37, cfg.d_in)).astype(np.float32))
    params = sage.init_params(jax.random.key(0), cfg)
    ref = sage.forward(params, cfg, x, backend="dense", plan=plan)
    out = sage.forward(params, cfg, x, backend=backend, plan=plan)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_gcn_pallas_gradients_flow():
    """The pallas executor carries a custom VJP — training must work."""
    from repro.models.gnn import gcn
    cfg = gcn.GCNConfig(d_in=6, d_hidden=4, n_classes=3, n_layers=2)
    rng, plan = _graph_and_plan(30, 100, 4, True)
    x = jnp.asarray(rng.normal(size=(31, cfg.d_in)).astype(np.float32))
    labels = jnp.asarray(rng.integers(0, 3, 31), jnp.int32)
    mask = jnp.asarray(np.arange(31) < 20)
    params = gcn.init_params(jax.random.key(1), cfg)
    loss_d, grads_d = jax.value_and_grad(gcn.loss_fn)(
        params, cfg, x, None, None, None, None, labels, mask,
        backend="dense", plan=plan)
    loss_p, grads_p = jax.value_and_grad(gcn.loss_fn)(
        params, cfg, x, None, None, None, None, labels, mask,
        backend="pallas", plan=plan)
    np.testing.assert_allclose(float(loss_p), float(loss_d), rtol=1e-4)
    for gd, gp in zip(jax.tree.leaves(grads_d), jax.tree.leaves(grads_p)):
        np.testing.assert_allclose(np.asarray(gp), np.asarray(gd),
                                   rtol=1e-3, atol=1e-4)


@pytest.mark.parametrize("backend", ("chunked", "pallas"))
def test_gin_schnet_dimenet_accept_backend(backend):
    """The remaining models route through the registry too (accumulate-only
    for the vector-valued multiply stages of schnet/dimenet)."""
    from repro.models.gnn import dimenet, gin, schnet
    from repro.sparse import triplets as tri
    rng = np.random.default_rng(0)
    n, e = 30, 90
    s = rng.integers(0, n, e)
    r = rng.integers(0, n, e)
    valid = np.ones(e, bool)

    cfg = gin.GINConfig(d_in=6, d_hidden=8, n_classes=3, n_layers=2)
    params = gin.init_params(jax.random.key(0), cfg)
    x = jnp.asarray(rng.normal(size=(n, 6)).astype(np.float32))
    plan = make_plan(s, r, n, backends=("dense", "chunked", "pallas"),
                     chunk=32)
    ref = gin.forward(params, cfg, x, backend="dense", plan=plan)
    out = gin.forward(params, cfg, x, backend=backend, plan=plan)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)

    scfg = schnet.SchNetConfig(d_hidden=8, n_rbf=16, n_interactions=2)
    sparams = schnet.init_params(jax.random.key(1), scfg)
    species = jnp.asarray(rng.integers(0, 10, n), jnp.int32)
    pos = jnp.asarray(rng.normal(size=(n, 3)).astype(np.float32))
    gid = jnp.zeros(n, jnp.int32)
    sv, rv, vv = jnp.asarray(s), jnp.asarray(r), jnp.asarray(valid)
    e_ref = schnet.forward(sparams, scfg, species, pos, sv, rv, vv, gid, 1,
                           backend="dense")
    e_out = schnet.forward(sparams, scfg, species, pos, sv, rv, vv, gid, 1,
                           backend=backend)
    np.testing.assert_allclose(np.asarray(e_out), np.asarray(e_ref),
                               rtol=1e-4, atol=1e-4)

    dcfg = dimenet.DimeNetConfig(n_blocks=1, d_hidden=8, n_bilinear=2,
                                 n_spherical=3, n_radial=2,
                                 max_triplets_per_edge=4)
    dparams = dimenet.init_params(jax.random.key(2), dcfg)
    t_in, t_out, t_val = tri.build_triplets(s, r, dcfg.max_triplets_per_edge)
    d_ref = dimenet.forward(dparams, dcfg, species, pos, sv, rv, vv,
                            jnp.asarray(t_in), jnp.asarray(t_out),
                            jnp.asarray(t_val), gid, 1, backend="dense")
    d_out = dimenet.forward(dparams, dcfg, species, pos, sv, rv, vv,
                            jnp.asarray(t_in), jnp.asarray(t_out),
                            jnp.asarray(t_val), gid, 1, backend=backend)
    np.testing.assert_allclose(np.asarray(d_out), np.asarray(d_ref),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# Pallas kernel layout edge cases: feature tiling, DMA waves, chunk splits
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("d", [48, 96, 200])
def test_pallas_feature_tiling_non_divisible(d):
    """D not a multiple of the feature tile: the kernel pads to whole tiles
    and slices back."""
    n, e = 48, 300
    s, r, w, valid, rng = _random_plan_inputs(n, e, d, n_invalid=20)
    x = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    plan = make_plan(s, r, n, edge_weight=w, edge_valid=valid,
                     backends=("dense", "pallas"), d_tile=64)
    ref = sb.aggregate(plan, None, x, backend="dense")
    out = sb.aggregate(plan, None, x, backend="pallas")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("group", [5, 7, 16])
def test_pallas_dma_group_not_dividing_width(group):
    """DMA-wave width not dividing the chunk width: the kernel lane-pads.
    Exercised in explicit-DMA gather mode, where waves matter."""
    from repro.kernels.gustavson_spmm.gustavson_spmm import spmm_dedup_chunks
    from repro.sparse.graph import pack_dedup_chunks
    n, e, d = 40, 220, 24
    rng = np.random.default_rng(group)
    s = rng.integers(0, n, e)
    r = rng.integers(0, n, e)
    vals = rng.normal(size=e).astype(np.float32)
    x = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    ch = pack_dedup_chunks(r, s, vals, n, n)
    assert ch.width % group or group == 16
    plan = make_plan(s, r, n, edge_weight=vals, backends=("dense",))
    ref = sb.aggregate(plan, None, x, backend="dense")
    for gather in ("dma", "stream"):
        out = spmm_dedup_chunks(
            jnp.asarray(ch.u_cols), jnp.asarray(ch.remaining),
            jnp.asarray(ch.out_block), jnp.asarray(ch.first),
            jnp.asarray(ch.a), x, block_rows=ch.block_rows,
            n_blocks=ch.n_blocks, group=group, gather=gather)
        np.testing.assert_allclose(np.asarray(out[:n]), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4, err_msg=gather)


def test_pallas_chunk_split_hub_rows():
    """A hub receiver forces width_cap chunk splits: later chunks revisit
    their output block and accumulate into the resident tile."""
    n, e, d = 64, 600, 16
    rng = np.random.default_rng(11)
    s = rng.integers(0, n, e)
    r = np.where(rng.random(e) < 0.5, 3, rng.integers(0, n, e))  # hub row 3
    w = rng.normal(size=e).astype(np.float32)
    x = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    plan = make_plan(s, r, n, edge_weight=w,
                     backends=("dense", "pallas"), width_cap=16)
    assert plan.ell_u_cols.shape[0] > plan.n_blocks  # really split
    ref = sb.aggregate(plan, None, x, backend="dense")
    out = sb.aggregate(plan, None, x, backend="pallas")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_pallas_empty_blocks_evict_zeros():
    """Blocks with zero nnz still evict (zero) tiles — remaining == 0."""
    n, d = 64, 8
    s = np.array([1, 2, 3])
    r = np.array([0, 0, 1])           # only block 0 receives
    x = jnp.asarray(np.random.default_rng(0).normal(size=(n, d))
                    .astype(np.float32))
    plan = make_plan(s, r, n, backends=("dense", "pallas"))
    assert int(np.asarray(plan.ell_remaining).min()) == 0
    out = sb.aggregate(plan, None, x, backend="pallas")
    assert float(jnp.abs(out[2:]).max()) == 0.0
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(x[1] + x[2]),
                               rtol=1e-5, atol=1e-5)


def test_pallas_vjp_matches_dense_autodiff():
    """Custom-VJP cotangents for BOTH `vals` and `x` match dense autodiff;
    the backward runs through the Pallas kernel, not a segment reduction."""
    import inspect
    from repro.kernels.gustavson_spmm import ops as gops
    n, e, d = 40, 250, 12
    s, r, w, valid, rng = _random_plan_inputs(n, e, 9, n_invalid=30)
    x = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    vals = jnp.asarray(w)
    plan = make_plan(s, r, n, edge_valid=valid,
                     backends=("dense", "chunked", "pallas"))

    def loss(v, xx, nm):
        y = sb.aggregate(plan, v, xx, backend=nm)
        return jnp.mean(y ** 2) + jnp.sum(y[:, 0])

    gv_d, gx_d = jax.grad(loss, argnums=(0, 1))(vals, x, "dense")
    gv_p, gx_p = jax.jit(jax.grad(loss, argnums=(0, 1)),
                         static_argnums=2)(vals, x, "pallas")
    np.testing.assert_allclose(np.asarray(gv_p), np.asarray(gv_d),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(gx_p), np.asarray(gx_d),
                               rtol=1e-4, atol=1e-5)
    # the acceptance contract: no plain-JAX segment reduction in the bwd
    assert "segment_sum" not in inspect.getsource(gops._ad_bwd)


def test_pallas_bf16_stays_bf16():
    """bf16 features are not upcast: output dtype bf16, f32 accumulation."""
    n, e, d = 32, 180, 16
    s, r, w, valid, rng = _random_plan_inputs(n, e, 13)
    xf = rng.normal(size=(n, d)).astype(np.float32)
    x16 = jnp.asarray(xf, jnp.bfloat16)
    plan = make_plan(s, r, n, edge_weight=w,
                     backends=("dense", "pallas"))
    out = sb.aggregate(plan, None, x16, backend="pallas")
    assert out.dtype == jnp.bfloat16
    ref = sb.aggregate(plan, None, jnp.asarray(xf), backend="dense")
    np.testing.assert_allclose(np.float32(out), np.asarray(ref),
                               rtol=5e-2, atol=5e-2)


# ---------------------------------------------------------------------------
# Plan cache
# ---------------------------------------------------------------------------

def test_plan_cache_hits_and_eviction():
    from repro.sparse import plan as plan_mod
    from repro.sparse.graph import make_graph
    plan_mod.plan_cache_clear()
    rng = np.random.default_rng(0)
    graphs = [make_graph(rng.integers(0, 24, 60), rng.integers(0, 24, 60), 24)
              for _ in range(3)]
    p1 = plan_mod.cached_plan_from_graph(graphs[0], backends=("pallas",))
    p2 = plan_mod.cached_plan_from_graph(graphs[0], backends=("pallas",))
    assert p1 is p2                                     # identity hit
    info = plan_mod.plan_cache_info()
    assert info["hits"] == 1 and info["misses"] == 1
    # different layout params → different entry
    p3 = plan_mod.cached_plan_from_graph(graphs[0], backends=("pallas",),
                                         block_rows=16)
    assert p3 is not p1
    # LRU eviction at maxsize
    for g in graphs:
        plan_mod.cached_plan_from_graph(g, backends=("dense",), maxsize=2)
    assert plan_mod.plan_cache_info()["size"] <= 2
    p4 = plan_mod.cached_plan_from_graph(graphs[0], backends=("dense",),
                                         maxsize=2)   # was evicted → repack
    assert isinstance(p4, plan_mod.AggregationPlan)
    plan_mod.plan_cache_clear()
    assert plan_mod.plan_cache_info() == {"hits": 0, "misses": 0, "size": 0}


def test_plan_cache_used_by_step_builder():
    from repro.launch import steps as steps_mod
    from repro.sparse import plan as plan_mod
    from repro.sparse.graph import make_graph
    plan_mod.plan_cache_clear()
    rng = np.random.default_rng(1)
    g = make_graph(rng.integers(0, 16, 40), rng.integers(0, 16, 40), 16)
    a = steps_mod.resolve_gnn_plan(g, "pallas")
    b = steps_mod.resolve_gnn_plan(g, "pallas")
    assert a is b and a.has("ell")
    assert steps_mod.resolve_gnn_plan(g, "dense") is None
    plan_mod.plan_cache_clear()


# ---------------------------------------------------------------------------
# Plan / registry contracts
# ---------------------------------------------------------------------------

def test_chunked_autopads_indivisible_edge_counts():
    """spmm_chunked no longer asserts on E % chunk != 0."""
    from repro.core import spgemm
    rng = np.random.default_rng(0)
    n, e, d = 40, 300, 8                       # 300 % 128 != 0
    rows = jnp.asarray(rng.integers(0, n, e))
    cols = jnp.asarray(rng.integers(0, n, e))
    vals = jnp.asarray(rng.normal(size=e).astype(np.float32))
    x = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    full = spgemm.spmm(rows, cols, vals, x, n)
    for chunk in (128, 7, 1024):               # incl. chunk > E
        out = spgemm.spmm_chunked(rows, cols, vals, x, n, chunk=chunk)
        np.testing.assert_allclose(np.asarray(out), np.asarray(full),
                                   rtol=1e-5, atol=1e-5)


def test_unknown_backend_raises():
    s, r, w, valid, rng = _random_plan_inputs(8, 16, 0)
    plan = edge_plan(jnp.asarray(s), jnp.asarray(r), 8)
    x = jnp.zeros((8, 4))
    with pytest.raises(KeyError, match="unknown sparse backend"):
        sb.aggregate(plan, None, x, backend="tpu-v7")


def test_missing_plan_section_raises():
    s, r, w, valid, rng = _random_plan_inputs(8, 16, 0)
    plan = edge_plan(jnp.asarray(s), jnp.asarray(r), 8)   # COO only
    x = jnp.zeros((8, 4), jnp.float32)
    with pytest.raises(BackendPlanError):
        sb.aggregate(plan, None, x, backend="pallas")
    with pytest.raises(BackendPlanError):
        sb.aggregate(plan, None, x, backend="distributed")


def test_plan_is_a_pytree():
    """Plans must cross jit boundaries as arguments."""
    s, r, w, valid, rng = _random_plan_inputs(16, 64, 7)
    plan = make_plan(s, r, 16, edge_weight=w,
                     backends=("dense", "chunked", "pallas"))
    x = jnp.asarray(rng.normal(size=(16, 4)).astype(np.float32))

    @jax.jit
    def f(pl, xx):
        return sb.aggregate(pl, None, xx, backend="pallas")

    out = f(plan, x)
    ref = sb.aggregate(plan, None, x, backend="dense")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# distributed executor — subprocess (8 emulated devices)
# ---------------------------------------------------------------------------

DIST_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np, jax, jax.numpy as jnp
from repro.models.gnn import gcn
from repro.sparse import backend as sb
from repro.sparse.plan import make_plan
from repro.sparse.graph import sym_norm_weights

rng = np.random.default_rng(2)
n, e, d = 96, 600, 16
s = rng.integers(0, n, e); r = rng.integers(0, n, e)
valid = np.ones(e, bool); valid[550:] = False
w = rng.normal(size=e).astype(np.float32)
x = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
plan = make_plan(s, r, n, edge_weight=w, edge_valid=valid,
                 backends=("dense", "distributed"))
assert plan.n_shards == 8
ref = sb.aggregate(plan, None, x, backend="dense")
out = sb.aggregate(plan, None, x, backend="distributed")
err = float(jnp.abs(ref - out).max())
assert err < 1e-4, f"aggregate parity {err}"

# traced vals + jit + grad through the distributed executor
@jax.jit
def loss(v, xx):
    return jnp.sum(sb.aggregate(plan, v, xx, backend="distributed") ** 2)
g = jax.grad(loss, argnums=1)(jnp.asarray(w), x)
g_ref = jax.grad(lambda v, xx: jnp.sum(
    sb.aggregate(plan, v, xx, backend="dense") ** 2), argnums=1)(
    jnp.asarray(w), x)
gerr = float(jnp.abs(g - g_ref).max())
assert gerr < 1e-3, f"grad parity {gerr}"

# accumulate-only entry
msgs = jnp.asarray(rng.normal(size=(e, d)).astype(np.float32))
a_ref = sb.accumulate(plan, msgs, backend="dense")
a_out = sb.accumulate(plan, msgs, backend="distributed")
aerr = float(jnp.abs(a_ref - a_out).max())
assert aerr < 1e-4, f"accumulate parity {aerr}"

# full GCN forward through the registry
cfg = gcn.GCNConfig(d_in=d, d_hidden=8, n_classes=4, n_layers=2)
s2, r2, w2 = sym_norm_weights(s, r, n)
plan2 = make_plan(s2, r2, n + 1, edge_weight=w2,
                  backends=("dense", "distributed"))
params = gcn.init_params(jax.random.key(0), cfg)
xp = jnp.asarray(rng.normal(size=(n + 1, d)).astype(np.float32))
f_ref = gcn.forward(params, cfg, xp, backend="dense", plan=plan2)
f_out = gcn.forward(params, cfg, xp, backend="distributed", plan=plan2)
ferr = float(jnp.abs(f_ref - f_out).max())
assert ferr < 1e-4, f"gcn forward parity {ferr}"
print("BACKEND_DIST_OK")
"""


def test_distributed_backend_subprocess():
    repo = Path(__file__).resolve().parent.parent
    proc = subprocess.run(
        [sys.executable, "-c", DIST_SCRIPT], capture_output=True, text=True,
        # JAX_PLATFORMS must survive into the child (see
        # test_cluster_serving.test_sharded_cluster_subprocess)
        env={"PYTHONPATH": str(repo / "src"), "PATH": "/usr/bin:/bin",
             "HOME": os.environ.get("HOME", str(repo)),
             "JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS", "cpu")},
        timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "BACKEND_DIST_OK" in proc.stdout
