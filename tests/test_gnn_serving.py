"""GNN serving subsystem: bucket structure, forest sampler, step parity,
engine end-to-end, and the zero-recompile steady-state contract."""
import numpy as np
import pytest

import jax

from repro.data import synthetic as syn
from repro.serve import compute
from repro.serve.buckets import (all_buckets, bucket_for,
                                 build_bucket_structure, stack_trees)
from repro.serve.compute import FeatureStore
from repro.serve.engine import GNNServer, offline_inference, offline_replay
from repro.sparse import sampler
from repro.sparse.graph import coo_to_csr

N, E, D = 400, 2000, 16
FANOUTS = (3, 2)


def _csr(seed=0):
    s, r = syn.powerlaw_graph(N, E, seed=seed)
    return coo_to_csr(s, r, N)[:2]


def _store(seed=1):
    rng = np.random.default_rng(seed)
    return FeatureStore.build(
        N, x=rng.normal(size=(N, D)).astype(np.float32),
        species=rng.integers(1, 9, N).astype(np.int32),
        pos=rng.normal(scale=2.0, size=(N, 3)).astype(np.float32))


def _trees(k, seed=2):
    indptr, indices = _csr()
    rng = np.random.default_rng(seed)
    return [sampler.sample_subgraph(indptr, indices,
                                    rng.integers(0, N, 1), FANOUTS, rng)
            for _ in range(k)]


# ---------------------------------------------------------------------------
# buckets & structure
# ---------------------------------------------------------------------------

def test_bucket_for_powers_of_two():
    assert [bucket_for(k, 16) for k in (1, 2, 3, 5, 8, 9, 16)] == \
        [1, 2, 4, 8, 8, 16, 16]
    assert all_buckets(16) == (1, 2, 4, 8, 16)
    assert all_buckets(12) == (1, 2, 4, 8, 12)
    with pytest.raises(ValueError):
        bucket_for(17, 16)
    with pytest.raises(ValueError):
        bucket_for(0, 16)


def test_structure_matches_sampler_arithmetic():
    """The bucket's static senders/receivers must equal what the sampler
    emits for any batch of that size — they are the same arithmetic."""
    indptr, indices = _csr()
    rng = np.random.default_rng(0)
    for k in (1, 4):
        sub = sampler.sample_subgraph(indptr, indices,
                                      rng.integers(0, N, k), FANOUTS, rng)
        st = build_bucket_structure(k, FANOUTS)
        assert np.array_equal(st.senders, np.concatenate(sub.hop_senders))
        assert np.array_equal(st.receivers,
                              np.concatenate(sub.hop_receivers))
        assert st.n_nodes == sub.node_ids.shape[0]


def test_structure_triplets_are_tree_consistent():
    """Every triplet pairs an in-edge (k→j) with an out-edge (j→i): the
    in-edge's receiver slot must be the out-edge's sender slot."""
    st = build_bucket_structure(3, (3, 2, 2))
    assert st.n_triplets == sum(sampler.budget(3, (3, 2, 2))[1:])
    s, r = st.senders, st.receivers
    assert np.array_equal(r[st.t_in], s[st.t_out])


def test_stack_trees_layout_and_padding():
    trees = _trees(3)
    node_ids, hop_valid = stack_trees(trees, 4, FANOUTS)
    st = build_bucket_structure(4, FANOUTS)
    assert node_ids.shape[0] == st.n_nodes
    assert hop_valid.shape[0] == st.n_hop_edges
    # seeds land in slots 0..k-1; the padding tree's lanes are dead
    for t, tree in enumerate(trees):
        assert node_ids[t] == tree.node_ids[0]
    assert node_ids[3] == -1
    # every valid edge connects the same global pair as in its source tree
    for t, tree in enumerate(trees):
        sub_ids, sub_valid = stack_trees([tree], 1, FANOUTS)
        st1 = build_bucket_structure(1, FANOUTS)
        v1 = sub_valid
        pairs1 = {(sub_ids[a], sub_ids[b])
                  for a, b in zip(st1.senders[v1], st1.receivers[v1])}
        # tree t's edges within the stacked batch
        vb = np.zeros(st.n_hop_edges, bool)
        off, toff = 0, 0
        sizes = sampler.budget(1, FANOUTS)
        for h, sz in enumerate(sizes):
            vb[off + t * sz: off + (t + 1) * sz] = tree.hop_valid[h]
            off += sz * 4
        pairsb = {(node_ids[a], node_ids[b])
                  for a, b in zip(st.senders[vb], st.receivers[vb])}
        assert pairs1 == pairsb


def test_stack_trees_overflow_raises():
    with pytest.raises(ValueError):
        stack_trees(_trees(3), 2, FANOUTS)


# ---------------------------------------------------------------------------
# forest sampler (serving data plane)
# ---------------------------------------------------------------------------

def test_forest_grouping_invariance():
    """A tree's draws depend only on (key, tree_key) — not on which other
    trees share the vectorized call."""
    indptr, indices = _csr()
    seeds = np.array([5, 77, 200, 5])        # duplicate seed ids too
    keys = np.array([3, 9, 11, 42], np.uint64)
    joint = sampler.sample_forest(indptr, indices, seeds, FANOUTS, key=7,
                                  tree_keys=keys)
    for i in range(4):
        solo = sampler.sample_forest(indptr, indices, seeds[i:i + 1],
                                     FANOUTS, key=7,
                                     tree_keys=keys[i:i + 1])[0]
        assert np.array_equal(joint[i].node_ids, solo.node_ids)
        for h in range(len(FANOUTS)):
            assert np.array_equal(joint[i].hop_valid[h], solo.hop_valid[h])
    # trees with the same seed but different keys differ (independent
    # streams), same key reproduces exactly
    again = sampler.sample_forest(indptr, indices, seeds[:1], FANOUTS, key=7,
                                  tree_keys=keys[:1])[0]
    assert np.array_equal(joint[0].node_ids, again.node_ids)


def test_forest_edges_exist_in_graph():
    indptr, indices = _csr()
    tree = sampler.sample_forest(indptr, indices, np.array([17]), FANOUTS,
                                 key=0, tree_keys=np.array([1], np.uint64))[0]
    for h in range(len(FANOUTS)):
        v = tree.hop_valid[h]
        src = tree.node_ids[tree.hop_senders[h][v]]
        dst = tree.node_ids[tree.hop_receivers[h][v]]
        for sg, dg in zip(src, dst):
            assert sg in indices[indptr[dg]:indptr[dg + 1]]


# ---------------------------------------------------------------------------
# step parity: batched-bucketed == one tree at a time
# ---------------------------------------------------------------------------

def _parity(arch, cfg, mod, backends, k=4, tol=1e-5):
    store = _store()
    trees = _trees(k)
    params = mod.init_params(jax.random.key(0), cfg)
    loops = arch == "gcn"
    stk = build_bucket_structure(k, FANOUTS, with_loops=loops)
    st1 = build_bucket_structure(1, FANOUTS, with_loops=loops)
    ref = None
    for backend in backends:
        stepk = compute.build_infer_step(arch, cfg, store, stk,
                                         backend=backend)
        step1 = compute.build_infer_step(arch, cfg, store, st1,
                                         backend=backend)
        batched = np.asarray(stepk(params, *stack_trees(trees, k, FANOUTS)))
        singles = np.concatenate(
            [np.asarray(step1(params, *stack_trees([t], 1, FANOUTS)))
             for t in trees])
        dev = float(np.abs(batched - singles).max())
        assert dev <= tol, (arch, backend, dev)
        assert np.isfinite(batched).all()
        if ref is None:
            ref = batched
        else:                                 # executors agree with dense
            assert float(np.abs(batched - ref).max()) <= 1e-4


def test_parity_gcn():
    from repro.models.gnn import gcn
    _parity("gcn", gcn.GCNConfig(d_in=D, d_hidden=8, n_classes=5), gcn,
            ("dense", "chunked", "pallas"))


def test_parity_sage():
    from repro.models.gnn import sage
    _parity("sage", sage.SAGEConfig(d_in=D, d_hidden=8, n_classes=5), sage,
            ("dense", "pallas"))


def test_parity_gin():
    from repro.models.gnn import gin
    _parity("gin", gin.GINConfig(d_in=D, d_hidden=8, n_classes=5), gin,
            ("dense", "chunked"))


def test_parity_gat():
    from repro.models.gnn import gat
    _parity("gat", gat.GATConfig(d_in=D, d_hidden=4, n_heads=2, n_classes=5),
            gat, ("dense",))


def test_parity_geometric():
    from repro.models.gnn import dimenet, schnet
    _parity("schnet",
            schnet.SchNetConfig(n_interactions=2, d_hidden=16, n_rbf=8),
            schnet, ("dense",), k=2)
    _parity("dimenet",
            dimenet.DimeNetConfig(n_blocks=1, d_hidden=8, n_bilinear=2,
                                  n_spherical=3),
            dimenet, ("dense",), k=2)


def test_padding_lanes_do_not_leak():
    """A bucket-4 batch holding 2 real trees must produce the same outputs
    for those trees as a bucket-2 batch — padding lanes contribute zero."""
    from repro.models.gnn import gin
    cfg = gin.GINConfig(d_in=D, d_hidden=8, n_classes=5)
    params = gin.init_params(jax.random.key(0), cfg)
    store = _store()
    trees = _trees(2)
    out4 = np.asarray(compute.build_infer_step(
        "gin", cfg, store, build_bucket_structure(4, FANOUTS))(
            params, *stack_trees(trees, 4, FANOUTS)))
    out2 = np.asarray(compute.build_infer_step(
        "gin", cfg, store, build_bucket_structure(2, FANOUTS))(
            params, *stack_trees(trees, 2, FANOUTS)))
    np.testing.assert_allclose(out4[:2], out2, atol=1e-5)


# ---------------------------------------------------------------------------
# engine end-to-end
# ---------------------------------------------------------------------------

CONFIGS = {"gcn": "GCNConfig", "sage": "SAGEConfig", "gin": "GINConfig"}


def _server(backend="dense", arch="gcn", **kw):
    mod = __import__(f"repro.models.gnn.{arch}", fromlist=[arch])
    indptr, indices = _csr()
    cfg = getattr(mod, CONFIGS[arch])(d_in=D, d_hidden=8, n_classes=5)
    params = mod.init_params(jax.random.key(0), cfg)
    return GNNServer(arch, cfg, params, indptr, indices, _store(),
                     fanouts=FANOUTS, backend=backend, max_batch_seeds=8,
                     max_wait_ms=2.0, n_workers=2, seed=0, **kw)


@pytest.mark.parametrize("arch,backend", [("gcn", "dense"),
                                          ("gcn", "pallas"),
                                          ("sage", "dense"),
                                          ("gin", "dense")])
def test_engine_serves_all_exactly_once_with_parity(arch, backend):
    rng = np.random.default_rng(3)
    with _server(backend, arch) as server:
        server.warmup()
        reqs = [server.submit(rng.integers(0, N, size=rng.integers(1, 4)))
                for _ in range(17)]
        server.drain(timeout=120)
        st = server.stats()
        assert st["n_served"] == 17
        for r in reqs:
            assert r.done and r.result.shape == (r.n_seeds, 5)
            # offline replay: deterministic re-sample + bucket-1 inference
            ref = offline_replay(server, r)
            assert float(np.abs(r.result - ref).max()) <= 1e-5
        # batches were actually formed, and bucket capacity covers the
        # trees each batch carried (bucket sizes include padding lanes)
        assert st["n_batches"] >= 1
        assert sum(int(b) * c for b, c in st["bucket_counts"].items()) >= \
            sum(r.n_seeds for r in reqs)


def test_engine_zero_recompiles_after_warmup():
    with _server() as server:
        server.warmup()                       # whole ladder: 1,2,4,8
        warm = server.steps.builds
        assert warm == len(all_buckets(8))
        rng = np.random.default_rng(4)
        for _ in range(3):                    # repeated steady-state traffic
            reqs = [server.submit([int(s)]) for s in rng.integers(0, N, 20)]
            server.drain(timeout=120)
            for r in reqs:
                assert r.done
        assert server.steps.builds == warm, \
            "steady-state serving must not rebuild bucket steps"
        assert server.stats()["recompiles"] == warm


def test_engine_second_request_in_bucket_zero_recompiles():
    """Bucket-cache contract without explicit warmup: the first request
    compiles its bucket, the second identical one must not."""
    with _server() as server:
        server.submit([7]).wait(120)
        builds = server.steps.builds
        server.submit([9]).wait(120)
        assert server.steps.builds == builds


def test_engine_offline_inference_matches_result_trees():
    with _server() as server:
        req = server.submit([3, 5])
        req.wait(120)
        ref = offline_inference(server, req.trees)
        np.testing.assert_allclose(req.result, ref, atol=1e-5)


def test_engine_rejects_bad_requests_and_survives():
    """Malformed requests fail the CALLER, not a worker thread; the server
    keeps serving afterwards (regression: a worker exception used to kill
    its lane and hang all subsequent traffic routed to it)."""
    with _server() as server:
        with pytest.raises(ValueError):
            server.submit([N + 5])                # out of range
        with pytest.raises(ValueError):
            server.submit([-1])
        with pytest.raises(ValueError):
            server.submit(np.arange(9))           # exceeds bucket cap (8)
        with pytest.raises(ValueError):
            server.submit([])
        out = server.submit([3]).wait(120)        # the lane still works
        assert out.shape == (1, 5)


def test_engine_close_serves_everything_submitted():
    """close() is graceful: requests still in the sampling pipeline at
    close time are served, not dropped (regression: the engine thread used
    to flush before the samplers finished, hanging their wait())."""
    server = _server()
    rng = np.random.default_rng(8)
    reqs = [server.submit([int(s)]) for s in rng.integers(0, N, 50)]
    server.close()                                # no drain first
    for r in reqs:
        out = r.wait(timeout=5.0)                 # must not hang
        assert out.shape == (1, 5)


def test_engine_duplicate_and_isolated_seeds():
    """Duplicate seed ids in one batch and zero-degree seeds must serve."""
    from repro.models.gnn import gin
    # a graph whose last node is isolated (regression: CSR end-of-array)
    s = np.array([0, 1, 2, 0], np.int64)
    r = np.array([1, 2, 0, 2], np.int64)
    indptr, indices, _ = coo_to_csr(s, r, 5)   # nodes 3, 4 isolated
    cfg = gin.GINConfig(d_in=D, d_hidden=8, n_classes=3)
    params = gin.init_params(jax.random.key(1), cfg)
    store = FeatureStore.build(
        5, x=np.random.default_rng(0).normal(size=(5, D)).astype(np.float32))
    with GNNServer("gin", cfg, params, indptr, indices, store,
                   fanouts=FANOUTS, max_batch_seeds=8, max_wait_ms=1.0,
                   n_workers=1, seed=0) as server:
        req = server.submit([4, 4, 2, 4])      # duplicates + isolated
        out = req.wait(120)
        assert out.shape == (4, 3)
        assert np.isfinite(out).all()
        # duplicate seeds get identical answers (same tree stream per lane?
        # no — per-lane streams differ, but isolated nodes have no valid
        # edges at all, so every lane reduces to the self feature)
        np.testing.assert_allclose(out[0], out[1], atol=1e-5)
        np.testing.assert_allclose(out[0], out[3], atol=1e-5)
