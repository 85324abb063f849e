"""Device-side forest sampler (``serve/device_sampler.py``): draw-for-draw
equality with the host sampler, and the fused device-sampling serving mode."""
import numpy as np
import pytest
try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:  # deterministic fallback; requirements-dev.txt has the real one
    from _hypothesis_shim import given, settings, st
from _tolerances import Q8_E2E_TOL

from repro.serve.device_sampler import (DeviceSamplerPlane,
                                        sample_forest_device, tree_key_mix)
from repro.sparse import sampler
from repro.sparse.graph import coo_to_csr


def _graph(n=120, e=900, seed=0):
    rng = np.random.default_rng(seed)
    s = rng.integers(0, n, e)
    r = rng.integers(0, n, e)
    indptr, indices, _ = coo_to_csr(s, r, n)
    return indptr, indices, n


def _assert_forest_equal(host, dev):
    assert len(host) == len(dev)
    for h, d in zip(host, dev):
        assert np.array_equal(np.asarray(h.node_ids), np.asarray(d.node_ids))
        for hv, dv in zip(h.hop_valid, d.hop_valid):
            assert np.array_equal(np.asarray(hv), np.asarray(dv))


# ---------------------------------------------------------------------------
# exact host/device equality — the hard invariant behind the serving parity
# ---------------------------------------------------------------------------

@given(st.integers(1, 12), st.lists(st.integers(1, 5), min_size=1,
                                    max_size=3), st.integers(0, 500))
@settings(max_examples=15, deadline=None)
def test_device_matches_host_exactly(b, fanouts, key):
    indptr, indices, n = _graph()
    seeds = np.random.default_rng(key).integers(0, n, b)
    host = sampler.sample_forest(indptr, indices, seeds, fanouts, key=key)
    dev = sample_forest_device(indptr, indices, seeds, fanouts, key=key)
    _assert_forest_equal(host, dev)


def test_device_matches_host_custom_tree_keys():
    indptr, indices, n = _graph(seed=3)
    seeds = np.array([5, 5, 7, 90])       # repeated seed, distinct tree keys
    tks = np.array([3, 11, 2**40 + 1, 0], np.uint64)
    host = sampler.sample_forest(indptr, indices, seeds, (4, 2), key=9,
                                 tree_keys=tks)
    dev = sample_forest_device(indptr, indices, seeds, (4, 2), key=9,
                               tree_keys=tks)
    _assert_forest_equal(host, dev)
    # same seed, different tree_key → different draws (counter really mixes)
    assert not np.array_equal(np.asarray(host[0].node_ids),
                              np.asarray(host[1].node_ids))


def test_device_matches_host_edgeless_graph():
    indptr = np.zeros(33, np.int64)       # 32 nodes, zero edges
    indices = np.zeros(0, np.int64)
    seeds = np.array([0, 7, 31])
    host = sampler.sample_forest(indptr, indices, seeds, (3, 2), key=1)
    dev = sample_forest_device(indptr, indices, seeds, (3, 2), key=1)
    _assert_forest_equal(host, dev)
    for t in dev:
        assert not np.asarray(t.hop_valid[0]).any()


def test_device_matches_host_fanout_exceeds_degree():
    # a 4-node chain: degrees ≤ 1, fanout 5 → draws repeat the one neighbor
    indptr, indices, _ = coo_to_csr(np.array([0, 1, 2]),
                                    np.array([1, 2, 3]), 4)
    host = sampler.sample_forest(indptr, indices, np.array([1, 3]), (5,),
                                 key=4)
    dev = sample_forest_device(indptr, indices, np.array([1, 3]), (5,),
                               key=4)
    _assert_forest_equal(host, dev)


def test_kernel_and_jnp_draw_paths_agree():
    indptr, indices, n = _graph(seed=6)
    seeds = np.random.default_rng(6).integers(0, n, 6)
    ref = sample_forest_device(indptr, indices, seeds, (4, 3), key=2,
                               use_kernel=False)
    ker = sample_forest_device(indptr, indices, seeds, (4, 3), key=2,
                               use_kernel=True)
    _assert_forest_equal(ref, ker)


def test_grouping_invariance_on_device():
    # sampling trees together or alone yields identical tables
    indptr, indices, n = _graph(seed=8)
    seeds = np.array([3, 60, 99])
    tks = np.array([7, 8, 9], np.uint64)
    joint = sample_forest_device(indptr, indices, seeds, (3, 3), key=5,
                                 tree_keys=tks)
    for i in range(3):
        alone = sample_forest_device(indptr, indices, seeds[i:i + 1], (3, 3),
                                     key=5, tree_keys=tks[i:i + 1])
        _assert_forest_equal([joint[i]], alone)


def test_sample_bucket_layout_matches_stack_trees():
    import jax.numpy as jnp

    from repro.serve.buckets import stack_trees

    indptr, indices, n = _graph(seed=9)
    seeds = np.array([2, 40, 77, 101])
    tks = np.arange(4, dtype=np.uint64)
    plane = DeviceSamplerPlane(indptr, indices, (3, 2), key=6)
    tk_hi, tk_lo = tree_key_mix(tks)
    node_ids, hop_valid = plane.sample_bucket(
        jnp.asarray(seeds.astype(np.int32)), jnp.asarray(tk_hi),
        jnp.asarray(tk_lo), jnp.ones((4,), bool))
    trees = sampler.sample_forest(indptr, indices, seeds, (3, 2), key=6,
                                  tree_keys=tks)
    host_nodes, host_valid = stack_trees(trees, 4, (3, 2))
    assert np.array_equal(np.asarray(node_ids), np.asarray(host_nodes))
    assert np.array_equal(np.asarray(hop_valid), np.asarray(host_valid))


def test_padding_lanes_are_dead():
    import jax.numpy as jnp

    indptr, indices, n = _graph(seed=10)
    plane = DeviceSamplerPlane(indptr, indices, (3, 2), key=0)
    tk_hi, tk_lo = tree_key_mix(np.arange(3, dtype=np.uint64))
    live = jnp.asarray(np.array([True, False, True]))
    levels, valid = plane.sample_levels(
        jnp.asarray(np.array([5, 0, 9], np.int32)), jnp.asarray(tk_hi),
        jnp.asarray(tk_lo), live)
    for lv in levels:
        assert np.all(np.asarray(lv)[1] == -1)     # dead lane: ghost nodes
    for v in valid:
        assert not np.asarray(v)[1].any()          # dead lane: no edges


# ---------------------------------------------------------------------------
# serving engine in device-sampling mode
# ---------------------------------------------------------------------------

def _server(sampler_mode, seed=0, arch="gcn", backend="dense"):
    from repro.launch.gnn_serve import build_world
    from repro.serve import GNNServer

    cfg, params, indptr, indices, store = build_world(arch, 256, 1024, 16,
                                                      seed=seed)
    return GNNServer(arch, cfg, params, indptr, indices, store,
                     fanouts=(3, 2), backend=backend, sampler=sampler_mode,
                     max_batch_seeds=4, max_wait_ms=1.0, seed=seed)


def test_engine_rejects_unknown_sampler():
    with pytest.raises(ValueError):
        _server("gpu")


def test_engine_device_mode_matches_host_mode():
    seeds = [3, 77, 200, 9, 141, 55]
    outs = {}
    for mode in ("host", "device"):
        with _server(mode) as srv:
            srv.warmup()
            reqs = [srv.submit([s]) for s in seeds]
            srv.drain(timeout=600)
            outs[mode] = np.stack([r.result for r in reqs])
            assert srv.steps.builds >= 1
    # same rids → same tree keys → identical trees; forward is the same
    # program modulo sampling placement, so results agree to float tolerance
    assert np.allclose(outs["host"], outs["device"], atol=1e-5)


@pytest.mark.parametrize("arch,backend", [
    ("gcn", "dense"), ("gcn", "pallas"), ("sage", "pallas"),
    ("gin", "pallas"), ("gat", "pallas"), ("gcn", "pallas_q8")])
def test_engine_device_mode_offline_parity(arch, backend):
    """Served output equals the host-sampled one-request replay, with no
    build after warm-up.  ``pallas`` with the device sampler is the path
    the chip benchmark times; ``pallas_q8`` quantizes each bucket's plan
    with its own chunk scales, so it is held to the end-to-end envelope."""
    from repro.serve.engine import offline_replay
    from repro.sparse import quantize

    with _server("device", seed=1, arch=arch, backend=backend) as srv:
        srv.warmup()
        warm = srv.steps.builds
        reqs = [srv.submit([s]) for s in (10, 20, 30, 40)]
        srv.drain(timeout=600)
        assert srv.steps.builds == warm
        for r in reqs:
            ref = offline_replay(srv, r)   # host-sampled replay
            dev = float(np.abs(np.asarray(r.result) - ref).max())
            if backend == "pallas_q8":
                assert quantize.q8_gate(dev, Q8_E2E_TOL), dev
            else:
                assert dev <= 1e-5, dev
