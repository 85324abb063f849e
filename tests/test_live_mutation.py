"""Zero-downtime live mutation (DESIGN.md §16): weight hot-swap + streaming
graph updates against a RUNNING cluster, under load.

The mutation-drill contract these tests pin (and the CI mutation leg runs):

* ≥3 consecutive hot-swaps under continuous traffic — zero lost requests,
  zero duplicated settlements, every request stamped with exactly ONE
  weight version, versions monotone, old versions drained + GCed;
* abort paths (torn checkpoint, shape-mismatched tree) leave the serving
  version untouched;
* streaming edge mutations install atomically with parity proven vs a cold
  re-pack before every install; post-mutation requests replay offline to
  ≤1e-5 on the mutated adjacency;
* feature rows re-home through the existing layout (replicated fetch-step
  rebuild; sharded DRHM scatter needs the 8-device mesh).

Replicated-mode tests run on any device count; sharded ones carry the
``multi_device`` skip and run in the CI mutation/multi-device legs.
"""
import numpy as np
import pytest

import jax

from repro.checkpoint import store as ckpt_store
from repro.launch.gnn_serve import build_world
from repro.serve import (ClusterServer, GraphStream, HotSwapError, hot_swap)
from repro.serve.errors import GraphMutationError
from repro.serve.live import _csr_to_coo

N_LANES = 8
multi_device = pytest.mark.skipif(
    jax.device_count() < N_LANES,
    reason=f"needs {N_LANES} devices (the CI mutation leg sets "
           "XLA_FLAGS=--xla_force_host_platform_device_count=8)")

N_NODES, N_EDGES, D_IN = 256, 2048, 16


def _server(**kw):
    cfg, params, indptr, indices, store = build_world(
        "gcn", N_NODES, N_EDGES, D_IN, 0)
    kw.setdefault("n_lanes", 2)
    srv = ClusterServer("gcn", cfg, params, indptr, indices, store,
                        seed=0, **kw)
    srv.warmup([1, 2])
    return srv, params, indptr, indices


def _perturbed(params, k):
    return jax.tree.map(lambda a: a * (1.0 + 0.01 * k)
                        if np.issubdtype(np.asarray(a).dtype, np.floating)
                        else a, params)


def _submit_load(srv, rng, n=24):
    return srv.submit_many(
        [rng.integers(0, N_NODES, size=2) for _ in range(n)])


# ---------------------------------------------------------------------------
# Hot swap
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mutate", [False, True])
def test_three_swaps_under_load_exactly_once(tmp_path, mutate):
    """The drill core: 3 consecutive swaps with traffic in flight — every
    request settles exactly once on exactly one version, nothing lost.
    With ``mutate``, a streaming edge flush between cycles installs under
    the same load, each proven equal to a cold re-pack."""
    srv, params, indptr, indices = _server()
    rng = np.random.default_rng(0)
    s0, r0 = _csr_to_coo(indptr, indices)
    gs = GraphStream(srv, max_pending=256, parity_every=1)
    all_reqs = []
    try:
        for k in (1, 2, 3):
            ckpt_store.save(tmp_path, k, _perturbed(params, k),
                            {"cycle": k})
        for k in (1, 2, 3):
            all_reqs += _submit_load(srv, rng)
            rep = hot_swap(srv, tmp_path, step=k, drain_timeout=60.0)
            assert rep.version == k and rep.old_version == k - 1
            assert rep.drained_old, "old version never drained"
            if mutate:
                for _ in range(16):
                    gs.insert(int(rng.integers(0, N_NODES)),
                              int(rng.integers(0, N_NODES)))
                for i in range(4 * k - 4, 4 * k):
                    gs.delete(int(s0[i]), int(r0[i]))
                assert gs.flush().parity_ok
            all_reqs += _submit_load(srv, rng)
        srv.drain()
    finally:
        srv.close()
    if mutate:
        assert len(gs.flushes) == 3
        assert {r.graph_epoch for r in all_reqs[-24:]} == {3}
    assert len(all_reqs) == 6 * 24
    for r in all_reqs:
        assert r.n_settles == 1, f"rid {r.rid} settled {r.n_settles}×"
        assert r.error is None and r.result is not None
        assert r.params_version is not None
        assert 0 <= r.params_version <= 3
    # versions observed are monotone in settle order is not guaranteed
    # (rounds interleave), but the final retired set must be empty
    assert srv.retired_versions() == []
    assert srv.params_version == 3


def test_swap_flips_router_epoch_and_results_change(tmp_path):
    srv, params, _, _ = _server()
    rng = np.random.default_rng(1)
    try:
        seeds = rng.integers(0, N_NODES, size=2)
        before = srv.submit(seeds).wait(30)
        epoch0 = srv.router.epoch
        ckpt_store.save(tmp_path, 5, _perturbed(params, 9))
        rep = hot_swap(srv, tmp_path)
        assert rep.step == 5
        assert srv.router.epoch == epoch0 + 1      # the epoch boundary
        after = srv.submit(seeds).wait(30)
        assert np.max(np.abs(after - before)) > 0  # new weights serve
        # offline replay parity holds on the new version too
        req = srv.submit(seeds)
        req.wait(30)
        np.testing.assert_allclose(srv.offline_replay(req), req.result,
                                   atol=1e-5)
    finally:
        srv.close()


def test_torn_checkpoint_aborts_swap_with_server_untouched(tmp_path):
    srv, params, _, _ = _server(n_lanes=1)
    try:
        step_dir = tmp_path / "step_000002"
        step_dir.mkdir(parents=True)
        (step_dir / "manifest.json").write_text("{}")   # no COMMIT
        with pytest.raises(HotSwapError) as ei:
            hot_swap(srv, tmp_path, step=2)
        assert ei.value.stage == "validate"
        assert srv.params_version == 0
        assert srv.retired_versions() == []
        # and a shape-mismatched tree also aborts pre-flip
        bad = jax.tree.map(lambda a: np.zeros((3, 3), np.float32), params)
        ckpt_store.save(tmp_path, 3, bad)
        with pytest.raises(HotSwapError):
            hot_swap(srv, tmp_path, step=3)
        assert srv.params_version == 0
        # server still serves
        srv.submit(np.array([1, 2])).wait(30)
    finally:
        srv.close()


def test_no_checkpoint_is_a_typed_abort(tmp_path):
    srv, _, _, _ = _server(n_lanes=1)
    try:
        with pytest.raises(HotSwapError) as ei:
            hot_swap(srv, tmp_path / "empty")
        assert ei.value.stage == "resolve"
    finally:
        srv.close()


def test_install_params_rejects_stale_version():
    srv, params, _, _ = _server(n_lanes=1)
    try:
        srv.install_params(_perturbed(params, 1), version=4)
        with pytest.raises(ValueError):
            srv.install_params(params, version=4)
    finally:
        srv.close()


# ---------------------------------------------------------------------------
# Streaming graph mutation
# ---------------------------------------------------------------------------

def test_graph_stream_parity_and_epoch_stamping():
    srv, _, indptr, indices = _server()
    rng = np.random.default_rng(2)
    try:
        gs = GraphStream(srv, max_pending=64, parity_every=1)
        # the reconstructed delta state starts bitwise at the serving CSR
        np.testing.assert_array_equal(gs.delta.csr()[0], indptr)
        np.testing.assert_array_equal(gs.delta.csr()[1], indices)
        s0, r0 = _csr_to_coo(indptr, indices)
        for i in range(40):
            gs.insert(int(rng.integers(0, N_NODES)),
                      int(rng.integers(0, N_NODES)))
            if i % 4 == 0:
                gs.delete(int(s0[i]), int(r0[i]))
        rep = gs.flush()
        assert rep is not None and rep.parity_ok is True
        assert rep.inserted == 40 and rep.deleted == 10
        # requests sampled after the flush carry the new epoch and replay
        # offline (the sampler + offline path share the swapped CSR)
        reqs = _submit_load(srv, rng, n=8)
        srv.drain()
        for r in reqs:
            assert r.error is None and r.graph_epoch == rep.epoch
        np.testing.assert_allclose(srv.offline_replay(reqs[0]),
                                   reqs[0].result, atol=1e-5)
    finally:
        srv.close()


def test_graph_stream_bounded_staleness_autoflush():
    srv, _, _, _ = _server(n_lanes=1)
    try:
        gs = GraphStream(srv, max_pending=4)
        for i in range(3):
            gs.insert(i, i + 1)
        assert gs.pending == 3 and not gs.flushes     # window open
        gs.insert(3, 4)                               # trips max_pending
        assert gs.pending == 0 and len(gs.flushes) == 1
        assert gs.staleness() == 0.0
    finally:
        srv.close()


def test_graph_stream_rejects_bad_mutations():
    srv, _, _, _ = _server(n_lanes=1)
    try:
        gs = GraphStream(srv)
        with pytest.raises(ValueError):               # DeltaGraphError
            gs.insert(N_NODES + 7, 0)
        # find an absent edge and try to delete it
        absent = next((s, r) for r in range(N_NODES) for s in range(N_NODES)
                      if not _has_edge(srv, s, r))
        with pytest.raises(ValueError):
            gs.delete(*absent)
        assert gs.pending == 0
    finally:
        srv.close()


def _has_edge(srv, s, r):
    lo, hi = srv.indptr[r], srv.indptr[r + 1]
    return bool(np.any(np.asarray(srv.indices[lo:hi]) == s))


def test_node_count_is_immutable():
    srv, _, indptr, indices = _server(n_lanes=1)
    try:
        with pytest.raises(ValueError):
            srv.apply_graph_update(np.asarray(indptr)[:-1],
                                   np.asarray(indices))
    finally:
        srv.close()


def test_feature_rehome_replicated():
    srv, _, _, _ = _server(n_lanes=1)
    rng = np.random.default_rng(3)
    try:
        seeds = np.array([7, 7])
        before = srv.submit(seeds).wait(30)
        rows = np.unique(rng.integers(0, N_NODES, 32).astype(np.int64))
        srv.update_feature_rows(
            rows, rng.normal(size=(rows.size, D_IN)).astype(np.float32))
        req = srv.submit(seeds)
        req.wait(30)
        # offline replay (rebuilt over the patched store) still matches
        np.testing.assert_allclose(srv.offline_replay(req), req.result,
                                   atol=1e-5)
        assert np.max(np.abs(req.result - before)) >= 0.0
    finally:
        srv.close()


# ---------------------------------------------------------------------------
# Sharded residency (8-device mesh)
# ---------------------------------------------------------------------------

@multi_device
def test_sharded_swap_and_mutation():
    """The full drill on sharded residency: a hot-swap and a graph flush
    on the 8-lane mesh, with offline-replay parity after both."""
    import tempfile
    cfg, params, indptr, indices, store = build_world(
        "gcn", N_NODES, N_EDGES, D_IN, 0)
    srv = ClusterServer("gcn", cfg, params, indptr, indices, store,
                        n_lanes=N_LANES, mode="sharded", placement="mesh",
                        seed=0)
    rng = np.random.default_rng(4)
    try:
        srv.warmup([1, 2])
        reqs = _submit_load(srv, rng)
        with tempfile.TemporaryDirectory() as d:
            ckpt_store.save(d, 1, _perturbed(params, 2))
            rep = hot_swap(srv, d, drain_timeout=60.0)
        assert rep.drained_old and srv.params_version == 1
        gs = GraphStream(srv, max_pending=512, parity_every=1)
        for _ in range(24):
            gs.insert(int(rng.integers(0, N_NODES)),
                      int(rng.integers(0, N_NODES)))
        frep = gs.flush()
        assert frep.parity_ok is True
        reqs += _submit_load(srv, rng)
        srv.drain()
        for r in reqs:
            assert r.n_settles == 1 and r.error is None
        np.testing.assert_allclose(srv.offline_replay(reqs[-1]),
                                   reqs[-1].result, atol=1e-5)
    finally:
        srv.close()


@multi_device
def test_sharded_feature_rehome_scatters_in_place():
    """Delta feature rows land at perm[row] in the resident sharded table —
    no re-shard, and the served result reflects the new rows."""
    cfg, params, indptr, indices, store = build_world(
        "gcn", N_NODES, N_EDGES, D_IN, 0)
    srv = ClusterServer("gcn", cfg, params, indptr, indices, store,
                        n_lanes=N_LANES, mode="sharded", placement="mesh",
                        seed=0)
    rng = np.random.default_rng(5)
    try:
        srv.warmup([1])
        rows = np.arange(0, 32, dtype=np.int64)
        new = rng.normal(size=(rows.size, D_IN)).astype(np.float32)
        srv.update_feature_rows(rows, new)
        x_perm = np.asarray(jax.device_get(srv._x_perm))
        np.testing.assert_array_equal(
            x_perm[srv.shard_plan.perm[rows]], new)
        req = srv.submit(np.array([3, 5]))
        req.wait(30)
        np.testing.assert_allclose(srv.offline_replay(req), req.result,
                                   atol=1e-5)
    finally:
        srv.close()
