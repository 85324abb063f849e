"""Checkpoint store: commit protocol, async, torn-write safety, elastic."""
import json
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import store


def _tree(seed=0):
    rng = np.random.default_rng(seed)
    return {"a": jnp.asarray(rng.normal(size=(8, 4)).astype(np.float32)),
            "b": {"c": jnp.asarray(rng.integers(0, 10, 5), jnp.int32)}}


def test_roundtrip(tmp_path):
    t = _tree()
    store.save(tmp_path, 3, t, metadata={"loss": 1.5})
    like = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), t)
    out, meta = store.restore(tmp_path, 3, like)
    assert meta["loss"] == 1.5
    for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(out)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_torn_checkpoint_ignored(tmp_path):
    t = _tree()
    store.save(tmp_path, 1, t)
    step2 = tmp_path / "step_000002"
    step2.mkdir()
    (step2 / "manifest.json").write_text(json.dumps({"step": 2}))  # no COMMIT
    assert store.latest_step(tmp_path) == 1


def test_async_and_gc(tmp_path):
    ck = store.AsyncCheckpointer(tmp_path)
    for s in (1, 2, 3, 4):
        ck.save_async(s, _tree(s))
    ck.wait()
    assert store.committed_steps(tmp_path) == [1, 2, 3, 4]
    store.gc_keep_last(tmp_path, keep=2)
    assert store.committed_steps(tmp_path) == [3, 4]


def test_elastic_restore_new_sharding(tmp_path):
    """Restore maps onto a different device layout (topology-free manifest)."""
    t = _tree()
    store.save(tmp_path, 7, t)
    mesh = jax.make_mesh((1,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    from jax.sharding import NamedSharding, PartitionSpec as P
    sh = jax.tree.map(lambda _: NamedSharding(mesh, P()), t)
    like = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), t)
    out, _ = store.restore(tmp_path, 7, like, shardings=sh)
    np.testing.assert_array_equal(np.asarray(out["a"]), np.asarray(t["a"]))
    assert out["a"].sharding == sh["a"]


def test_restore_uncommitted_step_raises_typed(tmp_path):
    """A torn (no-COMMIT) step restores as a typed CheckpointError — the
    hot-swap validate stage depends on never loading garbage."""
    t = _tree()
    step2 = tmp_path / "step_000002"
    step2.mkdir()
    (step2 / "manifest.json").write_text(json.dumps({"step": 2}))
    like = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), t)
    with pytest.raises(store.CheckpointError, match="COMMIT"):
        store.restore(tmp_path, 2, like)


def test_restore_shape_mismatch_raises_typed(tmp_path):
    t = _tree()
    store.save(tmp_path, 1, t)
    bad_like = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct((3, 3), jnp.float32), t)
    with pytest.raises(store.CheckpointError, match="shape mismatch"):
        store.restore(tmp_path, 1, bad_like)
    # and validate_step alone flags incomplete manifests
    shutil.copytree(tmp_path / "step_000001", tmp_path / "step_000009")
    man = json.loads((tmp_path / "step_000009" / "manifest.json").read_text())
    man["n_leaves"] = 99
    (tmp_path / "step_000009" / "manifest.json").write_text(json.dumps(man))
    with pytest.raises(store.CheckpointError, match="incomplete"):
        store.validate_step(tmp_path, 9)


def test_missing_leaf_file_raises_typed(tmp_path):
    t = _tree()
    store.save(tmp_path, 4, t)
    (tmp_path / "step_000004" / "leaf_00000.npy").unlink()
    with pytest.raises(store.CheckpointError, match="missing leaf"):
        store.validate_step(tmp_path, 4)


def test_gc_never_deletes_inflight_async_save(tmp_path):
    """The GC-vs-save_async race: a slow in-flight save is both shielded
    from deletion and counted toward the newest-``keep`` window."""
    import threading
    import time as _time

    for s in (1, 2, 3):
        store.save(tmp_path, s, _tree(s))

    gate = threading.Event()
    orig_save = store.save

    def slow_save(ckpt_dir, step, tree, metadata=None):
        gate.wait(10.0)               # hold the save un-committed
        return orig_save(ckpt_dir, step, tree, metadata)

    ck = store.AsyncCheckpointer(tmp_path)
    store.save, saved = slow_save, store.save
    try:
        ck.save_async(9, _tree(9))
        # the in-flight step is registered the moment save_async returns
        assert store.inflight_steps(tmp_path) == [9]
        # GC with keep=2: window = {3, 9} — step 9 counts toward it even
        # though uncommitted, so steps 1 AND 2 go, step 3 stays
        store.gc_keep_last(tmp_path, keep=2)
        assert store.committed_steps(tmp_path) == [3]
        assert (tmp_path / "step_000003").exists()
    finally:
        gate.set()
        ck.wait()
        store.save = saved
    assert store.committed_steps(tmp_path) == [3, 9]
    assert store.inflight_steps(tmp_path) == []
    # GC after commit behaves classically
    store.gc_keep_last(tmp_path, keep=1)
    assert store.committed_steps(tmp_path) == [9]
