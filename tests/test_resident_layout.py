"""Resident feature tables are committed row-major, once.

A row gather from a column-major table makes XLA relay the whole table out
inside every call of the step (on a TPU, narrow tables are column-major by
default).  ``compute.row_major`` re-commits such a table once, and every
server holds its tables that way, so the compiled step reads the argument
as it lies.  The CPU lays arrays out row-major but accepts a column-major
one on request, which is how these tests build the chip's case.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.experimental.layout import Format, Layout

from repro.data import synthetic as syn
from repro.models.gnn import sage
from repro.serve import compute
from repro.serve.cluster import ClusterServer
from repro.serve.compute import FeatureStore
from repro.serve.engine import GNNServer
from repro.sparse.graph import coo_to_csr
from repro.sparse.stats import kernel_stats

N, E, D = 300, 1500, 16
FANOUTS = (3, 2)
ROW_MAJOR = (0, 1)


def _csr():
    s, r = syn.powerlaw_graph(N, E, seed=0)
    return coo_to_csr(s, r, N)[:2]


def _x():
    return np.random.default_rng(1).normal(size=(N, D)).astype(np.float32)


def _relayouts() -> int:
    return kernel_stats().counters().get("feature_store.relayouts", 0)


def _column_major(a):
    # through ``_relayout``: a plain ``device_put`` could load its program
    # from a persistent cache another test turned on, layout lost
    try:
        out = jax.jit(compute._relayout, out_shardings=Format(
            Layout(major_to_minor=(1, 0)), a.sharding))(a)
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"this backend refuses a column-major layout: {e}")
    if out.format.layout.major_to_minor != (1, 0):
        pytest.skip("this backend does not keep a column-major layout")
    return out


def _column_major_store():
    store = FeatureStore.build(N, x=_x())
    return FeatureStore(n_nodes=N, x=_column_major(store.x))


def _tables(store):
    return [a for a in (store.x, store.species, store.pos)
            if a is not None and a.ndim == 2]


def _table_copies(hlo: str, shape) -> list:
    tag = f"f32[{shape[0]},{shape[1]}]"
    return [ln for ln in hlo.splitlines() if " copy(" in ln and tag in ln]


# one process: a table laid out column-major by ``compute._relayout`` (the
# CPU's non-default layout, as row-major is a TPU's for a narrow table), then
# re-committed row-major, each read by a jitted row gather, with JAX's
# persistent compilation cache on
_CACHED_RUN = """
import json, sys
import jax, jax.numpy as jnp, numpy as np
from jax.experimental.layout import Format, Layout
jax.config.update("jax_compilation_cache_dir", sys.argv[1])
jax.config.update("jax_enable_compilation_cache", True)
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
from repro.serve import compute
x = np.arange(301 * 16, dtype=np.float32).reshape(301, 16)
a = jnp.asarray(x)
a = jax.jit(compute._relayout, out_shardings=Format(
    Layout(major_to_minor=(1, 0)), a.sharding))(a)
y = compute.row_major(a)
take = jax.jit(lambda t, i: jnp.take(t, i, axis=0))
idx = jnp.arange(0, 300, 7)
print(json.dumps([[list(t.format.layout.major_to_minor),
                   bool((np.asarray(take(t, idx)) == x[0:300:7]).all())]
                  for t in (a, y)]))
"""


def _sage():
    cfg = sage.SAGEConfig(d_in=D, d_hidden=8, n_classes=3)
    return cfg, sage.init_params(jax.random.key(0), cfg)


def test_row_major_returns_the_same_object_when_row_major():
    x = jnp.asarray(_x())
    assert x.format.layout.major_to_minor == ROW_MAJOR
    before = _relayouts()
    assert compute.row_major(x) is x
    assert compute.row_major(None) is None
    v = jnp.arange(5)
    assert compute.row_major(v) is v
    store = FeatureStore.build(N, x=_x())
    assert compute.resident(store).x is store.x
    assert _relayouts() == before


def test_column_major_table_is_recommitted_bitwise():
    x = jnp.asarray(_x())
    xc = _column_major(x)
    before = _relayouts()
    y = compute.row_major(xc)
    assert y is not xc
    assert y.format.layout.major_to_minor == ROW_MAJOR
    assert y.sharding == xc.sharding
    np.testing.assert_array_equal(np.asarray(y), np.asarray(x))
    assert _relayouts() == before + 1
    assert compute.row_major(y) is y
    assert _relayouts() == before + 1


@pytest.mark.parametrize("sampler", ["host", "device"])
def test_gnn_server_holds_row_major_tables(sampler):
    cfg, params = _sage()
    indptr, indices = _csr()
    store = _column_major_store()
    before = _relayouts()
    server = GNNServer("sage", cfg, params, indptr, indices, store,
                       fanouts=FANOUTS, sampler=sampler, max_batch_seeds=4)
    try:
        assert _relayouts() == before + 1
        assert all(a.format.layout.major_to_minor == ROW_MAJOR
                   for a in _tables(server.store))
        np.testing.assert_array_equal(np.asarray(server.store.x),
                                      np.asarray(store.x))
    finally:
        server.close()


def test_geometric_store_pos_table_is_row_major():
    rng = np.random.default_rng(3)
    store = FeatureStore.build(
        N, species=rng.integers(1, 9, N).astype(np.int32),
        pos=rng.normal(size=(N, 3)).astype(np.float32))
    store = FeatureStore(n_nodes=N, species=store.species,
                         pos=_column_major(store.pos))
    held = compute.resident(store)
    assert held.species is store.species
    assert held.pos.format.layout.major_to_minor == ROW_MAJOR
    np.testing.assert_array_equal(np.asarray(held.pos),
                                  np.asarray(store.pos))


def test_cluster_server_holds_row_major_tables_after_updates():
    cfg, params = _sage()
    indptr, indices = _csr()
    server = ClusterServer("sage", cfg, params, indptr, indices,
                           _column_major_store(), n_lanes=2,
                           fanouts=FANOUTS, max_batch_seeds=4)
    try:
        assert all(a.format.layout.major_to_minor == ROW_MAJOR
                   for a in _tables(server.store))
        rows = np.full((2, D), 7.0, np.float32)
        server.update_feature_rows([3, 5], rows)
        assert all(a.format.layout.major_to_minor == ROW_MAJOR
                   for a in _tables(server.store))
        np.testing.assert_array_equal(np.asarray(server.store.x)[[3, 5]],
                                      rows)
    finally:
        server.close()


def test_fused_step_compiles_with_no_table_copy():
    """The device-sampler step reads the held table as it lies; the same
    program over the column-major table relays it out (the control: the
    check can see the copy) and answers bitwise the same."""
    cfg, params = _sage()
    indptr, indices = _csr()
    store = _column_major_store()
    server = GNNServer("sage", cfg, params, indptr, indices, store,
                       fanouts=FANOUTS, sampler="device", max_batch_seeds=4)
    try:
        step = server.steps.get((4,))
        plane, held = step.args
        inputs = (params, np.array([1, 7, 42, 0], np.int32),
                  np.arange(4, dtype=np.uint32),
                  np.arange(4, 8, dtype=np.uint32),
                  np.array([True, True, True, False]))
        shape = (N + 1, D)
        hlo = step.func.lower(plane, held, *inputs).compile().as_text()
        assert _table_copies(hlo, shape) == []
        hlo_c = step.func.lower(plane, store, *inputs).compile().as_text()
        assert _table_copies(hlo_c, shape)
        np.testing.assert_array_equal(
            np.asarray(step.func(plane, held, *inputs)),
            np.asarray(step.func(plane, store, *inputs)))
    finally:
        server.close()


def test_recommitted_table_survives_the_persistent_compile_cache(tmp_path):
    """A second process reads the programs the first one cached: a table
    the relayout program wrote must still report, and be read in, the
    layout it lies in."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(os.path.dirname(__file__), "..", "src"),
                    os.environ.get("PYTHONPATH", "")]))
    for _ in range(2):
        out = subprocess.run([sys.executable, "-c", _CACHED_RUN,
                              str(tmp_path)], env=env, capture_output=True,
                             text=True, timeout=300)
        assert out.returncode == 0, out.stderr[-2000:]
        got = json.loads(out.stdout.strip().splitlines()[-1])
        assert got == [[[1, 0], True], [list(ROW_MAJOR), True]]
    assert os.listdir(tmp_path)          # the gather's program was cached
