"""Compile rehearsals for the TPU: the served path's kernels, and the fused
serving step, compiled for one chip of a described v5e (nothing runs).

The TPU compiler refuses what the Pallas interpreter accepts — unaligned
slices, narrow one-row DMAs, packed-dtype tiles — so these guard the chip
path at no chip time.  The topology is described inside a fixture, never
at import: only one process may load the TPU library, and every xdist
worker imports this file.  The program asks ``core.platform.on_tpu`` which
backend it is on; each test steers that one check itself.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from repro.core import platform
from repro.kernels.forest_sampler.forest_sampler import hash_draws
from repro.kernels.gustavson_spmm.gustavson_spmm import (
    _auto_d_tile, spmm_dedup_chunks, spmm_dedup_chunks_q8)
from repro.serve.buckets import build_bucket_structure
from repro.serve.compute import bucket_plan

FANOUTS = (15, 10)     # the Reddit shape's (configs.shapes minibatch_lg)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compile_for_tpu(monkeypatch):
    """The program takes its TPU branches; compiled programs for a chip
    that is not attached stay out of the persistent cache."""
    monkeypatch.setattr(platform, "on_tpu", lambda: True)
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def _spec(a, sharding, dtype=None):
    return jax.ShapeDtypeStruct(a.shape, dtype or a.dtype, sharding=sharding)


def _custom_calls(fn, *args) -> int:
    return jax.jit(fn).lower(*args).compile().as_text().count(
        "tpu_custom_call")


@pytest.fixture
def serving_plan(compile_for_tpu):
    """The bucket-16 plan of the Reddit fanouts, packed as on a TPU: both
    layouts' chunks are whole 128-lane tiles wide."""
    plan = bucket_plan(build_bucket_structure(16, FANOUTS), "pallas", True)
    assert plan.ell_u_cols.shape[1] == plan.ell_t_u_cols.shape[1] == 128
    return plan


@pytest.mark.parametrize("layout", ["ell_", "ell_t_"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("d", [602, 128])
def test_gustavson_spmm_compiles(one_chip, compile_for_tpu, serving_plan,
                                 layout, dtype, d):
    p = serving_plan
    get = lambda name: _spec(getattr(p, layout + name), one_chip)  # noqa: E731
    n_blocks = p.n_blocks if layout == "ell_" else p.n_t_blocks
    x = jax.ShapeDtypeStruct((p.n_rows, d), dtype, sharding=one_chip)

    def f(uc, rem, ob, fi, a, x):
        return spmm_dedup_chunks(uc, rem, ob, fi, a, x,
                                 block_rows=p.block_rows, n_blocks=n_blocks)

    assert _custom_calls(f, get("u_cols"), get("remaining"),
                         get("out_block"), get("first"), get("a"), x) == 1


def test_gustavson_spmm_refuses_narrow_chunks_when_compiled():
    """A chunk width the packer did not round to whole lane tiles is
    refused before Mosaic sees it."""
    narrow = jnp.zeros((2, 16), jnp.int32)
    ones = jnp.ones(2, jnp.int32)
    with pytest.raises(ValueError, match="not a multiple of 128"):
        spmm_dedup_chunks(narrow, ones, ones, ones,
                          jnp.zeros((16, 16), jnp.float32),
                          jnp.zeros((8, 128), jnp.float32), block_rows=8,
                          n_blocks=2, interpret=False)


@pytest.mark.parametrize("d", [602, 128])
def test_gustavson_spmm_q8_compiles(one_chip, compile_for_tpu, serving_plan,
                                    d):
    p = serving_plan
    n_chunks = p.ell_u_cols.shape[0]
    d_tiles = -(-d // _auto_d_tile(d))

    def f(uc, rem, ob, fi, a_q8, a_scale, x_q8, x_scale):
        return spmm_dedup_chunks_q8(uc, rem, ob, fi, a_q8, a_scale, x_q8,
                                    x_scale, block_rows=p.block_rows,
                                    n_blocks=p.n_blocks)

    assert _custom_calls(
        f, _spec(p.ell_u_cols, one_chip), _spec(p.ell_remaining, one_chip),
        _spec(p.ell_out_block, one_chip), _spec(p.ell_first, one_chip),
        _spec(p.ell_a, one_chip, jnp.int8),
        jax.ShapeDtypeStruct((n_chunks,), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((p.n_rows, d), jnp.int8, sharding=one_chip),
        jax.ShapeDtypeStruct((d_tiles,), jnp.float32,
                             sharding=one_chip)) == 1


@pytest.mark.parametrize("t,lanes", [(16, 15), (16, 150), (1024, 16)])
def test_hash_draws_compiles(one_chip, compile_for_tpu, t, lanes):
    spec = jax.ShapeDtypeStruct((t, lanes), jnp.uint32, sharding=one_chip)
    assert _custom_calls(hash_draws, spec, spec, spec) == 1


def test_fused_sage_serving_step_compiles(one_chip, compile_for_tpu):
    """The per-bucket program ``GNNServer(backend="pallas",
    sampler="device")`` dispatches — device sampling, feature gather and
    GraphSAGE at 602 -> 64 -> 41 — with the draw kernel and one Gustavson
    kernel per layer in it."""
    from repro.launch.gnn_serve import build_world
    from repro.serve import GNNServer
    cfg, params, indptr, indices, store = build_world("sage", 300, 3000)
    assert (cfg.d_in, cfg.n_classes) == (602, 41)
    server = GNNServer("sage", cfg, params, indptr, indices, store,
                       fanouts=FANOUTS, backend="pallas", sampler="device",
                       max_batch_seeds=16)
    try:
        step = server.steps.get((16,))
        bound = jax.tree.map(lambda a: _spec(a, one_chip),
                             (step.args, params))
        seeds = jax.ShapeDtypeStruct((16,), jnp.int32, sharding=one_chip)
        keys = jax.ShapeDtypeStruct((16,), jnp.uint32, sharding=one_chip)
        live = jax.ShapeDtypeStruct((16,), jnp.bool_, sharding=one_chip)
        text = step.func.lower(*bound[0], bound[1], seeds, keys, keys,
                               live).compile().as_text()
    finally:
        server.close()
    # hop-1 and hop-2 draws + two SAGE layers' aggregations
    assert text.count("tpu_custom_call") >= 4


def _lower_fused_gat_step(one_chip):
    """The 16-seed bucket program of ``GNNServer("gat", backend="pallas",
    sampler="device")`` at ogbn-products GAT widths (100 -> 4x128 -> 4x128
    -> 47, last heads averaged, skips, fanouts (10, 10, 10)), lowered for
    one chip: the draw kernels, and per layer the attention and one valued
    Gustavson kernel per head."""
    from repro.data import synthetic as syn
    from repro.models.gnn import gat
    from repro.serve import GNNServer
    from repro.serve.compute import FeatureStore
    from repro.sparse.graph import coo_to_csr
    cfg = gat.GATConfig(name="gat-products", n_layers=3, d_in=100,
                        d_hidden=128, n_heads=4, n_classes=47, out_heads=4,
                        skip=True)
    params = gat.init_params(jax.random.key(0), cfg)
    s, r = syn.powerlaw_graph(300, 3000, seed=0)
    indptr, indices = coo_to_csr(s, r, 300)[:2]
    x = np.random.default_rng(0).normal(size=(300, 100)).astype(np.float32)
    server = GNNServer("gat", cfg, params, indptr, indices,
                       FeatureStore.build(300, x=x), fanouts=(10, 10, 10),
                       backend="pallas", sampler="device",
                       max_batch_seeds=16)
    try:
        step = server.steps.get((16,))
        bound = jax.tree.map(lambda a: _spec(a, one_chip),
                             (step.args, params))
        seeds = jax.ShapeDtypeStruct((16,), jnp.int32, sharding=one_chip)
        keys = jax.ShapeDtypeStruct((16,), jnp.uint32, sharding=one_chip)
        live = jax.ShapeDtypeStruct((16,), jnp.bool_, sharding=one_chip)
        return step.func.lower(*bound[0], bound[1], seeds, keys, keys, live)
    finally:
        server.close()


def _gustavson_calls(text: str) -> list:
    """``(layer scope, output rows)`` of each Gustavson kernel call in a
    compiled step's text, in program order."""
    calls = re.findall(r"= f32\[(\d+),\d+\]\S* custom-call\(.*?"
                       r"custom_call_target=\"tpu_custom_call\".*?"
                       r"op_name=\"[^\"/]*/(layer\d+)\.aggregate/", text)
    return [(layer, int(rows)) for rows, layer in calls]


def test_fused_gat_serving_step_compiles(one_chip, compile_for_tpu):
    text = _lower_fused_gat_step(one_chip).compile().as_text()
    kernels = re.findall(r"custom_call_target=\"tpu_custom_call\".*?"
                         r"op_name=\"([^\"]*)\"", text)
    gustavson = [k for k in kernels if "layer" in k and ".aggregate" in k]
    # one draw kernel per hop, and 3 layers x 4 heads of aggregation
    assert len(kernels) == 3 + 12
    assert sorted({k.split("/")[1] for k in gustavson}) == [
        "layer0.aggregate", "layer1.aggregate", "layer2.aggregate"]
    assert len(gustavson) == 12
    # each layer writes only the rows the next reads (16-row blocks):
    # levels 0-2 of the 17,776-row bucket, then 0-1, then the seeds
    assert sorted(_gustavson_calls(text)) == (
        [("layer0", 1776)] * 4 + [("layer1", 176)] * 4
        + [("layer2", 16)] * 4)


def test_fused_products_sage_step_aggregates_the_rows_it_answers(
        one_chip, compile_for_tpu):
    """The 16-seed bucket program of the products GraphSAGE (100 -> 256 ->
    256 -> 47, fanouts (15, 10, 5), 14,656 rows): one Gustavson call a
    layer, each over the levels the next layer reads."""
    from repro.data import synthetic as syn
    from repro.models.gnn import sage
    from repro.serve import GNNServer
    from repro.serve.compute import FeatureStore
    from repro.sparse.graph import coo_to_csr
    cfg = sage.SAGEConfig(n_layers=3, d_in=100, d_hidden=256, n_classes=47)
    params = sage.init_params(jax.random.key(0), cfg)
    s, r = syn.powerlaw_graph(300, 3000, seed=0)
    indptr, indices = coo_to_csr(s, r, 300)[:2]
    x = np.random.default_rng(0).normal(size=(300, 100)).astype(np.float32)
    server = GNNServer("sage", cfg, params, indptr, indices,
                       FeatureStore.build(300, x=x), fanouts=(15, 10, 5),
                       backend="pallas", sampler="device",
                       max_batch_seeds=16)
    try:
        step = server.steps.get((16,))
        bound = jax.tree.map(lambda a: _spec(a, one_chip),
                             (step.args, params))
        seeds = jax.ShapeDtypeStruct((16,), jnp.int32, sharding=one_chip)
        keys = jax.ShapeDtypeStruct((16,), jnp.uint32, sharding=one_chip)
        live = jax.ShapeDtypeStruct((16,), jnp.bool_, sharding=one_chip)
        text = step.func.lower(*bound[0], bound[1], seeds, keys, keys,
                               live).compile().as_text()
    finally:
        server.close()
    assert sorted(_gustavson_calls(text)) == [
        ("layer0", 2656), ("layer1", 256), ("layer2", 16)]


def test_fused_gat_step_carries_the_stage_scopes(one_chip, compile_for_tpu):
    text = _lower_fused_gat_step(one_chip).as_text(debug_info=True)
    for k in range(3):
        for stage in ("attend", "aggregate", "dense"):
            assert f"layer{k}.{stage}" in text, (k, stage)
    assert "serve.sample" in text and "serve.gather" in text


@pytest.mark.parametrize("fanouts,loops,block_rows", [
    ((25, 10), False, 8),          # sage-reddit
    ((15, 10, 5), False, 8),       # sage-products
    ((10, 10, 10), True, 16),      # gat-products: 17,776 rows and loops
])
def test_bucket_plans_keep_their_chunk_tables_in_smem(compile_for_tpu,
                                                      fanouts, loops,
                                                      block_rows):
    """Compiled, the kernel prefetches a layout's chunk tables into SMEM:
    a 16-seed bucket whose tables would not fit packs taller row blocks,
    and the GraphSAGE buckets keep the blocks they had."""
    from repro.kernels.gustavson_spmm.gustavson_spmm import tables_fit_smem
    plan = bucket_plan(build_bucket_structure(16, fanouts, with_loops=loops),
                       "pallas", True)
    assert plan.block_rows == block_rows
    assert tables_fit_smem(plan.ell_u_cols)
    assert tables_fit_smem(plan.ell_t_u_cols)
