"""int8 quantized fast path (``sparse/quantize.py``, ``pallas_q8``):
scale contract, scale-derived parity bounds, resident features, and the
compression zero-block guard (DESIGN.md §12)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:  # deterministic fallback; requirements-dev.txt has the real one
    from _hypothesis_shim import given, settings, st

from repro.data.synthetic import powerlaw_graph
from repro.kernels.gustavson_spmm.gustavson_spmm import _auto_d_tile
from repro.optim import compression
from repro.sparse import backend as sparse_backend
from repro.sparse import quantize
from repro.sparse.plan import make_plan
from repro.sparse.quantize import aggregate_q8_bound_for
from repro.sparse.spgemm import make_spgemm_plan


def _plan_x(n, e, d, seed=0, dtype=np.float32):
    rng = np.random.default_rng(seed)
    s, r = powerlaw_graph(n, e + 64, seed=seed)
    s, r = s[:e], r[:e]
    vals = rng.normal(size=e).astype(np.float32)
    x = jnp.asarray(rng.normal(size=(n, d)).astype(dtype))
    plan = make_plan(s, r, n, edge_weight=vals,
                     backends=sparse_backend.ALL_BACKENDS, chunk=min(512, e))
    return plan, x


# ---------------------------------------------------------------------------
# quantization contract
# ---------------------------------------------------------------------------

def test_chunk_tiles_roundtrip_error_within_half_scale():
    rng = np.random.default_rng(0)
    a = rng.normal(size=(6 * 8, 16)).astype(np.float32) * 3.0
    q8, scale = quantize.quantize_chunk_tiles(a, 6)
    assert q8.dtype == jnp.int8 and scale.shape == (6,)
    deq = np.asarray(q8, np.float32).reshape(6, -1) * np.asarray(scale)[:, None]
    err = np.abs(deq - a.reshape(6, -1))
    # symmetric rounding: per-entry error ≤ scale/2
    assert np.all(err <= np.asarray(scale)[:, None] * 0.5 + 1e-7)


def test_chunk_tiles_zero_tile_exact_and_scale_one():
    a = np.zeros((2 * 4, 8), np.float32)
    a[4:] = 1.0                       # second chunk non-zero
    q8, scale = quantize.quantize_chunk_tiles(a, 2)
    assert float(scale[0]) == 1.0     # all-zero chunk: guard scale
    assert np.all(np.asarray(q8)[:4] == 0)


def test_chunk_tiles_empty_layout():
    q8, scale = quantize.quantize_chunk_tiles(np.zeros((0, 8), np.float32), 0)
    assert q8.shape == (0, 8) and scale.shape == (0,)


@given(st.integers(1, 5), st.sampled_from([1, 3, 8, 16, 33]),
       st.sampled_from([4, 8, 16]))
@settings(max_examples=15, deadline=None)
def test_feature_tiles_roundtrip(seed, d, d_tile):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(12, d)).astype(np.float32) * (seed + 1)
    q8, scale = quantize.quantize_feature_tiles(x, d_tile)
    assert scale.shape == (-(-d // d_tile),)
    per_col = np.repeat(np.asarray(scale), d_tile)[:d]
    deq = np.asarray(q8, np.float32) * per_col[None, :]
    assert np.all(np.abs(deq - x) <= per_col[None, :] * 0.5 + 1e-7)


def test_quantized_features_is_jit_transparent():
    x = jnp.asarray(np.random.default_rng(0).normal(size=(8, 16)),
                    jnp.float32)
    qf = quantize.quantize_features(x, 8)
    out = jax.jit(lambda q: q.q8.astype(jnp.float32).sum() + q.scale.sum())(qf)
    assert np.isfinite(float(out))


def test_q8_gate_nan_fails():
    assert quantize.q8_gate(0.0, 0.0)
    assert quantize.q8_gate(1.0, 1.0)
    assert not quantize.q8_gate(float("nan"), 1.0)
    assert not quantize.q8_gate(2.0, 1.0)


# ---------------------------------------------------------------------------
# aggregate parity within the scale-derived bound
# ---------------------------------------------------------------------------

@given(st.sampled_from([(64, 256), (128, 512), (200, 800)]),
       st.sampled_from([8, 32, 48]), st.integers(0, 99),
       st.sampled_from(["f32", "bf16"]))
@settings(max_examples=8, deadline=None)
def test_aggregate_q8_within_bound(ne, d, seed, dtype):
    n, e = ne
    dt = np.float32 if dtype == "f32" else np.float32  # x cast below
    plan, x = _plan_x(n, e, d, seed=seed, dtype=dt)
    if dtype == "bf16":
        x = x.astype(jnp.bfloat16).astype(jnp.float32)
    ref = sparse_backend.aggregate(plan, None, x, backend="dense")
    out = sparse_backend.aggregate(plan, None, x, backend="pallas_q8")
    dev = float(jnp.abs(ref - out).max())
    bound = aggregate_q8_bound_for(plan, x)
    assert quantize.q8_gate(dev, bound), (dev, bound)


def test_aggregate_q8_hub_graph_within_bound():
    # star graph: one receiver with every edge — forces hub row splitting
    n, e = 64, 256
    s = np.random.default_rng(3).integers(0, n, e)
    r = np.zeros(e, np.int64)
    vals = np.random.default_rng(4).normal(size=e).astype(np.float32)
    plan = make_plan(s, r, n, edge_weight=vals,
                     backends=sparse_backend.ALL_BACKENDS, chunk=128)
    x = jnp.asarray(np.random.default_rng(5).normal(size=(n, 16)),
                    jnp.float32)
    ref = sparse_backend.aggregate(plan, None, x, backend="dense")
    out = sparse_backend.aggregate(plan, None, x, backend="pallas_q8")
    dev = float(jnp.abs(ref - out).max())
    assert quantize.q8_gate(dev, aggregate_q8_bound_for(plan, x))


def test_aggregate_q8_resident_features_bit_identical():
    plan, x = _plan_x(128, 512, 32, seed=7)
    dt = plan.ell_d_tile or _auto_d_tile(x.shape[1])
    qf = quantize.quantize_features(x, dt)
    in_trace = sparse_backend.aggregate(plan, None, x, backend="pallas_q8")
    resident = sparse_backend.aggregate(plan, None, qf, backend="pallas_q8")
    assert np.array_equal(np.asarray(in_trace), np.asarray(resident))


def test_aggregate_q8_resident_scale_shape_validated():
    plan, x = _plan_x(64, 256, 32, seed=1)
    bad = quantize.QuantizedFeatures(
        q8=jnp.zeros((64, 32), jnp.int8), scale=jnp.ones((99,), jnp.float32))
    with pytest.raises(ValueError):
        sparse_backend.aggregate(plan, None, bad, backend="pallas_q8")


def test_aggregate_q8_fwdbwd_runs_and_is_finite():
    plan, x = _plan_x(64, 256, 16, seed=2)
    v0 = jnp.ones_like(plan.base_vals)

    def loss(v, xx, nm):
        return jnp.mean(sparse_backend.aggregate(plan, v, xx, backend=nm)**2)

    gd = jax.grad(loss, argnums=(0, 1))(v0, x, "dense")
    gq = jax.grad(loss, argnums=(0, 1))(v0, x, "pallas_q8")
    for ref, got in zip(gd, gq):
        assert got.shape == ref.shape
        assert bool(jnp.isfinite(got).all())
        # straight-through backward: close to the f32 gradient, not exact
        assert float(jnp.abs(ref - got).max()) < 0.2
        # only the cotangent carries quantization error: the forward's
        # scale-derived bound holds for the gradients too
        assert quantize.q8_gate(float(jnp.abs(ref - got).max()),
                                aggregate_q8_bound_for(plan, x))


# ---------------------------------------------------------------------------
# SpGEMM parity within the scale-derived bound
# ---------------------------------------------------------------------------

def _spgemm_dev_bound(plan, av=None, bv=None):
    ref = sparse_backend.spgemm(plan, backend="dense")
    if av is not None:
        out = sparse_backend.spgemm(plan, jnp.asarray(av), jnp.asarray(bv),
                                    backend="pallas_q8")
    else:
        out = sparse_backend.spgemm(plan, backend="pallas_q8")
    dev = float(jnp.abs(ref - out).max()) if plan.nnz_out else 0.0
    bound = quantize.spgemm_q8_bound(plan.width, plan.ell_out_block,
                                     plan.n_blocks, plan.ell_a_scale,
                                     plan.slab_scale)
    return dev, bound


@given(st.integers(0, 99))
@settings(max_examples=6, deadline=None)
def test_spgemm_q8_square_within_bound(seed):
    n, e = 96, 384
    s, r = powerlaw_graph(n, e + 64, seed=seed)
    s, r = s[:e], r[:e]
    av = np.random.default_rng(seed).normal(size=e).astype(np.float32)
    plan = make_spgemm_plan(r, s, n, r, s, n, a_vals=av, b_vals=av,
                            chunk=512)
    dev, bound = _spgemm_dev_bound(plan)
    assert quantize.q8_gate(dev, bound), (dev, bound)


def test_spgemm_q8_rectangular_within_bound():
    # A (40 × 64) · B (64 × 24) — all three dims distinct
    rng = np.random.default_rng(11)
    ar, ac = rng.integers(0, 40, 300), rng.integers(0, 64, 300)
    br, bc = rng.integers(0, 64, 250), rng.integers(0, 24, 250)
    av = rng.normal(size=300).astype(np.float32)
    bv = rng.normal(size=250).astype(np.float32)
    plan = make_spgemm_plan(ar, ac, 40, br, bc, 64, 24,
                            a_vals=av, b_vals=bv, chunk=256)
    dev, bound = _spgemm_dev_bound(plan)
    assert quantize.q8_gate(dev, bound), (dev, bound)
    # output values land on the exact C = A·B CSR structure
    out = sparse_backend.spgemm(plan, backend="pallas_q8")
    assert out.shape == (plan.nnz_out,)


def test_spgemm_q8_traced_vals_match_baked():
    n, e = 80, 320
    s, r = powerlaw_graph(n, e + 64, seed=13)
    s, r = s[:e], r[:e]
    av = np.random.default_rng(13).normal(size=e).astype(np.float32)
    plan = make_spgemm_plan(r, s, n, r, s, n, a_vals=av, b_vals=av,
                            chunk=512)
    baked = sparse_backend.spgemm(plan, backend="pallas_q8")
    traced = sparse_backend.spgemm(plan, jnp.asarray(av), jnp.asarray(av),
                                   backend="pallas_q8")
    # same values in, same quantization in: identical outputs
    assert np.allclose(np.asarray(baked), np.asarray(traced), atol=1e-5)


# ---------------------------------------------------------------------------
# optim.compression zero-block guard (regression)
# ---------------------------------------------------------------------------

def test_compression_zero_block_scale_guard():
    x = jnp.zeros((512,), jnp.float32).at[300].set(5.0)
    q, scale = compression.quantize_int8(x, block=256)
    s = np.asarray(scale).reshape(-1)
    assert s[0] == 1.0                      # all-zero block: guard scale
    assert np.isfinite(1.0 / s).all()       # no inf/NaN in scale arithmetic
    back = compression.dequantize_int8(q, scale, x.shape, x.dtype)
    assert np.all(np.asarray(back[:256]) == 0.0)
    assert abs(float(back[300]) - 5.0) <= float(s[1]) * 0.5 + 1e-6
