"""GAT's heads, skips and self loops.

The Cora model (``GATConfig`` defaults) reads bit for bit as before the
last layer's head count and the skips came (``data/gat_cora_digest.json``:
weights, dense and Pallas forwards and the loss on a fixed graph).  The
new options agree with a direct transcription of PyG's ``GATConv`` plus a
linear skip on a 6-node graph, and serving builds GAT's buckets with one
self loop per node.
"""
import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.models.gnn import gat
from repro.serve.engine import _needs_loops
from repro.sparse.plan import make_plan

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, "data", "gat_cora_digest.json")) as _f:
    PINNED = json.load(_f)


def digest(a) -> str:
    a = np.ascontiguousarray(np.asarray(a))
    return (f"{a.dtype}{list(a.shape)}:"
            + hashlib.sha256(a.tobytes()).hexdigest())


@pytest.mark.parametrize("name,reduced", [("gat-cora", False),
                                          ("gat-reduced", True)])
def test_cora_defaults_read_as_pinned(name, reduced):
    cfg = registry.get_config("gat-cora", reduced=reduced)
    want = PINNED[name]
    rng = np.random.default_rng(7)
    n, e = 40, 150
    s = rng.integers(0, n, e).astype(np.int32)
    r = rng.integers(0, n, e).astype(np.int32)
    x = jnp.asarray(rng.normal(size=(n + 1, cfg.d_in)).astype(np.float32))
    params = gat.init_params(jax.random.key(0), cfg)
    assert {f"{k}/{m}": digest(v) for k, p in sorted(params.items())
            for m, v in sorted(p.items())} == want["params"]
    plan = make_plan(s, r, n + 1, backends=("dense", "chunked", "pallas"))
    for backend in ("dense", "pallas"):
        out = gat.forward(params, cfg, x, backend=backend, plan=plan)
        assert digest(out) == want[backend], backend
    labels = jnp.asarray(rng.integers(0, cfg.n_classes, n + 1), jnp.int32)
    mask = jnp.asarray(np.arange(n + 1) < 20)
    assert float(gat.loss_fn(params, cfg, x, None, None, None, labels, mask,
                             plan=plan)) == want["loss"]


# -- PyG's GATConv, written out ---------------------------------------------

def _pyg_gatconv(x, src, dst, p, concat):
    """``GATConv(heads=H, concat=concat)`` with its defaults: one ``lin``
    for source and target, self loops added, LeakyReLU 0.2, softmax over
    each target's incoming edges, bias after the concat or the mean."""
    n = x.shape[0]
    d, heads, f = p["w"].shape
    src = np.concatenate([src, np.arange(n)])
    dst = np.concatenate([dst, np.arange(n)])
    z = (x @ p["w"].reshape(d, heads * f)).reshape(n, heads, f)
    a_s = (z * p["a_src"]).sum(-1)
    a_d = (z * p["a_dst"]).sum(-1)
    e = a_s[src] + a_d[dst]
    e = np.where(e > 0, e, 0.2 * e)
    alpha = np.zeros_like(e)
    for i in range(n):
        m = dst == i
        w = np.exp(e[m] - e[m].max(axis=0))
        alpha[m] = w / w.sum(axis=0)
    out = np.zeros((n, heads, f))
    np.add.at(out, dst, alpha[:, :, None] * z[src])
    out = out.reshape(n, heads * f) if concat else out.mean(axis=1)
    return out + p["b"]


def _pyg_forward(params, cfg, x, src, dst):
    """The PyG example's ``forward``: conv plus a linear skip of the target
    rows, ELU between layers (dropout off)."""
    h = x
    for i in range(cfg.n_layers):
        p = {k: np.asarray(v, np.float64) for k, v in
             params[f"layer{i}"].items()}
        last = i == cfg.n_layers - 1
        out = _pyg_gatconv(h, src, dst, p, concat=not last)
        out = out + h @ p["w_skip"] + p["b_skip"]
        h = out if last else np.where(out > 0, out, np.expm1(out))
    return h


@pytest.mark.parametrize("backend", ["dense", "pallas"])
def test_heads_skips_and_loops_match_pygs_gatconv(backend):
    cfg = gat.GATConfig(name="gat-products-tiny", n_layers=3, d_in=5,
                        d_hidden=3, n_heads=4, n_classes=4, out_heads=4,
                        skip=True)
    params = gat.init_params(jax.random.key(3), cfg)
    keys = iter(jax.random.split(jax.random.key(4), 16))
    # nonzero biases, so that their place (after the concat or the mean)
    # shows
    params = {name: {k: (v + 0.3 * jax.random.normal(next(keys), v.shape)
                         if k.startswith("b") else v)
                     for k, v in p.items()}
              for name, p in params.items()}
    assert params["layer2"]["b"].shape == (4,)
    assert params["layer0"]["w_skip"].shape == (5, 12)
    n = 6
    src = np.array([1, 2, 3, 0, 4, 5, 5, 2, 0], np.int32)
    dst = np.array([0, 0, 0, 1, 1, 2, 3, 4, 5], np.int32)
    x = np.random.default_rng(0).normal(size=(n, cfg.d_in)).astype(
        np.float32)
    loops = np.arange(n, dtype=np.int32)
    plan = make_plan(np.concatenate([src, loops]),
                     np.concatenate([dst, loops]), n,
                     backends=("dense", "chunked", "pallas"))
    with jax.default_matmul_precision("highest"):
        got = gat.forward(params, cfg, jnp.asarray(x), backend=backend,
                          plan=plan)
    want = _pyg_forward(params, cfg, x.astype(np.float64), src, dst)
    assert got.shape == (n, cfg.n_classes)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-5)


def test_serving_gives_gat_one_self_loop_per_node():
    assert _needs_loops("gat") and _needs_loops("gat-cora")
    assert _needs_loops("gcn") and not _needs_loops("sage")
