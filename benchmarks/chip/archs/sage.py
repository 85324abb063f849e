"""GraphSAGE, mean aggregator: the program's config, the weights, the plain
reference over sampled trees, and the work the algorithm needs.

The reference is GraphSAGE's minibatch forward (Hamilton et al.,
arXiv:1706.02216, Alg. 2) on one fanout tree per seed: layer k updates
only the levels that the seed still needs (levels 0 .. K-k), each node
from itself and the mean of its valid children:

    h' = h @ W_self + mean(children h) @ W_nbr + b,   ReLU between layers.

The sum over the children is the sparse product A·H of the 0/1 edge mask
with the children's rows, a matmul like the dense ones; the mean divides it
by the number of valid children.  A node with no valid child aggregates 0.
Nothing of the reference imports the served program.

Work per seed, layer ``i`` over ``workcount.layer_shapes``:

* FLOPs: ``4 N_i d_i d_{i+1}`` for the two matmuls (self and neighbour)
  and ``2 E_i d_i`` for the aggregation (one multiply-add per edge and
  column, the SpMM convention).  Bias, mean and ReLU are left out.
* Aggregation bytes (float32): each child row read once (``4 E_i d_i``),
  each aggregated row written once (``4 N_i d_i``) and one int32 column
  index per edge (``4 E_i``).
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import reference, workcount


def model_config(cfg: dict):
    from repro.models.gnn.sage import SAGEConfig
    return SAGEConfig(name=cfg["name"], n_layers=cfg["n_layers"],
                      d_in=cfg["d_in"], d_hidden=cfg["d_hidden"],
                      n_classes=cfg["n_classes"], param_dtype=cfg["dtype"])


def layer_dims(cfg: dict) -> Tuple[int, ...]:
    """(d_in, hidden..., n_classes) of the configuration's layers."""
    return ((cfg["d_in"],) + (cfg["d_hidden"],) * (cfg["n_layers"] - 1)
            + (cfg["n_classes"],))


def init_params(key: jax.Array, cfg: dict) -> Dict:
    """``{"layer{i}": {"w_self", "w_nbr", "b"}}``: W ~ N(0, 1/d_in),
    b ~ N(0, 0.01)."""
    dims = layer_dims(cfg)
    params = {}
    for i, (d_in, d_out) in enumerate(zip(dims[:-1], dims[1:])):
        k1, k2, k3, key = jax.random.split(key, 4)
        scale = 1.0 / np.sqrt(d_in)
        params[f"layer{i}"] = {
            "w_self": jax.random.normal(k1, (d_in, d_out), jnp.float32)
            * scale,
            "w_nbr": jax.random.normal(k2, (d_in, d_out), jnp.float32)
            * scale,
            "b": jax.random.normal(k3, (d_out,), jnp.float32) * 0.1,
        }
    return params


def tree_forward(params: Dict, x_levels: Sequence[jax.Array],
                 valid: Sequence[jax.Array], fanouts: Sequence[int],
                 compute: str = "f32") -> jax.Array:
    """Seed outputs ``(T, n_classes)`` float32.

    ``x_levels[l]``: (T, s_l, d_in) features of level ``l`` (zeros where
    the node is invalid); ``valid[h]``: (T, s_{h+1}) bool, the hop-h edge
    masks.
    """
    st, mm, spmm = reference.policy(compute)
    n_layers = len(fanouts)
    h = [st(jnp.asarray(a, jnp.float32)) for a in x_levels]
    for k in range(n_layers):
        p = {n: st(jnp.asarray(v, jnp.float32))
             for n, v in params[f"layer{k}"].items()}
        new = []
        for lv in range(n_layers - k):
            t, s, d = h[lv].shape
            f = fanouts[lv]
            child = h[lv + 1].reshape(t, s, f, d)
            m = jnp.asarray(valid[lv]).reshape(t, s, f).astype(jnp.float32)
            cnt = m.sum(axis=2, keepdims=True)
            agg = st(spmm(m, child))
            agg = st(agg / st(jnp.maximum(cnt, 1.0)))
            out = st(st(mm(h[lv], p["w_self"])) + st(mm(agg, p["w_nbr"])))
            out = st(out + p["b"])
            if k < n_layers - 1:
                out = jnp.maximum(out, 0)
            new.append(out)
        h = new
    return h[0][:, 0, :].astype(jnp.float32)


def flops_per_seed(cfg: dict) -> int:
    return sum(4 * s["nodes"] * s["d_in"] * s["d_out"]
               + 2 * s["edges"] * s["d_in"]
               for s in workcount.layer_shapes(cfg["fanouts"],
                                               layer_dims(cfg)))


def aggregation_work(cfg: dict) -> List[Dict[str, int]]:
    """Per layer, per seed: the aggregation's FLOPs and bytes."""
    return [{"flops": 2 * s["edges"] * s["d_in"],
             "bytes": workcount.F32 * (s["edges"] * s["d_in"]
                                       + s["nodes"] * s["d_in"]
                                       + s["edges"])}
            for s in workcount.layer_shapes(cfg["fanouts"], layer_dims(cfg))]


def kernel_calls_per_layer(cfg: dict) -> int:
    """One Gustavson call aggregates a layer's neighbours for the batch."""
    return 1
