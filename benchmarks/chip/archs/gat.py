"""Multi-head GAT with linear skips: the program's config, the weights, the
plain reference over sampled trees, and the work the algorithm needs.

The model is PyG's ``examples/ogbn_products_gat.py`` (GAT, Veličković et
al., arXiv:1710.10903; ``GATConv`` defaults: one shared ``lin`` for source
and target, ``add_self_loops``, LeakyReLU slope 0.2, bias after the concat
or the mean).  Layer k, with H heads of width F, updates every node i that
the seed still needs (levels 0 .. K-1-k of its fanout tree), over its
valid children j and itself:

    z_j    = h_j @ W                               (H, F) per node
    e_ij   = LeakyReLU(z_j · a_src + z_i · a_dst)  per head
    α_ij   = softmax of e_ij over j ∈ valid children(i) ∪ {i}
    o_i    = Σ_j α_ij z_j                          per head
    h_i'   = concat_H(o_i) + b   (hidden layers)   or   mean_H(o_i) + b  (last)
    h_i'  += h_i @ W_skip + b_skip;   ELU between layers.

The weighted sum over a node's children and itself is the sparse product
A·Z of the attention weights with their rows, one per head, a matmul like
the dense ones.  Nothing of the reference imports the served program.

Departures from PyG's example, all of them serving's:

* logits are compared, not their ``log_softmax``;
* serving samples [10, 10, 10], as the example's training loader does,
  where its inference is layer-wise over every neighbour; the trees are
  drawn with replacement by the counter-hash rule (``sampling.py``) and
  each sampled slot is its own node of the tree, where PyG's sampler draws
  without replacement and merges repeated nodes;
* dropout is off (inference).

Work per seed, layer ``i`` over ``workcount.layer_shapes`` (``N_i`` nodes
updated, ``E_i`` children, input width ``d_i``; ``H F_i`` transformed
columns, ``w_i`` output columns: ``H F_i`` concatenated, ``F_i`` averaged):

* FLOPs: ``2 (E_i + 1) d_i H F_i`` for the transforms, each node of the
  levels the layer reads once (the updated nodes and their children);
  ``2 N_i d_i w_i`` for the skips; ``2 H F_i (E_i + 1)`` and
  ``2 H F_i N_i`` for the scores ``z·a_src`` and ``z·a_dst``; and
  ``2 (E_i + N_i) H F_i`` for the aggregation (one multiply-add per edge
  and column, each self loop an edge).  LeakyReLU, the softmax, bias, mean
  and ELU are left out.
* Aggregation bytes (float32): each edge's source row read once
  (``4 (E_i + N_i) H F_i``), each aggregated row written once
  (``4 N_i H F_i``), one int32 column index and H float32 attention values
  per edge (``4 (E_i + N_i) (1 + H)``).
* One valued Gustavson call a head: ``kernel_calls_per_layer`` is H.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import reference, workcount

# attention vectors and biases: N(0, SCALE²), so that a score z·a over
# F = 128 unit-scale columns is about unit scale and attention is far from
# uniform
SCALE = 0.1
# LeakyReLU's slope on the scores, GATConv's default
NEGATIVE_SLOPE = 0.2


def model_config(cfg: dict):
    from repro.models.gnn.gat import GATConfig
    return GATConfig(name=cfg["name"], n_layers=cfg["n_layers"],
                     d_in=cfg["d_in"], d_hidden=cfg["d_hidden"],
                     n_heads=cfg["n_heads"], n_classes=cfg["n_classes"],
                     out_heads=cfg["out_heads"], skip=cfg["skip"],
                     param_dtype=cfg["dtype"])


def layers(cfg: dict) -> List[Dict[str, int]]:
    """Per layer: input width, heads, head width, output width."""
    out, d_in = [], cfg["d_in"]
    for i in range(cfg["n_layers"]):
        last = i == cfg["n_layers"] - 1
        heads = cfg["out_heads"] if last else cfg["n_heads"]
        f = cfg["n_classes"] if last else cfg["d_hidden"]
        width = f if last else heads * f
        out.append({"d_in": d_in, "heads": heads, "f": f, "width": width})
        d_in = width
    return out


def init_params(key: jax.Array, cfg: dict) -> Dict:
    """``{"layer{i}": {"w", "a_src", "a_dst", "b", "w_skip", "b_skip"}}``:
    W and W_skip ~ N(0, 1/d_in); attention vectors and biases
    ~ N(0, SCALE²)."""
    # the program's config first: a program that cannot serve this
    # configuration fails here, while the world is traced, before any
    # device data is made
    model_config(cfg)
    params = {}
    for i, ly in enumerate(layers(cfg)):
        ks = jax.random.split(key, 7)
        key = ks[6]
        d, h, f, width = ly["d_in"], ly["heads"], ly["f"], ly["width"]
        scale = 1.0 / np.sqrt(d)

        def normal(k, shape, s):
            return jax.random.normal(k, shape, jnp.float32) * s
        params[f"layer{i}"] = {
            "w": normal(ks[0], (d, h, f), scale),
            "a_src": normal(ks[1], (h, f), SCALE),
            "a_dst": normal(ks[2], (h, f), SCALE),
            "b": normal(ks[3], (width,), SCALE),
            "w_skip": normal(ks[4], (d, width), scale),
            "b_skip": normal(ks[5], (width,), SCALE),
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
        d, heads, f = p["w"].shape
        last = k == n_layers - 1
        # every node of the levels this layer reads, transformed once
        z = [st(mm(a, p["w"].reshape(d, heads * f))).reshape(
            a.shape[:2] + (heads, f)) for a in h]
        new = []
        for lv in range(n_layers - k):
            t, s = h[lv].shape[:2]
            fo = fanouts[lv]
            zi = z[lv]
            # the node's children, then the node itself (its self loop)
            zj = jnp.concatenate([z[lv + 1].reshape(t, s, fo, heads, f),
                                  zi[:, :, None]], axis=2)
            m = jnp.concatenate([jnp.asarray(valid[lv]).reshape(t, s, fo),
                                 jnp.ones((t, s, 1), bool)], axis=2)
            e = st(st((zj * p["a_src"]).sum(-1))
                   + st((zi * p["a_dst"]).sum(-1))[:, :, None])
            e = jnp.where(e >= 0, e, st(e * NEGATIVE_SLOPE))
            e = jnp.where(m[..., None], e, -jnp.inf)
            alpha = st(jax.nn.softmax(e.astype(jnp.float32), axis=2))
            out = jnp.stack([st(spmm(alpha[..., hd], zj[..., hd, :]))
                             for hd in range(heads)], axis=2)
            out = (st(out.mean(axis=2)) if last
                   else out.reshape(t, s, heads * f))
            out = st(st(out + p["b"]) + st(mm(h[lv], p["w_skip"])))
            out = st(out + p["b_skip"])
            if not last:
                out = st(jax.nn.elu(out))
            new.append(out)
        h = new
    return h[0][:, 0, :].astype(jnp.float32)


def _shapes(cfg: dict):
    dims = [ly["d_in"] for ly in layers(cfg)] + [cfg["n_classes"]]
    return zip(workcount.layer_shapes(cfg["fanouts"], dims), layers(cfg))


def flops_per_seed(cfg: dict) -> int:
    total = 0
    for s, ly in _shapes(cfg):
        n, e = s["nodes"], s["edges"]
        hf = ly["heads"] * ly["f"]
        total += (2 * (e + 1) * ly["d_in"] * hf
                  + 2 * n * ly["d_in"] * ly["width"]
                  + 2 * hf * (e + 1) + 2 * hf * n
                  + 2 * (e + n) * hf)
    return total


def aggregation_work(cfg: dict) -> List[Dict[str, int]]:
    """Per layer, per seed: the aggregation's FLOPs and bytes."""
    out = []
    for s, ly in _shapes(cfg):
        n, edges = s["nodes"], s["edges"] + s["nodes"]
        hf = ly["heads"] * ly["f"]
        out.append({"flops": 2 * edges * hf,
                    "bytes": workcount.F32 * (edges * hf + n * hf
                                              + edges * (1 + ly["heads"]))})
    return out


def kernel_calls_per_layer(cfg: dict) -> int:
    """One valued Gustavson call a head; every layer has as many heads."""
    if cfg["out_heads"] != cfg["n_heads"]:
        raise ValueError("kernel calls differ by layer: the last layer has "
                         f"{cfg['out_heads']} heads, the others "
                         f"{cfg['n_heads']}")
    return cfg["n_heads"]
