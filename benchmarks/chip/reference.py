"""Plain GraphSAGE (mean aggregator) in jax.numpy, over sampled trees.

GraphSAGE's minibatch forward (Hamilton et al., arXiv:1706.02216, Alg. 2)
on one fanout tree per seed: layer k updates only the levels that the seed
still needs (levels 0 .. K-k), each node from itself and the mean of its
valid children:

    h' = h @ W_self + mean(children h) @ W_nbr + b,   ReLU between layers.

The sum over the children is the sparse product A·H of the 0/1 edge mask
with the children's rows, a matmul like the dense ones; the mean divides it
by the number of valid children.  A node with no valid child aggregates 0.  Nothing here imports the served
program; the trees come from ``sampling.py`` and the features and weights
from the benchmark's own generator.

``compute`` picks the arithmetic:

* ``"f32"`` — the reference, at the precision the configurations state:
  float32 arrays, every matmul (the dense ones and A·H) at XLA's
  ``DEFAULT`` precision: on a TPU one bfloat16 pass, bf16 operands and
  float32 accumulation; float32 on a CPU;
* ``"bf16"`` — the control, the next precision below: every array and
  every result rounded to bfloat16.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

COMPUTES = ("f32", "bf16")
# A·H per tree: (T, s, f) edge mask with (T, s, f, d) children rows
SPMM = "tsf,tsfd->tsd"


def _policy(compute: str):
    """(store, matmul, A·H) in the arithmetic ``compute`` names."""
    if compute == "f32":
        def mm(a, b):
            return jnp.matmul(a, b, precision=jax.lax.Precision.DEFAULT)

        def spmm(m, child):
            return jnp.einsum(SPMM, m, child,
                              precision=jax.lax.Precision.DEFAULT)
        return (lambda a: a), mm, spmm
    if compute == "bf16":
        def st(a):
            return a.astype(jnp.bfloat16)

        def mm(a, b):
            return jnp.matmul(st(a), st(b),
                              preferred_element_type=jnp.bfloat16)

        def spmm(m, child):
            return jnp.einsum(SPMM, st(m), st(child),
                              preferred_element_type=jnp.bfloat16)
        return st, mm, spmm
    raise ValueError(f"compute must be one of {COMPUTES}, got {compute!r}")


def sage_trees(params: Dict, x_levels: Sequence[jax.Array],
               valid: Sequence[jax.Array], fanouts: Sequence[int],
               compute: str = "f32") -> jax.Array:
    """Seed outputs ``(T, n_classes)`` float32.

    ``x_levels[l]``: (T, s_l, d_in) features of level ``l`` (zeros where
    the node is invalid); ``valid[h]``: (T, s_{h+1}) bool, the hop-h edge
    masks.  ``params`` is ``{"layer{i}": {"w_self", "w_nbr", "b"}}``.
    """
    st, mm, spmm = _policy(compute)
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


def tree_features(x_table: jax.Array, levels: List[np.ndarray]
                  ) -> List[jax.Array]:
    """Gather each level's rows from the feature table (ghost row last,
    zeros) — an exact row copy; invalid ids (-1) read the ghost row."""
    ghost = x_table.shape[0] - 1
    out = []
    for lv in levels:
        ids = np.where(lv >= 0, lv, ghost).astype(np.int32)
        out.append(jnp.take(x_table, jnp.asarray(ids), axis=0))
    return out


def reference_outputs(x_table: jax.Array, params: Dict,
                      levels: List[np.ndarray], valid: List[np.ndarray],
                      fanouts: Sequence[int], compute: str = "f32",
                      block: int = 64) -> np.ndarray:
    """Seed outputs of every tree, computed ``block`` trees at a time so
    that the gathered neighbourhoods stay small on the device."""
    outs = []
    for i in range(0, levels[0].shape[0], block):
        lv = [a[i:i + block] for a in levels]
        va = [a[i:i + block] for a in valid]
        xs = tree_features(x_table, lv)
        outs.append(np.asarray(sage_trees(params, xs, va, fanouts, compute)))
    return np.concatenate(outs)
