"""The plain reference over sampled trees, for any architecture.

Each seed's fanout tree comes from ``sampling.py``, its rows from the
benchmark's own feature table, and the forward over the tree from the
configuration's ``archs/<arch>.py`` (``tree_forward``).  Nothing here
imports the served program.

``compute`` picks the arithmetic (``policy``):

* ``"f32"`` — the reference, at the precision the configurations state:
  float32 arrays, every matmul (the dense ones and A·H) at XLA's
  ``DEFAULT`` precision: on a TPU one bfloat16 pass, bf16 operands and
  float32 accumulation; float32 on a CPU;
* ``"bf16"`` — the control, the next precision below: every array and
  every result rounded to bfloat16.
"""
from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import archs

COMPUTES = ("f32", "bf16")
# A·H per tree: (T, s, f) edge mask with (T, s, f, d) children rows
SPMM = "tsf,tsfd->tsd"


def policy(compute: str):
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


def reference_outputs(cfg: dict, x_table: jax.Array, params: Dict,
                      levels: List[np.ndarray], valid: List[np.ndarray],
                      compute: str = "f32", block: int = 64) -> np.ndarray:
    """Seed outputs of every tree, computed ``block`` trees at a time so
    that the gathered neighbourhoods stay small on the device."""
    forward = archs.of(cfg).tree_forward
    outs = []
    for i in range(0, levels[0].shape[0], block):
        lv = [a[i:i + block] for a in levels]
        va = [a[i:i + block] for a in valid]
        xs = tree_features(x_table, lv)
        outs.append(np.asarray(forward(params, xs, va, cfg["fanouts"],
                                       compute)))
    return np.concatenate(outs)
