"""The benchmark's data: graph, features and weights, made on the device.

One jitted call per configuration turns ``--seed`` into everything the
served program holds: a power-law graph as CSR (rows are receivers, the
aggregation viewpoint), the feature table with its zero ghost row last, and
the weights of the configuration's architecture (``archs/<arch>.py``,
``init_params``).  Nothing is built on the host, so set-up pays no
host graph build; the program's API takes the CSR as host arrays, so one
copy comes back (``World.host_csr``), and the reference shares it.

Degree law: as the repo's synthetic generator, senders follow Zipf weights
``i^(-alpha/2)`` over node ids and receivers are uniform.  The sender is
drawn by inverting the continuous power law, so that the tail keeps its
weight in float32.  A self loop gets a new receiver, so the graph keeps
exactly the requested number of edges.  ``stored_both_ways`` stores every
undirected edge in both directions (ogbn-products).
"""
from __future__ import annotations

import dataclasses
import functools
import json
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import archs


def run_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative whole number, 64 bits and beyond."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


@functools.partial(jax.jit, static_argnames=("cfg_json",))
def _make(key, *, cfg_json: str):
    cfg = json.loads(cfg_json)
    n, e = int(cfg["n_nodes"]), int(cfg["n_edges"])
    k_src, k_dst, k_fix, k_x, k_w = jax.random.split(key, 5)
    a = float(cfg["assumed"]["power_law_alpha"]) / 2.0
    u = jax.random.uniform(k_src, (e,), jnp.float32)
    top = (n + 1.0) ** (1.0 - a)
    src = jnp.floor((1.0 + u * (top - 1.0)) ** (1.0 / (1.0 - a)))
    src = jnp.clip(src.astype(jnp.int32) - 1, 0, n - 1)
    dst = jax.random.randint(k_dst, (e,), 0, n, jnp.int32)
    shift = jax.random.randint(k_fix, (e,), 0, n - 1, jnp.int32)
    dst = jnp.where(dst == src, (src + 1 + shift) % n, dst)
    if cfg.get("stored_both_ways", False):
        src, dst = jnp.concatenate([src, dst]), jnp.concatenate([dst, src])
    rows, cols = jax.lax.sort((dst, src), num_keys=2)
    indptr = jnp.searchsorted(rows, jnp.arange(n + 1, dtype=jnp.int32),
                              side="left").astype(jnp.int32)
    x = jax.random.normal(k_x, (n + 1, cfg["d_in"]), jnp.float32)
    x = x.at[n].set(0.0)
    return indptr, cols, x, archs.of(cfg).init_params(k_w, cfg)


@dataclasses.dataclass
class World:
    n_nodes: int
    indptr: jax.Array            # (n+1,) int32, on the device
    indices: jax.Array           # (E,) int32, on the device
    x: jax.Array                 # (n+1, d_in) float32, ghost row last
    params: Dict                 # the architecture's init_params

    def host_csr(self) -> Tuple[np.ndarray, np.ndarray]:
        return np.asarray(self.indptr), np.asarray(self.indices)


def make_world(cfg: dict, seed: int) -> World:
    indptr, indices, x, params = _make(
        run_key(seed), cfg_json=json.dumps(cfg, sort_keys=True))
    jax.block_until_ready((indptr, indices, x, params))
    return World(int(cfg["n_nodes"]), indptr, indices, x, params)
