"""GraphSAGE (Hamilton et al., arXiv:1706.02216) — mean-aggregator variant.

h_i' = act(W_self · h_i  ||  W_nbr · mean_{j∈N(i)} h_j)

Beyond the assigned four GNNs: exercises the minibatch/fanout-sampler path
(its native training regime).  The neighbor *sum* dispatches through the
unified backend engine; the mean denominator (in-degree) is layout metadata
computed once from the plan, so the executor swap touches only the
bandwidth-bound reduction.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.sparse import backend as sb
from repro.sparse.plan import AggregationPlan, edge_plan

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class SAGEConfig:
    name: str = "graphsage"
    n_layers: int = 2
    d_in: int = 602
    d_hidden: int = 64
    n_classes: int = 41
    param_dtype: str = "float32"


def init_params(key, cfg: SAGEConfig):
    dt = jnp.dtype(cfg.param_dtype)
    params = {}
    d_in = cfg.d_in
    for i in range(cfg.n_layers):
        d_out = cfg.n_classes if i == cfg.n_layers - 1 else cfg.d_hidden
        k1, k2, key = jax.random.split(key, 3)
        params[f"layer{i}"] = {
            "w_self": jax.random.normal(k1, (d_in, d_out), dt)
            / jnp.sqrt(d_in),
            "w_nbr": jax.random.normal(k2, (d_in, d_out), dt)
            / jnp.sqrt(d_in),
            "b": jnp.zeros((d_out,), dt),
        }
        d_in = d_out
    return params


def forward(params, cfg: SAGEConfig, x: Array, senders: Array = None,
            receivers: Array = None, edge_valid: Array = None,
            backend: str = "dense",
            plan: Optional[AggregationPlan] = None) -> Array:
    pl = plan if plan is not None else edge_plan(
        senders, receivers, x.shape[0], edge_valid=edge_valid)
    # in-degree: per-graph layout metadata, not per-layer compute
    deg = jax.ops.segment_sum(pl.valid.astype(x.dtype), pl.rows,
                              num_segments=pl.n_rows)
    inv_deg = (1.0 / jnp.maximum(deg, 1.0))[:, None]
    h = x
    for i in range(cfg.n_layers):
        p = params[f"layer{i}"]
        # stable names for a profile: the neighbour mean, then the dense part
        with jax.named_scope(f"layer{i}.aggregate"):
            nbr = sb.aggregate(pl, None, h, backend=backend) * inv_deg
        with jax.named_scope(f"layer{i}.dense"):
            h = (h @ p["w_self"].astype(h.dtype)
                 + nbr @ p["w_nbr"].astype(h.dtype) + p["b"].astype(h.dtype))
            if i < cfg.n_layers - 1:
                h = jax.nn.relu(h)
    return h


def loss_fn(params, cfg: SAGEConfig, x, senders, receivers, edge_valid,
            labels, label_mask, backend: str = "dense",
            plan: Optional[AggregationPlan] = None):
    logits = forward(params, cfg, x, senders, receivers, edge_valid,
                     backend=backend, plan=plan).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, labels[:, None].astype(jnp.int32),
                             axis=-1)[:, 0]
    m = label_mask.astype(jnp.float32)
    return -(ll * m).sum() / jnp.maximum(m.sum(), 1.0)
