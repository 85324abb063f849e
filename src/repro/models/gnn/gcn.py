"""GCN (Kipf & Welling) on the decoupled SpMM core — the paper's own GNN
workload (NeuraChip §5.4 evaluates a GCN layer; A.3.3 uses Cora/Tile-16).

Aggregation goes through the unified sparse-backend engine
(``repro.sparse.backend``): pass ``backend="dense"|"chunked"|"pallas"|
"distributed"`` to pick the executor — the model is agnostic (paper C1 as a
framework property).  ``dense``/``chunked`` run off an inline plan built from
the traced edge arrays; ``pallas``/``distributed`` need a host-built
``repro.sparse.plan.make_plan`` passed as ``plan=``.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp

from repro.sparse import backend as sb
from repro.sparse.plan import AggregationPlan, edge_plan, per_layer

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class GCNConfig:
    name: str = "gcn-cora"
    n_layers: int = 2
    d_in: int = 1433
    d_hidden: int = 16
    n_classes: int = 7
    param_dtype: str = "float32"
    # node-dim sharding constraint axes (empty ⇒ no constraints)
    dp_axes: tuple = ()


def _pin_nodes(x, cfg: GCNConfig):
    """Keep node-major tensors sharded over dp — without this GSPMD
    replicates post-scatter activations (256× redundant compute on
    ogb_products; §Perf gcn iteration 1)."""
    if not cfg.dp_axes:
        return x
    from jax.sharding import PartitionSpec as P
    return jax.lax.with_sharding_constraint(
        x, P(cfg.dp_axes, *([None] * (x.ndim - 1))))


def init_params(key, cfg: GCNConfig):
    dims = [cfg.d_in] + [cfg.d_hidden] * (cfg.n_layers - 1) + [cfg.n_classes]
    keys = jax.random.split(key, cfg.n_layers)
    dt = jnp.dtype(cfg.param_dtype)
    return {
        f"layer{i}": {
            "w": jax.random.normal(keys[i], (dims[i], dims[i + 1]), dt)
            * (1.0 / jnp.sqrt(dims[i])),
            "b": jnp.zeros((dims[i + 1],), dt),
        }
        for i in range(cfg.n_layers)
    }


def forward(params, cfg: GCNConfig, x: Array, senders: Array = None,
            receivers: Array = None, edge_weight: Optional[Array] = None,
            edge_valid: Array = None, backend: str = "dense",
            plan: Union[AggregationPlan, Sequence[AggregationPlan]] = None
            ) -> Array:
    """x: (N_pad, d_in) — returns logits (N_pad, n_classes), or with one
    plan a layer the last plan's ``out_rows`` of them.

    Aggregation direction: receivers accumulate sender features (rows =
    receivers, cols = senders) — one Gustavson SpMM per layer, dispatched on
    the named backend.
    """
    plans = per_layer(plan if plan is not None else edge_plan(
        senders, receivers, x.shape[0], edge_weight, edge_valid),
        cfg.n_layers)
    h = x
    for i, pl in enumerate(plans):
        p = params[f"layer{i}"]
        h = _pin_nodes(h @ p["w"].astype(h.dtype), cfg)   # combination (dense)
        h = sb.aggregate(pl, None, h, backend=backend)    # aggregation
        h = _pin_nodes(h, cfg) + p["b"].astype(h.dtype)
        if i < cfg.n_layers - 1:
            h = jax.nn.relu(h)
    return _pin_nodes(h, cfg)


def loss_fn(params, cfg: GCNConfig, x, senders, receivers, edge_weight,
            edge_valid, labels, label_mask, backend: str = "dense",
            plan: Optional[AggregationPlan] = None):
    logits = forward(params, cfg, x, senders, receivers, edge_weight,
                     edge_valid, backend=backend, plan=plan
                     ).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, labels[:, None].astype(jnp.int32), axis=-1)[:, 0]
    m = label_mask.astype(jnp.float32)
    return -(ll * m).sum() / jnp.maximum(m.sum(), 1.0)
