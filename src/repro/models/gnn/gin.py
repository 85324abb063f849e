"""GIN (Xu et al., arXiv:1810.00826) — sum-aggregation isomorphism network.

h_i' = MLP((1 + ε) · h_i + Σ_{j∈N(i)} h_j)

Beyond the assigned four GNNs: the sum aggregator is the purest decoupled
multiply/accumulate instance (vals ≡ 1), dispatched through the unified
backend engine.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp

from repro.models.common import mlp_apply, mlp_init
from repro.sparse import backend as sb
from repro.sparse.plan import AggregationPlan, edge_plan, per_layer

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class GINConfig:
    name: str = "gin"
    n_layers: int = 3
    d_in: int = 64
    d_hidden: int = 64
    n_classes: int = 4
    train_eps: bool = True
    param_dtype: str = "float32"
    # aggregate over the Â² two-hop neighborhood: the step builder
    # precomputes A@A once via the SpGEMM engine (sparse.spgemm) and passes
    # its plan in — every training step is then plain SpMM on Â²
    two_hop: bool = False


def init_params(key, cfg: GINConfig):
    dt = jnp.dtype(cfg.param_dtype)
    params = {}
    d_in = cfg.d_in
    for i in range(cfg.n_layers):
        d_out = cfg.n_classes if i == cfg.n_layers - 1 else cfg.d_hidden
        k1, key = jax.random.split(key)
        params[f"layer{i}"] = {
            "mlp": mlp_init(k1, [d_in, cfg.d_hidden, d_out], dt),
            "eps": jnp.zeros((), dt),
        }
        d_in = d_out
    return params


def forward(params, cfg: GINConfig, x: Array, senders: Array = None,
            receivers: Array = None, edge_valid: Array = None,
            backend: str = "dense",
            plan: Union[AggregationPlan, Sequence[AggregationPlan]] = None
            ) -> Array:
    plans = per_layer(plan if plan is not None else edge_plan(
        senders, receivers, x.shape[0], edge_valid=edge_valid), cfg.n_layers)
    h = x
    for i, pl in enumerate(plans):
        p = params[f"layer{i}"]
        agg = sb.aggregate(pl, None, h, backend=backend)
        h = mlp_apply(p["mlp"], (1.0 + p["eps"]) * h[: pl.out_rows] + agg,
                      act=jax.nn.relu)
        if i < cfg.n_layers - 1:
            h = jax.nn.relu(h)
    return h


def graph_readout(h: Array, graph_ids: Array, n_graphs: int) -> Array:
    """Sum-pool node embeddings per graph (GIN's graph-level readout)."""
    return jax.ops.segment_sum(h, graph_ids, num_segments=n_graphs)


def loss_fn(params, cfg: GINConfig, x, senders, receivers, edge_valid,
            graph_ids, n_graphs, labels, backend: str = "dense",
            plan: Optional[AggregationPlan] = None):
    h = forward(params, cfg, x, senders, receivers, edge_valid,
                backend=backend, plan=plan)
    logits = graph_readout(h, graph_ids, n_graphs).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, labels[:, None].astype(jnp.int32),
                             axis=-1)[:, 0]
    return -ll.mean()
