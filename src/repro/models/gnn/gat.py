"""GAT (Veličković et al.) — SDDMM (edge scores) → segment-softmax → SpMM.

The attention-score stage is exactly the paper's multiply stage with a
different reducer: NeuraCore produces per-edge partial products (here score
logits), NeuraMem merges per destination row (here a max/sum pair for the
softmax) — the decoupled structure carries over unchanged.  The weighted
aggregation itself dispatches through the unified backend engine with the
traced attention weights as the per-edge values (the plan's scatter slots
route them into the packed pallas / distributed layouts on device).

Beyond the Cora model, ``out_heads`` and ``skip`` give PyG's
``examples/ogbn_products_gat.py``: the last layer averages ``out_heads``
heads (``concat=False``) and adds its bias after the mean, and every layer
adds a linear skip of its target rows, ``x @ W_skip + b_skip``, before the
ELU.  Each layer's stages carry the profiler names ``layer{k}.dense``
(transform, bias, skip, ELU), ``layer{k}.attend`` (scores, logits,
softmax) and ``layer{k}.aggregate`` (the per-head SpMMs), as GraphSAGE's.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp

from repro.sparse import backend as sb
from repro.sparse.plan import AggregationPlan, edge_plan, per_layer
from repro.sparse.segment_ops import segment_softmax

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class GATConfig:
    name: str = "gat-cora"
    n_layers: int = 2
    d_in: int = 1433
    d_hidden: int = 8
    n_heads: int = 8
    n_classes: int = 7
    negative_slope: float = 0.2
    out_heads: int = 1        # heads of the last layer, averaged
    skip: bool = False        # a linear skip of the target rows per layer
    param_dtype: str = "float32"
    dp_axes: tuple = ()


def _pin(x, cfg: "GATConfig"):
    """Node/edge-major tensors stay dp-sharded (see gcn._pin_nodes)."""
    if not cfg.dp_axes:
        return x
    from jax.sharding import PartitionSpec as P
    return jax.lax.with_sharding_constraint(
        x, P(cfg.dp_axes, *([None] * (x.ndim - 1))))


def init_params(key, cfg: GATConfig):
    dt = jnp.dtype(cfg.param_dtype)
    params = {}
    d_in = cfg.d_in
    for i in range(cfg.n_layers):
        last = i == cfg.n_layers - 1
        heads = cfg.out_heads if last else cfg.n_heads
        d_out = cfg.n_classes if last else cfg.d_hidden
        width = d_out if last else heads * d_out
        k1, k2, k3, key = jax.random.split(key, 4)
        params[f"layer{i}"] = p = {
            "w": jax.random.normal(k1, (d_in, heads, d_out), dt)
            * (1.0 / jnp.sqrt(d_in)),
            "a_src": jax.random.normal(k2, (heads, d_out), dt) * 0.1,
            "a_dst": jax.random.normal(k3, (heads, d_out), dt) * 0.1,
            "b": jnp.zeros((width,), dt),
        }
        if cfg.skip:
            k4, key = jax.random.split(key)
            p["w_skip"] = (jax.random.normal(k4, (d_in, width), dt)
                           * (1.0 / jnp.sqrt(d_in)))
            p["b_skip"] = jnp.zeros((width,), dt)
        d_in = width
    return params


def gat_layer(p, cfg: GATConfig, x: Array, pl: AggregationPlan,
              last: bool, backend: str, name: str) -> Array:
    """Rows ``[0, pl.out_rows)`` of the layer's output, from every row of
    ``x`` (the source transform covers each row an edge reads)."""
    n = pl.out_rows
    senders, receivers, edge_valid = pl.cols, pl.rows, pl.valid
    with jax.named_scope(f"{name}.dense"):
        h = _pin(jnp.einsum("nd,dhf->nhf", x, p["w"].astype(x.dtype)), cfg)
    # SDDMM stage: per-edge attention logits, softmax over each row's edges
    with jax.named_scope(f"{name}.attend"):
        e_src = (h * p["a_src"].astype(x.dtype)).sum(-1)       # (N, H)
        e_dst = (h[:n] * p["a_dst"].astype(x.dtype)).sum(-1)
        logits = jax.nn.leaky_relu(
            jnp.take(e_src, senders, axis=0)
            + jnp.take(e_dst, receivers, axis=0),
            cfg.negative_slope,
        ).astype(jnp.float32)                                  # (E, H)
        logits = _pin(jnp.where(edge_valid[:, None], logits, -1e30), cfg)
        alpha = segment_softmax(logits, receivers, n).astype(x.dtype)
        alpha = _pin(jnp.where(edge_valid[:, None], alpha, 0), cfg)
    # multiply stage: attention-weighted messages; accumulate stage: one
    # decoupled SpMM per head on the selected executor
    with jax.named_scope(f"{name}.aggregate"):
        agg = jnp.stack(
            [sb.aggregate(pl, alpha[:, hd], h[:, hd, :], backend=backend)
             for hd in range(h.shape[1])], axis=1)
        agg = _pin(agg, cfg)
    with jax.named_scope(f"{name}.dense"):
        out = agg.mean(axis=1) if last else agg.reshape(n, -1)
        out = out + p["b"].astype(x.dtype)
        if cfg.skip:
            out = (out + x[:n] @ p["w_skip"].astype(x.dtype)
                   + p["b_skip"].astype(x.dtype))
        if not last:
            out = jax.nn.elu(out)
    return _pin(out, cfg)


def forward(params, cfg: GATConfig, x: Array, senders: Array = None,
            receivers: Array = None, edge_valid: Array = None,
            backend: str = "dense",
            plan: Union[AggregationPlan, Sequence[AggregationPlan]] = None
            ) -> Array:
    plans = per_layer(plan if plan is not None else edge_plan(
        senders, receivers, x.shape[0], edge_valid=edge_valid), cfg.n_layers)
    h = x
    for i, pl in enumerate(plans):
        h = gat_layer(params[f"layer{i}"], cfg, h, pl,
                      last=i == cfg.n_layers - 1, backend=backend,
                      name=f"layer{i}")
    return h


def loss_fn(params, cfg: GATConfig, x, senders, receivers, edge_valid,
            labels, label_mask, backend: str = "dense",
            plan: Optional[AggregationPlan] = None):
    logits = forward(params, cfg, x, senders, receivers, edge_valid,
                     backend=backend, plan=plan)
    logits = logits.astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, labels[:, None].astype(jnp.int32), axis=-1)[:, 0]
    m = label_mask.astype(jnp.float32)
    return -(ll * m).sum() / jnp.maximum(m.sum(), 1.0)
