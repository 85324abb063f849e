"""Compute plane: one jitted inference step per (arch, bucket, backend).

Each step takes the bucket's traced per-request data — ``node_ids`` (global
ids, ``-1`` on padding lanes) and ``hop_valid`` — gathers features from the
resident device store (padding lanes hit the zero ghost row), re-values the
bucket's static host aggregation plan (``plan_with_values``), runs the
model forward through the unified backend registry, and returns the seed
rows (slots ``0..n_seeds-1`` of the breadth-major bucket layout).  A conv
model runs each layer on a cut of that plan (``layer_plans``) that writes
only the rows the next layer reads, so its last layer writes just the
seeds.

All six GNN models serve through here.  The conv family (gcn / sage / gin /
gat) returns per-seed logits; the geometric family (schnet / dimenet)
returns per-seed atomwise energies — their graph readout runs with
``graph_ids = arange`` so the segment-sum degenerates to per-node outputs
and the seed rows are well-defined without a molecule boundary.

``StepCache`` is the bounded LRU over built steps with an explicit
``builds`` recompile counter — the number every steady-state test and the
serving benchmark assert to be zero after bucket warm-up.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.layout import Format, Layout

from repro.serve.buckets import BucketStructure, build_bucket_structure
from repro.sparse import sampler
from repro.sparse.graph import chunk_width_multiple
from repro.sparse.plan import pack_plan, plan_with_values, row_prefix_plan
from repro.sparse.stats import record_count, record_value

Array = jax.Array

# arch prefix → (family kind, needs self-loops, needs triplets)
CONV_ARCHS = ("gcn", "gat", "sage", "gin")
GEOM_ARCHS = ("schnet", "dimenet")
SERVABLE_ARCHS = CONV_ARCHS + GEOM_ARCHS


def _arch_key(arch_id: str) -> str:
    for a in SERVABLE_ARCHS:
        if arch_id == a or arch_id.startswith(a + "-"):
            return a
    raise KeyError(f"unservable arch {arch_id!r}; servable: "
                   f"{SERVABLE_ARCHS}")


@dataclasses.dataclass(frozen=True)
class FeatureStore:
    """Resident per-node features on device, ghost row (zeros) last.

    ``x`` feeds the conv family; ``species``/``pos`` feed the geometric
    family.  Lookups use ``row_index(node_ids)`` so padding lanes
    (``node_id == -1``) read the ghost row.
    """

    n_nodes: int
    x: Optional[Array] = None         # (n_nodes+1, d) f32
    species: Optional[Array] = None   # (n_nodes+1,) int32
    pos: Optional[Array] = None       # (n_nodes+1, 3) f32

    @staticmethod
    def build(n_nodes: int, x: Optional[np.ndarray] = None,
              species: Optional[np.ndarray] = None,
              pos: Optional[np.ndarray] = None) -> "FeatureStore":
        def ghost(a, fill=0):
            pad = np.full((1,) + a.shape[1:], fill, a.dtype)
            return jnp.asarray(np.concatenate([a, pad]))
        return resident(FeatureStore(
            n_nodes=n_nodes,
            x=None if x is None else ghost(np.asarray(x, np.float32)),
            species=(None if species is None
                     else ghost(np.asarray(species, np.int32))),
            pos=None if pos is None else ghost(np.asarray(pos, np.float32))))

    def row_index(self, node_ids: Array) -> Array:
        return jnp.where(node_ids >= 0, node_ids, self.n_nodes).astype(
            jnp.int32)


# a pytree, so steps take the resident tables as jit ARGUMENTS: a closed-over
# array is embedded in the program as a constant (the Reddit-width table is
# ~0.56 GB), once per bucket step
jax.tree_util.register_dataclass(FeatureStore,
                                 data_fields=["x", "species", "pos"],
                                 meta_fields=["n_nodes"])


def row_major(x: Optional[Array]) -> Optional[Array]:
    """``x`` committed in the row-major layout a row gather reads.

    A TPU lays a narrow 2-D table out column-major by default (it pads
    least: Reddit's 602 columns would pad to 640 row-major), and a jitted
    step that gathers rows from such an argument relays the whole table
    out on every call.  A table already row-major — always so on the CPU
    — comes back as the same object; otherwise it is re-committed once on
    its device and ``feature_store.relayouts`` counts it.  ``jit`` then
    compiles against the committed layout, with no copy in the step.
    """
    layout = getattr(getattr(x, "format", None), "layout", None)
    if layout is None:
        return x
    order = tuple(range(x.ndim))
    if layout.major_to_minor == order:
        return x
    record_count("feature_store.relayouts")
    return jax.jit(_relayout, out_shardings=Format(
        Layout(major_to_minor=order), x.sharding))(x)


def _relayout(a):
    # JAX's persistent compilation cache loads a program back with its
    # outputs reported in the default layout, so a table this program wrote
    # in a later process would lie in one layout and be read in another by
    # every jit after it (a size fault on a TPU, wrong rows on the CPU).  A
    # host callback keeps this one program out of that cache; the steps
    # that read the table are cached as usual, since argument layouts load
    # back intact.
    jax.debug.callback(lambda: None)
    return a


def resident(store: FeatureStore) -> FeatureStore:
    """``store`` with its tables of rank 2 committed row-major (not in
    ``__post_init__``: jit rebuilds the dataclass on tracers)."""
    return dataclasses.replace(store, x=row_major(store.x),
                               pos=row_major(store.pos))


# ---------------------------------------------------------------------------
# Step/plan cache — bounded LRU with the recompile counter tests assert on
# ---------------------------------------------------------------------------

class StepCache:
    """LRU over built artifacts keyed by tuple (bucket steps, bucket plans).

    For steps, ``builds`` counts cache misses — every miss is a host plan
    pack plus an XLA trace/compile on first call, i.e. a *recompile* in
    serving terms.  Steady state must hold it constant; the engine and the
    benchmark both export it.
    """

    def __init__(self, builder: Callable, maxsize: int = 16):
        self._builder = builder
        self.maxsize = maxsize
        self._cache: Dict[tuple, Callable] = {}
        self.builds = 0
        self.hits = 0

    def get(self, key: tuple):
        if key in self._cache:
            self.hits += 1
            fn = self._cache.pop(key)
            self._cache[key] = fn
            return fn
        self.builds += 1
        fn = self._builder(key)
        self._cache[key] = fn
        while len(self._cache) > self.maxsize:
            self._cache.pop(next(iter(self._cache)))
        return fn

    def info(self) -> dict:
        return {"builds": self.builds, "hits": self.hits,
                "size": len(self._cache)}


# ---------------------------------------------------------------------------
# Bucket plans — one host packing per (structure, backend layout set)
# ---------------------------------------------------------------------------

def _build_bucket_plan(key: tuple):
    n_seeds, fanouts, with_loops, backend, need_ell, width_multiple = key
    struct = build_bucket_structure(n_seeds, fanouts, with_loops=with_loops)
    backends = ["dense", "chunked"]
    if backend in ("pallas", "pallas_q8") and need_ell:
        backends.append(backend)
    if backend == "distributed":
        backends.append("distributed")
    return pack_plan(struct.senders, struct.receivers, struct.n_nodes,
                     backends=tuple(backends), width_multiple=width_multiple)


_BUCKET_PLANS = StepCache(_build_bucket_plan, maxsize=32)


def _plan_key(struct: BucketStructure, backend: str, need_ell: bool):
    return (struct.n_seeds, struct.fanouts, struct.with_loops, backend,
            bool(need_ell), chunk_width_multiple())


def bucket_plan(struct: BucketStructure, backend: str, need_ell: bool):
    """Host aggregation plan for a bucket's static edge structure, all edges
    valid (per-request validity flows in via ``plan_with_values``)."""
    return _BUCKET_PLANS.get(_plan_key(struct, backend, need_ell))


def bucket_plan_cache_info() -> dict:
    """Process-wide bucket-plan cache counters (builds/hits/size) — the
    KernelStats registry snapshots these per bench run."""
    return _BUCKET_PLANS.info()


def layer_rows(struct: BucketStructure, n_layers: int) -> Tuple[int, ...]:
    """The rows each layer of an ``n_layers`` conv model writes on the
    bucket: layer ``l`` only feeds levels ``0..n_layers-1-l`` of the
    breadth-major layout to the layers after it (every row, once the model
    is deeper than the tree), so the last writes just the seeds."""
    levels = np.cumsum([struct.n_seeds]
                       + sampler.budget(struct.n_seeds, struct.fanouts))
    return tuple(int(levels[min(n_layers - 1 - i, len(levels) - 1)])
                 for i in range(n_layers))


def _build_layer_plans(key: tuple):
    n_layers = key[-1]
    plan = _BUCKET_PLANS.get(key[:-1])
    struct = build_bucket_structure(*key[:3])
    n_in, cuts = struct.n_nodes, []
    # DRHM-permuted rows have no prefix: every layer runs the whole plan
    rows = ((struct.n_nodes,) * n_layers if plan.has("dist")
            else layer_rows(struct, n_layers))
    for r in rows:
        cuts.append(row_prefix_plan(plan, r, n_in))
        record_value("plan.layer_rows", r)
        n_in = r
    record_count("plan.rows_trimmed", sum(struct.n_nodes - r for r in rows))
    return tuple(cuts)


_LAYER_PLANS = StepCache(_build_layer_plans, maxsize=32)


def layer_plans(struct: BucketStructure, backend: str, n_layers: int):
    """Each layer's ``(plan, edges)`` for an ``n_layers`` conv model on the
    bucket, cut on the host from the bucket's one plan
    (``plan.row_prefix_plan``): layer ``l`` writes ``layer_rows[l]`` rows
    from the previous layer's.  ``edges`` picks the per-edge values each
    layer keeps (``_valued``)."""
    return _LAYER_PLANS.get(_plan_key(struct, backend, True) + (n_layers,))


def _take_edges(a: Array, edges: np.ndarray) -> Array:
    """``a[edges]`` for a static sorted ``edges``, as slices of its runs
    (a bucket keeps a prefix of its hop edges and one of its self loops)."""
    runs = np.split(edges, np.flatnonzero(np.diff(edges) != 1) + 1)
    parts = [a[r[0]:r[-1] + 1] for r in runs]
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts)


def _valued(cuts, edge_weight: Optional[Array], edge_valid: Array) -> list:
    """Each layer's plan re-valued with the request's edges (traced)."""
    return [plan_with_values(
        pl, edge_weight=(None if edge_weight is None
                         else _take_edges(edge_weight, edges)),
        edge_valid=_take_edges(edge_valid, edges)) for pl, edges in cuts]


def _conv_forward(arch_id: str, cfg, struct: BucketStructure,
                  backend: str) -> Callable:
    """``forward(params, x, node_ids, hop_valid) -> (n_seeds, d_out)`` of a
    conv-family model on the bucket, each layer on its own cut plan — the
    one body the single-device step and the cluster's lanes share."""
    arch = _arch_key(arch_id)
    if arch == "gcn" and not struct.with_loops:
        raise ValueError("gcn serving needs with_loops=True structure "
                         "(A + I normalization)")
    import importlib
    m = importlib.import_module(f"repro.models.gnn.{arch}")
    cuts = layer_plans(struct, backend, cfg.n_layers)
    n, k = struct.n_nodes, struct.n_seeds
    senders, receivers = struct.senders, struct.receivers

    def forward(params, x, node_ids, hop_valid):
        ev = _edge_validity(struct, node_ids, hop_valid)
        w = None
        if arch == "gcn":
            # symmetric normalization on the sampled subgraph, traced:
            # in-degree over valid edges (self loops included)
            deg = jax.ops.segment_sum(ev.astype(jnp.float32), receivers,
                                      num_segments=n)
            dinv = jax.lax.rsqrt(jnp.maximum(deg, 1.0))
            w = jnp.take(dinv, senders) * jnp.take(dinv, receivers)
        return m.forward(params, cfg, x, backend=backend,
                         plan=_valued(cuts, w, ev))[:k]
    return forward


def _edge_validity(struct: BucketStructure, node_ids, hop_valid):
    if struct.with_loops:
        return jnp.concatenate([hop_valid, node_ids >= 0])
    return hop_valid


# ---------------------------------------------------------------------------
# Inference steps
# ---------------------------------------------------------------------------

def build_infer_step(arch_id: str, cfg, store: FeatureStore,
                     struct: BucketStructure, backend: str = "dense",
                     jit: bool = True) -> Callable:
    """``step(params, node_ids, hop_valid) -> (n_seeds, d_out)`` for one
    bucket, jitted, with ``store`` bound as its first jit argument.  The
    bucket's structure and plans are closed over.  ``jit=False`` returns the
    unbound traced body ``step(store, params, node_ids, hop_valid)``."""
    arch = _arch_key(arch_id)
    n = struct.n_nodes
    k = struct.n_seeds
    if arch in CONV_ARCHS and store.x is None:
        raise ValueError(f"{arch} serving needs FeatureStore.x")
    if arch in GEOM_ARCHS and (store.species is None or store.pos is None):
        raise ValueError(f"{arch} serving needs FeatureStore.species/pos")

    if arch in CONV_ARCHS:
        forward = _conv_forward(arch_id, cfg, struct, backend)

        def step(store, params, node_ids, hop_valid):
            with jax.named_scope("serve.gather"):
                x = jnp.take(store.x, store.row_index(node_ids), axis=0)
            return forward(params, x, node_ids, hop_valid)

        return functools.partial(jax.jit(step), store) if jit else step

    # the geometric family only `accumulate`s vector messages (pallas falls
    # back to the chunked schedule there — DESIGN.md §3.3), so COO
    # sections suffice
    plan0 = bucket_plan(struct, backend, need_ell=False)
    senders = jnp.asarray(struct.senders)
    receivers = jnp.asarray(struct.receivers)
    graph_ids = jnp.arange(n, dtype=jnp.int32)

    if arch == "schnet":
        from repro.models.gnn import schnet as m

        def step(store, params, node_ids, hop_valid):
            idx = store.row_index(node_ids)
            species = jnp.take(store.species, idx)
            pos = jnp.take(store.pos, idx, axis=0)
            pl = plan_with_values(plan0, edge_valid=_edge_validity(
                struct, node_ids, hop_valid))
            e = m.forward(params, cfg, species, pos, graph_ids=graph_ids,
                          n_graphs=n, backend=backend, plan=pl)
            return e[:k, None]

    else:  # dimenet
        from repro.models.gnn import dimenet as m
        t_in = jnp.asarray(struct.t_in)
        t_out = jnp.asarray(struct.t_out)

        def step(store, params, node_ids, hop_valid):
            idx = store.row_index(node_ids)
            species = jnp.take(store.species, idx)
            pos = jnp.take(store.pos, idx, axis=0)
            ev = _edge_validity(struct, node_ids, hop_valid)
            tv = jnp.take(ev, t_in) & jnp.take(ev, t_out)
            pl = plan_with_values(plan0, edge_valid=ev)
            e = m.forward(params, cfg, species, pos, senders, receivers, ev,
                          t_in, t_out, tv, graph_ids, n, backend=backend,
                          plan=pl)
            return e[:k, None]

    return functools.partial(jax.jit(step), store) if jit else step


# ---------------------------------------------------------------------------
# Cluster steps — lane-stacked variants for the scale-out tier (DESIGN.md §11)
# ---------------------------------------------------------------------------
#
# The cluster compute plane splits feature *fetch* from the model *step* so
# replicated and sharded residency can share one compiled compute program:
# the fetch differs (device take vs halo exchange over the lane mesh), the
# step is identical — which is what makes sharded output BITWISE equal to
# replicated output (a gather is an exact row copy).

def _lane_body(arch_id: str, cfg, struct: BucketStructure,
               backend: str) -> Callable:
    """``body(params, x, node_ids, hop_valid) -> (k, d_out)`` — one lane's
    inference with features already fetched.  Conv family only: the cluster
    tier serves gcn/sage/gin/gat (the geometric family's species/pos stores
    stay single-device until a later PR)."""
    arch = _arch_key(arch_id)
    if arch not in CONV_ARCHS:
        raise ValueError(f"cluster serving covers the conv family "
                         f"{CONV_ARCHS}; {arch!r} is single-device only")
    return _conv_forward(arch_id, cfg, struct, backend)


def build_lane_infer_step(arch_id: str, cfg, struct: BucketStructure,
                          backend: str = "dense", *,
                          placement: str = "stacked",
                          mesh=None) -> Callable:
    """``step(params, x, node_ids, hop_valid) -> (L, k, d_out)`` over
    lane-stacked inputs ``x (L, n, d)`` / ``node_ids (L, n)`` /
    ``hop_valid (L, E)``.

    ``placement="stacked"`` vmaps the lanes into ONE dispatch on the default
    device — the round-amortization that carries the cluster's aggregate
    throughput win (per-dispatch overhead is paid once per *round*, not once
    per lane; measured ≥3× on CPU CI).  ``placement="mesh"`` shard_maps the
    lane axis over an L-device mesh — the true multi-device placement the
    8-device CI leg exercises; both produce bitwise-identical outputs.
    """
    body = _lane_body(arch_id, cfg, struct, backend)
    if placement == "stacked":
        return jax.jit(jax.vmap(body, in_axes=(None, 0, 0, 0)))
    if placement != "mesh":
        raise ValueError(f"unknown placement {placement!r}; "
                         "have ('stacked', 'mesh')")
    if mesh is None:
        raise ValueError("placement='mesh' needs a 1-D ('lane',) mesh")
    from jax.sharding import PartitionSpec as P

    def lane_fn(params, x, node_ids, hop_valid):
        return body(params, x[0], node_ids[0], hop_valid[0])[None]

    # check_vma=False: a lane runs no collective, and the Pallas kernels'
    # out_shapes carry no varying-axes annotation for the checker to match
    return jax.jit(jax.shard_map(
        lane_fn, mesh=mesh,
        in_specs=(P(), P("lane"), P("lane"), P("lane")),
        out_specs=P("lane"), check_vma=False))


def build_fetch_step(store: FeatureStore) -> Callable:
    """Replicated-residency feature fetch: ``(node_ids (L, n)) ->
    x (L, n, d)`` straight off the resident device table (ghost row for
    padding lanes).  The sharded-residency counterpart is
    ``core.distributed.make_halo_gather`` — same rows, different transport,
    bitwise-equal output."""
    def fetch(store, node_ids):
        return jnp.take(store.x, store.row_index(node_ids), axis=0)
    return functools.partial(jax.jit(fetch), store)


