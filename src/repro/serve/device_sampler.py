"""Device-side forest sampling — the SamplerPool round-trip, collapsed.

The host data plane (``engine.SamplerPool``) drains queued requests into
grouped ``sample_forest`` calls, assembles per-tree tables, and the
dispatcher re-splices them into the bucket layout and ships the node table
to the device.  Every step of that is avoidable: the counter-hash sampler
is pure arithmetic in ``(key, tree_key, hop, lane)``, and the bucket layout
is a static reshape of the per-tree tables.  This module runs the whole
chain *inside the dispatch program*: seeds + per-tree counter keys go in
(a few hundred bytes), the sampled ``(node_ids, hop_valid)`` bucket arrays
come out on device, already in the layout ``buckets.stack_trees`` would
have produced — so the per-bucket jitted step fuses sampling, feature
gather, and the GNN forward into one program, and the host never touches a
node table.

Draw-for-draw equality with the host sampler is a hard invariant, not an
aspiration: the splitmix64 emulation (``kernels.forest_sampler``) is
bit-exact, the serving parity anchor replays requests through the HOST
sampler (``GNNServer.sample_for``) and compares at ≤1e-5, and
``tests/test_device_sampler.py`` asserts exact node-table equality.

Grouping-invariance does the heavy lifting here exactly as it did for the
host pool: a tree's draws depend only on its own ``tree_key``, so sampling
inside per-bucket dispatch batches reproduces what isolated sampling would
have produced, whatever the batch composition.
"""
from __future__ import annotations

from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.forest_sampler.ops import counter_draws, split64
from repro.sparse import sampler as host_sampler
from repro.sparse.sampler import (_K_HOP, _K_LANE, _K_TREE, _mix64,
                                  SampledSubgraph, budget)

Array = jax.Array


def tree_key_mix(tree_keys: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Host-side per-tree counter term ``tree_key · C₁`` as uint32 halves.

    The xor-combine of the counter splits per term, so this is the only
    per-request arithmetic the host still does — two uint32 per tree.
    """
    with np.errstate(over="ignore"):
        tkm = np.asarray(tree_keys, np.uint64) * _K_TREE
    return split64(tkm)


class DeviceSamplerPlane:
    """Per-graph device state + per-bucket traced sampling bodies.

    Holds the CSR arrays on device and the per-hop constant counter terms
    ``mix64(key) ⊕ hop·C₂ ⊕ lane·C₃`` (uint32 halves, precomputed host-side
    once — they depend only on ``(key, fanouts)``, never on requests).

    A pytree (arrays are leaves): the fused step takes the plane as a jit
    argument, so the resident CSR is never embedded as a program constant.
    """

    def __init__(self, indptr: np.ndarray, indices: np.ndarray,
                 fanouts: Sequence[int], key: int = 0,
                 use_kernel: bool = None):
        self.fanouts = tuple(int(f) for f in fanouts)
        self.indptr = jnp.asarray(np.asarray(indptr, np.int32))
        self.indices = jnp.asarray(np.asarray(indices, np.int32))
        self.n_edges = int(np.asarray(indices).size)
        self.use_kernel = use_kernel
        key_c = _mix64(np.uint64(int(key) % (1 << 64)))
        self._hop_consts = []
        lanes = 1
        for h, f in enumerate(self.fanouts):
            lane_idx = np.arange(lanes * f, dtype=np.uint64)
            with np.errstate(over="ignore"):
                zc = (key_c ^ (np.uint64(h + 1) * _K_HOP)
                      ^ (lane_idx * _K_LANE))
            hi, lo = split64(zc)
            self._hop_consts.append((jnp.asarray(hi), jnp.asarray(lo)))
            lanes *= f

    # -- traced bodies (closed over by the fused dispatch step's jit) -----

    def sample_levels(self, seeds: Array, tk_hi: Array, tk_lo: Array,
                      live: Array):
        """One vectorized pass over T trees → per-level tables.

        seeds (T,) int32, tk_hi/tk_lo (T,) uint32 (``tree_key_mix``), live
        (T,) bool (False ⇒ padding lane: all nodes -1, all edges invalid).
        Returns ``(levels, valid_hops)``: levels[ℓ] is (T, size_ℓ) int32,
        valid_hops[h] is (T, budget_h) bool — the mirror of the host
        ``sample_forest`` loop, arithmetic shared down to the draw kernel.
        """
        t = seeds.shape[0]
        seeds = seeds.astype(jnp.int32)
        frontier = jnp.where(live, seeds, 0).reshape(t, 1)
        live_l = live.reshape(t, 1)
        levels = [jnp.where(live, seeds, -1).reshape(t, 1)]
        valid_hops = []
        lanes = 1
        for h, f in enumerate(self.fanouts):
            deg = (jnp.take(self.indptr, frontier + 1, mode="clip")
                   - jnp.take(self.indptr, frontier, mode="clip"))
            has_nbr = deg > 0
            zc_hi, zc_lo = self._hop_consts[h]
            z_hi = tk_hi[:, None] ^ zc_hi[None, :]
            z_lo = tk_lo[:, None] ^ zc_lo[None, :]
            dmax = jnp.repeat(jnp.maximum(deg, 1).astype(jnp.uint32),
                              f, axis=1)
            r = counter_draws(z_hi, z_lo, dmax, use_kernel=self.use_kernel)
            r = r.reshape(t, lanes, f)
            if self.n_edges:
                gather = jnp.minimum(
                    jnp.take(self.indptr, frontier, mode="clip")[:, :, None]
                    + r, self.n_edges - 1)
                nbr = jnp.take(self.indices, gather, mode="clip")
            else:
                nbr = jnp.zeros((t, lanes, f), jnp.int32)
            valid = jnp.broadcast_to((has_nbr & live_l)[:, :, None],
                                     (t, lanes, f))
            nbr = jnp.where(valid, nbr, -1)
            levels.append(nbr.reshape(t, lanes * f))
            valid_hops.append(valid.reshape(t, lanes * f))
            frontier = jnp.where(valid, nbr, 0).reshape(t, lanes * f)
            live_l = valid.reshape(t, lanes * f)
            lanes *= f
        return levels, valid_hops

    def sample_bucket(self, seeds: Array, tk_hi: Array, tk_lo: Array,
                      live: Array):
        """Sampled batch in the bucket's breadth-major layout, on device.

        A bucket level block viewed as (n_seeds, size) rows is tree-major
        (see ``buckets.stack_trees``), so the (T, size) level tables ARE
        the bucket blocks — flatten and concatenate, no index shuffle.
        Returns ``(node_ids (n_nodes,) int32, hop_valid (Σbudgets,) bool)``.
        """
        levels, valid_hops = self.sample_levels(seeds, tk_hi, tk_lo, live)
        node_ids = jnp.concatenate([lv.reshape(-1) for lv in levels])
        hop_valid = jnp.concatenate([v.reshape(-1) for v in valid_hops])
        return node_ids, hop_valid


def _plane_flatten(p: DeviceSamplerPlane):
    return ((p.indptr, p.indices, p._hop_consts),
            (p.fanouts, p.n_edges, p.use_kernel))


def _plane_unflatten(aux, leaves):
    p = object.__new__(DeviceSamplerPlane)
    p.indptr, p.indices, p._hop_consts = leaves
    p.fanouts, p.n_edges, p.use_kernel = aux
    return p


jax.tree_util.register_pytree_node(DeviceSamplerPlane, _plane_flatten,
                                   _plane_unflatten)


def sample_forest_device(indptr: np.ndarray, indices: np.ndarray,
                         seeds: np.ndarray, fanouts: Sequence[int],
                         key: int = 0, tree_keys: np.ndarray = None,
                         use_kernel: bool = None) -> List[SampledSubgraph]:
    """Drop-in device twin of ``sparse.sampler.sample_forest``.

    Runs the device pass and re-assembles per-tree host ``SampledSubgraph``
    views — the equality-test entry (and a one-call way to use the device
    sampler outside the serving engine).  Output is exactly
    ``sample_forest(indptr, indices, seeds, fanouts, key, tree_keys)``.
    """
    seeds = np.atleast_1d(np.asarray(seeds, np.int64))
    n_trees = seeds.shape[0]
    fanouts = tuple(int(f) for f in fanouts)
    if tree_keys is None:
        tree_keys = np.arange(n_trees, dtype=np.uint64)
    plane = DeviceSamplerPlane(indptr, indices, fanouts, key=key,
                               use_kernel=use_kernel)
    tk_hi, tk_lo = tree_key_mix(tree_keys)
    levels, valid_hops = jax.jit(DeviceSamplerPlane.sample_levels)(
        plane, jnp.asarray(seeds.astype(np.int32)), jnp.asarray(tk_hi),
        jnp.asarray(tk_lo), jnp.ones((n_trees,), bool))
    nodes_all = np.concatenate([np.asarray(lv, np.int64) for lv in levels],
                               axis=1)
    valids = [np.asarray(v) for v in valid_hops]
    tmpl = host_sampler.hop_slots(1, fanouts)
    tmpl_s = [s for s, _ in tmpl]
    tmpl_r = [r for _, r in tmpl]
    return [SampledSubgraph(
        node_ids=nodes_all[t], hop_senders=tmpl_s, hop_receivers=tmpl_r,
        hop_valid=[valids[h][t] for h in range(len(fanouts))], n_seeds=1)
        for t in range(n_trees)]
