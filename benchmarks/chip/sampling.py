"""The counter-hash fanout sampling rule, kept with the benchmark.

A copy of the rule the served program documents (splitmix64 counter draws,
one stream per (request id, seed index)), written out here so that the
reference never calls the code under test.  A tree's draws are a pure
function of ``(key, tree_key, hop, lane)``:

    r = mix64(mix64(key) ^ tree_key*C_TREE ^ (hop+1)*C_HOP ^ lane*C_LANE)
        mod max(deg, 1)

and the tree key of seed ``i`` of request ``rid`` is ``(rid << 16) + i``.
Neighbour ``r`` of node ``v`` is ``indices[indptr[v] + r]``; a node with no
in-edges, and every child of an invalid lane, is invalid (id -1).
"""
from __future__ import annotations

from typing import List, Sequence, Tuple

import numpy as np

_GAMMA = np.uint64(0x9E3779B97F4A7C15)
_M1 = np.uint64(0xBF58476D1CE4E5B9)
_M2 = np.uint64(0x94D049BB133111EB)
C_TREE = np.uint64(0xD1B54A32D192ED03)
C_HOP = np.uint64(0x8CB92BA72F3D8DD7)
C_LANE = np.uint64(0x2545F4914F6CDD1D)


def mix64(z: np.ndarray) -> np.ndarray:
    """splitmix64 finalizer on uint64, wrapping."""
    with np.errstate(over="ignore"):
        z = z + _GAMMA
        z = (z ^ (z >> np.uint64(30))) * _M1
        z = (z ^ (z >> np.uint64(27))) * _M2
        return z ^ (z >> np.uint64(31))


def tree_keys(rid: int, n_seeds: int) -> np.ndarray:
    """Counter stream of each seed of request ``rid``."""
    return (np.uint64(rid) << np.uint64(16)) + np.arange(n_seeds,
                                                         dtype=np.uint64)


def sample_trees(indptr: np.ndarray, indices: np.ndarray, seeds: np.ndarray,
                 keys: np.ndarray, fanouts: Sequence[int], key: int
                 ) -> Tuple[List[np.ndarray], List[np.ndarray]]:
    """One tree per seed.  Returns ``(levels, valid)``: ``levels[l]`` is the
    (T, prod(fanouts[:l])) int64 node-id table of level ``l`` (-1 where
    invalid) and ``valid[h]`` the (T, prod(fanouts[:h+1])) bool mask of the
    hop-``h`` edges (child lane of level h+1 -> its parent in level h)."""
    seeds = np.asarray(seeds, np.int64)
    t = seeds.shape[0]
    keys = np.asarray(keys, np.uint64)
    key_c = mix64(np.uint64(int(key) % (1 << 64)))
    frontier = seeds.reshape(t, 1)
    live = np.ones((t, 1), bool)
    levels, valid = [seeds.reshape(t, 1).copy()], []
    lanes = 1
    n_idx = indices.shape[0]
    for h, f in enumerate(fanouts):
        start = indptr[frontier].astype(np.int64)
        deg = indptr[frontier + 1].astype(np.int64) - start
        lane = np.arange(lanes * f, dtype=np.uint64)
        with np.errstate(over="ignore"):
            z = (key_c ^ (keys[:, None] * C_TREE)
                 ^ (np.uint64(h + 1) * C_HOP) ^ (lane[None, :] * C_LANE))
        draws = mix64(z).reshape(t, lanes, f)
        r = (draws % np.maximum(deg, 1)[:, :, None].astype(np.uint64)
             ).astype(np.int64)
        pos = np.minimum(start[:, :, None] + r, max(n_idx - 1, 0))
        nbr = indices[pos].astype(np.int64) if n_idx else np.zeros_like(pos)
        ok = np.broadcast_to(((deg > 0) & live)[:, :, None], nbr.shape)
        nbr = np.where(ok, nbr, -1)
        levels.append(nbr.reshape(t, lanes * f))
        valid.append(ok.reshape(t, lanes * f))
        frontier = np.where(ok, nbr, 0).reshape(t, lanes * f)
        live = ok.reshape(t, lanes * f)
        lanes *= f
    return levels, valid
