"""Work that GraphSAGE's minibatch algorithm needs, counted from shapes.

Counts come from the fanout tree and the layer widths alone, never from a
kernel's chunk layout, its padding, or rows the implementation computes
and throws away.  So a change to how the work is done cannot change them.

Per seed, layer ``i`` (0-based, K layers) updates the tree levels
``0 .. K-1-i``: ``N_i`` nodes, which aggregate over their ``E_i`` children.

* FLOPs of layer i: ``4 N_i d_i d_{i+1}`` for the two matmuls (self and
  neighbour) and ``2 E_i d_i`` for the aggregation (one multiply-add per
  edge and column, the SpMM convention).  Bias, mean and ReLU are left out.
* Aggregation bytes of layer i (float32): each child row read once
  (``4 E_i d_i``), each aggregated row written once (``4 N_i d_i``) and one
  int32 column index per edge (``4 E_i``).
"""
from __future__ import annotations

from typing import Dict, List, Sequence

F32 = 4


def level_sizes(fanouts: Sequence[int]) -> List[int]:
    out = [1]
    for f in fanouts:
        out.append(out[-1] * int(f))
    return out


def layer_shapes(fanouts: Sequence[int], dims: Sequence[int]
                 ) -> List[Dict[str, int]]:
    """Per layer: nodes updated, edges aggregated, input and output width."""
    sizes = level_sizes(fanouts)
    k = len(fanouts)
    out = []
    for i in range(k):
        top = k - 1 - i
        out.append({"nodes": sum(sizes[:top + 1]),
                    "edges": sum(sizes[1:top + 2]),
                    "d_in": int(dims[i]), "d_out": int(dims[i + 1])})
    return out


def flops_per_seed(fanouts: Sequence[int], dims: Sequence[int]) -> int:
    return sum(4 * s["nodes"] * s["d_in"] * s["d_out"]
               + 2 * s["edges"] * s["d_in"]
               for s in layer_shapes(fanouts, dims))


def aggregation_work(fanouts: Sequence[int], dims: Sequence[int]
                     ) -> List[Dict[str, int]]:
    """Per layer, per seed: the aggregation's FLOPs and bytes."""
    return [{"flops": 2 * s["edges"] * s["d_in"],
             "bytes": F32 * (s["edges"] * s["d_in"] + s["nodes"] * s["d_in"]
                             + s["edges"])}
            for s in layer_shapes(fanouts, dims)]


def aggregation_floor_s(fanouts: Sequence[int], dims: Sequence[int],
                        peak_flops: float, peak_bytes: float) -> float:
    """Least device time the aggregations of one seed can take: per layer,
    the larger of FLOPs over peak FLOP/s and bytes over peak bytes/s."""
    return sum(max(w["flops"] / peak_flops, w["bytes"] / peak_bytes)
               for w in aggregation_work(fanouts, dims))
