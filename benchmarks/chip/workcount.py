"""Work that an architecture's minibatch algorithm needs, counted from shapes.

Counts come from the fanout tree and the layer widths alone, never from a
kernel's chunk layout, its padding, or rows the implementation computes
and throws away.  So a change to how the work is done cannot change them.

Per seed, layer ``i`` (0-based, K layers) updates the tree levels
``0 .. K-1-i``: ``N_i`` nodes, which aggregate over their ``E_i`` children
(``layer_shapes``).  Each ``archs/<arch>.py`` turns these shapes into its
FLOPs per seed and its aggregation's FLOPs and bytes per layer;
``aggregation_floor_s`` is the least time that aggregation work can take.
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


def aggregation_floor_s(work: Sequence[Dict[str, int]], peak_flops: float,
                        peak_bytes: float) -> float:
    """Least device time the aggregations of one seed can take: per layer
    of ``work`` (the architecture's ``aggregation_work``), the larger of
    FLOPs over peak FLOP/s and bytes over peak bytes/s."""
    return sum(max(w["flops"] / peak_flops, w["bytes"] / peak_bytes)
               for w in work)
