"""Paper Table 1 — SpGEMM memory-bloat percentages.

Two independent counts per graph at the paper's exact (node, edge) sizes:

* **analytic** — ``neurasim.model.stats_from_coo`` (the Eq.-1 walk the
  performance model uses);
* **measured** — the SpGEMM engine's symbolic phase
  (``repro.sparse.spgemm.symbolic``), i.e. the structure an actual
  sparse-output execution would fill.  Table 1 is thereby validated by the
  engine rather than assumed: ``match`` must be True on every row.

Structure differs from the SNAP originals (synthetic power-law stand-ins),
so agreement with the paper's column is a band check, not an equality.
"""
from __future__ import annotations

import time

import numpy as np

from repro.core.eviction import bloat_percent
from repro.neurasim import datasets
from repro.neurasim.model import stats_from_coo
from repro.sparse.spgemm import symbolic


def run(fast: bool = True):
    names = datasets.FAST_SET if fast else list(datasets.TABLE1)
    rows = []
    for name in names:
        s, r, n = datasets.synth(name)
        t0 = time.time()
        w = stats_from_coo(s, r, n)
        analytic = bloat_percent(w.pp_interim, w.nnz_out)
        t1 = time.time()
        sym = symbolic(s, r, n, s, r, n)   # same orientation as the walk
        measured = sym.bloat_pct
        t2 = time.time()
        match = (sym.pp_interim == w.pp_interim
                 and sym.nnz_out == w.nnz_out)
        paper = datasets.TABLE1[name][2]
        rows.append((name, w.pp_interim, w.nnz_out, analytic, measured,
                     match, paper, (t1 - t0) * 1e6, (t2 - t1) * 1e6))
    return rows


def main():
    print("# Table 1 repro: bloat percent (synthetic structure)")
    print("name,pp_interim,nnz_out,bloat_analytic_pct,bloat_measured_pct,"
          "match,bloat_paper_pct,us_analytic,us_symbolic")
    mismatches = 0
    for (name, pp, nnz, analytic, measured, match, paper, us_a,
         us_s) in run():
        mismatches += not match
        print(f"{name},{pp},{nnz},{analytic:.1f},{measured:.1f},"
              f"{match},{paper},{us_a:.0f},{us_s:.0f}")
    if mismatches:
        raise RuntimeError(f"{mismatches} measured/analytic mismatches")


if __name__ == "__main__":
    main()
