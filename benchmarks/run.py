"""Benchmark harness — one module per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run            # all, CSV to stdout
  PYTHONPATH=src python -m benchmarks.run --serving  # serving engine only
  PYTHONPATH=src python -m benchmarks.run --cluster  # scale-out tier only

Modules: bloat_table (Table 1), speedup_table (Table 5 / Fig 16),
mapping_heatmap (Fig 12/13), cpi_histograms (Fig 14/15), gnn_speedup
(Fig 17), kernel_bench (Pallas kernels), backend_sweep (unified sparse
executors — also emitted as BENCH_backends.json for the perf trajectory),
spgemm_sweep (sparse×sparse engine — emitted as BENCH_spgemm.json),
serving_bench (GNN inference serving — emitted as BENCH_serving.json),
cluster_bench (multi-lane scale-out serving — emitted as
BENCH_cluster.json; in-process on a TPU, a subprocess elsewhere, because
off the chip it must set the 8-device host-platform flag before jax
initializes), roofline (§Roofline from dry-run).

The BENCH_*.json files together are the reproducible perf trajectory —
``--backends`` / ``--spgemm`` / ``--serving`` / ``--cluster`` rerun any
slice alone, and ``benchmarks/trajectory.py`` appends each run's gated
metrics to the files' bounded history.
"""
from __future__ import annotations

import argparse
import os
import subprocess
import sys
import time
import traceback

from benchmarks import (backend_sweep, bloat_table, cluster_bench,
                        cpi_histograms, gnn_speedup, kernel_bench,
                        mapping_heatmap, roofline, serving_bench,
                        speedup_table, spgemm_sweep)
from repro.core import platform

MODULES = [
    ("table1_bloat", bloat_table),
    ("table5_fig16_speedups", speedup_table),
    ("fig12_13_mapping", mapping_heatmap),
    ("fig14_15_cpi", cpi_histograms),
    ("fig17_gnn", gnn_speedup),
    ("pallas_kernels", kernel_bench),
    ("backend_sweep", backend_sweep),
    ("spgemm_sweep", spgemm_sweep),
    ("serving_bench", serving_bench),
    ("roofline", roofline),
]

BACKENDS_JSON = "BENCH_backends.json"
SPGEMM_JSON = "BENCH_spgemm.json"
SERVING_JSON = serving_bench.DEFAULT_JSON
CLUSTER_JSON = "BENCH_cluster.json"


def _run_cluster():
    """On a TPU the chip belongs to this process (a child could not open
    it), so cluster_bench runs in-process over the real devices.  Off the
    chip it needs ``--xla_force_host_platform_device_count=8`` set BEFORE
    jax initializes — by the time run.py gets here jax is long live, so
    the CPU slice runs in a child process."""
    if platform.on_tpu():
        rc = cluster_bench.main([])
        if rc:
            raise RuntimeError(f"cluster_bench returned {rc}")
        return
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.cluster_bench"], env=env)
    if proc.returncode:
        raise RuntimeError(f"cluster_bench exited {proc.returncode}")


# the tracked perf-trajectory emitters: (json path, collect, write)
TRAJECTORY = [
    ("backends", BACKENDS_JSON,
     lambda: backend_sweep.write_json(BACKENDS_JSON, backend_sweep.collect())),
    ("spgemm", SPGEMM_JSON,
     lambda: spgemm_sweep.write_json(SPGEMM_JSON, spgemm_sweep.collect())),
    ("serving", SERVING_JSON,
     lambda: serving_bench.write_json(SERVING_JSON, serving_bench.collect())),
    ("cluster", CLUSTER_JSON, _run_cluster),
]


def _run_trajectory(names) -> int:
    failures = 0
    for name, path, emit in TRAJECTORY:
        if names is not None and name not in names:
            continue
        try:
            emit()
            print(f"wrote {path}")
        except Exception:  # noqa: BLE001
            failures += 1
            traceback.print_exc()
    return failures


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--serving", action="store_true",
                    help="only the serving engine benchmark "
                         "(BENCH_serving.json)")
    ap.add_argument("--backends", action="store_true",
                    help="only the sparse-backend sweep "
                         "(BENCH_backends.json)")
    ap.add_argument("--spgemm", action="store_true",
                    help="only the SpGEMM engine sweep (BENCH_spgemm.json)")
    ap.add_argument("--cluster", action="store_true",
                    help="only the scale-out serving benchmark "
                         "(BENCH_cluster.json; off a TPU, a subprocess on "
                         "an emulated 8-device mesh)")
    args = ap.parse_args()

    only = [n for n, flag in (("serving", args.serving),
                              ("backends", args.backends),
                              ("spgemm", args.spgemm),
                              ("cluster", args.cluster)) if flag]
    if only:
        sys.exit(1 if _run_trajectory(only) else 0)

    failures = 0
    for name, mod in MODULES:
        print(f"\n### {name}")
        t0 = time.time()
        try:
            if mod is serving_bench:
                mod.main([])          # don't re-parse run.py's argv
            else:
                mod.main()
            print(f"### {name} done in {time.time() - t0:.1f}s")
        except Exception:  # noqa: BLE001
            failures += 1
            print(f"### {name} FAILED")
            traceback.print_exc()
    # perf trajectory, tracked from PR 1 (backends), PR 3 (spgemm),
    # PR 4 (serving), PR 5 (cluster) onward — serving_bench.main() already
    # wrote its JSON
    failures += _run_trajectory(("backends", "spgemm", "cluster"))
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
