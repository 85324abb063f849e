#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip this process finds.

  python benchmarks/chip/run.py --workload sage-reddit.poisson \\
      --seed 7 --seconds 20 --trace 0

``--trace 0`` prints the cell's end-to-end metrics; ``--trace 1`` runs the
same window with the program's spans and the profiler on, and prints the
per-layer metrics, the device's busy time and a breakdown.  The last line
of standard output is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, [``breakdown``], ``checks``); the
last lines of standard error give each number checked beside its limit.
With no TPU, an unknown chip or too few chips it exits non-zero and prints
no result.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        import repro.serve  # noqa: F401 — the system under test
        from benchmarks.chip import harness
    except ImportError as exc:
        print(f"[bench] FAIL: cannot import the benchmark or the program "
              f"({exc})", file=sys.stderr)
        return 2
    try:
        cell = harness.load_cell(args.workload)
        out = harness.run_cell(cell, args.seed, args.seconds,
                               bool(args.trace), T_START)
    except harness.HarnessError as exc:
        print(f"[bench] FAIL: {exc}", file=sys.stderr)
        return 2
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
