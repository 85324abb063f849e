#!/usr/bin/env python3
"""Readings that the check's limit is set from (not part of a run).

  python benchmarks/chip/control.py --workload sage-reddit.poisson \\
      --seeds 1,2,3 --seconds 4

For each seed, in one process: the cell's world from that seed, a short
window at the cell's own load through the timed path, and the same sample
of served answers that a run compares.  It prints, per seed, the number a
run compares (widest logit gap over the reference's RMS; share of served
logits that are exact bfloat16 values; the mean gap beside them) for the
program, and for the control put in the program's
place: the reference computed in bfloat16.
The program's readings over a dozen seeds are the lower end of the limit;
the smallest control reading is its upper end.
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    from benchmarks.chip import harness, loadgen, reference, worldgen
    harness.enable_compile_cache()
    cell = harness.load_cell(args.workload)
    dev = harness.device_info(cell.chips)
    counter = harness.CompileCounter()
    cfg, traffic = cell.config, cell.traffic
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        world = worldgen.make_world(cfg, seed)
        csr = world.host_csr()
        server = harness.build_server(cfg, world, csr, seed)
        server.warmup(loadgen.buckets_used(
            traffic, cfg["serving"]["max_batch_seeds"]))
        win = harness.run_window(server, traffic, world.n_nodes, seed,
                                 args.seconds, counter)
        server.close()
        del server
        sample = harness.served_sample(win, seed)
        row = {"workload": cell.name, "seed": seed, "device": dev["kind"],
               "requests": int(harness.window_requests(win)["due"].size)}
        for compute in reference.COMPUTES:
            cmp = harness.compare(cfg, world, csr, seed, sample, compute)
            name = "program" if compute == "f32" else compute
            row[name] = cmp["rel_err"]
            row[name + "_mean"] = cmp["mean_rel_err"]
            row[name + "_bf16_share"] = cmp["bf16_share"]
            row["seeds_compared"] = cmp["seeds"]
        rows.append(row)
        print(json.dumps(row), flush=True)
        del world, csr
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "a") as f:
            for row in rows:
                f.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
