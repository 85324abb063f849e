#!/usr/bin/env python3
"""Find an open-loop cell's knee once, by a sweep of fixed rates.

  python benchmarks/chip/sweep.py --workload sage-reddit.poisson \\
      --rates 1000,2000,4000 --seconds 10 --seed 1

One process, one world: the cell's configuration and traffic file, as
``BENCHMARK.json`` names them, with only ``rate_per_s`` changed; for each
rate a window of that traffic, then every request's latency from its due
time.  The knee is the highest rate at which at least ``WITHIN`` of the
requests settle within ``LIMIT_MS`` and the backlog does not grow: the
last fifth of the window's requests waits no longer, at the median, than
twice the first fifth plus 5 ms.  The sweep stops after two rates in a
row miss.  Each rate's line goes to standard output and to ``--out`` (JSON
lines); the cell then fixes its rate at about four fifths of the knee.
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

# the ``interactive`` objective of ``repro.serve.slo``: 50 ms, 1% budget
LIMIT_MS = 50.0
WITHIN = 0.99


def growing(lat_ms, due) -> bool:
    import numpy as np
    order = np.argsort(due)
    fifth = max(len(order) // 5, 1)
    first = np.median(lat_ms[order[:fifth]])
    last = np.median(lat_ms[order[-fifth:]])
    return bool(last > 2 * first + 5.0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated requests per second")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    import numpy as np

    from benchmarks.chip import harness, loadgen, measures, worldgen
    harness.enable_compile_cache()
    cell = harness.load_cell(args.workload)
    if cell.traffic["kind"] != "open_poisson":
        raise SystemExit(f"{cell.name} is not an open-loop cell")
    cfg = cell.config
    dev = harness.device_info(cell.chips)
    world = worldgen.make_world(cfg, args.seed)
    csr = world.host_csr()
    server = harness.build_server(cfg, world, csr, args.seed)
    server.warmup(loadgen.buckets_used(cell.traffic,
                                       cfg["serving"]["max_batch_seeds"]))
    counter = harness.CompileCounter()
    knee, lines, misses = None, [], 0
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        traffic = dict(cell.traffic, rate_per_s=rate)
        win = harness.run_window(server, traffic, world.n_nodes,
                                 args.seed + i, args.seconds, counter)
        reqs = harness.window_requests(win)
        lat = measures.latencies_ms(reqs)
        due = reqs["due"]
        lag = (reqs["sent"] - due) * 1e3
        within = float(np.mean(lat <= LIMIT_MS))
        grows = growing(lat, due)
        ok = within >= WITHIN and not grows
        line = {"workload": cell.name, "rate_per_s": rate,
                "seconds": args.seconds, "requests": int(due.size),
                "batches": win.batches,
                "p50_ms": float(np.percentile(lat, 50)),
                "p95_ms": float(np.percentile(lat, 95)),
                "p99_ms": float(np.percentile(lat, 99)),
                "within_limit": within, "backlog_grows": grows,
                "gen_lag_p95_ms": float(np.percentile(lag, 95)),
                "compiles": len(win.compiles) + win.step_builds,
                "meets": ok, "device": dev["kind"]}
        lines.append(line)
        print(json.dumps(line), flush=True)
        if ok:
            knee, misses = rate, 0
        else:
            misses += 1
            if misses == 2:
                break
    server.close()
    summary = {"workload": cell.name, "knee_rate_per_s": knee,
               "cell_rate_per_s": None if knee is None else 0.8 * knee}
    print(json.dumps(summary), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "a") as f:
            for line in lines + [summary]:
                f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
