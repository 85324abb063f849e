"""GNN inference serving driver (DESIGN.md §10–11).

  PYTHONPATH=src python -m repro.launch.gnn_serve --arch gcn --requests 100 \
      --backend pallas --max-batch 16 --fanouts 5,3

  # scale-out: 8 replica lanes with DRHM request routing (DESIGN.md §11)
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  PYTHONPATH=src python -m repro.launch.gnn_serve --arch gcn --replicas 8

  # sharded residency: lanes own DRHM row shards, halo-exchange boundaries
  ... --replicas 8 --shard

Stands up a ``GNNServer`` (or, with ``--replicas``/``--shard``, a
``ClusterServer``) over a synthetic power-law resident graph, fires a
seeded open-loop request trace at it, drains, and reports throughput,
latency percentiles, per-lane utilization, reseeds, and the recompile
counter — then replays every request offline (one at a time, same sampled
trees) and checks parity.
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Optional

import jax
import numpy as np

from repro.core import platform
from repro.data import synthetic as syn
from repro.serve import (ClusterServer, FeatureStore, GNNServer,
                         offline_inference)
from repro.sparse.graph import coo_to_csr
from repro.sparse.plan import ALL_BACKENDS


def build_world(arch: str, n_nodes: int, n_edges: int,
                d_in: Optional[int] = None, seed: int = 0):
    """(cfg, params, indptr, indices, store) on a synthetic resident graph.

    Conv archs take their config's own widths (``d_in``, ``n_classes``);
    ``d_in`` overrides the input width only.
    """
    s, r = syn.powerlaw_graph(n_nodes, n_edges, seed=seed)
    indptr, indices, _ = coo_to_csr(s, r, n_nodes)
    rng = np.random.default_rng(seed + 1)
    key = jax.random.key(seed)
    if arch in ("schnet", "dimenet"):
        mod = __import__(f"repro.models.gnn.{arch}", fromlist=[arch])
        # explicit small configs keep the CPU driver snappy
        if arch == "schnet":
            cfg = mod.SchNetConfig(n_interactions=2, d_hidden=32, n_rbf=16)
        else:
            cfg = mod.DimeNetConfig(n_blocks=1, d_hidden=16, n_bilinear=2,
                                    n_spherical=3)
        params = mod.init_params(key, cfg)
        store = FeatureStore.build(
            n_nodes,
            species=rng.integers(1, 9, n_nodes).astype(np.int32),
            pos=rng.normal(scale=2.0, size=(n_nodes, 3)).astype(np.float32))
        return cfg, params, indptr, indices, store
    mods = {"gcn": ("gcn", "GCNConfig"), "gat": ("gat", "GATConfig"),
            "sage": ("sage", "SAGEConfig"), "gin": ("gin", "GINConfig")}
    name, cfg_name = mods[arch]
    mod = __import__(f"repro.models.gnn.{name}", fromlist=[name])
    cfg = getattr(mod, cfg_name)()
    if d_in is not None:
        cfg = dataclasses.replace(cfg, d_in=d_in)
    params = mod.init_params(key, cfg)
    x = rng.normal(size=(n_nodes, cfg.d_in)).astype(np.float32)
    return cfg, params, indptr, indices, FeatureStore.build(n_nodes, x=x)


def _run_live_mutation(server, params, args):
    """Drive the live-mutation plane (DESIGN.md §16) mid-burst:
    ``--swap-versions`` hot-swaps from perturbed checkpoints (saved to
    ``--ckpt-dir`` or a tempdir) interleaved with a ``--mutate-edges``
    insert/delete stream, each flush parity-proven before install."""
    import contextlib
    import tempfile

    import jax

    from repro.checkpoint import store as ckpt_store
    from repro.serve import GraphStream, hot_swap
    rng = np.random.default_rng(args.seed + 7)
    swaps, stream = [], None
    with contextlib.ExitStack() as stack:
        ckpt_dir = args.ckpt_dir or stack.enter_context(
            tempfile.TemporaryDirectory())
        for k in range(1, args.swap_versions + 1):
            ckpt_store.save(ckpt_dir, k, jax.tree.map(
                lambda a, _k=k: a * (1.0 + 0.01 * _k)
                if np.issubdtype(np.asarray(a).dtype, np.floating) else a,
                params), {"cycle": k})
        if args.mutate_edges:
            stream = GraphStream(server,
                                 max_pending=args.mutation_flush_every,
                                 parity_every=1)
        cycles = max(args.swap_versions, 1 if args.mutate_edges else 0)
        per_cycle = -(-args.mutate_edges // cycles) if cycles else 0
        for k in range(1, cycles + 1):
            if k <= args.swap_versions:
                swaps.append(hot_swap(server, ckpt_dir, step=k))
            for _ in range(min(per_cycle,
                               args.mutate_edges - (k - 1) * per_cycle)):
                stream.insert(int(rng.integers(0, args.nodes)),
                              int(rng.integers(0, args.nodes)))
            if stream is not None and stream.pending:
                stream.flush()
    return swaps, (stream.flushes if stream else [])


def run_cluster(args, fanouts, cfg, params, indptr, indices, store) -> int:
    """The scale-out path: N replica lanes, DRHM-routed (DESIGN.md §11),
    under the fault-tolerant control plane (DESIGN.md §13)."""
    rng = np.random.default_rng(args.seed + 2)
    traces = [rng.integers(0, args.nodes, max(args.seeds_per_request, 1))
              for _ in range(args.requests)]
    mode = "sharded" if args.shard else "replicated"
    chaos = None
    if args.chaos_kill_lane is not None:
        from repro.serve import ChaosInjector, LaneFault
        chaos = ChaosInjector(seed=args.seed, lane_faults=[
            LaneFault(lane=args.chaos_kill_lane, at_round=args.chaos_round)])
    server = ClusterServer(args.arch, cfg, params, indptr, indices, store,
                           n_lanes=args.replicas, mode=mode,
                           placement=args.placement, fanouts=fanouts,
                           backend=args.backend,
                           max_batch_seeds=args.max_batch,
                           max_wait_ms=args.max_wait_ms,
                           n_workers=args.workers, seed=args.seed,
                           chaos=chaos,
                           telemetry_jsonl=args.telemetry_jsonl,
                           stall_timeout=args.stall_timeout,
                           restart_after=args.restart_after,
                           shed_queue_hwm=args.shed_hwm,
                           scale_min_lanes=args.scale_min_lanes,
                           slo=True if args.slo else None,
                           metrics_port=args.metrics_port)
    with server:
        if args.metrics_port is not None:
            print(f"[gnn-serve] metrics exposition at "
                  f"{server._metrics_server.url} "
                  f"(watch live: python -m repro.launch.neurascope "
                  f"{server._metrics_server.url} --live)")
        server.warmup()
        warm_builds = server.steps.builds
        server.reset_stats()
        t0 = time.perf_counter()
        swaps, flushes = [], []
        if args.swap_versions or args.mutate_edges:
            # split the burst around the mutation window so traffic is in
            # flight at every flip AND some requests settle on the final
            # version/epoch (those anchor the replay-parity check below)
            half = len(traces) // 2
            reqs = server.submit_many(traces[:half],
                                      deadline_ms=args.deadline_ms,
                                      cls=args.request_class)
            swaps, flushes = _run_live_mutation(server, params, args)
            reqs += server.submit_many(traces[half:],
                                       deadline_ms=args.deadline_ms,
                                       cls=args.request_class)
        else:
            reqs = server.submit_many(traces, deadline_ms=args.deadline_ms,
                                      cls=args.request_class)
        server.drain()
        dt = time.perf_counter() - t0
        st = server.stats()
        ls = server.lane_stats()
        print(f"[gnn-serve] {args.arch}/{args.backend} {mode} "
              f"x{args.replicas} ({args.placement}): "
              f"{args.requests} requests in {dt:.2f}s "
              f"({args.requests / dt:.1f} req/s)  "
              f"p50={st['p50_ms']:.1f}ms p99={st['p99_ms']:.1f}ms  "
              f"rounds={st['n_rounds']} reseeds={st['reseeds']} "
              f"recompiles(post-warmup)={server.steps.builds - warm_builds}")
        print(f"[gnn-serve] per-lane served={ls['served']} "
              f"spread={ls['served_spread']:.2f}x mean "
              f"states={ls['states']}")
        if swaps or flushes:
            bl = [s.blackout_ms for s in swaps
                  if s.blackout_ms == s.blackout_ms]        # drop NaN
            ins = sum(f.inserted for f in flushes)
            dels = sum(f.deleted for f in flushes)
            parity = all(f.parity_ok for f in flushes)
            drained = server.retired_versions() == []
            print(f"[gnn-serve] live mutation: {len(swaps)} swap(s) -> "
                  f"v{server.params_version}"
                  + (f" blackout_max={max(bl):.1f}ms" if bl else "")
                  + f"  graph +{ins}/-{dels} over {len(flushes)} "
                    f"flush(es) parity={'OK' if parity else 'FAIL'} "
                    f"drained={'OK' if drained else 'FAIL'}")
            if not parity or not drained:
                return 1
        if (st["failed"] or st["timeouts"] or st["lane_deaths"]
                or chaos is not None):
            print(f"[gnn-serve] control plane: deaths={st['lane_deaths']} "
                  f"restores={st['lane_restores']} "
                  f"reroutes={st['reroutes']} retries={st['retries']} "
                  f"timeouts={st['timeouts']} shed={st['shed']} "
                  f"failed={st['failed']}")
            if chaos is None and (st["failed"] or st["timeouts"]):
                return 1
        if args.slo:
            for cls, s in st.get("classes", {}).items():
                print(f"[gnn-serve] slo {cls:<12} n={s['n']:<6} "
                      f"viol={s['violations']:<6} "
                      f"burn(fast/slow)={s['burn_fast']:.2f}/"
                      f"{s['burn_slow']:.2f} p99={s['p99_ms']:.1f}ms"
                      + ("  SHED" if s["shed"] else ""))
        served_once = sum(1 for r in reqs
                          if r.n_settles == 1 and r.error is None)
        settled = sum(1 for r in reqs if r.done)
        if settled != len(reqs):
            print(f"[gnn-serve] DELIVERY VIOLATION: "
                  f"{len(reqs) - settled} request(s) never settled")
            return 1
        if chaos is not None and served_once != len(reqs):
            print(f"[gnn-serve] chaos run lost "
                  f"{len(reqs) - served_once} request(s)")
            return 1
        if not args.skip_offline:
            # replay runs against the LIVE params/graph — requests that
            # settled on a retired version or an older graph epoch are
            # correct-but-unreplayable by design (old versions GC)
            cur_ep = flushes[-1].epoch if flushes else None
            live = [r for r in reqs
                    if r.params_version in (None, server.params_version)
                    and (cur_ep is None or r.graph_epoch == cur_ep)]
            sub = live[:min(32, len(live))]
            if not sub:
                print("[gnn-serve] offline replay skipped (no request "
                      "settled on the live version/epoch)")
            else:
                ref = np.concatenate([server.offline_replay(r)
                                      for r in sub])
                got = np.concatenate([r.result for r in sub])
                dev = float(np.abs(got - ref).max())
                print(f"[gnn-serve] offline replay parity max|Δ| {dev:.2e} "
                      f"({'OK' if dev <= 1e-5 else 'FAIL'}, "
                      f"{len(sub)} live-version request(s))")
                if dev > 1e-5:
                    return 1
    return 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gcn",
                    choices=["gcn", "gat", "sage", "gin", "schnet",
                             "dimenet"])
    ap.add_argument("--backend", default="dense", choices=list(ALL_BACKENDS))
    ap.add_argument("--requests", type=int, default=100)
    ap.add_argument("--nodes", type=int, default=2048)
    ap.add_argument("--edges", type=int, default=8192)
    ap.add_argument("--d-in", type=int, default=None,
                    help="input feature width (default: the arch "
                         "config's own)")
    ap.add_argument("--fanouts", default="5,3")
    ap.add_argument("--max-batch", type=int, default=16)
    ap.add_argument("--max-wait-ms", type=float, default=5.0)
    ap.add_argument("--workers", type=int, default=2)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--skip-offline", action="store_true")
    # scale-out tier (DESIGN.md §11)
    ap.add_argument("--replicas", type=int, default=1,
                    help="serving lanes; >1 stands up the DRHM-routed "
                         "cluster tier (conv archs only)")
    ap.add_argument("--shard", action="store_true",
                    help="shard the resident feature table over the lanes "
                         "(DRHM row shards + halo exchange); needs "
                         "replicas devices")
    ap.add_argument("--placement", default="stacked",
                    choices=["stacked", "mesh"],
                    help="lane compute placement: one vmapped dispatch "
                         "(stacked) or shard_map over a lane mesh")
    ap.add_argument("--seeds-per-request", type=int, default=1)
    # control plane (DESIGN.md §13) — cluster path only
    ap.add_argument("--telemetry-jsonl", default=None, metavar="PATH",
                    help="append per-lane telemetry samples/events as JSON "
                         "lines (the flight recorder neurascope renders)")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-request deadline; queued requests past it "
                         "fail typed (DeadlineExceeded) instead of serving "
                         "stale")
    ap.add_argument("--stall-timeout", type=float, default=1.0,
                    help="seconds of stale lane heartbeat (with queued "
                         "work) before the supervisor declares it dead")
    ap.add_argument("--restart-after", type=float, default=2.0,
                    help="seconds after a lane death before the supervisor "
                         "restarts it through a shadow warm-up")
    ap.add_argument("--shed-hwm", type=float, default=None,
                    help="total queued requests beyond which sustained "
                         "growth sheds new submissions (typed Overloaded); "
                         "default: no shedding")
    ap.add_argument("--scale-min-lanes", type=int, default=None,
                    help="enable telemetry-driven elastic lane parking "
                         "down to this floor (default: disabled)")
    ap.add_argument("--metrics-port", type=int, default=None, metavar="PORT",
                    help="serve the Prometheus-style /metrics exposition "
                         "from a background HTTP thread on this port "
                         "(0 = ephemeral; launch.metrics_server)")
    ap.add_argument("--slo", action="store_true",
                    help="enable per-class SLO burn-rate shedding "
                         "(cluster path; serve.slo defaults: best_effort "
                         "sheds before batch, interactive never)")
    ap.add_argument("--request-class", default="interactive",
                    choices=["interactive", "batch", "best_effort"],
                    help="request class the generated traffic is tagged "
                         "with (cluster path)")
    ap.add_argument("--chaos-kill-lane", type=int, default=None,
                    metavar="LANE",
                    help="chaos: kill this lane mid-stream (deterministic "
                         "fault injection; the run then asserts zero lost "
                         "requests)")
    ap.add_argument("--chaos-round", type=int, default=3,
                    help="dispatch round the --chaos-kill-lane fault "
                         "triggers at")
    ap.add_argument("--swap-versions", type=int, default=0, metavar="N",
                    help="live mutation (cluster path): hot-swap N "
                         "perturbed weight versions mid-burst via the "
                         "checkpoint store, printing per-swap blackout "
                         "and asserting old versions drain")
    ap.add_argument("--ckpt-dir", default=None, metavar="PATH",
                    help="checkpoint directory --swap-versions writes to "
                         "and swaps from (default: a tempdir)")
    ap.add_argument("--mutate-edges", type=int, default=0, metavar="N",
                    help="live mutation (cluster path): stream N random "
                         "edge inserts mid-burst, parity-proven delta "
                         "re-pack at every flush")
    ap.add_argument("--mutation-flush-every", type=int, default=64,
                    metavar="N",
                    help="bounded-staleness window: the mutation stream "
                         "auto-flushes every N buffered edges")
    args = ap.parse_args()
    platform.enable_compile_cache()

    fanouts = tuple(int(f) for f in args.fanouts.split(","))
    cfg, params, indptr, indices, store = build_world(
        args.arch, args.nodes, args.edges, args.d_in, args.seed)
    if args.replicas > 1 or args.shard:
        return run_cluster(args, fanouts, cfg, params, indptr, indices,
                           store)
    rng = np.random.default_rng(args.seed + 2)
    seeds = rng.integers(0, args.nodes, args.requests)

    server = GNNServer(args.arch, cfg, params, indptr, indices, store,
                       fanouts=fanouts, backend=args.backend,
                       max_batch_seeds=args.max_batch,
                       max_wait_ms=args.max_wait_ms, n_workers=args.workers,
                       seed=args.seed)
    with server:
        server.warmup()
        warm_builds = server.steps.builds
        server.reset_stats()
        t0 = time.perf_counter()
        reqs = [server.submit([s]) for s in seeds]
        server.drain()
        dt = time.perf_counter() - t0
        st = server.stats()
        failed = sum(1 for r in reqs if not r.done or r.error is not None)
        print(f"[gnn-serve] {args.arch}/{args.backend}: "
              f"{args.requests} requests in {dt:.2f}s "
              f"({args.requests / dt:.1f} req/s)  "
              f"p50={st['p50_ms']:.1f}ms p95={st['p95_ms']:.1f}ms "
              f"p99={st['p99_ms']:.1f}ms  "
              f"batches={st['n_batches']} buckets={st['bucket_counts']} "
              f"recompiles(post-warmup)={server.steps.builds - warm_builds} "
              f"failed={failed}")
        if failed:
            return 1
        if not args.skip_offline:
            t0 = time.perf_counter()
            ref = np.concatenate(
                [offline_inference(server, r.trees) for r in reqs])
            dt_off = time.perf_counter() - t0
            got = np.concatenate([r.result for r in reqs])
            dev = float(np.abs(got - ref).max())
            print(f"[gnn-serve] offline replay: {dt_off:.2f}s "
                  f"({args.requests / dt_off:.1f} req/s) — "
                  f"batched speedup {dt_off / dt:.1f}×, "
                  f"parity max|Δ| {dev:.2e} ({'OK' if dev <= 1e-5 else 'FAIL'})")
            if dev > 1e-5:
                return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
