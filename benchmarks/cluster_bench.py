"""Cluster serving benchmark — DRHM-routed multi-lane scale-out vs 1 lane.

  PYTHONPATH=src python -m benchmarks.cluster_bench            # table + JSON
  PYTHONPATH=src python -m benchmarks.cluster_bench --check-json BENCH_cluster.json

Off a TPU it runs on the emulated 8-device mesh (the module exports
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` before jax loads, so
run it in its own process — ``benchmarks/run.py --cluster`` does).  On a
TPU it runs over the chips there; with fewer than 8 the sharded cell, which
needs a device per lane, is not run.  Three
records per run (DESIGN.md §11):

* **scaling** — aggregate req/s of ``n_lanes`` replicated lanes vs 1 lane
  on the same request trace (median-of-k bursts; the committed trajectory
  tracks the ~2× round-amortization win; recalibrated from ≥3× when the
  control-plane engine rework made the *single-lane denominator* ~1.7×
  faster while aggregate multi-lane throughput also rose) + ≤1e-5 parity
  of every measured request against single-device offline replay;
* **sharded** — the same trace through DRHM-sharded feature residency with
  halo exchange; must match replicated **bitwise** (the gather is an exact
  row copy);
* **reseed** — an adversarially skewed seed stream (every request routes to
  one lane under the initial γ): the router must reseed and the post-reseed
  per-lane utilization spread must fall under 1.5× mean.

Plus three chaos/SLO records (DESIGN.md §13, §15), also runnable alone via
``--chaos`` (which refreshes just those records inside the committed JSON):

* **chaos_failover** — a scripted lane kill mid-burst: zero lost requests,
  exactly-once settlement, and detection/recovery/restart latencies mined
  from the telemetry JSONL flight recorder, plus the p99 spike ratio vs an
  identical clean run.  The chaos server runs with NeuraScope tracing ON
  and its flight recorder persists at ``artifacts/BENCH_chaos_flight.jsonl``
  — the artifact ``neurascope`` renders and CI uploads on failure; its
  trace records must pass ``verify_traces`` (``trace_contract_ok``);
* **chaos_overload** — every lane wedged under sustained submissions: the
  server must shed with typed ``Overloaded`` backpressure while every
  *accepted* request still settles exactly once at close;
* **slo_shed** — burn-rate shedding precedence (DESIGN.md §15): under a
  serving-but-slow load with unreachable latency targets, best_effort must
  shed before any interactive request, and the scraped ``/metrics``
  exposition must agree with the engine's own summary (per-class p99
  within one histogram bucket, burn-rate gauge within 25%).

``tracing_overhead`` and ``metrics_overhead`` records price the two
observability planes at cluster scale (instrumented vs bare interleaved
closed loops, each gated ≤5%), and the JSON carries a ``kernel_stats``
snapshot of the compute-plane counter registry.

Two live-mutation records (DESIGN.md §16), runnable alone via ``--mutation``
(same partial-refresh semantics as ``--chaos``; ``--long`` stretches both
into the nightly drill):

* **mutation_drill** — ≥3 consecutive weight hot-swaps under continuous
  load, with a streaming graph mutation (parity-proven delta re-pack +
  atomic CSR swap) between cycles: ``swap_blackout_ms`` per cycle (first
  post-flip dispatch minus the flip — the router-never-stalls record), zero
  lost requests, exactly-once settlement, every request stamped with
  exactly one weight version, all old versions drained + GCed;
* **delta_repack** — incremental re-pack (dirty blocks only) vs cold
  ``pack_dedup_chunks`` over the same mutated graph across several epochs:
  ``delta_repack_speedup`` (gated ≥ 3×) at ``mutation_parity_ok`` (every
  epoch bitwise vs the cold pack).
"""
from __future__ import annotations

import os
import sys

if "jax" not in sys.modules:          # must precede the first jax import
    os.environ.setdefault(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import argparse
import json
import time

import numpy as np

DEFAULT_JSON = "BENCH_cluster.json"
FLIGHT_JSONL = os.path.join("artifacts", "BENCH_chaos_flight.jsonl")
MUTATION_JSONL = os.path.join("artifacts", "BENCH_mutation_flight.jsonl")
N_LANES = 8
MAX_TRACING_OVERHEAD_PCT = 5.0
MAX_METRICS_OVERHEAD_PCT = 5.0
MIN_REPACK_SPEEDUP = 3.0


def _one_burst(server, traces) -> float:
    server.reset_stats()
    t0 = time.perf_counter()
    server.submit_many(traces)
    server.drain(timeout=600)
    return len(traces) / (time.perf_counter() - t0)


def _world(arch, backend, n_nodes, n_edges, d_in, seed):
    from repro.launch.gnn_serve import build_world
    return build_world(arch, n_nodes, n_edges, d_in, seed)


def bench_scaling(arch="gcn", backend="dense", *, n_nodes=2048, n_edges=8192,
                  d_in=16, fanouts=(5, 3), max_batch=8, seeds_per_request=4,
                  n_requests=768, reps=10, n_offline=24, seed=0) -> dict:
    from repro.serve import ClusterServer
    cfg, params, indptr, indices, store = _world(arch, backend, n_nodes,
                                                 n_edges, d_in, seed)
    rng = np.random.default_rng(seed + 2)
    traces = [rng.integers(0, n_nodes, seeds_per_request)
              for _ in range(n_requests)]

    # one config at a time (a second resident server adds GC/thread noise);
    # best-of-k bursts per config because shared-runner noise is one-sided
    # — preemption episodes only ever *slow* a burst — so the max over a
    # few seconds of bursts is the honest capability estimate for both
    import gc
    all_rates = {}
    parity = 0.0
    for lanes in (1, N_LANES):
        srv = ClusterServer(arch, cfg, params, indptr, indices, store,
                            n_lanes=lanes, mode="replicated",
                            placement="stacked", fanouts=fanouts,
                            backend=backend, max_batch_seeds=max_batch,
                            max_wait_ms=2.0, seed=seed)
        with srv:
            srv.warmup()
            for r in srv.submit_many(traces[:64]):
                r.wait(600)
            all_rates[lanes] = [_one_burst(srv, traces)
                                for _ in range(reps)]
            if lanes == N_LANES:
                # parity of a final burst vs single-device offline replay
                reqs = srv.submit_many(traces[:n_offline])
                srv.drain(timeout=600)
                for r in reqs:
                    ref = srv.offline_replay(r)
                    parity = max(parity,
                                 float(np.abs(r.result - ref).max()))
                recompiles = srv.steps.builds
                srv.warmup()     # proves the ladder stayed warm: no builds
                recompiles = srv.steps.builds - recompiles
        gc.collect()
    rates = {lanes: max(rs) for lanes, rs in all_rates.items()}
    return {
        "kind": "scaling", "arch": arch, "backend": backend,
        "n_nodes": n_nodes, "n_edges": n_edges, "d_in": d_in,
        "fanouts": list(fanouts),
        "n_lanes": N_LANES, "max_batch_seeds": max_batch,
        "seeds_per_request": seeds_per_request, "n_requests": n_requests,
        "reqs_per_s_1lane": round(rates[1], 2),
        "reqs_per_s": round(rates[N_LANES], 2),
        "scaling_vs_1lane": round(rates[N_LANES] / rates[1], 2),
        "burst_rates_1lane": [round(r, 1) for r in all_rates[1]],
        "burst_rates": [round(r, 1) for r in all_rates[N_LANES]],
        "parity_max_dev_vs_offline": parity,
        "recompiles_steady_state": recompiles,
    }


def bench_sharded(arch="gcn", backend="dense", *, n_nodes=2048, n_edges=8192,
                  d_in=32, fanouts=(5, 3), max_batch=8, seeds_per_request=4,
                  n_requests=192, seed=0) -> dict:
    from repro.serve import ClusterServer
    cfg, params, indptr, indices, store = _world(arch, backend, n_nodes,
                                                 n_edges, d_in, seed)
    rng = np.random.default_rng(seed + 2)
    traces = [rng.integers(0, n_nodes, seeds_per_request)
              for _ in range(n_requests)]
    results = {}
    for mode in ("replicated", "sharded"):
        srv = ClusterServer(arch, cfg, params, indptr, indices, store,
                            n_lanes=N_LANES, mode=mode, placement="stacked",
                            fanouts=fanouts, backend=backend,
                            max_batch_seeds=max_batch, seed=seed)
        with srv:
            srv.warmup()
            reqs = srv.submit_many(traces)
            srv.drain(timeout=600)
            # fresh servers assign the same rids → identical trees; only
            # the feature residency (and its halo transport) differs
            results[mode] = np.concatenate([r.result for r in reqs])
    dev = float(np.abs(results["sharded"] - results["replicated"]).max())
    return {
        "kind": "sharded_parity", "arch": arch, "backend": backend,
        "n_nodes": n_nodes, "n_edges": n_edges, "n_lanes": N_LANES,
        "n_requests": n_requests,
        "bitwise_match": bool(np.array_equal(results["sharded"],
                                             results["replicated"])),
        "max_dev_sharded_vs_replicated": dev,
    }


def bench_reseed(arch="gcn", backend="dense", *, n_nodes=2048, n_edges=8192,
                 d_in=32, fanouts=(5, 3), max_batch=8, n_requests=512,
                 seed=0) -> dict:
    from repro.serve import ClusterServer, DRHMRouter, utilization_spread
    cfg, params, indptr, indices, store = _world(arch, backend, n_nodes,
                                                 n_edges, d_in, seed)
    # adversarial stream: every seed routes to one lane under the initial γ
    probe = DRHMRouter(N_LANES, seed=seed)
    hot = [i for i in range(n_nodes) if probe.lane_of([i]) == 0]
    rng = np.random.default_rng(seed + 3)
    traces = [[int(rng.choice(hot))] for _ in range(n_requests)]

    srv = ClusterServer(arch, cfg, params, indptr, indices, store,
                        n_lanes=N_LANES, mode="replicated",
                        placement="stacked", fanouts=fanouts,
                        backend=backend, max_batch_seeds=max_batch,
                        seed=seed)
    with srv:
        srv.warmup()
        srv.submit_many(traces)
        srv.drain(timeout=600)
        info = srv.router.info()
    pre = np.asarray(info["routed_per_epoch"][0], np.float64)
    post = np.sum([np.asarray(c, np.float64)
                   for c in info["routed_per_epoch"][1:]], axis=0)
    return {
        "kind": "reseed", "arch": arch, "backend": backend,
        "n_lanes": N_LANES, "n_requests": n_requests,
        "reseeds": int(info["reseeds"]),
        "pre_reseed_spread": round(utilization_spread(pre), 3),
        "post_reseed_spread": round(utilization_spread(post), 3),
        "post_reseed_requests": int(post.sum()),
    }


def _mine_jsonl(path: str):
    """Parse the telemetry flight recorder: (event records, sample count,
    trace records) — the trace records feed ``tracing.verify_traces`` so
    the chaos gate also proves the observability contract held under
    faults (exactly-one-terminal span trees, no duplicate trace ids)."""
    events, n_samples, traces = [], 0, []
    with open(path) as f:
        for line in f:
            try:
                rec = json.loads(line)
            except ValueError:
                continue
            if rec.get("kind") == "event":
                events.append(rec)
            elif rec.get("kind") == "sample":
                n_samples += 1
            elif rec.get("kind") == "trace":
                traces.append(rec)
    return events, n_samples, traces


def bench_chaos_failover(arch="gcn", backend="dense", *, n_nodes=2048,
                         n_edges=8192, d_in=16, fanouts=(5, 3), max_batch=8,
                         seeds_per_request=4, n_requests=384, kill_lane=2,
                         at_round=3, seed=0,
                         jsonl_path=FLIGHT_JSONL) -> dict:
    """Scripted lane kill mid-burst: the supervisor must detect the death,
    rebalance the survivors, reroute the stranded queue, and auto-restart
    the lane — zero lost requests, exactly-once settlement.  Latencies are
    mined from the telemetry JSONL (the flight recorder an operator would
    have), not from in-process state.  The chaos server traces every
    request; the recorder persists at ``jsonl_path`` so ``neurascope`` can
    render the run and CI can archive it on failure."""
    from repro.serve import ChaosInjector, ClusterServer, LaneFault
    cfg, params, indptr, indices, store = _world(arch, backend, n_nodes,
                                                 n_edges, d_in, seed)
    rng = np.random.default_rng(seed + 2)
    traces = [rng.integers(0, n_nodes, seeds_per_request)
              for _ in range(n_requests)]

    def build(chaos, jsonl=None):
        return ClusterServer(arch, cfg, params, indptr, indices, store,
                             n_lanes=N_LANES, mode="replicated",
                             placement="stacked", fanouts=fanouts,
                             backend=backend, max_batch_seeds=max_batch,
                             max_wait_ms=2.0, seed=seed, chaos=chaos,
                             telemetry_jsonl=jsonl, telemetry_interval=0.02,
                             tracing=jsonl is not None,
                             stall_timeout=0.15, restart_after=0.4)

    # clean twin on the same trace: the baseline the p99 spike is over
    srv = build(None)
    with srv:
        srv.warmup()
        rate_clean = _one_burst(srv, traces)
        clean_p99 = srv.stats()["p99_ms"]

    chaos = ChaosInjector(seed=seed, lane_faults=[
        LaneFault(lane=kill_lane, at_round=at_round)])
    # the flight recorder persists (intentionally — it is the run's
    # post-mortem artifact, uploaded by CI and rendered by neurascope)
    if os.path.dirname(jsonl_path):
        os.makedirs(os.path.dirname(jsonl_path), exist_ok=True)
    srv = build(chaos, jsonl_path)
    with srv:
        srv.warmup()
        srv.reset_stats()
        t0 = time.perf_counter()
        reqs = srv.submit_many(traces)
        srv.drain(timeout=600)
        dt = time.perf_counter() - t0
        # the restart may land after the burst drains — wait it out
        deadline = time.monotonic() + 30
        while (srv.router.n_active < N_LANES
               and time.monotonic() < deadline):
            time.sleep(0.02)
        restored = srv.router.n_active == N_LANES
        st = srv.stats()
        trig = chaos.triggered_wall_times()
        trigger_rel = (min(trig.values()) - srv.telemetry.t0
                       if trig else None)
    events, n_samples, trace_recs = _mine_jsonl(jsonl_path)
    from repro.serve import verify_traces
    trace_probs = verify_traces(trace_recs)
    for p in trace_probs[:5]:
        print(f"  trace contract violation: {p}")

    lost = sum(1 for r in reqs if not r.done or r.error is not None)
    dup = sum(1 for r in reqs if r.n_settles != 1)
    t_dead = next((e["t"] for e in events if e["event"] == "lane_dead"),
                  None)
    t_reb = next((e["t"] for e in events
                  if e["event"] == "rebalance"
                  and t_dead is not None and e["t"] >= t_dead), None)
    t_rest = next((e["t"] for e in events
                   if e["event"] == "lane_restored"), None)

    def _since_trigger(t):
        if t is None or trigger_rel is None:
            return -1.0
        return round(t - trigger_rel, 3)

    chaos_p99 = st["p99_ms"]
    return {
        "kind": "chaos_failover", "arch": arch, "backend": backend,
        "n_nodes": n_nodes, "n_edges": n_edges, "d_in": d_in,
        "fanouts": list(fanouts), "n_lanes": N_LANES,
        "max_batch_seeds": max_batch,
        "seeds_per_request": seeds_per_request, "n_requests": n_requests,
        "killed_lane": kill_lane, "kill_at_round": at_round,
        "lost_requests": lost, "duplicate_results": dup,
        "zero_lost_ok": lost == 0, "exactly_once_ok": dup == 0,
        "lane_deaths": st["lane_deaths"],
        "lane_restores": st["lane_restores"], "lane_restored_ok": restored,
        "reroutes": st["reroutes"], "retries": st["retries"],
        "detection_s": _since_trigger(t_dead),
        "recovery_s": _since_trigger(t_reb),
        "restart_s": _since_trigger(t_rest),
        "clean_p99_ms": round(clean_p99, 2),
        "chaos_p99_ms": round(chaos_p99, 2),
        "p99_spike_x": (round(chaos_p99 / clean_p99, 2)
                        if clean_p99 > 0 else -1.0),
        "reqs_per_s_clean": round(rate_clean, 2),
        "reqs_per_s_chaos": round(n_requests / dt, 2),
        "flight_recorder_events": len(events),
        "flight_recorder_samples": n_samples,
        "flight_recorder_ok": len(events) > 0 and n_samples > 0,
        "flight_recorder_path": jsonl_path,
        "trace_records": len(trace_recs),
        "trace_violations": len(trace_probs),
        "trace_contract_ok": bool(trace_recs) and not trace_probs,
    }


def bench_chaos_overload(arch="gcn", backend="dense", *, n_nodes=2048,
                         n_edges=8192, d_in=16, fanouts=(5, 3), max_batch=8,
                         queue_hwm=24, n_requests=96, n_extra=64,
                         seed=0) -> dict:
    """Every lane wedged (unacknowledged kill faults, supervision timeout
    parked at 60 s) under sustained submissions: the queue only grows, so
    after the sustain window new work must be shed with typed ``Overloaded``
    backpressure — while every accepted request still settles exactly once
    when the close flush serves the backlog."""
    from repro.serve import (ChaosInjector, ClusterServer, LaneFault,
                             Overloaded)
    cfg, params, indptr, indices, store = _world(arch, backend, n_nodes,
                                                 n_edges, d_in, seed)
    rng = np.random.default_rng(seed + 2)
    traces = [rng.integers(0, n_nodes, 1) for _ in range(n_requests)]
    chaos = ChaosInjector(seed=seed, lane_faults=[
        LaneFault(lane=i) for i in range(N_LANES)])
    srv = ClusterServer(arch, cfg, params, indptr, indices, store,
                        n_lanes=N_LANES, mode="replicated",
                        placement="stacked", fanouts=fanouts,
                        backend=backend, max_batch_seeds=max_batch,
                        seed=seed, chaos=chaos, stall_timeout=60.0,
                        telemetry_interval=0.02, shed_queue_hwm=queue_hwm,
                        shed_sustain_ticks=1)
    accepted = srv.submit_many(traces)
    deadline = time.monotonic() + 30
    while not srv._shedding and time.monotonic() < deadline:
        time.sleep(0.01)
    shed, typed_ok = 0, bool(srv._shedding)
    for i in range(n_extra):
        try:
            accepted.append(srv.submit([i % n_nodes]))
        except Overloaded as e:
            shed += 1
            typed_ok = typed_ok and e.retry_after_s > 0
        except Exception:                     # anything untyped fails the gate
            typed_ok = False
    srv.close()                # shutdown flush serves the wedged backlog
    lost = sum(1 for r in accepted if not r.done or r.error is not None)
    dup = sum(1 for r in accepted if r.n_settles != 1)
    attempted = n_requests + n_extra
    return {
        "kind": "chaos_overload", "arch": arch, "backend": backend,
        "n_nodes": n_nodes, "n_lanes": N_LANES, "n_requests": attempted,
        "queue_hwm": queue_hwm, "accepted": len(accepted),
        "shed_submissions": shed, "shed_rate": round(shed / attempted, 3),
        "shed_typed_ok": bool(typed_ok and shed >= 1),
        "lost_accepted": lost, "duplicate_results": dup,
        "accepted_served_ok": lost == 0 and dup == 0,
    }


def bench_slo_shed(arch="gcn", backend="dense", *, n_nodes=2048,
                   n_edges=8192, d_in=16, fanouts=(5, 3), max_batch=8,
                   waves=40, seed=0) -> dict:
    """Per-class SLO burn-rate shedding under a serving-but-slow load:
    targets far below the achievable latency drive every class's burn rate
    over threshold, and the engine must shed **best_effort before any
    interactive request** (``SHED_ORDER``; interactive is never SLO-shed —
    the queue-HWM backstop stays class-blind).  The record also proves the
    exposition endpoint is truthful: the scraped per-class p99 must land
    within one histogram bucket of ``stats()['classes']`` and the exported
    burn-rate gauge must track the engine's own summary.

    Windows are long relative to the run (fast 5 s / slow 30 s) so the
    burn rate is stable between the scrape and the summary read — the
    whole burst stays inside both windows."""
    import urllib.request
    from repro.serve import ClassSLO, ClusterServer, Overloaded
    from repro.serve.metrics import (bucket_index,
                                     histogram_counts_from_samples,
                                     parse_exposition, quantile_from_counts)
    cfg, params, indptr, indices, store = _world(arch, backend, n_nodes,
                                                 n_edges, d_in, seed)
    rng = np.random.default_rng(seed + 7)
    slos = [ClassSLO("interactive", 1.0, 0.01),
            ClassSLO("batch", 1.0, 0.05),
            ClassSLO("best_effort", 1.0, 0.20)]
    srv = ClusterServer(arch, cfg, params, indptr, indices, store,
                        n_lanes=2, mode="replicated", placement="stacked",
                        fanouts=fanouts, backend=backend,
                        max_batch_seeds=max_batch, max_wait_ms=2.0,
                        seed=seed, telemetry_interval=0.02,
                        slo=slos, slo_fast_window=5.0, slo_slow_window=30.0,
                        slo_burn_threshold=2.0, slo_sustain_ticks=1,
                        slo_recover_ticks=10**6, metrics_port=0)
    shed = {"interactive": 0, "batch": 0, "best_effort": 0}
    served = {"interactive": 0, "best_effort": 0}
    served_int_post_shed = 0
    with srv:
        srv.warmup()
        for _ in range(waves):
            pend = []
            for cls in ("interactive", "best_effort", "interactive",
                        "best_effort"):
                try:
                    pend.append(srv.submit(
                        rng.integers(0, n_nodes, 2), cls=cls))
                    served[cls] += 1
                    if cls == "interactive" and shed["best_effort"]:
                        served_int_post_shed += 1
                except Overloaded as e:
                    shed[e.cls or "interactive"] += 1
            for r in pend:
                r.wait_done(timeout=60)
            if shed["best_effort"] >= 8 and served_int_post_shed >= 8:
                break
        # the hub keeps ticking; give it one interval so the burn gauges
        # include everything observed above, then scrape + summarize
        time.sleep(0.06)
        with urllib.request.urlopen(srv.stats()["metrics_url"],
                                    timeout=10) as resp:
            fams = parse_exposition(resp.read().decode())
        summary = srv.slo.summary()
        first_shed = next((e for e in srv.telemetry.events
                           if e.get("event") == "shed_class"
                           and e.get("on")), None)
    hist = fams.get("neurachip_request_latency_seconds",
                    {}).get("samples", [])
    burn = {}
    for _n, labels, v, _ex in fams.get("neurachip_slo_burn_rate",
                                       {}).get("samples", []):
        if labels.get("window") == "fast":
            burn[labels.get("class")] = v
    p99_dist, burn_dev = -1, 0.0
    for cls, s in summary.items():
        if not s["n"]:
            continue
        counts = histogram_counts_from_samples(hist, {"class": cls})
        scraped_i = quantile_from_counts(counts, 0.99)
        exact_i = bucket_index(s["p99_ms"] / 1e3)
        p99_dist = max(p99_dist, abs(scraped_i - exact_i))
        ref = max(abs(s["burn_fast"]), 1.0)
        burn_dev = max(burn_dev,
                       abs(burn.get(cls, 0.0) - s["burn_fast"]) / ref)
    ordering_ok = (shed["best_effort"] >= 1 and shed["interactive"] == 0
                   and first_shed is not None
                   and first_shed["cls"] == "best_effort")
    export_ok = 0 <= p99_dist <= 1 and burn_dev <= 0.25
    return {
        "kind": "slo_shed", "arch": arch, "backend": backend,
        "n_nodes": n_nodes, "n_lanes": 2,
        "submitted_interactive": served["interactive"],
        "submitted_best_effort": served["best_effort"],
        "shed_interactive": shed["interactive"],
        "shed_batch": shed["batch"],
        "shed_best_effort": shed["best_effort"],
        "first_shed_class": first_shed["cls"] if first_shed else None,
        "interactive_served_post_shed": served_int_post_shed,
        "burn_fast_best_effort": round(
            summary["best_effort"]["burn_fast"], 2),
        "scrape_p99_bucket_dist_max": int(p99_dist),
        "scrape_burn_rel_dev_max": round(burn_dev, 4),
        "slo_shed_ordering_ok": bool(ordering_ok),
        "slo_export_match_ok": bool(export_ok),
    }


def bench_tracing_overhead(arch="gcn", backend="dense", *, n_nodes=2048,
                           n_edges=8192, d_in=16, fanouts=(5, 3),
                           max_batch=8, seeds_per_request=4, n_requests=192,
                           reps=5, seed=0) -> dict:
    """NeuraScope budget at cluster scale: traced vs untraced closed loop
    (submit → wait) through the full routed path — route, sample,
    queue_wait, bucket_pack, dispatch, settle spans all on the measured
    path.  Closed-loop with the production ``max_wait_ms`` is the *stable*
    regime on a shared runner (throughput is clocked by batch formation,
    so run-to-run drift is ~1% where open-loop bursts swing ±15%), and
    the 5% budget against that clock still bounds any structural
    per-request tracing cost.  Both servers stay live and the reps
    interleave (off, on, off, on, …) so a slow stretch hits both arms;
    best-of-``reps`` per arm cancels the one-sided noise (the
    ``bench_scaling`` argument).  The gated invariant is
    ``tracing_overhead_ok`` ≤ ``MAX_TRACING_OVERHEAD_PCT``."""
    import contextlib
    import gc
    from repro.serve import ClusterServer
    cfg, params, indptr, indices, store = _world(arch, backend, n_nodes,
                                                 n_edges, d_in, seed)
    rng = np.random.default_rng(seed + 5)
    traces = [rng.integers(0, n_nodes, seeds_per_request)
              for _ in range(n_requests)]

    def closed_loop(srv) -> float:
        t0 = time.perf_counter()
        for s in traces:
            srv.submit(s).wait(600)
        return len(traces) / (time.perf_counter() - t0)

    rates = {False: 0.0, True: 0.0}
    with contextlib.ExitStack() as stack:
        servers = {}
        for tracing in (False, True):
            srv = ClusterServer(arch, cfg, params, indptr, indices, store,
                                n_lanes=N_LANES, mode="replicated",
                                placement="stacked", fanouts=fanouts,
                                backend=backend, max_batch_seeds=max_batch,
                                max_wait_ms=2.0, seed=seed, tracing=tracing)
            stack.enter_context(srv)
            srv.warmup()
            for s in traces[:16]:
                srv.submit(s).wait(600)
            servers[tracing] = srv
        for _ in range(reps):
            for tracing in (False, True):
                rates[tracing] = max(rates[tracing],
                                     closed_loop(servers[tracing]))
    gc.collect()
    overhead_pct = 100.0 * (1.0 - rates[True] / rates[False])
    return {
        "kind": "tracing_overhead", "arch": arch, "backend": backend,
        "n_lanes": N_LANES, "n_requests": n_requests,
        "seeds_per_request": seeds_per_request,
        "untraced_reqs_per_s": round(rates[False], 2),
        "traced_reqs_per_s": round(rates[True], 2),
        "tracing_overhead_pct": round(overhead_pct, 2),
        "tracing_overhead_ok": bool(overhead_pct
                                    <= MAX_TRACING_OVERHEAD_PCT),
    }


def bench_metrics_overhead(arch="gcn", backend="dense", *, n_nodes=2048,
                           n_edges=8192, d_in=16, fanouts=(5, 3),
                           max_batch=8, seeds_per_request=4, n_requests=192,
                           reps=5, seed=0) -> dict:
    """Metrics-plane budget at cluster scale: the fully instrumented server
    (registry + per-class latency histograms + SLO engine + exposition
    endpoint live and scrapable) vs a bare one, same interleaved
    best-of-``reps`` closed loop as ``bench_tracing_overhead``.  Gated at
    ``metrics_overhead_ok`` ≤ ``MAX_METRICS_OVERHEAD_PCT`` — streaming
    instruments must be cheap enough to leave on in production."""
    import contextlib
    import gc
    from repro.serve import ClusterServer
    cfg, params, indptr, indices, store = _world(arch, backend, n_nodes,
                                                 n_edges, d_in, seed)
    rng = np.random.default_rng(seed + 5)
    traces = [rng.integers(0, n_nodes, seeds_per_request)
              for _ in range(n_requests)]

    def closed_loop(srv) -> float:
        t0 = time.perf_counter()
        for s in traces:
            srv.submit(s).wait(600)
        return len(traces) / (time.perf_counter() - t0)

    rates = {False: 0.0, True: 0.0}
    with contextlib.ExitStack() as stack:
        servers = {}
        for metrics in (False, True):
            srv = ClusterServer(arch, cfg, params, indptr, indices, store,
                                n_lanes=N_LANES, mode="replicated",
                                placement="stacked", fanouts=fanouts,
                                backend=backend, max_batch_seeds=max_batch,
                                max_wait_ms=2.0, seed=seed,
                                slo=metrics or None,
                                metrics_port=0 if metrics else None)
            stack.enter_context(srv)
            srv.warmup()
            for s in traces[:16]:
                srv.submit(s).wait(600)
            servers[metrics] = srv
        for _ in range(reps):
            for metrics in (False, True):
                rates[metrics] = max(rates[metrics],
                                     closed_loop(servers[metrics]))
    gc.collect()
    overhead_pct = 100.0 * (1.0 - rates[True] / rates[False])
    return {
        "kind": "metrics_overhead", "arch": arch, "backend": backend,
        "n_lanes": N_LANES, "n_requests": n_requests,
        "seeds_per_request": seeds_per_request,
        "bare_reqs_per_s": round(rates[False], 2),
        "metered_reqs_per_s": round(rates[True], 2),
        "metrics_overhead_pct": round(overhead_pct, 2),
        "metrics_overhead_ok": bool(overhead_pct
                                    <= MAX_METRICS_OVERHEAD_PCT),
    }


def bench_mutation_drill(arch="gcn", backend="dense", *, n_nodes=2048,
                         n_edges=8192, d_in=16, fanouts=(5, 3), max_batch=8,
                         seeds_per_request=4, swap_cycles=3,
                         reqs_per_cycle=64, stream_edges=96,
                         seed=0, jsonl_path=MUTATION_JSONL) -> dict:
    """The live-mutation drill: ≥3 consecutive checkpoint hot-swaps under
    continuous load, with a parity-proven streaming graph mutation between
    cycles.  ``swap_blackout_ms`` is first-dispatch-after-flip minus the
    flip — the price of an epoch boundary as the router sees it.  The
    delivery contract is the chaos one: zero lost, exactly-once, and every
    request stamped with exactly one weight version; every old version must
    drain and GC before the drill ends."""
    import tempfile

    import jax

    from repro.checkpoint import store as ckpt_store
    from repro.serve import ClusterServer, GraphStream, hot_swap
    from repro.serve.live import _csr_to_coo
    cfg, params, indptr, indices, store = _world(arch, backend, n_nodes,
                                                 n_edges, d_in, seed)
    rng = np.random.default_rng(seed + 2)
    s0, r0 = _csr_to_coo(indptr, indices)

    def _perturb(k):
        return jax.tree.map(
            lambda a: a * (1.0 + 0.01 * k)
            if np.issubdtype(np.asarray(a).dtype, np.floating) else a,
            params)

    def _load(srv, n):
        return srv.submit_many(
            [rng.integers(0, n_nodes, seeds_per_request) for _ in range(n)])

    # the flight recorder persists the swap/flush event stream — the
    # post-mortem artifact the nightly drill uploads on failure
    if os.path.dirname(jsonl_path):
        os.makedirs(os.path.dirname(jsonl_path), exist_ok=True)
    open(jsonl_path, "w").close()       # fresh recorder per drill
    srv = ClusterServer(arch, cfg, params, indptr, indices, store,
                        n_lanes=N_LANES, mode="replicated",
                        placement="stacked", fanouts=fanouts,
                        backend=backend, max_batch_seeds=max_batch,
                        max_wait_ms=2.0, seed=seed,
                        telemetry_jsonl=jsonl_path,
                        telemetry_interval=0.02)
    blackouts, flushes, all_reqs = [], [], []
    graph_parity = True
    del_cursor = 0
    with srv:
        srv.warmup()
        with tempfile.TemporaryDirectory() as ckpt_dir:
            for k in range(1, swap_cycles + 1):
                ckpt_store.save(ckpt_dir, k, _perturb(k), {"cycle": k})
            gs = GraphStream(srv, max_pending=4 * stream_edges,
                             parity_every=1)
            t0 = time.perf_counter()
            for k in range(1, swap_cycles + 1):
                all_reqs += _load(srv, reqs_per_cycle)     # in flight at flip
                rep = hot_swap(srv, ckpt_dir, step=k, drain_timeout=120.0)
                blackouts.append(rep.blackout_ms)
                # streaming mutation between swap cycles, under the same load
                for _ in range(stream_edges):
                    gs.insert(int(rng.integers(0, n_nodes)),
                              int(rng.integers(0, n_nodes)))
                for _ in range(stream_edges // 4):
                    gs.delete(int(s0[del_cursor]), int(r0[del_cursor]))
                    del_cursor += 1
                frep = gs.flush()
                graph_parity = graph_parity and frep.parity_ok
                flushes.append(frep)
                all_reqs += _load(srv, reqs_per_cycle)
            srv.drain(timeout=600)
            dt = time.perf_counter() - t0
            retired = srv.retired_versions()
            final_version = srv.params_version

    events, n_samples, _ = _mine_jsonl(jsonl_path)
    swap_events = sum(1 for e in events if e["event"] == "params_swap")
    lost = sum(1 for r in all_reqs if not r.done or r.error is not None)
    dup = sum(1 for r in all_reqs if r.n_settles != 1)
    one_version = all(r.params_version is not None
                      and 0 <= r.params_version <= swap_cycles
                      for r in all_reqs)
    finite = [b for b in blackouts if b == b]       # drop NaN (idle flips)
    return {
        "kind": "mutation_drill", "arch": arch, "backend": backend,
        "n_nodes": n_nodes, "n_edges": n_edges, "d_in": d_in,
        "fanouts": list(fanouts), "n_lanes": N_LANES,
        "seeds_per_request": seeds_per_request,
        "n_requests": len(all_reqs),
        "swap_cycles": swap_cycles,
        "swap_blackout_ms": (round(float(np.median(finite)), 3)
                             if finite else -1.0),
        "swap_blackout_ms_max": (round(float(np.max(finite)), 3)
                                 if finite else -1.0),
        "swap_blackouts_measured": len(finite),
        "lost_requests": lost, "duplicate_results": dup,
        "swap_zero_lost_ok": lost == 0,
        "swap_exactly_once_ok": dup == 0,
        "swap_one_version_ok": bool(one_version),
        "swap_drained_ok": retired == [] and final_version == swap_cycles,
        "graph_flushes": len(flushes),
        "edges_inserted": int(sum(f.inserted for f in flushes)),
        "edges_deleted": int(sum(f.deleted for f in flushes)),
        "graph_epochs_served": len({r.graph_epoch for r in all_reqs}),
        "graph_parity_ok": bool(graph_parity),
        "reqs_per_s_under_mutation": round(len(all_reqs) / dt, 2),
        "flight_recorder_events": len(events),
        "flight_recorder_samples": n_samples,
        "flight_recorder_swaps": swap_events,
        "flight_recorder_ok": swap_events >= swap_cycles and n_samples > 0,
        "flight_recorder_path": jsonl_path,
    }


def bench_delta_repack(*, n_nodes=4096, n_edges=60_000, batch=48,
                       epochs=6, seed=0) -> dict:
    """Incremental dedup-chunk re-pack (dirty blocks only) vs a cold
    ``pack_dedup_chunks`` of the same mutated graph, host-side, over
    ``epochs`` small delta batches on a large graph.  Parity is proven per
    epoch (``chunks_match`` bitwise on both layouts) and once at the end
    through the full plan — the speedup only counts if it is exact."""
    from repro.sparse.delta import (DeltaGraphError, DeltaGraphState,
                                    chunks_match, plans_match)
    rng = np.random.default_rng(seed)
    d = DeltaGraphState(rng.integers(0, n_nodes, n_edges),
                        rng.integers(0, n_nodes, n_edges), n_nodes)
    inc_s = cold_s = 0.0
    parity = True
    dirty = clean = 0
    for _ in range(epochs):
        for _ in range(batch):
            d.insert_edge(int(rng.integers(0, n_nodes)),
                          int(rng.integers(0, n_nodes)))
        for _ in range(batch // 3):
            k = int(rng.integers(0, d._s.size))
            try:
                d.delete_edge(int(d._s[k]), int(d._r[k]))
            except DeltaGraphError:
                pass               # every copy of that edge already booked
        res = d.flush()
        dirty += res.dirty_blocks
        clean += res.clean_blocks
        t0 = time.perf_counter()
        inc = d.repack()
        inc_s += time.perf_counter() - t0
        t0 = time.perf_counter()
        cold = d.cold_repack()
        cold_s += time.perf_counter() - t0
        for a, b in zip(inc, cold):
            ok, _ = chunks_match(a, b)
            parity = parity and ok
    ok, _ = plans_match(d.plan(), d.cold_plan())
    parity = parity and ok
    return {
        "kind": "delta_repack", "n_nodes": n_nodes, "n_edges": n_edges,
        "epochs": epochs, "batch_inserts": batch,
        "batch_deletes": batch // 3,
        "dirty_blocks": int(dirty), "clean_blocks": int(clean),
        "incremental_repack_s": round(inc_s, 4),
        "cold_repack_s": round(cold_s, 4),
        "delta_repack_speedup": (round(cold_s / inc_s, 2)
                                 if inc_s > 0 else -1.0),
        "mutation_parity_ok": bool(parity),
    }


def collect_mutation(long: bool = False) -> list:
    records = []
    r = bench_mutation_drill(swap_cycles=6 if long else 3,
                             reqs_per_cycle=96 if long else 64,
                             stream_edges=256 if long else 96)
    print(f"  mutation: {r['swap_cycles']} swaps, blackout "
          f"{r['swap_blackout_ms']:.1f}ms (max "
          f"{r['swap_blackout_ms_max']:.1f}ms)  lost={r['lost_requests']} "
          f"dup={r['duplicate_results']} one_version="
          f"{r['swap_one_version_ok']} drained={r['swap_drained_ok']}  "
          f"graph +{r['edges_inserted']}/-{r['edges_deleted']} over "
          f"{r['graph_flushes']} flushes parity={r['graph_parity_ok']}")
    records.append(r)
    r = bench_delta_repack(epochs=12 if long else 6)
    n_blocks = r["dirty_blocks"] + r["clean_blocks"]
    print(f"  repack  : cold {r['cold_repack_s'] * 1e3:8.1f}ms vs "
          f"incremental {r['incremental_repack_s'] * 1e3:8.1f}ms -> "
          f"{r['delta_repack_speedup']:.1f}x  "
          f"parity={r['mutation_parity_ok']} "
          f"(dirty {r['dirty_blocks']}/{n_blocks} blocks)")
    records.append(r)
    return records


def collect_chaos() -> list:
    records = []
    r = bench_chaos_failover()
    print(f"  failover: lost={r['lost_requests']} "
          f"dup={r['duplicate_results']} deaths={r['lane_deaths']} "
          f"reroutes={r['reroutes']} detect={r['detection_s']:.3f}s "
          f"recover={r['recovery_s']:.3f}s restart={r['restart_s']:.2f}s  "
          f"p99 {r['clean_p99_ms']:.1f}->{r['chaos_p99_ms']:.1f}ms "
          f"({r['p99_spike_x']:.2f}x)")
    records.append(r)
    r = bench_chaos_overload()
    print(f"  overload: shed {r['shed_submissions']}/{r['n_requests']} "
          f"({100 * r['shed_rate']:.0f}%) typed={r['shed_typed_ok']} "
          f"accepted_served={r['accepted_served_ok']}")
    records.append(r)
    r = bench_slo_shed()
    print(f"  slo_shed: best_effort={r['shed_best_effort']} "
          f"batch={r['shed_batch']} interactive={r['shed_interactive']} "
          f"first={r['first_shed_class']} "
          f"burn={r['burn_fast_best_effort']:.1f}x "
          f"ordering={r['slo_shed_ordering_ok']} "
          f"export_match={r['slo_export_match_ok']}")
    records.append(r)
    return records


def collect(**kw) -> dict:
    records = []
    r = bench_scaling(**kw)
    print(f"  scaling : {r['reqs_per_s']:9.1f} req/s x{r['n_lanes']} lanes "
          f"vs {r['reqs_per_s_1lane']:9.1f} x1 -> "
          f"{r['scaling_vs_1lane']:.2f}x  "
          f"parity {r['parity_max_dev_vs_offline']:.1e}")
    records.append(r)
    import jax
    if jax.device_count() >= N_LANES:
        r = bench_sharded()
        print(f"  sharded : bitwise={r['bitwise_match']} "
              f"max_dev={r['max_dev_sharded_vs_replicated']:.1e}")
        records.append(r)
    else:
        # one feature shard per lane needs a device per lane; every other
        # cell stacks its lanes and runs on any device count
        print(f"  sharded : not run — {N_LANES} lanes need {N_LANES} "
              f"devices, found {jax.device_count()}")
    r = bench_reseed()
    print(f"  reseed  : {r['reseeds']} reseeds, spread "
          f"{r['pre_reseed_spread']:.2f}x -> {r['post_reseed_spread']:.2f}x "
          f"({r['post_reseed_requests']} post-reseed requests)")
    records.append(r)
    r = bench_tracing_overhead()
    print(f"  tracing : off {r['untraced_reqs_per_s']:9.1f} req/s  "
          f"on {r['traced_reqs_per_s']:9.1f} req/s  "
          f"overhead {r['tracing_overhead_pct']:+.1f}% "
          f"(ok={r['tracing_overhead_ok']})")
    records.append(r)
    r = bench_metrics_overhead()
    print(f"  metrics : off {r['bare_reqs_per_s']:9.1f} req/s  "
          f"on {r['metered_reqs_per_s']:9.1f} req/s  "
          f"overhead {r['metrics_overhead_pct']:+.1f}% "
          f"(ok={r['metrics_overhead_ok']})")
    records.append(r)
    records.extend(collect_chaos())
    records.extend(collect_mutation())
    from repro.sparse.stats import stats as kernel_stats_snapshot
    return {"bench": "cluster", "records": records,
            "kernel_stats": kernel_stats_snapshot()}


def write_json(path: str, data: dict):
    # atomic + preserves the accumulated trajectory history (one shared
    # implementation — benchmarks.trajectory.write_preserving)
    from benchmarks.trajectory import write_preserving
    write_preserving(path, data)


def check(data: dict, *, tol: float = 1e-5, min_scaling: float = 1.7,
          max_spread: float = 1.5, kinds=None) -> int:
    """CI gate: scaling, offline parity, bitwise sharded match, rebalance,
    and the chaos delivery guarantees.  ``kinds`` restricts the gate to a
    subset of record kinds (the ``--chaos`` partial-refresh path)."""
    failures = 0
    by_kind = {r["kind"]: r for r in data["records"]}

    def gate(kind):
        return kinds is None or kind in kinds

    s = by_kind.get("scaling")
    if not gate("scaling"):
        pass
    elif s is None:
        print("FAIL cluster: no scaling record")
        failures += 1
    else:
        if s["scaling_vs_1lane"] < min_scaling:
            print(f"FAIL scaling: {s['scaling_vs_1lane']}x < {min_scaling}x "
                  f"aggregate req/s over 1 lane")
            failures += 1
        if s["parity_max_dev_vs_offline"] > tol:
            print(f"FAIL scaling: parity "
                  f"{s['parity_max_dev_vs_offline']:.2e} > {tol:.0e} vs "
                  "single-device offline replay")
            failures += 1
        if s["recompiles_steady_state"] != 0:
            print(f"FAIL scaling: {s['recompiles_steady_state']} "
                  "steady-state recompiles (want 0)")
            failures += 1
    sh = by_kind.get("sharded_parity")
    if not gate("sharded_parity"):
        pass
    elif sh is None:
        print(f"FAIL sharded: no sharded_parity record (the cell needs "
              f"{N_LANES} devices)")
        failures += 1
    elif not sh["bitwise_match"]:
        print("FAIL sharded: output does not bitwise-match replicated "
              f"(max dev {sh['max_dev_sharded_vs_replicated']})")
        failures += 1
    rs = by_kind.get("reseed")
    if not gate("reseed"):
        pass
    elif rs is None or rs["reseeds"] < 1:
        print("FAIL reseed: router never reseeded on the skewed stream")
        failures += 1
    elif rs["post_reseed_spread"] >= max_spread:
        print(f"FAIL reseed: post-reseed spread {rs['post_reseed_spread']}x "
              f">= {max_spread}x mean")
        failures += 1
    cf = by_kind.get("chaos_failover")
    if not gate("chaos_failover"):
        pass
    elif cf is None:
        print("FAIL chaos_failover: no record")
        failures += 1
    else:
        if cf["lost_requests"] or not cf["zero_lost_ok"]:
            print(f"FAIL chaos_failover: {cf['lost_requests']} request(s) "
                  "lost across the lane kill (must be 0)")
            failures += 1
        if cf["duplicate_results"] or not cf["exactly_once_ok"]:
            print(f"FAIL chaos_failover: {cf['duplicate_results']} "
                  "request(s) settled more than once")
            failures += 1
        if cf["lane_deaths"] < 1 or cf["reroutes"] < 1:
            print("FAIL chaos_failover: the injected kill never took "
                  f"effect (deaths={cf['lane_deaths']} "
                  f"reroutes={cf['reroutes']})")
            failures += 1
        if not cf["lane_restored_ok"]:
            print("FAIL chaos_failover: the killed lane never rejoined")
            failures += 1
        if not cf["flight_recorder_ok"]:
            print("FAIL chaos_failover: telemetry JSONL recorded no "
                  "events/samples")
            failures += 1
        if not cf.get("trace_contract_ok", True):
            print(f"FAIL chaos_failover: {cf.get('trace_violations')} "
                  "span-tree contract violation(s) in the flight recorder "
                  "(verify_traces)")
            failures += 1
    to = by_kind.get("tracing_overhead")
    if gate("tracing_overhead") and to is not None \
            and (not to["tracing_overhead_ok"]
                 or to["tracing_overhead_pct"] > MAX_TRACING_OVERHEAD_PCT):
        print(f"FAIL tracing_overhead: tracing costs "
              f"{to['tracing_overhead_pct']}% cluster req/s "
              f"(> {MAX_TRACING_OVERHEAD_PCT}% budget)")
        failures += 1
    mo = by_kind.get("metrics_overhead")
    if gate("metrics_overhead") and mo is not None \
            and (not mo["metrics_overhead_ok"]
                 or mo["metrics_overhead_pct"] > MAX_METRICS_OVERHEAD_PCT):
        print(f"FAIL metrics_overhead: metrics plane costs "
              f"{mo['metrics_overhead_pct']}% cluster req/s "
              f"(> {MAX_METRICS_OVERHEAD_PCT}% budget)")
        failures += 1
    ss = by_kind.get("slo_shed")
    if not gate("slo_shed"):
        pass
    elif ss is None:
        print("FAIL slo_shed: no record")
        failures += 1
    else:
        if not ss["slo_shed_ordering_ok"]:
            print(f"FAIL slo_shed: shed precedence violated "
                  f"(best_effort={ss['shed_best_effort']} "
                  f"interactive={ss['shed_interactive']} "
                  f"first={ss['first_shed_class']}; best_effort must shed "
                  "first and interactive never)")
            failures += 1
        if not ss["slo_export_match_ok"]:
            print(f"FAIL slo_shed: scraped exposition disagrees with the "
                  f"engine summary (p99 bucket dist "
                  f"{ss['scrape_p99_bucket_dist_max']} > 1 or burn dev "
                  f"{ss['scrape_burn_rel_dev_max']} > 0.25)")
            failures += 1
    co = by_kind.get("chaos_overload")
    if not gate("chaos_overload"):
        pass
    elif co is None:
        print("FAIL chaos_overload: no record")
        failures += 1
    else:
        if co["shed_submissions"] < 1 or not co["shed_typed_ok"]:
            print("FAIL chaos_overload: overload was not shed with typed "
                  f"Overloaded (shed={co['shed_submissions']})")
            failures += 1
        if not co["accepted_served_ok"]:
            print(f"FAIL chaos_overload: {co['lost_accepted']} accepted "
                  f"request(s) lost / {co['duplicate_results']} duplicated")
            failures += 1
    md = by_kind.get("mutation_drill")
    if not gate("mutation_drill"):
        pass
    elif md is None:
        print("FAIL mutation_drill: no record")
        failures += 1
    else:
        if md["swap_cycles"] < 3:
            print(f"FAIL mutation_drill: only {md['swap_cycles']} swap "
                  "cycle(s); the drill requires >= 3 consecutive hot-swaps")
            failures += 1
        if md["lost_requests"] or not md["swap_zero_lost_ok"]:
            print(f"FAIL mutation_drill: {md['lost_requests']} request(s) "
                  "lost across the swap cycles (must be 0)")
            failures += 1
        if md["duplicate_results"] or not md["swap_exactly_once_ok"]:
            print(f"FAIL mutation_drill: {md['duplicate_results']} "
                  "request(s) settled more than once")
            failures += 1
        if not md["swap_one_version_ok"]:
            print("FAIL mutation_drill: a request was served without a "
                  "single well-defined params version")
            failures += 1
        if not md["swap_drained_ok"]:
            print("FAIL mutation_drill: an old params version was never "
                  "drained + GCed")
            failures += 1
        if not (md["swap_blackouts_measured"] >= 1
                and md["swap_blackout_ms"] >= 0):
            print("FAIL mutation_drill: swap_blackout_ms was never "
                  "measured (no dispatch observed after any flip)")
            failures += 1
        if not md["graph_parity_ok"]:
            print("FAIL mutation_drill: a streaming graph flush failed "
                  "parity vs the cold re-pack")
            failures += 1
    dr = by_kind.get("delta_repack")
    if not gate("delta_repack"):
        pass
    elif dr is None:
        print("FAIL delta_repack: no record")
        failures += 1
    else:
        if not dr["mutation_parity_ok"]:
            print("FAIL delta_repack: incremental layouts are not "
                  "bitwise/1e-5 equal to the cold pack")
            failures += 1
        if dr["delta_repack_speedup"] < MIN_REPACK_SPEEDUP:
            print(f"FAIL delta_repack: {dr['delta_repack_speedup']}x < "
                  f"{MIN_REPACK_SPEEDUP}x over cold pack_dedup_chunks")
            failures += 1
    if not failures:
        scope = "partial: " + ", ".join(sorted(kinds)) if kinds else "full"
        print(f"cluster gate OK ({scope}): scaling ≥ {min_scaling}x, "
              f"parity ≤ {tol:.0e}, sharded bitwise, rebalance < "
              f"{max_spread}x, failover zero-lost/exactly-once + trace "
              "contract, overload shed typed, slo shed ordered + export "
              "truthful, mutation drill zero-lost/one-version + repack "
              f"≥ {MIN_REPACK_SPEEDUP}x at parity")
    return failures


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default=None)
    ap.add_argument("--check", action="store_true")
    ap.add_argument("--check-json", default=None, metavar="PATH")
    ap.add_argument("--min-scaling", type=float, default=1.7)
    ap.add_argument("--requests", type=int, default=768)
    ap.add_argument("--chaos", action="store_true",
                    help="run only the chaos scenarios and refresh their "
                         "records inside the JSON (other kinds are kept)")
    ap.add_argument("--mutation", action="store_true",
                    help="run only the live-mutation drill (hot-swap + "
                         "delta re-pack) and refresh its records inside "
                         "the JSON (other kinds are kept)")
    ap.add_argument("--long", action="store_true",
                    help="nightly drill sizing: more swap cycles and a "
                         "longer mutation stream (with --mutation)")
    args = ap.parse_args(argv)

    if args.check_json:
        with open(args.check_json) as f:
            data = json.load(f)
        return 1 if check(data, min_scaling=args.min_scaling) else 0

    import jax

    from repro.core import platform
    if not platform.on_tpu() and jax.device_count() < N_LANES:
        print(f"cluster_bench needs {N_LANES} devices, found "
              f"{jax.device_count()} — jax was already initialized without "
              "the host-platform flag; run this module in its own process")
        return 2
    path = args.json or DEFAULT_JSON
    if args.chaos or args.mutation:
        records = []
        if args.chaos:
            records += collect_chaos()
        if args.mutation:
            records += collect_mutation(long=args.long)
        fresh_kinds = {r["kind"] for r in records}
        try:
            with open(path) as f:
                kept = [r for r in json.load(f).get("records", [])
                        if r["kind"] not in fresh_kinds]
        except (OSError, ValueError):
            kept = []
        data = {"bench": "cluster", "records": kept + records}
        write_json(path, data)
        print(f"wrote {path} (refreshed {sorted(fresh_kinds)})")
        if args.check:
            return 1 if check(data, min_scaling=args.min_scaling,
                              kinds=fresh_kinds) else 0
        return 0
    data = collect(n_requests=args.requests)
    write_json(path, data)
    print(f"wrote {path}")
    if args.check:
        return 1 if check(data, min_scaling=args.min_scaling) else 0
    return 0


if __name__ == "__main__":
    sys.exit(main())
