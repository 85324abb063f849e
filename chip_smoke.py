#!/usr/bin/env python3
"""Chip smoke test: serve GraphSAGE at Reddit widths on a TPU.

  python chip_smoke.py               # one chip
  python chip_smoke.py --four-chips  # sharded 4-lane cluster vs one lane

One chip: builds the Reddit-shape world (``configs.shapes`` minibatch_lg:
232,965 nodes, ~114.6M power-law edges, fanouts (15, 10)) with GraphSAGE at
its configured widths (602 -> 64 -> 41, random weights from seed 0), stands
up ``GNNServer(backend="pallas", sampler="device")`` — features and CSR
resident on the chip, sampling fused into the per-bucket step — warms every
bucket, answers a few dozen seed-node requests, drains, and compares every
result with a plain float32 reference (the ``dense`` executor through
``offline_inference`` on the same sampled trees, matmuls at "highest").

``--four-chips``: only the sharded path — ``ClusterServer(mode="sharded",
placement="mesh")``, 4 lanes, one per chip, feature rows DRHM-sharded over
the chips — compared with single-lane results on the same requests.

Earlier lines say what was measured; the last line is one JSON object,
``{"ok": true, "device": {...}}``.  The script exits non-zero without that
line when JAX finds no TPU, when the repo's ``src/`` is not beside it, or
when any phase fails.  Everything runs in this one process: the chip
belongs to one process at a time.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

ARCH = "sage"
REQUEST_SEEDS = [1] * 40 + [4] * 8          # 48 requests, 72 seed nodes
MAX_BATCH = 16
N_LANES = 4
# Largest max|Δ| admitted against the float32 reference.  XLA's DEFAULT
# precision for an f32 matmul on a TPU is one bf16 pass (operands rounded
# to 8 significant bits); the reference runs at "highest".  Rounding every
# matmul operand of this model to bf16 at these widths gives max|Δ| of
# 9.7e-3 over 64 trees in a float64 emulation on the CPU, against outputs
# of magnitude ~2.  The limit is three times that.
TOLERANCE = 3e-2
# Four chips compare the chip with itself on the same arithmetic: served
# results against single-lane replay within the cluster's parity contract
# (README: 1e-5; replay runs bucket 1, the cluster batched buckets), and
# the sharded lane step against the single-device step bitwise.
REPLAY_TOLERANCE = 1e-5


class SmokeFailure(Exception):
    pass


def log(msg: str):
    print(f"[chip-smoke] {msg}", flush=True)


def check(cond: bool, msg: str):
    if not cond:
        raise SmokeFailure(msg)


def build_reddit_world():
    """(cfg, params, indptr, indices, store, fanouts) at the Reddit shape."""
    from repro.configs.shapes import GNN_SHAPES
    from repro.launch.gnn_serve import build_world
    shape = GNN_SHAPES["minibatch_lg"]
    n_nodes, n_edges = shape.n_nodes, shape.n_edges
    fanouts = tuple(shape.fanout)
    t0 = time.perf_counter()
    cfg, params, indptr, indices, store = build_world(ARCH, n_nodes, n_edges,
                                                      seed=0)
    gen_s = time.perf_counter() - t0
    check(cfg.d_in == shape.d_feat and cfg.n_classes == shape.n_classes,
          f"GraphSAGE config {cfg.d_in}/{cfg.n_classes} is not the Reddit "
          f"shape {shape.d_feat}/{shape.n_classes}")
    log(f"model GraphSAGE {cfg.d_in} -> {cfg.d_hidden} -> {cfg.n_classes}, "
        f"{cfg.n_layers} layers, random weights (seed 0)")
    log(f"graph {n_nodes:,} nodes, {indices.size:,} edges (requested "
        f"{n_edges:,}; the generator drops {n_edges - indices.size:,} self "
        f"loops; no edge cut), fanouts {fanouts}, built in {gen_s:.1f} s")
    return cfg, params, indptr, indices, store, fanouts


def cache_entries(cache_dir: str) -> int:
    return sum(len(files) for _, _, files in os.walk(cache_dir))


def warm(server, cache_dir: str, what: str):
    """Warm every bucket; say how long it took and how many programs the
    persistent compile cache gained (none when all came from it)."""
    n0 = cache_entries(cache_dir)
    t0 = time.perf_counter()
    server.warmup()
    log(f"warm-up ({what}): {time.perf_counter() - t0:.2f} s; compile "
        f"cache files {n0} -> {cache_entries(cache_dir)}")


def request_seeds(n_nodes: int) -> list:
    rng = np.random.default_rng(1)
    return [rng.integers(0, n_nodes, k) for k in REQUEST_SEEDS]


def settle_check(reqs) -> np.ndarray:
    failed = [r for r in reqs if not r.done or r.error is not None]
    log(f"requests served {len(reqs) - len(failed)}, failed {len(failed)}")
    check(not failed, f"{len(failed)} request(s) failed or never settled: "
          f"{[repr(r.error) for r in failed[:3]]}")
    return np.concatenate([r.result for r in reqs])


def serve_one_chip(world, cache_dir: str) -> None:
    import jax

    from repro.serve import GNNServer, offline_inference
    from repro.serve.buckets import all_buckets
    cfg, params, indptr, indices, store, fanouts = world
    dev = jax.devices()[0]
    server = GNNServer(ARCH, cfg, params, indptr, indices, store,
                       fanouts=fanouts, backend="pallas", sampler="device",
                       max_batch_seeds=MAX_BATCH, seed=0)
    with server:
        mem = dev.memory_stats() or {}
        log(f"resident: features {store.x.nbytes:,} B f32, CSR "
            f"{(indptr.size + indices.size) * 4:,} B int32; device bytes in "
            f"use {mem.get('bytes_in_use', 'not reported')}")
        warm(server, cache_dir, f"compile + one batch per bucket, buckets "
                                f"{all_buckets(MAX_BATCH)}")

        # the compiled per-bucket step must hold the Pallas kernels — the
        # pallas executor may not silently become another one
        step = server.steps.get((MAX_BATCH,))
        z = np.zeros(MAX_BATCH, np.uint32)
        t0 = time.perf_counter()
        compiled = step.func.lower(*step.args, params,
                                   np.zeros(MAX_BATCH, np.int32), z, z,
                                   np.zeros(MAX_BATCH, bool)).compile()
        n_custom = compiled.as_text().count("tpu_custom_call")
        log(f"bucket-{MAX_BATCH} step re-lowered and compiled in "
            f"{time.perf_counter() - t0:.2f} s: {n_custom} tpu_custom_call "
            f"op(s) (Pallas kernels present: {n_custom > 0})")
        check(n_custom > 0, "no Pallas custom call in the per-bucket step")

        builds = server.steps.builds
        t0 = time.perf_counter()
        reqs = [server.submit(s) for s in request_seeds(store.n_nodes)]
        server.drain(timeout=600)
        log(f"{len(reqs)} requests ({sum(REQUEST_SEEDS)} seed nodes) "
            f"submitted and drained in {time.perf_counter() - t0:.2f} s, "
            f"recompiles {server.steps.builds - builds}")
        got = settle_check(reqs)
        trees = [server.sample_for(r.seeds, r.rid) for r in reqs]

    check(got.shape == (sum(REQUEST_SEEDS), cfg.n_classes),
          f"result shape {got.shape}")
    check(bool(np.isfinite(got).all()), "non-finite results")
    # plain float32 reference: the dense executor on the same trees
    ref_server = GNNServer(ARCH, cfg, params, indptr, indices, store,
                           fanouts=fanouts, backend="dense", sampler="host",
                           n_workers=1, seed=0)
    with ref_server, jax.default_matmul_precision("highest"):
        ref = np.concatenate([offline_inference(ref_server, t)
                              for t in trees])
    dev_max = float(np.abs(got - ref).max())
    log(f"max|Δ| vs float32 dense reference {dev_max:.3e} (tolerance "
        f"{TOLERANCE:.0e}: TPU default f32 matmul precision is one bf16 "
        f"pass, the reference runs at 'highest'; max|ref| "
        f"{float(np.abs(ref).max()):.3f})")
    check(dev_max <= TOLERANCE, f"max|Δ| {dev_max:.3e} > {TOLERANCE:.0e}")


def serve_four_chips(world, cache_dir: str) -> None:
    import jax

    from repro.serve import ClusterServer
    from repro.serve.buckets import stack_trees
    from repro.serve.compute import build_infer_step
    from repro.serve.engine import default_tree_keys
    from repro.sparse.sampler import sample_forest
    cfg, params, indptr, indices, store, fanouts = world
    check(len(jax.devices()) >= N_LANES,
          f"--four-chips needs {N_LANES} devices, found {len(jax.devices())}")
    cluster = ClusterServer(ARCH, cfg, params, indptr, indices, store,
                            n_lanes=N_LANES, mode="sharded", placement="mesh",
                            fanouts=fanouts, backend="pallas",
                            max_batch_seeds=MAX_BATCH, seed=0)
    with cluster:
        shard_devs = sorted({s.device.id
                             for s in cluster._x_perm.addressable_shards})
        shard_bytes = cluster._x_perm.addressable_shards[0].data.nbytes
        log(f"feature shards: {cluster._x_perm.shape} f32 over devices "
            f"{shard_devs} ({shard_bytes:,} B each)")
        check(len(shard_devs) == N_LANES, "feature shards share a device")
        warm(cluster, cache_dir, "compile + one round per bucket")
        seeds = request_seeds(store.n_nodes)
        t0 = time.perf_counter()
        reqs = cluster.submit_many(seeds)
        cluster.drain(timeout=600)
        st = cluster.stats()
        log(f"{len(reqs)} requests in {st['n_rounds']} rounds drained in "
            f"{time.perf_counter() - t0:.2f} s; timeouts {st['timeouts']}, "
            f"lane deaths {st['lane_deaths']}, per-lane served "
            f"{cluster.lane_stats()['served']}")
        got = settle_check(reqs)
        check(not st["timeouts"] and not st["lane_deaths"],
              "the control plane timed out or killed a lane")

        # the same requests through the single-lane path (one device, one
        # tree at a time — the cluster's parity anchor)
        refs = [cluster.offline_replay(r) for r in reqs]
        n_eq = sum(bool(np.array_equal(r.result, ref))
                   for r, ref in zip(reqs, refs))
        dev_max = float(np.abs(got - np.concatenate(refs)).max())
        log(f"served vs single-lane replay: max|Δ| {dev_max:.3e}, bitwise "
            f"equal {n_eq}/{len(reqs)} requests (tolerance "
            f"{REPLAY_TOLERANCE:.0e}; replay runs bucket 1, the cluster "
            f"bucketed batches)")
        check(dev_max <= REPLAY_TOLERANCE,
              f"max|Δ| {dev_max:.3e} > {REPLAY_TOLERANCE:.0e}")

        # one explicit round: where the lane outputs live, and the lane step
        # against the single-device step of the SAME bucket on the same rows
        b = MAX_BATCH
        struct = cluster._struct(b)
        node_ids = np.full((N_LANES, struct.n_nodes), -1, np.int64)
        hop_valid = np.zeros((N_LANES, struct.n_hop_edges), bool)
        rng = np.random.default_rng(2)
        for lane in range(N_LANES):
            trees = sample_forest(indptr, indices,
                                  rng.integers(0, store.n_nodes, b), fanouts,
                                  key=0, tree_keys=default_tree_keys(lane, b))
            node_ids[lane], hop_valid[lane] = stack_trees(trees, b, fanouts)
        out = cluster.steps.get((b,))(cluster.params,
                                      cluster._gather(node_ids), node_ids,
                                      hop_valid)
        out_devs = sorted(d.id for d in out.sharding.device_set)
        log(f"lane outputs {out.shape} over devices {out_devs}")
        check(len(out_devs) == N_LANES, "lane outputs share a device")
        single = build_infer_step(ARCH, cfg, store, struct, backend="pallas")
        one = np.stack([np.asarray(single(cluster.params, node_ids[ln],
                                          hop_valid[ln]))
                        for ln in range(N_LANES)])
        out = np.asarray(out)
        log(f"sharded lane step vs single-device step, bucket {b}: max|Δ| "
            f"{float(np.abs(out - one).max()):.3e}, bitwise equal "
            f"{bool(np.array_equal(out, one))}")
        check(bool(np.array_equal(out, one)),
              "sharded lane step is not bitwise equal to the single-device "
              "step")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded 4-lane cluster path, one "
                         "lane per chip, against single-lane results")
    args = ap.parse_args(argv)
    try:
        from repro.core import platform
    except ImportError as exc:
        print(f"[chip-smoke] FAIL: the repo's src/ is not beside this "
              f"script ({exc})", file=sys.stderr)
        return 2
    cache_dir = platform.enable_compile_cache()
    import jax
    devices = jax.devices()
    dev = devices[0]
    log(f"jax {jax.__version__}, {len(devices)} x {dev.platform} "
        f"({dev.device_kind}), compile cache {cache_dir}")
    if dev.platform != "tpu":
        print(f"[chip-smoke] FAIL: no TPU (JAX found {dev.platform})",
              file=sys.stderr)
        return 1
    try:
        world = build_reddit_world()
        if args.four_chips:
            serve_four_chips(world, cache_dir)
        else:
            serve_one_chip(world, cache_dir)
    except SmokeFailure as exc:
        print(f"[chip-smoke] FAIL: {exc}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
