"""One cell, run once: set-up, the measured window, the check, the metrics.

Everything that belongs to one configuration, traffic mix or metric lives
in a file of its own, found by the name ``BENCHMARK.json`` gives it:

* ``configs/<config>.json`` — sizes and serving settings;
* ``traffic/<traffic>.json`` — parameters that ``loadgen`` reads;
* ``metrics/<metric>.py`` — ``read(ctx) -> float | None``;
* ``peaks.json`` — the chip's peaks, keyed by ``device_kind``.

The system under test is ``repro.serve.GNNServer`` (GraphSAGE, Pallas
backend, sampling fused into the device step), driven through ``submit``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import os
import sys
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from benchmarks.chip import loadgen, reference, sampling, tracereduce
from benchmarks.chip import worldgen

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CACHE_DIR = os.path.join(ROOT, ".jax_cache")

# the numbers compared, and their limits (PERF.md gives the readings they
# were set from): the widest logit gap over the reference's RMS, and the
# share of served logits that are exact bfloat16 values (float32 outputs,
# as the configurations state, almost never are)
REL_ERR_LIMIT = 0.05
BF16_SHARE_LIMIT = 0.01
# requests whose answers are compared, drawn from the seed among those the
# window finished (each of 1 seed in an open cell, 16 in a closed one)
CHECK_SEEDS = 256


class HarnessError(Exception):
    """The run cannot give a result (no chip, unknown chip, bad cell)."""


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


# -- the cell --------------------------------------------------------------

@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(workload: str, root: str = ROOT) -> Cell:
    bench = _json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise HarnessError(f"no workload {workload!r} in BENCHMARK.json; "
                           f"have {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _json(os.path.join(root, configs[w["config"]]["file"]))
    traffic = _json(os.path.join(HERE, "traffic", w["traffic"] + ".json"))

    def mine(m):
        return workload in m.get("workloads", [workload])

    e2e = [m for m in bench["end_to_end"] if mine(m)]
    e2e_names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if (workload in m["workloads"] if "workloads" in m
                 else m["moves"] in e2e_names)]
    return Cell(workload, int(w["chips"]), config, traffic, e2e, layer)


def reader(metric: str) -> Callable[[dict], Optional[float]]:
    path = os.path.join(HERE, "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{metric.replace('.', '_').replace('-', '_')}",
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# -- the device ------------------------------------------------------------

def device_info(chips: int, require_tpu: bool = True) -> dict:
    import jax
    devs = jax.devices()
    d = devs[0]
    if require_tpu and d.platform != "tpu":
        raise HarnessError(f"no TPU: JAX found {len(devs)} x {d.platform}")
    if len(devs) < chips:
        raise HarnessError(f"the cell needs {chips} chips, JAX found "
                           f"{len(devs)}")
    peaks = _json(os.path.join(HERE, "peaks.json"))["devices"]
    if require_tpu and d.device_kind not in peaks:
        raise HarnessError(f"device kind {d.device_kind!r} is not in "
                           f"peaks.json ({sorted(peaks)})")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs), "peaks": peaks.get(d.device_kind)}


def memory_peak_bytes() -> Optional[int]:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def enable_compile_cache(cache_dir: str = CACHE_DIR) -> None:
    """JAX's persistent cache at a fixed path inside the checkout."""
    import jax
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileCounter:
    """Counts JAX traces and compiles while armed (none belong in the
    window: every shape is warmed in set-up)."""

    def __init__(self):
        import jax.monitoring
        self.armed = False
        self.events: List[str] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw):
        if self.armed and ("compile" in event or "trace" in event):
            self.events.append(event)

    def close(self):
        import jax.monitoring
        jax.monitoring.unregister_event_duration_listener(self._on)


# -- the system under test -------------------------------------------------

def build_server(cfg: dict, world, csr: tuple, seed: int,
                 tracing: bool = False, trace_capacity: int = 4096):
    from repro.models.gnn.sage import SAGEConfig
    from repro.serve import GNNServer
    from repro.serve.compute import FeatureStore
    scfg = SAGEConfig(name=cfg["name"], n_layers=cfg["n_layers"],
                      d_in=cfg["d_in"], d_hidden=cfg["d_hidden"],
                      n_classes=cfg["n_classes"], param_dtype=cfg["dtype"])
    indptr, indices = csr
    serving = cfg["serving"]
    return GNNServer(cfg["arch"], scfg, world.params, indptr, indices,
                     FeatureStore(n_nodes=world.n_nodes, x=world.x),
                     fanouts=cfg["fanouts"], backend=serving["backend"],
                     sampler=serving["sampler"],
                     max_batch_seeds=serving["max_batch_seeds"], seed=seed,
                     tracing=tracing, trace_capacity=trace_capacity)


# -- the measured window ---------------------------------------------------

@dataclasses.dataclass
class Window:
    t0: float
    t_end: float
    ledger: loadgen.Ledger
    compiles: List[str]
    step_builds: int
    batches: int
    # seeds of the requests sent in the window; ``run_window`` returns once
    # every one has settled, so the batches counted (and a trace taken
    # around the window) served exactly these
    seeds_submitted: int


def annotation(on: bool):
    if not on:
        return contextlib.nullcontext
    from jax.profiler import TraceAnnotation
    return TraceAnnotation


def run_window(server, traffic: dict, n_nodes: int, seed: int,
               seconds: float, counter: CompileCounter, marks: bool = False
               ) -> Window:
    mark = annotation(marks)
    draw = loadgen.SeedDraw(traffic, n_nodes, seed)
    if traffic["kind"] == "open_poisson":
        due = loadgen.open_schedule(traffic, seconds, seed)
        seeds = draw.draw(len(due))
        # keep about four times the requests the check compares
        keep = min(1.0, 4.0 * CHECK_SEEDS / seeds.size)
    elif traffic["kind"] == "closed":
        keep = 1.0 / 32
    else:
        raise HarnessError(f"unknown traffic kind {traffic['kind']!r}")
    ledger = loadgen.Ledger(seed, keep)
    builds0 = server.steps.builds
    batches0 = server.stats()["n_batches"]
    counter.armed = True
    t0 = time.monotonic() + 0.01
    t_end = t0 + seconds
    with mark("bench.window"):
        if traffic["kind"] == "open_poisson":
            loadgen.run_open(server.submit, due, seeds, t0, ledger,
                             mark=mark)
            left = t_end - time.monotonic()
            if left > 0:
                time.sleep(left)
        else:
            while time.monotonic() < t0:
                pass
            loadgen.run_closed(server.submit, draw.stream(),
                               traffic["clients"], t_end, ledger, mark=mark)
    ledger.settle()
    counter.armed = False
    return Window(t0, t_end, ledger, list(counter.events),
                  server.steps.builds - builds0,
                  server.stats()["n_batches"] - batches0,
                  int(sum(ledger.n_seeds)))


# -- is it correct ---------------------------------------------------------

@dataclasses.dataclass
class Check:
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return bool(np.isfinite(self.value)) and self.value <= self.limit


def window_requests(win: Window) -> Dict[str, np.ndarray]:
    """The requests due inside the window (an open loop) or sent in it, as
    arrays: due, sent, done (nan if no reply), ok, n_seeds, rid."""
    a = win.ledger.arrays()
    inside = a["due"] < win.t_end
    return {k: v[inside] for k, v in a.items()}


def served_sample(win: Window, seed: int, n_seeds: int = CHECK_SEEDS
                  ) -> list:
    """Requests the window finished, drawn from the seed among those the
    generator kept: about ``n_seeds`` seed nodes in all."""
    ok = [h for i, h in sorted(win.ledger.kept.items())
          if win.ledger.due[i] < win.t_end and h.done and h.error is None]
    if not ok:
        return []
    k = max(n_seeds // max(len(ok[0].seeds), 1), 1)
    rng = np.random.default_rng([int(seed), 0x5A3E])
    pick = np.sort(rng.choice(len(ok), size=min(k, len(ok)), replace=False))
    return [ok[i] for i in pick]


def compare(cfg: dict, world, csr: tuple, server_seed: int, sample: list,
            compute: str = "f32") -> Dict[str, float]:
    """Served answers of ``sample`` against the plain reference (or, with
    another ``compute``, a control in the program's place against it).

    Returns ``rel_err``: the widest gap between a served and a reference
    logit, over the RMS of the reference logits; ``mean_rel_err``: the mean
    gap over the same RMS; ``bf16_share``: the share of served logits that
    are exact bfloat16 values; and the sizes compared."""
    import jax
    import jax.numpy as jnp
    if not sample:
        return {"rel_err": float("inf"), "mean_rel_err": float("inf"),
                "bf16_share": float("inf"), "seeds": 0}
    indptr, indices = csr
    seeds = np.concatenate([np.asarray(h.seeds) for h in sample])
    keys = np.concatenate([sampling.tree_keys(h.rid, len(h.seeds))
                           for h in sample])
    levels, valid = sampling.sample_trees(indptr, indices, seeds, keys,
                                          cfg["fanouts"], server_seed)
    got = np.concatenate([np.asarray(h.result, np.float32) for h in sample])
    params = jax.tree.map(np.asarray, world.params)
    ref = reference.reference_outputs(world.x, params, levels, valid,
                                      cfg["fanouts"], "f32")
    if compute != "f32":
        got = reference.reference_outputs(world.x, params, levels, valid,
                                          cfg["fanouts"], compute)
    rms = max(float(np.sqrt(np.mean(ref.astype(np.float64) ** 2))), 1e-30)
    gap = np.abs(got.astype(np.float64) - ref)
    exact = got == got.astype(jnp.bfloat16).astype(np.float32)
    return {"rel_err": float(gap.max() / rms),
            "mean_rel_err": float(gap.mean() / rms),
            "bf16_share": float(exact.mean()),
            "seeds": int(seeds.size), "ref_rms": rms}


def checks_for(win: Window, cmp: Dict[str, float]) -> List[Check]:
    failed = int((window_requests(win)["ok"] == 0).sum())
    return [Check("max_logit_gap_over_rms", cmp["rel_err"], REL_ERR_LIMIT),
            Check("bf16_exact_logit_share", cmp["bf16_share"],
                  BF16_SHARE_LIMIT),
            Check("failed_requests", float(failed), 0.0),
            Check("compiles_in_window", float(len(win.compiles)
                                              + win.step_builds), 0.0)]


# -- metrics ---------------------------------------------------------------

def metric_context(cell: Cell, win: Window, setup_s: float, dev: dict,
                   summary: Optional[tracereduce.Summary],
                   spans: Optional[list]) -> dict:
    return {"cell": cell.name, "config": cell.config, "traffic": cell.traffic,
            "peaks": dev["peaks"], "setup_s": setup_s, "t0": win.t0,
            "t_end": win.t_end, "seconds": win.t_end - win.t0,
            "requests": window_requests(win), "batches": win.batches,
            "seeds_submitted": win.seeds_submitted,
            "dims": worldgen.layer_dims(cell.config),
            "trace": summary, "spans": spans}


def read_metrics(specs: List[dict], ctx: dict) -> Dict[str, dict]:
    out = {}
    for m in specs:
        v = reader(m["name"])(ctx)
        if v is None:
            log(f"metric {m['name']}: nothing to read, left out")
            continue
        out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


# -- one run ---------------------------------------------------------------

def trace_capacity(traffic: dict, seconds: float) -> int:
    """Span ring big enough for every request of the window."""
    if traffic["kind"] == "open_poisson":
        return int(traffic["rate_per_s"] * seconds * 1.2) + 1024
    return 1 << 18


def profile_options():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, require_tpu: bool = True,
             compile_cache: bool = True) -> dict:
    """One run of ``cell``: returns the result line as a dict.
    ``require_tpu=False`` and ``compile_cache=False`` are for the CPU
    tests, which drive a tiny cell through the same run."""
    import shutil
    import tempfile

    import jax
    if compile_cache:
        enable_compile_cache()
    dev = device_info(cell.chips, require_tpu)
    log(f"{dev['count']} x {dev['platform']} ({dev['kind']}), jax "
        f"{jax.__version__}, cell {cell.name}, seed {seed}, {seconds} s, "
        f"trace {int(trace)}")
    counter = CompileCounter()
    cfg, traffic = cell.config, cell.traffic
    t = time.monotonic()
    world = worldgen.make_world(cfg, seed)
    t_world = time.monotonic() - t
    csr = world.host_csr()
    t_csr = time.monotonic() - t - t_world
    server = build_server(cfg, world, csr, seed, tracing=trace,
                          trace_capacity=trace_capacity(traffic, seconds))
    buckets = loadgen.buckets_used(traffic,
                                   cfg["serving"]["max_batch_seeds"])
    t = time.monotonic()
    server.warmup(buckets)
    log(f"set-up: device data {t_world:.2f} s, host CSR copy {t_csr:.2f} "
        f"s, warm-up of buckets {buckets} {time.monotonic() - t:.2f} s; "
        f"{time.monotonic() - t_start:.2f} s since start")
    log_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    try:
        if trace:
            jax.profiler.start_trace(log_dir,
                                     profiler_options=profile_options())
        try:
            win = run_window(server, traffic, world.n_nodes, seed, seconds,
                             counter, marks=trace)
        finally:
            if trace:
                jax.profiler.stop_trace()
        setup_s = win.t0 - t_start
        if win.compiles or win.step_builds:
            log(f"FAIL: {len(win.compiles)} JAX trace/compile event(s) and "
                f"{win.step_builds} step build(s) inside the window: "
                f"{sorted(set(win.compiles))}")
        mem = memory_peak_bytes()
        spans = server.tracer.traces() if trace else None
        server.close()
        del server
        cmp = compare(cfg, world, csr, seed, served_sample(win, seed))
        checks = checks_for(win, cmp)
        summary = None
        if trace:
            summary = tracereduce.summarize(tracereduce.load_xspace(
                tracereduce.find_xspace(log_dir)))
    finally:
        counter.close()
        if log_dir:
            shutil.rmtree(log_dir, ignore_errors=True)
    ctx = metric_context(cell, win, setup_s, dev, summary, spans)
    metrics = read_metrics(cell.per_layer if trace else cell.end_to_end, ctx)
    reqs = window_requests(win)
    device = {"platform": dev["platform"], "kind": dev["kind"],
              "count": dev["count"], "memory_peak_bytes": mem}
    out = {"correct": all(c.ok for c in checks),
           "attempted": int(reqs["due"].size),
           "failed": int(sum(c.value for c in checks
                             if c.name == "failed_requests")),
           "metrics": metrics,
           "device": device}
    if summary is not None:
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
        out["breakdown"] = {"device_ops": summary.top_ops(10),
                            "idle_gaps": [list(g) for g in
                                          summary.idle_gaps[:10]]}
    log(f"{reqs['due'].size} requests, {win.batches} batches, compared "
        f"{cmp['seeds']} served seeds with the reference (reference RMS "
        f"{cmp.get('ref_rms', float('nan')):.4f}, mean gap over it "
        f"{cmp['mean_rel_err']!r})")
    out["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                     for c in checks}
    log(f"run took {time.monotonic() - t_start:.1f} s")
    for c in checks:
        log(f"check {c.name}: {c.value!r} (limit {c.limit!r}) "
            f"{'ok' if c.ok else 'FAIL'}")
    return out
