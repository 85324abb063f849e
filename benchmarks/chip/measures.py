"""Shared arithmetic of the metric readers, over the benchmark's own
record of the window's requests (``ctx["requests"]``: arrays ``due``,
``sent``, ``done`` (nan without a reply), ``ok``, ``n_seeds``, ``rid``)
and, in a traced run, the profiler trace's summary (``ctx["trace"]``)."""
from __future__ import annotations

import re
from typing import List, Optional, Tuple

import numpy as np

from benchmarks.chip import workcount

# the jitted bucket step (sampling, gather, aggregation, dense layers) on
# the trace's ``XLA Modules`` line
STEP_PROGRAM = "jit_fused"
# the Gustavson kernel's own ops (``%_spmm_dedup_chunks.3 = ...
# custom-call(...)``), not the ops that take its output as an operand
KERNEL = re.compile(r"^%?_spmm_dedup_chunks(_q8)?(\.\d+)?$")


def latencies_ms(req: dict) -> np.ndarray:
    """From the time each request was due to the time it settled; a
    request that failed or never settled misses every limit (inf)."""
    return np.where(req["ok"] == 1, (req["done"] - req["due"]) * 1e3,
                    np.inf)


def percentile(values: np.ndarray, q: float) -> Optional[float]:
    if values.size == 0:
        return None
    v = float(np.percentile(values, q))
    return v if np.isfinite(v) else None


def seeds_served(ctx: dict) -> int:
    """Seed nodes answered inside the window."""
    req = ctx["requests"]
    inside = (req["ok"] == 1) & (req["done"] <= ctx["t_end"])
    return int(req["n_seeds"][inside].sum())


def _window_traces(ctx: dict):
    rids = set(ctx["requests"]["rid"].tolist())
    return [tr for tr in ctx.get("spans") or () if tr["trace"] in rids]


def span_ms(ctx: dict, name: str) -> List[float]:
    """Durations of the program's ``name`` spans of the window's requests
    (one per request; per batch spans repeat on each request)."""
    return [(s["t1"] - s["t0"]) * 1e3 for tr in _window_traces(ctx)
            for s in tr["spans"] if s["name"] == name]


def per_round_ms(ctx: dict, names: tuple) -> List[float]:
    """Per dispatched batch, the summed durations of its ``names`` spans
    (every request of a batch carries the batch's spans, keyed by round)."""
    rounds = {}
    for tr in _window_traces(ctx):
        for s in tr["spans"]:
            if s["name"] in names:
                rounds.setdefault(s["round"], {})[s["name"]] = \
                    (s["t1"] - s["t0"]) * 1e3
    return [sum(v.values()) for v in rounds.values()]


def fused_step(ctx: dict) -> Optional[Tuple[int, float]]:
    """Calls of the fused bucket step in the traced window, and their
    device seconds; None without a trace or a call."""
    tr = ctx.get("trace")
    if tr is None:
        return None
    hits = [v for name, v in tr.modules.items()
            if name.split("(")[0] == STEP_PROGRAM]
    n = sum(c for c, _ in hits)
    return (n, float(sum(s for _, s in hits))) if n else None


def step_ms(ctx: dict) -> Optional[float]:
    """Device time of one call of the fused step, mean over the calls."""
    step = fused_step(ctx)
    return None if step is None else 1e3 * step[1] / step[0]


def gustavson_roofline(ctx: dict) -> Optional[float]:
    """The Gustavson kernel's share of its roofline, in %.

    Device time: every call of the kernel in the traced window.  Work: what
    the algorithm's aggregations need for the seeds those calls served
    (``workcount.aggregation_floor_s``).  The trace spans the window and
    the settling of every request sent in it, so its calls serve the seeds
    submitted (``seeds_submitted``), one call per layer and batch."""
    tr, peaks = ctx.get("trace"), ctx.get("peaks")
    if tr is None or not peaks or not ctx["batches"]:
        return None
    calls = [v for name, v in tr.ops.items()
             if KERNEL.match(name.split(" = ")[0])]
    n = sum(c for c, _ in calls)
    sec = sum(s for _, s in calls)
    if not n or sec <= 0:
        return None
    fanouts = ctx["config"]["fanouts"]
    seeds = n / len(fanouts) * ctx["seeds_submitted"] / ctx["batches"]
    floor = seeds * workcount.aggregation_floor_s(
        fanouts, ctx["dims"], peaks["bf16_flops"], peaks["hbm_bytes_per_s"])
    return 100.0 * floor / sec


def step_mfu(ctx: dict) -> Optional[float]:
    """FLOPs that GraphSAGE's minibatch algorithm needs for the seeds the
    traced steps served (``workcount``), over the steps' device time times
    the chip's bf16 peak, in %."""
    step, peaks = fused_step(ctx), ctx.get("peaks")
    if step is None or not peaks or not ctx["seeds_submitted"]:
        return None
    flops = workcount.flops_per_seed(ctx["config"]["fanouts"], ctx["dims"])
    return (100.0 * flops * ctx["seeds_submitted"] / step[1]
            / peaks["bf16_flops"])
