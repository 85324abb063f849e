#!/usr/bin/env python3
"""Engine states and fused-step stages on the profiler's clock.

  python benchmarks/chip/enginetrace.py --workload sage-products.poisson \\
      --seed 7 --seconds 51 [--fixture stages.json]

Runs one cell's window traced, as ``run.py --trace 1`` does, and writes
one ``Tracer.anchor()`` (a ``serve.clock`` annotation) right after the
profiler starts and one right before it stops.  It prints one JSON object
that lays the window's time at the program's door:

* ``idle``: every stretch of the window in which no operation runs on the
  device, split among the program's own spans on the trace's clock (the
  engine thread's ``engine.*`` states, which tile its loop, and
  ``host.gc``, which takes precedence where a collection overlaps); the
  parts no program span covers fall back to ``tracereduce``'s host events;
* ``stages``: device time of the fused step's ops by ``jax.named_scope``
  (``serve.sample``, ``serve.gather``, ``layer<k>.aggregate``,
  ``layer<k>.dense``), per call, the ``unscoped`` rest, and the ops that
  take most time in each;
* ``requests``: ``queue_wait`` against ``engine_lag`` (the part of the wait
  after the batch was ripe), and for the longest waits the engine states
  that filled them.

The functions below are the reduction; ``tests/test_chipbench_stages.py``
checks them on a CPU trace, a hand-made one and a recorded v5e slice.
"""
from __future__ import annotations

import collections
import os
import re
import statistics
import sys
from typing import Dict, List, Optional, Tuple

if __name__ == "__main__":
    _ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

from benchmarks.chip import tracereduce  # noqa: E402

Event = tracereduce.Event
CLOCK_MARK = "serve.clock"
# an XLA op's JAX name stack is the string stat of its event metadata that
# starts so (``jit(fused)/layer0.aggregate/...``; ``tf_op`` on a v5e)
NAME_STACK_PREFIX = "jit("
# a name-stack component that names a stage of the fused step
STAGE = re.compile(r"^(serve\.(sample|gather)|layer\d+\.(aggregate|dense))$")
GC_SPAN = "host.gc"
ENGINE_PREFIX = "engine."
IDLE_STATE = "engine.idle"


# -- reading the trace -----------------------------------------------------

class Trace:
    """``tracereduce``'s planes, plus what they drop: each ``serve.clock``
    anchor as ``(start_ns, mono_ns)``, and each device plane's XLA ops with
    their name stack."""

    def __init__(self, planes: tracereduce.Planes,
                 anchors: List[Tuple[float, int]],
                 ops: Dict[str, List[Tuple[Event, str]]],
                 stack_stats: Dict[str, int]):
        self.planes = planes
        self.anchors = anchors
        self.ops = ops
        self.stack_stats = stack_stats  # stat name -> op kinds it named


def _xspace_class():
    """The few fields of XLA's ``XSpace`` protobuf (``xplane.proto``) that
    hold each event's metadata stats, which ``ProfileData`` does not
    expose; every other field is skipped when parsing."""
    from google.protobuf import (descriptor_pb2, descriptor_pool,
                                 message_factory)
    F = descriptor_pb2.FieldDescriptorProto
    fd = descriptor_pb2.FileDescriptorProto(
        name="chipbench_xplane.proto", package="chipbench", syntax="proto3")

    def msg(name, fields, parent=None):
        m = parent.nested_type.add() if parent else fd.message_type.add()
        m.name = name
        for fname, num, typ, tname in fields:
            f = m.field.add(name=fname, number=num, type=typ,
                            label=(F.LABEL_REPEATED if fname in
                                   ("stats", "planes", "event_metadata",
                                    "stat_metadata") else F.LABEL_OPTIONAL))
            if tname:
                f.type_name = ".chipbench." + tname
        return m

    msg("XStat", [("metadata_id", 1, F.TYPE_INT64, None),
                  ("str_value", 5, F.TYPE_STRING, None),
                  ("ref_value", 7, F.TYPE_UINT64, None)])
    msg("XEventMetadata", [("name", 2, F.TYPE_STRING, None),
                           ("display_name", 4, F.TYPE_STRING, None),
                           ("stats", 5, F.TYPE_MESSAGE, "XStat")])
    msg("XStatMetadata", [("name", 2, F.TYPE_STRING, None)])
    plane = msg("XPlane", [
        ("name", 2, F.TYPE_STRING, None),
        ("event_metadata", 4, F.TYPE_MESSAGE, "XPlane.EventMetadataEntry"),
        ("stat_metadata", 5, F.TYPE_MESSAGE, "XPlane.StatMetadataEntry")])
    for entry, value in (("EventMetadataEntry", "XEventMetadata"),
                         ("StatMetadataEntry", "XStatMetadata")):
        m = msg(entry, [("key", 1, F.TYPE_INT64, None),
                        ("value", 2, F.TYPE_MESSAGE, value)], parent=plane)
        m.options.map_entry = True
    msg("XSpace", [("planes", 1, F.TYPE_MESSAGE, "XPlane")])
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("chipbench.XSpace"))


def op_stacks(path: str) -> Tuple[Dict[str, str], Dict[str, int]]:
    """Each device op's name (as ``ProfileData`` names its events) -> its
    JAX name stack, from the op's event metadata; and how many ops each
    stat name carried a stack for."""
    space = _xspace_class()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    stacks: Dict[str, str] = {}
    keys: Dict[str, int] = collections.Counter()
    for plane in space.planes:
        if not plane.name.startswith(tracereduce.DEVICE_PREFIX):
            continue
        names = {k: v.name for k, v in plane.stat_metadata.items()}
        for md in plane.event_metadata.values():
            for st in md.stats:
                v = st.str_value or names.get(st.ref_value, "")
                if v.startswith(NAME_STACK_PREFIX):
                    keys[names.get(st.metadata_id, "?")] += 1
                    for n in (md.name, md.display_name):
                        if n:
                            stacks[n] = v
                    break
    return stacks, dict(keys)


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    planes: tracereduce.Planes = {}
    anchors: List[Tuple[float, int]] = []
    ops: Dict[str, List[Tuple[Event, str]]] = {}
    stacks, stack_stats = op_stacks(path)
    for plane in ProfileData.from_file(path).planes:
        lines = planes.setdefault(plane.name, {})
        device = plane.name.startswith(tracereduce.DEVICE_PREFIX)
        for line in plane.lines:
            if device and line.name not in (tracereduce.OPS_LINE,
                                            tracereduce.MODULES_LINE):
                continue
            evs = lines.setdefault(line.name, [])
            for e in line.events:
                ev = Event(e.name, e.start_ns, e.end_ns)
                evs.append(ev)
                if device and line.name == tracereduce.OPS_LINE:
                    ops.setdefault(plane.name, []).append(
                        (ev, stacks.get(e.name, "")))
                elif e.name == CLOCK_MARK:
                    anchors.append((e.start_ns,
                                    int(dict(e.stats)["mono_ns"])))
    return Trace(planes, anchors, ops, stack_stats)


# -- one clock ---------------------------------------------------------------

def clock_offset(anchors: List[Tuple[float, int]]) -> float:
    """Trace ns minus tracer ns, the median over the anchors."""
    if not anchors:
        raise ValueError(f"no {CLOCK_MARK!r} annotation in the trace")
    return statistics.median(s - m for s, m in anchors)


def on_trace(spans: List[dict], offset: float) -> List[Event]:
    """The tracer's spans (``t0``-relative seconds) on the trace's clock."""
    return [Event(s["name"], s["t0"] * 1e9 + offset, s["t1"] * 1e9 + offset)
            for s in spans]


# -- idle gaps ---------------------------------------------------------------

def _owners(program: List[Event]) -> List[Event]:
    """Disjoint owner intervals, in order: the engine states (which tile
    the engine thread), with every ``host.gc`` span cut out of them and
    put in their place."""
    gcs = tracereduce._union([(e.start_ns, e.end_ns) for e in program
                              if e.name == GC_SPAN])
    out = [Event(GC_SPAN, a, b) for a, b in gcs]
    j = 0
    for e in sorted((e for e in program if e.name != GC_SPAN),
                    key=lambda e: e.start_ns):
        t = e.start_ns
        while j < len(gcs) and gcs[j][1] <= t:
            j += 1
        k = j
        while k < len(gcs) and gcs[k][0] < e.end_ns:
            if gcs[k][0] > t:
                out.append(Event(e.name, t, gcs[k][0]))
            t = max(t, gcs[k][1])
            k += 1
        if e.end_ns > t:
            out.append(Event(e.name, t, e.end_ns))
    return sorted(out, key=lambda e: e.start_ns)


def attribute(gaps: List[Tuple[float, float]], program: List[Event],
              planes: tracereduce.Planes) -> Dict[str, float]:
    """Idle seconds per owner: each gap is split among the program spans
    that cover it, by overlap; what no program span covers goes to the
    host event ``tracereduce`` would name."""
    owners = _owners(program)
    total: Dict[str, float] = collections.defaultdict(float)
    rest: List[Tuple[float, float]] = []
    i = 0
    for a, b in gaps:
        while i < len(owners) and owners[i].end_ns <= a:
            i += 1
        t, k = a, i
        while k < len(owners) and owners[k].start_ns < b:
            o = owners[k]
            lo, hi = max(o.start_ns, a), min(o.end_ns, b)
            if lo > t:
                rest.append((t, lo))
            if hi > lo:
                total[o.name] += (hi - lo) / 1e9
            t = max(t, hi)
            k += 1
        if b > t:
            rest.append((t, b))
    for name, sec in tracereduce._attribute(rest, planes):
        total[name] += sec
    return dict(total)


def starved_share(idle: Dict[str, float], window_s: float) -> float:
    """Device idle laid at an engine state other than ``engine.idle`` (work
    was waiting), in % of the window."""
    return 100.0 * sum(s for n, s in idle.items()
                       if n.startswith(ENGINE_PREFIX)
                       and n != IDLE_STATE) / window_s


# -- the fused step's stages -------------------------------------------------

def stage_of(name_stack: str) -> Optional[str]:
    """The first component of ``name_stack`` that names a stage."""
    for part in name_stack.split("/"):
        if STAGE.match(part):
            return part
    return None


def family(stage: str) -> str:
    """``serve.sample`` -> ``sample``, ``layer1.dense`` -> ``dense``."""
    return stage.rsplit(".", 1)[1]


def stage_times(ops: List[Tuple[Event, str]], modules: List[Event],
                lo: float, hi: float, program: str
                ) -> Tuple[Dict[str, List[float]], float, int]:
    """Device seconds of the ops inside ``program``'s calls in [lo, hi]:
    ``{stage: [count, s]}``, the unscoped seconds, and the calls.  The
    stages and the unscoped rest add up to the calls' op time."""
    calls = sorted((e.start_ns, e.end_ns) for e in modules
                   if e.name.split("(")[0] == program
                   and e.end_ns > lo and e.start_ns < hi)
    stages: Dict[str, List[float]] = collections.defaultdict(
        lambda: [0, 0.0])
    unscoped, j = 0.0, 0
    for ev, stack in sorted(ops, key=lambda o: o[0].start_ns):
        while j < len(calls) and calls[j][1] <= ev.start_ns:
            j += 1
        if j == len(calls) or ev.start_ns < calls[j][0]:
            continue
        c = tracereduce._clip(ev, lo, hi)
        if not c:
            continue
        sec = (c[1] - c[0]) / 1e9
        st = stage_of(stack)
        if st is None:
            unscoped += sec
        else:
            stages[st][0] += 1
            stages[st][1] += sec
    return dict(stages), unscoped, len(calls)


def family_ms(stages: Dict[str, List[float]], calls: int
              ) -> Dict[str, float]:
    """Device ms per call of each stage family (sample, gather,
    aggregate, dense)."""
    out: Dict[str, float] = collections.defaultdict(float)
    for st, (_, sec) in stages.items():
        out[family(st)] += 1e3 * sec / max(calls, 1)
    return dict(out)


def top_ops(ops: List[Tuple[Event, str]], lo: float, hi: float,
            k: int = 3, width: int = 90) -> Dict[str, List[list]]:
    """The ``k`` ops that take most device time in each stage."""
    per: Dict[str, Dict[str, float]] = collections.defaultdict(
        lambda: collections.defaultdict(float))
    for ev, stack in ops:
        c = tracereduce._clip(ev, lo, hi)
        if c:
            per[stage_of(stack) or "unscoped"][ev.name[:width]] += \
                (c[1] - c[0]) / 1e9
    return {st: [[n, s] for n, s in sorted(d.items(), key=lambda kv: -kv[1])
                 [:k]] for st, d in per.items()}


# -- requests ------------------------------------------------------------------

def wait_breakdown(traces: List[dict], stages: List[dict], k: int = 5
                   ) -> dict:
    """``queue_wait`` and ``engine_lag`` over the requests, and for the
    ``k`` longest waits the seconds each program span filled of them."""
    rows = []
    for tr in traces:
        sp = {s["name"]: s for s in tr["spans"]}
        if "queue_wait" in sp and "engine_lag" in sp:
            q, g = sp["queue_wait"], sp["engine_lag"]
            rows.append((q["t1"] - q["t0"], g["t1"] - g["t0"], q["t0"],
                         q["t1"], tr["trace"]))
    if not rows:
        return {}
    qw = sorted(r[0] for r in rows)
    lag = sorted(r[1] for r in rows)

    def pct(v, p):
        return 1e3 * v[min(int(p / 100 * len(v)), len(v) - 1)]

    worst = []
    for q, g, a, b, rid in sorted(rows, key=lambda r: -r[0])[:k]:
        fill: Dict[str, float] = collections.defaultdict(float)
        for s in stages:
            ov = min(s["t1"], b) - max(s["t0"], a)
            if ov > 0:
                fill[s["name"]] += 1e3 * ov
        worst.append({"rid": rid, "queue_wait_ms": 1e3 * q,
                      "engine_lag_ms": 1e3 * g, "filled_ms": dict(fill)})
    return {"n": len(rows), "queue_wait_ms": {"p50": pct(qw, 50),
                                              "p95": pct(qw, 95),
                                              "max": 1e3 * qw[-1]},
            "engine_lag_ms": {"p50": pct(lag, 50), "p95": pct(lag, 95),
                              "max": 1e3 * lag[-1]},
            "worst": worst}


# -- one traced run --------------------------------------------------------------

def reduce(trace: Trace, stages: List[dict], traces: List[dict],
           program: str = "jit_fused") -> dict:
    summary = tracereduce.summarize(trace.planes, max_gaps=10 ** 9)
    lo, hi = tracereduce.window_of(trace.planes)
    offset = clock_offset(trace.anchors)
    spans = on_trace(stages, offset)
    dev = sorted(p for p in trace.planes
                 if p.startswith(tracereduce.DEVICE_PREFIX))[0]
    busy = tracereduce._union([c for ev in
                               trace.planes[dev].get(tracereduce.OPS_LINE, [])
                               for c in [tracereduce._clip(ev, lo, hi)] if c])
    idle = attribute(tracereduce._gaps(busy, lo, hi), spans, trace.planes)
    idle_s = sum(idle.values())
    program_s = sum(s for n, s in idle.items()
                    if n.startswith(ENGINE_PREFIX) or n == GC_SPAN)
    ops = trace.ops.get(dev, [])
    st, unscoped, calls = stage_times(
        ops, trace.planes[dev].get(tracereduce.MODULES_LINE, []), lo, hi,
        program)
    scoped = sum(s for _, s in st.values())
    state_s: Dict[str, float] = collections.defaultdict(float)
    for e in spans:
        c = tracereduce._clip(e, lo, hi)
        if c:
            state_s[e.name] += (c[1] - c[0]) / 1e9
    step_s = sum(s for n, (c, s) in summary.modules.items()
                 if n.split("(")[0] == program)
    return {
        "name_stack_stats": {str(k): n for k, n in trace.stack_stats.items()},
        "window_s": summary.window_s, "busy_s": summary.busy_s,
        "idle_s": idle_s, "anchors": len(trace.anchors),
        "anchor_spread_us": (max(s - m for s, m in trace.anchors)
                             - min(s - m for s, m in trace.anchors)) / 1e3,
        "idle": dict(sorted(idle.items(), key=lambda kv: -kv[1])),
        "idle_at_program_share": program_s / idle_s if idle_s else None,
        "starved_share": starved_share(idle, summary.window_s),
        "idle_gaps_before": summary.idle_gaps[:10],
        "engine_state_s": dict(sorted(state_s.items(),
                                      key=lambda kv: -kv[1])),
        "stages": st, "unscoped_s": unscoped, "calls": calls,
        "stage_ms": family_ms(st, calls),
        "step_ms": 1e3 * step_s / calls if calls else None,
        "scoped_share_of_step": scoped / step_s if step_s else None,
        "top_ops": top_ops(ops, lo, hi),
        "requests": wait_breakdown(traces, stages),
    }


def slice_fixture(trace: Trace, program: str = "jit_fused",
                  skip: int = 50, width: int = 100) -> dict:
    """One call of ``program`` from the trace, ``skip`` calls in: its
    module event and every op inside it with its name stack, names cut to
    ``width`` characters."""
    dev = sorted(trace.ops)[0]
    mods = sorted((e for e in trace.planes[dev][tracereduce.MODULES_LINE]
                   if e.name.split("(")[0] == program),
                  key=lambda e: e.start_ns)
    m = mods[min(skip, len(mods) - 1)]
    ops = [[ev.name[:width], ev.start_ns, ev.end_ns, stack[:2 * width]]
           for ev, stack in trace.ops[dev]
           if m.start_ns <= ev.start_ns < m.end_ns]
    return {"source": f"one call of {program} from a profiler trace on a TPU "
                      f"v5 lite: its XLA Ops with their name stacks, names "
                      f"cut to {width} characters",
            "module": [m.name, m.start_ns, m.end_ns], "ops": ops}


def main(argv=None) -> int:
    import argparse
    import json
    import shutil
    import tempfile
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--fixture", default=None,
                    help="write one call's ops and name stacks here")
    args = ap.parse_args(argv)
    import jax
    from benchmarks.chip import harness, loadgen, worldgen
    harness.enable_compile_cache()
    cell = harness.load_cell(args.workload)
    dev = harness.device_info(cell.chips)
    cfg, traffic = cell.config, cell.traffic
    counter = harness.CompileCounter()
    world = worldgen.make_world(cfg, args.seed)
    csr = world.host_csr()
    server = harness.build_server(
        cfg, world, csr, args.seed, tracing=True,
        trace_capacity=harness.trace_capacity(traffic, args.seconds))
    server.warmup(loadgen.buckets_used(traffic,
                                       cfg["serving"]["max_batch_seeds"]))
    log_dir = tempfile.mkdtemp(prefix="enginetrace-")
    try:
        jax.profiler.start_trace(log_dir,
                                 profiler_options=harness.profile_options())
        server.tracer.anchor()
        try:
            harness.run_window(server, traffic, world.n_nodes, args.seed,
                               args.seconds, counter, marks=True)
        finally:
            server.tracer.anchor()
            jax.profiler.stop_trace()
        stages, traces = server.tracer.stages(), server.tracer.traces()
        server.close()
        trace = load(tracereduce.find_xspace(log_dir))
    finally:
        counter.close()
        shutil.rmtree(log_dir, ignore_errors=True)
    out = {"workload": cell.name, "seed": args.seed, "device": dev["kind"],
           "compiles_in_window": len(counter.events)}
    out.update(reduce(trace, stages, traces))
    if args.fixture:
        os.makedirs(os.path.dirname(os.path.abspath(args.fixture)),
                    exist_ok=True)
        with open(args.fixture, "w") as f:
            json.dump(slice_fixture(trace), f)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
