"""Engine states and fused-step stages on the profiler's clock
(``enginetrace``), the metrics that read the engine's new spans, and the
accepted trace metrics pinned on the traces they were set from."""
import json
import os
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from benchmarks.chip import enginetrace as et  # noqa: E402
from benchmarks.chip import harness, tracereduce, worldgen  # noqa: E402
from benchmarks.chip.tests import test_chipbench_trace as recorded  # noqa

E = tracereduce.Event
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


# -- one clock ---------------------------------------------------------------

def test_anchor_maps_a_span_onto_the_profile(tmp_path):
    """A tracer span and a profiler annotation opened at the same moment
    land within 100 us of each other once the anchors map one clock onto
    the other (CPU profiler; the offset is read off each trace)."""
    import jax
    from jax.profiler import TraceAnnotation

    from repro.serve.tracing import Tracer
    tracer = Tracer()
    jax.profiler.start_trace(str(tmp_path))
    try:
        tracer.anchor()
        for _ in range(3):
            time.sleep(0.02)
            t = tracer.clock()
            with TraceAnnotation("probe"):
                tracer.stage("probe", t, t)
        tracer.anchor()
    finally:
        jax.profiler.stop_trace()
    trace = et.load(tracereduce.find_xspace(str(tmp_path)))
    assert len(trace.anchors) == 2
    probes = sorted(e.start_ns for lines in trace.planes.values()
                    for evs in lines.values() for e in evs
                    if e.name == "probe")
    mapped = et.on_trace(tracer.stages(), et.clock_offset(trace.anchors))
    assert len(probes) == len(mapped) == 3
    for want, got in zip(probes, mapped):
        assert abs(got.start_ns - want) < 100e3


def test_on_trace_and_offset_median():
    anchors = [(1_000.0, 10), (2_000.0, 1_005), (3_000.0, 2_000)]
    assert et.clock_offset(anchors) == 995.0
    [ev] = et.on_trace([{"name": "engine.sync", "t0": 1e-6, "t1": 2e-6}],
                       995.0)
    assert ev.name == "engine.sync"
    assert (ev.start_ns, ev.end_ns) == pytest.approx((1995.0, 2995.0))
    with pytest.raises(ValueError):
        et.clock_offset([])


# -- idle gaps ---------------------------------------------------------------

def hand_made():
    """Window [0, 1200] ns; the device runs [0,100], [400,500], [900,950]
    and [1100,1200], so the gaps are [100,400], [500,900], [950,1100]."""
    planes = {
        "/host:CPU": {
            "bench": [E("bench.window", 0, 1200),
                      E("bench.generator_sleep", 600, 1200)],
            "python": [E("np.asarray(jax.Array)", 100, 400)],
        },
        "/device:TPU:0": {
            "XLA Ops": [E("fusion.1", 0, 100), E("fusion.2", 400, 500),
                        E("fusion.3", 900, 950), E("fusion.4", 1100, 1200)],
            "XLA Modules": [],
        },
    }
    program = [E("engine.idle", 0, 50), E("engine.sync", 50, 400),
               E("engine.scatter", 400, 520), E("engine.form", 520, 800),
               E("engine.pack", 800, 900), E("engine.idle", 900, 1000),
               E("host.gc", 700, 760)]
    return planes, program


def test_engine_span_wins_a_gap_the_sync_covers_equally():
    planes, program = hand_made()
    s = tracereduce.summarize(planes)
    gaps = tracereduce._gaps([(0, 100), (400, 500), (900, 950),
                              (1100, 1200)], 0, 1200)
    # without the engine's spans the first gap goes to JAX's own event
    assert dict(s.idle_gaps)["np.asarray(jax.Array)"] == \
        pytest.approx(300e-9)
    idle = et.attribute(gaps, program, planes)
    want = {"engine.sync": 300, "engine.scatter": 20, "engine.form": 220,
            "host.gc": 60, "engine.pack": 100, "engine.idle": 50,
            "bench.generator_sleep": 100}      # past the engine's last span
    assert idle == pytest.approx({k: v * 1e-9 for k, v in want.items()})
    assert sum(idle.values()) == pytest.approx(s.window_s - s.busy_s)
    # work was waiting in sync, scatter, form and pack: 640 of 1200 ns
    assert et.starved_share(idle, s.window_s) == pytest.approx(
        100 * 640 / 1200)


def test_collector_cuts_into_the_engine_state_it_overlaps():
    owners = et._owners([E("engine.form", 0, 100), E("engine.pack", 100, 200),
                         E("host.gc", 50, 150), E("host.gc", 180, 190)])
    assert [(o.name, o.start_ns, o.end_ns) for o in owners] == [
        ("engine.form", 0, 50), ("host.gc", 50, 150),
        ("engine.pack", 150, 180), ("host.gc", 180, 190),
        ("engine.pack", 190, 200)]


# -- the fused step's stages -------------------------------------------------

def test_op_stacks_read_the_ops_event_metadata(tmp_path):
    """The name stack sits in each op's event metadata, as a string stat
    or a reference to an interned one; host planes are not read."""
    space = et._xspace_class()()
    dev = space.planes.add(name="/device:TPU:0")
    dev.stat_metadata[1].name = "tf_op"
    dev.stat_metadata[2].name = "jit(fused)/layer0.dense/dot_general"
    dev.stat_metadata[3].name = "hlo_category"
    md = dev.event_metadata[7]
    md.name, md.display_name = "%fusion.6 = f32[4416,602] fusion(...)", "f6"
    md.stats.add(metadata_id=3, str_value="data formatting")
    md.stats.add(metadata_id=1,
                 str_value="jit(fused)/serve.gather/jit(_take)/gather")
    dev.event_metadata[8].name = "%dot.1 = f32[16,41] dot(...)"
    dev.event_metadata[8].stats.add(metadata_id=1, ref_value=2)
    dev.event_metadata[9].name = "%copy.1 = f32[232966,602] copy(...)"
    dev.event_metadata[9].stats.add(metadata_id=1, str_value="store.x")
    host = space.planes.add(name="/host:CPU")
    host.stat_metadata[1].name = "tf_op"
    host.event_metadata[1].name = "np.asarray"
    host.event_metadata[1].stats.add(metadata_id=1, str_value="jit(x)/y")
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(space.SerializeToString())
    stacks, keys = et.op_stacks(str(path))
    assert stacks == {
        "%fusion.6 = f32[4416,602] fusion(...)":
            "jit(fused)/serve.gather/jit(_take)/gather",
        "f6": "jit(fused)/serve.gather/jit(_take)/gather",
        "%dot.1 = f32[16,41] dot(...)": "jit(fused)/layer0.dense/dot_general"}
    assert keys == {"tf_op": 2}
    assert et.stage_of(stacks["f6"]) == "serve.gather"


def test_stage_times_by_hand():
    ops = [(E("%fusion.1", 10, 20), "jit(fused)/serve.sample/gather"),
           (E("%copy.1", 20, 50), ""),
           (E("%fusion.2", 50, 60), "jit(fused)/serve.gather/take"),
           (E("%sc.1", 60, 80), "jit(fused)/layer0.aggregate/pallas_call"),
           (E("%dot.1", 80, 95), "jit(fused)/layer0.dense/dot_general"),
           (E("%dot.2", 95, 99), "jit(fused)/layer1.dense/dot_general"),
           (E("%other", 150, 160), "jit(warm)/serve.sample/x")]  # no call
    mods = [E("jit_fused(1)", 5, 100), E("jit_warm(2)", 140, 170)]
    stages, unscoped, calls = et.stage_times(ops, mods, 0, 200, "jit_fused")
    assert calls == 1 and unscoped == pytest.approx(30e-9)
    assert stages == pytest.approx({
        "serve.sample": [1, 10e-9], "serve.gather": [1, 10e-9],
        "layer0.aggregate": [1, 20e-9], "layer0.dense": [1, 15e-9],
        "layer1.dense": [1, 4e-9]})
    assert et.family_ms(stages, calls) == pytest.approx({
        "sample": 1e-5, "gather": 1e-5, "aggregate": 2e-5, "dense": 1.9e-5})
    assert et.stage_of("jit(fused)/layer12.aggregate/x") == "layer12.aggregate"
    assert et.stage_of("jit(fused)/layer0.aggregated/x") is None


def test_recorded_step_stages_add_up():
    """One call of the Reddit bucket-16 step from a v5e trace: the four
    stages and the unscoped rest add up to the call's op time, and the
    rest is the relayout of the ``store.x`` argument (``%copy.1``), which
    no scope owns."""
    with open(os.path.join(DATA, "trace_reddit_stages.json")) as f:
        rec = json.load(f)
    mod = E(*rec["module"])
    ops = [(E(n, a, b), stack) for n, a, b, stack in rec["ops"]]
    stages, unscoped, calls = et.stage_times(ops, [mod], mod.start_ns,
                                             mod.end_ns, "jit_fused")
    op_s = sum(min(e.end_ns, mod.end_ns) - e.start_ns for e, _ in ops) / 1e9
    assert calls == 1
    assert sum(s for _, s in stages.values()) + unscoped == \
        pytest.approx(op_s)
    assert set(stages) == {"serve.sample", "serve.gather", "layer0.aggregate",
                           "layer0.dense", "layer1.aggregate", "layer1.dense"}
    ms = et.family_ms(stages, calls)
    assert ms == pytest.approx({"sample": 0.09863, "gather": 0.039583,
                                "aggregate": 0.799518, "dense": 0.020513})
    [copy] = [(e, st) for e, st in ops if e.name.startswith("%copy.1 = ")]
    assert copy[1] == "" and "copy(f32[232966,602]{0,1" in copy[0].name
    assert (copy[0].end_ns - copy[0].start_ns) / 1e9 > 0.9 * unscoped


# -- the new metrics, and the parent's spans -----------------------------------

def _ctx(traces, rids):
    return {"requests": {"rid": np.asarray(rids)}, "spans": traces}


def _trace(rid, wait, lag, rnd, n_seeds, bucket):
    attrs = {"bucket": bucket, "round": rnd}
    if n_seeds is not None:
        attrs["n_seeds"] = n_seeds
    spans = [{"name": "queue_wait", "t0": 0.0, "t1": wait}]
    if lag is not None:
        spans.append({"name": "engine_lag", "t0": wait - lag, "t1": wait})
    spans.append(dict(name="bucket_pack", t0=wait, t1=wait, **attrs))
    return {"trace": rid, "spans": spans}


def test_engine_lag_and_batch_fill_readers():
    lags = np.linspace(0.0, 0.099, 100)
    traces = [_trace(i, 0.2, float(lags[i]), i // 2, 3, 4)
              for i in range(100)]
    traces.append(_trace(999, 0.2, 5.0, 99, 1, 1))   # outside the window
    ctx = _ctx(traces, list(range(100)))
    assert harness.reader("engine_lag_p95_ms")(ctx) == pytest.approx(
        1e3 * np.percentile(lags, 95))
    assert harness.reader("batch_fill.open")(ctx) == pytest.approx(75.0)


def test_new_readers_find_nothing_in_the_parents_spans():
    traces = [_trace(i, 0.2, None, i, None, 4) for i in range(8)]
    ctx = _ctx(traces, list(range(8)))
    assert harness.reader("engine_lag_p95_ms")(ctx) is None
    assert harness.reader("batch_fill.open")(ctx) is None


# -- the accepted trace metrics read what they read before ---------------------

PINNED = {  # the readers of the parent commit on the same two traces
    "hand": {"idle_share": 68.0, "idle_share.open": 68.0,
             "step_ms": 0.00037, "step_ms.open": 0.00037,
             "gustavson_roofline": None, "gustavson_roofline.open": None,
             "step_mfu.open": 720.583103306352},
    "recorded": {"idle_share": 2.087299999999992,
                 "idle_share.open": 2.087299999999992,
                 "step_ms": 1.4666310000000002,
                 "step_ms.open": 1.4666310000000002,
                 "gustavson_roofline": 1.8511696730154248,
                 "gustavson_roofline.open": 1.8511696730154248,
                 "step_mfu.open": 0.09089394272429473},
}


@pytest.mark.parametrize("trace,metric", [(t, m) for t in PINNED
                                          for m in PINNED[t]])
def test_accepted_trace_metrics_are_unchanged(trace, metric):
    planes = (recorded.hand_made() if trace == "hand"
              else recorded.recorded()[0])
    with open(os.path.join(ROOT, "benchmarks/chip/configs/sage-reddit.json")
              ) as f:
        cfg = json.load(f)
    with open(os.path.join(ROOT, "benchmarks/chip/peaks.json")) as f:
        peaks = json.load(f)["devices"]["TPU v5 lite"]
    ctx = {"trace": tracereduce.summarize(planes), "peaks": peaks,
           "config": cfg, "dims": worldgen.layer_dims(cfg), "batches": 2,
           "seeds_submitted": 32}
    want = PINNED[trace][metric]
    got = harness.reader(metric)(ctx)
    assert got == (None if want is None else pytest.approx(want, rel=1e-12))
