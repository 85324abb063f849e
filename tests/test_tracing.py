"""NeuraScope tracing (DESIGN.md §14): span-tree completeness properties.

The contract under test, end to end: every **accepted** request — served,
retried, re-routed across a lane kill, deadline-expired, or force-failed at
close — yields **exactly one** complete span tree with **exactly one**
terminal span (``settle`` XOR ``error``), and tracing disabled allocates
nothing at all.  ``tracing.verify_trace``/``verify_traces`` is the single
verifier shared with ``neurascope --check`` (``tests/test_neurascope.py``),
so the two always agree on what "well-formed" means.
"""
import gc
import json
import os
import time

import numpy as np
import pytest

from repro.launch.gnn_serve import build_world
from repro.serve import (ChaosInjector, ClusterServer, GNNServer, LaneFault,
                         Overloaded, TelemetryHub, percentiles_ms)
from repro.serve.tracing import (ENGINE_STATES, SCHEMA_VERSION,
                                 TERMINAL_SPANS, Tracer, verify_trace,
                                 verify_traces)

try:
    from hypothesis import given, settings, strategies as st
except ImportError:                              # pragma: no cover
    from tests._hypothesis_shim import given, settings, st

N = 4                                     # lanes in every cluster test


def _world(arch="sage", n_nodes=256, seed=0):
    return build_world(arch, n_nodes, 4 * n_nodes, 8, seed=seed)


def _server(world, **kw):
    cfg, params, indptr, indices, store = world
    kw.setdefault("fanouts", (2, 2))
    kw.setdefault("backend", "dense")
    kw.setdefault("max_batch_seeds", 4)
    return GNNServer("sage", cfg, params, indptr, indices, store, **kw)


def _cluster(world, chaos=None, **kw):
    cfg, params, indptr, indices, store = world
    kw.setdefault("n_lanes", N)
    kw.setdefault("fanouts", (2, 2))
    kw.setdefault("backend", "dense")
    kw.setdefault("seed", 0)
    kw.setdefault("max_batch_seeds", 4)
    kw.setdefault("telemetry_interval", 0.02)
    kw.setdefault("tracing", True)
    return ClusterServer("sage", cfg, params, indptr, indices, store,
                         chaos=chaos, **kw)


def _assert_one_tree_per_request(tracer, reqs, allow_shed=0):
    """The core property: exactly one well-formed trace per accepted
    request, terminal agreeing with the request's settled state."""
    recs = tracer.traces()
    assert verify_traces(recs) == []
    by_id = {r["trace"]: r for r in recs if r["trace"] is not None}
    rids = {r.rid for r in reqs}
    assert set(by_id) >= rids, \
        f"missing traces for rids {sorted(rids - set(by_id))[:5]}"
    for req in reqs:
        spans = by_id[req.rid]["spans"]
        terminal = spans[-1]["name"]
        assert req.n_settles == 1
        if req.error is None:
            assert terminal == "settle", \
                f"rid {req.rid} served but terminal is {terminal}"
        else:
            assert terminal == "error", \
                f"rid {req.rid} failed but terminal is {terminal}"
    assert tracer.stats()["open"] == 0          # nothing half-finished


# ---------------------------------------------------------------------------
# Tracer unit behaviour (pure host logic, virtual time)
# ---------------------------------------------------------------------------

def test_tracer_records_and_ring_bound():
    t = [0.0]
    tr = Tracer(capacity=4, clock=lambda: t[0], t0=0.0)
    for i in range(10):
        tr.span(i, "sample", 0.0, 1.0, {"lane": 0})
        tr.settle(i, "settle", 1.0, 1.0)
    recs = tr.traces()
    assert len(recs) == 4                        # ring keeps the newest
    assert [r["trace"] for r in recs] == [6, 7, 8, 9]
    assert verify_traces(recs) == []
    st_ = tr.stats()
    assert st_["traces"] == 10 and st_["spans"] == 20 and st_["open"] == 0
    # record shape: versioned, t0-relative, attrs inlined
    rec = recs[0]
    assert rec["kind"] == "trace"
    assert rec["schema_version"] == SCHEMA_VERSION
    assert rec["spans"][0] == {"name": "sample", "t0": 0.0, "t1": 1.0,
                               "lane": 0}


def test_tracer_stage_ring_bound_and_collector_spans():
    t = [0.0]
    tr = Tracer(capacity=4, clock=lambda: t[0], t0=0.0)
    for i in range(10):
        tr.stage("engine.reap", float(i), i + 0.5, {"round": i})
    st_ = tr.stages()
    assert [s["round"] for s in st_] == [6, 7, 8, 9]    # newest kept
    assert st_[0] == {"name": "engine.reap", "t0": 6.0, "t1": 6.5,
                      "round": 6}
    tr.watch_gc()
    try:
        t[0] = 20.0
        tr._on_gc("start", {"generation": 2})
        t[0] = 20.25
        tr._on_gc("stop", {"generation": 2, "collected": 7})
        gc.collect()                              # a real pass is recorded
    finally:
        tr.close()
    assert tr._on_gc not in gc.callbacks
    gcs = [s for s in tr.stages() if s["name"] == "host.gc"]
    assert gcs[0] == {"name": "host.gc", "t0": 20.0, "t1": 20.25,
                      "generation": 2, "collected": 7}
    assert len(gcs) >= 2


def test_tracer_drops_late_spans_after_settlement():
    tr = Tracer(capacity=8, clock=lambda: 0.0, t0=0.0)
    tr.span(1, "sample", 0.0, 1.0)
    tr.settle(1, "settle", 1.0, 1.0)
    tr.span(1, "retry", 2.0, 2.0)                # raced straggler
    tr.settle(1, "error", 2.0, 2.0)              # raced duplicate terminal
    recs = tr.traces()
    assert len(recs) == 1 and len(recs[0]["spans"]) == 2
    assert tr.stats()["dropped"] == 2
    assert tr.stats()["open"] == 0               # nothing reopened


def test_tracer_point_and_sink():
    flushed = []
    tr = Tracer(capacity=8, clock=lambda: 1.5, t0=1.0, sink=flushed.append)
    tr.point("shed", {"n": 3})
    assert len(flushed) == 1
    rec = flushed[0]
    assert rec["trace"] is None
    assert rec["spans"] == [{"name": "shed", "t0": 0.5, "t1": 0.5, "n": 3}]
    assert verify_trace(rec) == []


def test_verify_trace_catches_malformations():
    ok = {"kind": "trace", "schema_version": SCHEMA_VERSION, "trace": 1,
          "spans": [{"name": "sample", "t0": 0.0, "t1": 1.0},
                    {"name": "settle", "t0": 1.0, "t1": 1.0}]}
    assert verify_trace(ok) == []
    no_terminal = dict(ok, spans=[{"name": "sample", "t0": 0.0, "t1": 1.0}])
    assert any("terminal" in p for p in verify_trace(no_terminal))
    two_terminals = dict(ok, spans=ok["spans"] + [
        {"name": "error", "t0": 1.0, "t1": 1.0}])
    assert any("terminal" in p for p in verify_trace(two_terminals))
    not_last = dict(ok, spans=list(reversed(ok["spans"])))
    assert any("not last" in p for p in verify_trace(not_last))
    backwards = dict(ok, spans=[{"name": "sample", "t0": 2.0, "t1": 1.0},
                                ok["spans"][1]])
    assert any("malformed interval" in p for p in verify_trace(backwards))
    stale = dict(ok, schema_version=SCHEMA_VERSION + 1)
    assert any("schema_version" in p for p in verify_trace(stale))
    empty = dict(ok, spans=[])
    assert any("no spans" in p for p in verify_trace(empty))
    dup = verify_traces([ok, dict(ok)])
    assert any("duplicate" in p for p in dup)
    # shed point-traces carry trace=None and must NOT count as duplicates
    shed = {"kind": "trace", "schema_version": SCHEMA_VERSION, "trace": None,
            "spans": [{"name": "shed", "t0": 0.0, "t1": 0.0}]}
    assert verify_traces([shed, dict(shed)]) == []


@settings(max_examples=20)
@given(st.lists(st.integers(min_value=0, max_value=49), min_size=1,
                max_size=60))
def test_tracer_property_random_interleavings(ids):
    """Arbitrary span/settle interleavings over reused ids: every flushed
    record is well-formed and ids never produce two live records (the
    closed-set guard)."""
    flushed = []
    tr = Tracer(capacity=128, clock=lambda: 0.0, t0=0.0,
                sink=flushed.append)
    settled = set()
    for i, trace in enumerate(ids):
        if trace in settled:
            tr.span(trace, "retry", float(i), float(i))      # late — dropped
            continue
        tr.span(trace, "sample", float(i), float(i) + 0.5)
        if i % 3 != 0:
            tr.settle(trace, "settle" if i % 2 else "error",
                      float(i) + 0.5, float(i) + 0.5)
            settled.add(trace)
    # settle the stragglers the way drain would
    for trace in list(tr.open_traces()):
        tr.settle(trace, "error", 99.0, 99.0, {"error": "DrainTimeout"})
    assert verify_traces(flushed) == []
    assert {r["trace"] for r in flushed} == set(ids)
    assert tr.stats()["open"] == 0


# ---------------------------------------------------------------------------
# Disabled tracing: zero allocation, zero stats surface
# ---------------------------------------------------------------------------

def test_tracing_disabled_allocates_nothing():
    hooks = list(gc.callbacks)
    srv = _server(_world())
    with srv:
        assert srv.tracer is None
        reqs = [srv.submit([i % 256]) for i in range(8)]
        srv.drain(timeout=120)
        assert all(r.error is None for r in reqs)
        assert "tracing" not in srv.stats()
        # no stage span either: the engine keeps no state clock, and no
        # collector hook is installed
        assert not hasattr(srv, "_t_state")
        assert gc.callbacks == hooks
    csrv = _cluster(_world(), tracing=False)
    with csrv:
        assert csrv.tracer is None
        for r in csrv.submit_many([[i % 256] for i in range(8)]):
            r.wait(120)
        assert "tracing" not in csrv.stats()


# ---------------------------------------------------------------------------
# Engine span trees: happy path, retries, deadlines, close
# ---------------------------------------------------------------------------

def test_engine_happy_path_span_trees():
    srv = _server(_world(), tracing=True)
    with srv:
        reqs = [srv.submit([i % 256]) for i in range(16)]
        srv.drain(timeout=120)
        _assert_one_tree_per_request(srv.tracer, reqs)
        rec = srv.tracer.traces()[0]
        names = [s["name"] for s in rec["spans"]]
        assert names == ["sample", "queue_wait", "engine_lag", "bucket_pack",
                         "dispatch", "settle"]
        # stats surface for operators
        ts = srv.stats()["tracing"]
        assert ts["traces"] == 16 and ts["dropped"] == 0


@pytest.mark.parametrize("sampler", ["host", "device"])
def test_engine_states_tile_the_loop(sampler):
    """The engine thread's ``engine.*`` stage spans follow one another with
    no gap, in loop order; every dispatched round has one pack, one
    dispatch and one sync; every request's ``engine_lag`` lies inside its
    ``queue_wait``."""
    srv = _server(_world(), tracing=True, sampler=sampler, max_wait_ms=2.0)
    with srv:
        reqs = [srv.submit([i % 256]) for i in range(12)]    # size trigger
        for i in range(6):                                   # deadline
            time.sleep(0.01)
            reqs.append(srv.submit([(5 * i) % 256]))
        srv.drain(timeout=120)
    _assert_one_tree_per_request(srv.tracer, reqs)
    eng = [s for s in srv.tracer.stages() if s["name"] != "host.gc"]
    assert {s["name"] for s in eng} <= set(ENGINE_STATES)
    assert {"engine.idle", "engine.pack", "engine.dispatch", "engine.sync",
            "engine.scatter", "engine.reap"} <= {s["name"] for s in eng}
    for a, b in zip(eng, eng[1:]):
        assert b["t0"] == a["t1"] and b["t1"] >= b["t0"]
    rounds = {}
    for s in eng:
        if "round" in s:
            rounds.setdefault(s["round"], []).append(s["name"])
    assert len(rounds) == srv.stats()["n_batches"]
    for names in rounds.values():
        assert names == ["engine.pack", "engine.dispatch", "engine.sync",
                         "engine.scatter"]
    for rec in srv.tracer.traces():
        sp = {s["name"]: s for s in rec["spans"]}
        q, lag = sp["queue_wait"], sp["engine_lag"]
        assert q["t0"] <= lag["t0"] <= lag["t1"] == q["t1"]
        assert sp["bucket_pack"]["n_seeds"] <= sp["bucket_pack"]["bucket"]


def test_engine_deadline_expiry_yields_error_terminal():
    srv = _server(_world(), tracing=True, max_wait_ms=40.0)
    with srv:
        # a deadline in the past expires in the reaper before any dispatch
        req = srv.submit([3], deadline_ms=0.01)
        req.wait_done(120)
        srv.drain(timeout=120)
        assert req.error is not None
        _assert_one_tree_per_request(srv.tracer, [req])
        rec = next(r for r in srv.tracer.traces() if r["trace"] == req.rid)
        assert rec["spans"][-1]["name"] == "error"
        assert rec["spans"][-1]["error"] == "DeadlineExceeded"


# no deadline: each example starts and drains a server's threads
@settings(max_examples=5, deadline=None)
@given(st.integers(min_value=1, max_value=24))
def test_engine_property_every_accepted_request_traced(n_requests):
    srv = _server(_world(), tracing=True)
    with srv:
        reqs = [srv.submit([(7 * i) % 256]) for i in range(n_requests)]
        srv.drain(timeout=120)
        _assert_one_tree_per_request(srv.tracer, reqs)


# ---------------------------------------------------------------------------
# Cluster span trees under chaos: kill, retry, shed, forced close
# ---------------------------------------------------------------------------

def test_cluster_happy_path_has_route_span():
    srv = _cluster(_world())
    with srv:
        reqs = srv.submit_many([[i % 256] for i in range(16)])
        srv.drain(timeout=120)
        _assert_one_tree_per_request(srv.tracer, reqs)
        rec = srv.tracer.traces()[0]
        names = [s["name"] for s in rec["spans"]]
        assert names[0] == "route" and names[-1] == "settle"
        assert "sample" in names and "dispatch" in names


def test_cluster_lane_kill_traces_reroutes():
    chaos = ChaosInjector(seed=0, lane_faults=[LaneFault(lane=1, at_round=2)])
    srv = _cluster(_world(), chaos=chaos, stall_timeout=0.15,
                   restart_after=0.4)
    with srv:
        srv.warmup()
        reqs = srv.submit_many([[i % 256] for i in range(64)])
        srv.drain(timeout=120)
        _assert_one_tree_per_request(srv.tracer, reqs)
        assert srv.stats()["reroutes"] >= 1
        # the stranded queue's traces carry the reroute hop
        rerouted = [r for r in srv.tracer.traces()
                    if any(s["name"] == "reroute" for s in r["spans"])]
        assert rerouted, "lane kill produced no reroute spans"
        for rec in rerouted:
            hop = next(s for s in rec["spans"] if s["name"] == "reroute")
            assert hop["from"] != hop["to"]


def test_cluster_transient_step_fault_traces_retry():
    chaos = ChaosInjector(seed=0, step_fault_rounds=(1,))
    srv = _cluster(_world(), chaos=chaos, max_retries=1)
    with srv:
        srv.warmup()
        reqs = srv.submit_many([[i % 256] for i in range(16)])
        srv.drain(timeout=120)
        _assert_one_tree_per_request(srv.tracer, reqs)
        retried = [r for r in srv.tracer.traces()
                   if any(s["name"] == "retry" for s in r["spans"])]
        assert retried, "injected step fault produced no retry spans"
        for rec in retried:                      # retried AND settled once
            assert rec["spans"][-1]["name"] in TERMINAL_SPANS


def _all_lanes_wedged():
    return ChaosInjector(seed=0, lane_faults=[LaneFault(lane=i)
                                              for i in range(N)])


def test_cluster_shed_emits_point_traces_and_close_settles_backlog():
    srv = _cluster(_world(), chaos=_all_lanes_wedged(), stall_timeout=60.0,
                   shed_queue_hwm=8, shed_sustain_ticks=1)
    accepted = srv.submit_many([[i % 256] for i in range(24)])
    deadline = time.monotonic() + 30
    while not srv._shedding and time.monotonic() < deadline:
        time.sleep(0.01)
    shed = 0
    for i in range(16):
        try:
            accepted.append(srv.submit([i % 256]))
        except Overloaded:
            shed += 1
    srv.close()                        # flush serves the wedged backlog
    assert shed >= 1
    recs = srv.tracer.traces()
    assert verify_traces(recs) == []
    shed_recs = [r for r in recs if r["trace"] is None]
    assert len(shed_recs) == shed
    assert all(r["spans"][0]["name"] == "shed" for r in shed_recs)
    _assert_one_tree_per_request(srv.tracer, accepted)


# ---------------------------------------------------------------------------
# Flight recorder hardening: schema versioning + size-bounded rotation
# ---------------------------------------------------------------------------

def test_jsonl_schema_version_and_rotation(tmp_path):
    path = str(tmp_path / "flight.jsonl")
    t = [0.0]
    hub = TelemetryHub(2, jsonl_path=path, jsonl_max_bytes=2048,
                       clock=lambda: t[0])
    tracer = Tracer(capacity=64, clock=lambda: t[0], t0=hub.t0,
                    sink=hub.emit)
    for i in range(40):
        t[0] += 0.01
        hub.event("tick", i=i)
        tracer.span(i, "sample", t[0], t[0])
        tracer.settle(i, "settle", t[0], t[0])
    hub.stop()
    assert hub.jsonl_rotations >= 1
    assert os.path.exists(path) and os.path.exists(path + ".1")
    recs = []
    for p in (path + ".1", path):
        with open(p) as f:
            recs += [json.loads(line) for line in f]
    assert all(r["schema_version"] == SCHEMA_VERSION for r in recs)
    kinds = {r["kind"] for r in recs}
    assert kinds == {"event", "trace"}
    assert verify_traces([r for r in recs if r["kind"] == "trace"]) == []
    # rotation is single-slot: total retained bytes stay bounded
    total = os.path.getsize(path) + os.path.getsize(path + ".1")
    assert total <= 2 * 2048 + 512


def test_percentiles_ms_shared_helper():
    assert percentiles_ms([]) == {"p50_ms": 0.0, "p95_ms": 0.0,
                                  "p99_ms": 0.0}
    out = percentiles_ms([0.001 * (i + 1) for i in range(100)])
    assert out["p50_ms"] == pytest.approx(50.5, rel=0.02)
    assert out["p95_ms"] == pytest.approx(95.05, rel=0.02)
    assert out["p99_ms"] == pytest.approx(99.01, rel=0.02)
    assert out["p50_ms"] <= out["p95_ms"] <= out["p99_ms"]
