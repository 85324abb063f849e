"""Streaming metrics plane + per-class SLO burn-rate engine (DESIGN.md §15).

Histogram/exposition/SLO logic is pure host code on virtual clocks — no
jax, no wall-clock flake.  The jax-backed tests at the bottom prove the
full wiring: a live ``ClusterServer`` under unreachable latency targets
must shed best_effort before any interactive request, and its scraped
``/metrics`` exposition must agree with the engine's own summary; a
metered ``GNNServer`` must export every required family.

Property tests run under real ``hypothesis`` when installed, else the
deterministic shim (``tests/_hypothesis_shim.py``).
"""
import math
import threading
import time
import urllib.request

import pytest

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:  # deterministic fallback; requirements-dev.txt has the real one
    from _hypothesis_shim import given, settings, st

from repro.serve.metrics import (BUCKET_UPPERS, HIST_MIN, N_BUCKETS,
                                 LatencyHistogram, MetricsRegistry,
                                 bucket_index, bucket_lower, bucket_upper,
                                 histogram_counts_from_samples,
                                 parse_exposition, quantile_from_counts)
from repro.serve.slo import (CLASSES, DEFAULT_SLOS, SHED_ORDER, ClassSLO,
                             SLOEngine)
from repro.serve.telemetry import TelemetryHub

# latencies as integer microseconds, 1 µs .. ~16 s — spans the whole ladder
lat_us = st.integers(min_value=1, max_value=16_000_000)


# ---------------------------------------------------------------------------
# Bucket scheme
# ---------------------------------------------------------------------------

def test_bucket_bounds_partition_the_line():
    assert bucket_lower(0) == 0.0 and bucket_upper(0) == HIST_MIN
    for i in range(1, N_BUCKETS):
        assert bucket_upper(i - 1) == bucket_lower(i)
        assert bucket_upper(i) / bucket_upper(i - 1) == pytest.approx(
            math.sqrt(2.0))
    assert bucket_upper(N_BUCKETS) == math.inf


@settings(max_examples=200)
@given(lat_us)
def test_bucket_index_contains_its_value(us):
    v = us / 1e6
    i = bucket_index(v)
    assert bucket_lower(i) < v <= bucket_upper(i)


def test_bucket_index_exact_boundaries_land_inside():
    # v == upper must stay in bucket i ((lower, upper] is right-closed)
    for i in (0, 1, 7, N_BUCKETS - 1):
        assert bucket_index(BUCKET_UPPERS[i]) == i


# ---------------------------------------------------------------------------
# Mergeable histograms: per-lane merge bounds the true percentile
# ---------------------------------------------------------------------------

@settings(max_examples=60)
@given(st.lists(lat_us, min_size=1, max_size=120),
       st.integers(min_value=1, max_value=5))
def test_merged_lane_histograms_bound_true_percentiles(us_list, n_lanes):
    """Round-robin observations across per-lane histograms, merge, and the
    exact q-quantile order statistic must lie inside the merged histogram's
    reported bucket — the one-bucket exactness contract merging promises."""
    vals = [us / 1e6 for us in us_list]
    lanes = [LatencyHistogram() for _ in range(n_lanes)]
    for k, v in enumerate(vals):
        lanes[k % n_lanes].observe(v)
    merged = LatencyHistogram()
    for h in lanes:
        merged.merge(h)
    assert merged.count == len(vals)
    assert merged.sum == pytest.approx(sum(vals))
    ordered = sorted(vals)
    for q in (0.5, 0.95, 0.99):
        exact = ordered[min(max(math.ceil(q * len(vals)), 1),
                            len(vals)) - 1]
        lo, hi = merged.quantile_bounds(q)
        assert lo < exact <= hi, (q, exact, lo, hi)
        assert merged.quantile(q) == hi


def test_quantile_from_counts_empty_and_rank_clamp():
    assert quantile_from_counts([0] * (N_BUCKETS + 1), 0.99) == -1
    counts = [0] * (N_BUCKETS + 1)
    counts[5] = 1
    for q in (0.0, 0.5, 1.0):
        assert quantile_from_counts(counts, q) == 5


# ---------------------------------------------------------------------------
# Counters / gauges
# ---------------------------------------------------------------------------

@settings(max_examples=60)
@given(st.lists(st.integers(min_value=0, max_value=1000), min_size=1,
                max_size=50))
def test_counter_is_monotonic_across_increments(incs):
    reg = MetricsRegistry()
    c = reg.counter("events_total")
    seen = []
    for n in incs:
        c.inc(n, kind="x")
        seen.append(c.value(kind="x"))
    assert seen == sorted(seen)
    assert seen[-1] == sum(incs)


def test_counter_rejects_negative_increment():
    c = MetricsRegistry().counter("events_total")
    with pytest.raises(ValueError):
        c.inc(-1)


def test_family_kind_mismatch_raises():
    reg = MetricsRegistry()
    reg.counter("x")
    with pytest.raises(ValueError):
        reg.gauge("x")


# ---------------------------------------------------------------------------
# Exposition round-trip
# ---------------------------------------------------------------------------

def _registry_with_everything():
    reg = MetricsRegistry()
    reg.counter("requests_total", "served requests").inc(
        7, outcome="served", **{"class": "interactive"})
    reg.gauge("queue", "batcher depth").set(3.5, lane="0")
    h = reg.histogram("request_latency_seconds", "e2e latency")
    for us in (90, 300, 300, 5000, 250_000):
        h.observe(us / 1e6, exemplar=f"rid-{us}", **{"class": "interactive"})
    return reg


@settings(max_examples=30)
@given(st.lists(lat_us, min_size=1, max_size=60))
def test_histogram_round_trips_through_exposition(us_list):
    """render → parse → rebuilt non-cumulative counts must equal the
    original bucket counts exactly (the ``le`` bounds re-parse to the
    shared float64 bounds)."""
    reg = MetricsRegistry()
    h = reg.histogram("request_latency_seconds")
    for us in us_list:
        h.observe(us / 1e6, **{"class": "batch"})
    fams = parse_exposition(reg.render())
    samples = fams["neurachip_request_latency_seconds"]["samples"]
    counts = histogram_counts_from_samples(samples, {"class": "batch"})
    assert counts == h.labeled(**{"class": "batch"}).counts
    for q in (0.5, 0.99):
        assert (quantile_from_counts(counts, q)
                == quantile_from_counts(
                    h.labeled(**{"class": "batch"}).counts, q))


def test_exposition_text_shape_and_values():
    reg = _registry_with_everything()
    text = reg.render()
    assert "# TYPE neurachip_requests_total counter" in text
    assert "# HELP neurachip_queue batcher depth" in text
    fams = parse_exposition(text)
    (_, labels, v, _), = fams["neurachip_requests_total"]["samples"]
    assert v == 7 and labels == {"outcome": "served",
                                 "class": "interactive"}
    assert fams["neurachip_queue"]["samples"][0][2] == 3.5
    hist = fams["neurachip_request_latency_seconds"]
    assert hist["type"] == "histogram"
    names = {n for n, _, _, _ in hist["samples"]}
    assert {"neurachip_request_latency_seconds_bucket",
            "neurachip_request_latency_seconds_sum",
            "neurachip_request_latency_seconds_count"} <= names
    count = [v for n, _, v, _ in hist["samples"] if n.endswith("_count")][0]
    assert count == 5
    # cumulative buckets are non-decreasing and end at the total count
    les = [(float("inf") if l["le"] == "+Inf" else float(l["le"]), v)
           for n, l, v, _ in hist["samples"] if n.endswith("_bucket")]
    vals = [v for _, v in sorted(les)]
    assert vals == sorted(vals) and vals[-1] == 5


def test_exemplars_survive_the_round_trip():
    reg = _registry_with_everything()
    fams = parse_exposition(reg.render())
    ex = {e for _, _, _, e in
          fams["neurachip_request_latency_seconds"]["samples"]
          if e is not None}
    ids = {trace_id for trace_id, _ in ex}
    assert "rid-90" in ids and "rid-250000" in ids
    # the exemplar value is the observed latency, inside its bucket
    for trace_id, v in ex:
        i = bucket_index(v)
        assert bucket_lower(i) < v <= bucket_upper(i)


# ---------------------------------------------------------------------------
# TelemetryHub feed
# ---------------------------------------------------------------------------

def test_connect_hub_refreshes_gauges_and_counter_totals():
    t = {"now": 0.0}
    hub = TelemetryHub(2, clock=lambda: t["now"])
    reg = MetricsRegistry()
    reg.connect_hub(hub)
    hub.register_probe("queue_depth", lambda: [3, 7])
    hub.count("served", 0, 4)
    hub.sample()
    lane = reg.gauge("lane")
    assert lane.value(lane="0", field="queue_depth") == 3.0
    assert lane.value(lane="1", field="queue_depth") == 7.0
    tot = reg.counter("telemetry_total")
    assert tot.value(lane="0", counter="served") == 4
    # totals stay monotonic across ticks as the hub counts up
    hub.count("served", 0, 2)
    hub.sample()
    assert tot.value(lane="0", counter="served") == 6


def test_render_is_thread_safe_under_concurrent_observes():
    reg = MetricsRegistry()
    h = reg.histogram("request_latency_seconds")
    stop = threading.Event()

    def pound():
        k = 0
        while not stop.is_set():
            h.observe((k % 1000 + 1) / 1e4, **{"class": "batch"})
            k += 1

    thread = threading.Thread(target=pound)
    thread.start()
    try:
        for _ in range(20):
            fams = parse_exposition(reg.render())
            samples = fams["neurachip_request_latency_seconds"]["samples"]
            counts = histogram_counts_from_samples(samples,
                                                   {"class": "batch"})
            cnt = [v for n, _, v, _ in samples if n.endswith("_count")]
            assert sum(counts) == int(cnt[0])
    finally:
        stop.set()
        thread.join()


# ---------------------------------------------------------------------------
# SLO burn-rate engine (virtual clock)
# ---------------------------------------------------------------------------

def _engine(**kw):
    t = {"now": 0.0}
    kw.setdefault("clock", lambda: t["now"])
    kw.setdefault("slos", [ClassSLO("interactive", 10.0, 0.01),
                           ClassSLO("batch", 10.0, 0.05),
                           ClassSLO("best_effort", 10.0, 0.20)])
    kw.setdefault("fast_window", 1.0)
    kw.setdefault("slow_window", 5.0)
    kw.setdefault("sustain_ticks", 2)
    kw.setdefault("recover_ticks", 3)
    return SLOEngine(**kw), t


def _burn_all(eng, t, seconds, n=10):
    for c in CLASSES:
        for _ in range(n):
            eng.observe(c, seconds)


def test_burn_rate_is_violation_fraction_over_budget():
    eng, t = _engine()
    for _ in range(8):
        eng.observe("batch", 0.001)        # under the 10 ms target
    for _ in range(2):
        eng.observe("batch", 0.5)          # over
    t["now"] = 0.5
    eng.tick()
    s = eng.summary()["batch"]
    # 2/10 violations over budget 0.05 → burn 4.0 on both windows
    assert s["burn_fast"] == pytest.approx(4.0)
    assert s["burn_slow"] == pytest.approx(4.0)
    assert s["n"] == 10 and s["violations"] == 2


def test_quiet_class_has_zero_burn():
    eng, t = _engine()
    t["now"] = 1.0
    eng.tick()
    assert all(s["burn_fast"] == 0.0 for s in eng.summary().values())


def test_shed_order_best_effort_first_then_batch_never_interactive():
    eng, t = _engine(sustain_ticks=2)
    evs = []
    for k in range(1, 7):
        _burn_all(eng, t, 0.5)             # everything violates
        t["now"] = 0.1 * k
        evs += eng.tick()
    # tick 2 sheds best_effort; the escalation needs a fresh sustain,
    # so batch sheds on tick 4
    assert [(e["cls"], e["on"]) for e in evs] == [
        ("best_effort", True), ("batch", True)]
    assert eng.shed_classes == frozenset(SHED_ORDER)
    assert not eng.should_shed("interactive")
    assert eng.should_shed("best_effort") and eng.should_shed("batch")
    for e in evs:
        assert e["burn_fast"] > eng.burn_threshold


def test_transient_spike_does_not_shed():
    """One hot tick under sustain_ticks=2 then quiet — no shed event."""
    eng, t = _engine(sustain_ticks=2)
    _burn_all(eng, t, 0.5)
    t["now"] = 0.1
    assert eng.tick() == []
    # fast window (1 s) slides past the burst; slow keeps it — not both hot
    for k in range(2, 6):
        t["now"] = k * 1.0
        assert eng.tick() == []
    assert eng.shed_classes == frozenset()


def test_recovery_unsheds_in_reverse_after_quiet_ticks():
    eng, t = _engine(sustain_ticks=1, recover_ticks=2)
    _burn_all(eng, t, 0.5)
    t["now"] = 0.1
    eng.tick()                             # sheds best_effort
    t["now"] = 0.2
    eng.tick()                             # escalates to batch
    assert eng.shed_classes == frozenset(SHED_ORDER)
    evs = []
    for k in range(1, 10):
        t["now"] = 10.0 + k                # windows empty: cool ticks
        evs += eng.tick()
        if not eng.shed_classes:
            break
    assert [(e["cls"], e["on"]) for e in evs] == [
        ("batch", False), ("best_effort", False)]


def test_engine_writes_burn_and_shed_gauges():
    reg = MetricsRegistry()
    eng, t = _engine(registry=reg, sustain_ticks=1)
    _burn_all(eng, t, 0.5)
    t["now"] = 0.1
    eng.tick()
    g = reg.gauge("slo_burn_rate")
    s = eng.summary()
    for c in CLASSES:
        assert g.value(**{"class": c, "window": "fast"}) == pytest.approx(
            s[c]["burn_fast"])
    assert reg.gauge("slo_shed").value(**{"class": "best_effort"}) == 1.0
    assert reg.gauge("slo_shed").value(**{"class": "interactive"}) == 0.0
    # observes flowed into the registry histogram too
    hist = reg.histogram("request_latency_seconds")
    assert hist.labeled(**{"class": "interactive"}).count == 10


def test_default_slos_cover_every_class_and_validate():
    assert tuple(s.name for s in DEFAULT_SLOS) == CLASSES
    with pytest.raises(ValueError):
        ClassSLO("premium", 10.0, 0.01)
    with pytest.raises(ValueError):
        ClassSLO("batch", 10.0, 0.0)
    with pytest.raises(ValueError):
        SLOEngine(slos=[ClassSLO("batch", 10.0, 0.1)])


# ---------------------------------------------------------------------------
# HTTP exposition endpoint
# ---------------------------------------------------------------------------

def test_metrics_server_serves_render_and_healthz():
    from repro.launch.metrics_server import MetricsServer
    reg = _registry_with_everything()
    srv = MetricsServer(reg.render, port=0)
    try:
        assert srv.port > 0
        with urllib.request.urlopen(srv.url, timeout=10) as resp:
            assert resp.status == 200
            fams = parse_exposition(resp.read().decode())
        assert "neurachip_requests_total" in fams
        ex = [e for _, _, _, e in
              fams["neurachip_request_latency_seconds"]["samples"]
              if e is not None]
        assert ex, "exemplars must survive the HTTP round trip"
        health = srv.url.rsplit("/", 1)[0] + "/healthz"
        with urllib.request.urlopen(health, timeout=10) as resp:
            assert resp.read() == b"ok\n"
    finally:
        srv.close()


# ---------------------------------------------------------------------------
# Full wiring: live cluster sheds by class and exports truthfully
# ---------------------------------------------------------------------------

def test_cluster_slo_sheds_best_effort_before_interactive():
    """End-to-end: unreachable targets drive the burn over threshold; the
    admission arm must reject best_effort with a typed, class-carrying
    ``Overloaded`` while interactive keeps flowing, and the scraped
    exposition must agree with ``stats()['classes']`` (p99 within one
    bucket)."""
    import numpy as np

    from repro.launch.gnn_serve import build_world
    from repro.serve import ClusterServer, Overloaded

    cfg, params, indptr, indices, store = build_world("gcn", 256, 1024, 8,
                                                      seed=0)
    slos = [ClassSLO("interactive", 1.0, 0.01),
            ClassSLO("batch", 1.0, 0.05),
            ClassSLO("best_effort", 1.0, 0.20)]
    srv = ClusterServer("gcn", cfg, params, indptr, indices, store,
                        n_lanes=2, fanouts=(2, 2), backend="dense", seed=0,
                        telemetry_interval=0.02, slo=slos,
                        slo_fast_window=5.0, slo_slow_window=30.0,
                        slo_sustain_ticks=1, slo_recover_ticks=10**6,
                        metrics_port=0)
    rng = np.random.default_rng(1)
    shed = {"interactive": 0, "best_effort": 0}
    int_after_shed = 0
    with srv:
        srv.warmup()
        for _ in range(40):
            pend = []
            for cls in ("interactive", "best_effort"):
                try:
                    pend.append(srv.submit(rng.integers(0, 256, 2),
                                           cls=cls))
                    if cls == "interactive" and shed["best_effort"]:
                        int_after_shed += 1
                except Overloaded as e:
                    assert e.cls == cls
                    shed[cls] += 1
            for r in pend:
                r.wait_done(timeout=60)
            if shed["best_effort"] >= 3 and int_after_shed >= 3:
                break
        time.sleep(0.06)         # one hub tick past the last observation
        st_classes = srv.stats()["classes"]
        with urllib.request.urlopen(srv.stats()["metrics_url"],
                                    timeout=10) as resp:
            fams = parse_exposition(resp.read().decode())
        summary = srv.slo.summary()
        events = [e for e in srv.telemetry.events
                  if e.get("event") == "shed_class" and e.get("on")]
    assert shed["best_effort"] >= 3 and shed["interactive"] == 0
    assert int_after_shed >= 3
    assert events and events[0]["cls"] == "best_effort"
    assert st_classes["best_effort"]["shed"]
    assert not st_classes["interactive"]["shed"]
    hist = fams["neurachip_request_latency_seconds"]["samples"]
    for cls, s in st_classes.items():
        if not s["n"]:
            continue
        counts = histogram_counts_from_samples(hist, {"class": cls})
        scraped = quantile_from_counts(counts, 0.99)
        assert abs(scraped - bucket_index(s["p99_ms"] / 1e3)) <= 1
    # the exported fast-window burn gauge tracks the engine's own summary
    burn = {labels.get("class"): v for _n, labels, v, _ex in
            fams["neurachip_slo_burn_rate"]["samples"]
            if labels.get("window") == "fast"}
    for cls, s in summary.items():
        if s["n"]:
            ref = max(abs(s["burn_fast"]), 1.0)
            assert abs(burn.get(cls, 0.0) - s["burn_fast"]) / ref <= 0.25


# ---------------------------------------------------------------------------
# The single-lane engine's exposition endpoint
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("sampler", ["host", "device"])
def test_engine_metrics_endpoint_exports_required_families(sampler):
    """A metered ``GNNServer`` serves a parseable exposition, scraped while
    it is up, that holds every family an operator's dashboard reads."""
    from repro.launch.gnn_serve import build_world
    from repro.serve import GNNServer

    cfg, params, indptr, indices, store = build_world("gcn", 256, 1024, 8,
                                                      seed=0)
    with GNNServer("gcn", cfg, params, indptr, indices, store,
                   fanouts=(2, 2), backend="dense", sampler=sampler,
                   max_batch_seeds=4, max_wait_ms=1.0, seed=0,
                   metrics_port=0) as srv:
        for r in [srv.submit([s]) for s in range(0, 256, 16)]:
            r.wait(120)
        with urllib.request.urlopen(srv.stats()["metrics_url"],
                                    timeout=10) as resp:
            assert resp.status == 200
            fams = parse_exposition(resp.read().decode())
    for fam in ("neurachip_requests_total",
                "neurachip_request_latency_seconds", "neurachip_queue",
                "neurachip_cache_hit_rate"):
        assert fams.get(fam, {}).get("samples"), fam
