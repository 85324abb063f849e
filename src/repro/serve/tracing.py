"""NeuraScope distributed tracing: per-request span trees (DESIGN.md §14).

One trace per accepted request, identified by the request id — the same
integer the TAG key stream is derived from (``default_tree_keys(rid, n)``),
so a trace, its sampled trees, and its offline replay all share one name.
Spans are **completed intervals**: the engines emit a span only once both
endpoints are known (there is no open/close handle to leak), append-only
into a per-trace list, and the *terminal* span (``settle`` XOR ``error``,
gated on ``ServeRequest.finish``/``fail`` returning ``True``) moves the
finished tree into a bounded ring buffer — and, when a sink is attached,
flushes it as one ``{"kind": "trace", ...}`` line through the TelemetryHub
JSONL flight recorder, sharing the time axis and ``schema_version`` with
the event/sample records already there.

Beside the request trees the tracer keeps the **engine's own time**: the
single-lane engine thread records its loop as consecutive ``engine.*``
stage spans (idle, form, pack, dispatch, sync, scatter, reap), and, while
tracing is on, Python's collector adds ``host.gc`` spans.  Both go to a
bounded ring (``stages``) sized like the trace ring.  ``anchor`` writes
one ``serve.clock`` annotation into a running ``jax.profiler`` trace
carrying the tracer's own time, so a reader of the profile can put every
span on the device trace's clock.

Cost model: tracing is **off by default** — engines built without it hold
``tracer = None`` and their hot loops carry a single ``is None`` test per
stage (the chaos-injector convention).  Enabled, a span is one tuple
append (no dict until flush).  On a TPU v5e, the chip benchmark's closed
Reddit cell (``benchmarks/chip``) served 5,625 and 5,638 seeds/s traced
against 5,622–5,673 untraced: under 1% when no host stall hits.

The span *tree* is two-level by construction: the request is the implicit
root and every span is its child, ordered by emission.  Exactly-once
settlement makes exactly-one-terminal structural: duplicate terminals are
impossible (first transition wins) and late non-terminal spans from a
raced retry/drain are dropped against the recently-closed set instead of
reopening a flushed trace.
"""
from __future__ import annotations

import collections
import gc
import threading
import time
from typing import Callable, Dict, List, Optional

# versions every flight-recorder record (events, samples, traces): bump on
# any breaking change to record shape so neurascope can refuse mismatches
SCHEMA_VERSION = 1

# exactly one of these ends a trace; "shed" is the whole trace for a
# submission rejected at admission (it never gains a request id)
TERMINAL_SPANS = ("settle", "error", "shed")

# the canonical request lifecycle, in pipeline order (the waterfall's row
# order; retry/reroute interleave wherever the failover machinery fired)
STAGE_ORDER = ("submit", "route", "sample", "queue_wait", "engine_lag",
               "bucket_pack", "dispatch", "retry", "reroute", "settle",
               "error", "shed")

# the engine thread's states, one ``stages`` span each, tiling its loop
ENGINE_STATES = ("engine.idle", "engine.form", "engine.pack",
                 "engine.dispatch", "engine.sync", "engine.scatter",
                 "engine.reap")
# the profiler annotation that ties the tracer's clock to the trace's
CLOCK_MARK = "serve.clock"


class Tracer:
    """Thread-safe completed-span recorder with a bounded trace ring.

    ``span`` appends a ``(name, t0, t1, attrs)`` tuple to the trace's open
    list; ``settle`` appends the terminal span, moves the finished tree
    into the ring buffer, and flushes it through ``sink`` (one JSON-ready
    dict per trace).  Times are absolute monotonic-clock values at emit and
    ``t0``-relative in flushed records, so trace spans land on the same
    axis as the TelemetryHub's event/sample timestamps.
    """

    def __init__(self, *, capacity: int = 4096,
                 clock: Callable[[], float] = time.monotonic,
                 t0: Optional[float] = None,
                 sink: Optional[Callable[[dict], None]] = None):
        self.clock = clock
        self.t0 = clock() if t0 is None else float(t0)
        self.capacity = max(int(capacity), 1)
        self.sink = sink
        self._open: Dict[int, list] = {}
        self._done: "collections.deque" = collections.deque(
            maxlen=self.capacity)
        # recently-closed ids: a late span from a raced retry/drain must be
        # dropped, not reopen a flushed trace (bounded like the ring)
        self._closed: "collections.deque" = collections.deque()
        self._closed_set: set = set()
        self._lock = threading.Lock()          # completion path only
        self.n_spans = 0
        self.n_traces = 0
        self.n_dropped = 0                     # late spans against closed ids
        # engine states and collector passes, not tied to one request
        self._stages: "collections.deque" = collections.deque(
            maxlen=self.capacity)
        self._gc_t0: Optional[float] = None

    # -- hot path (engines guard every call with ``tracer is not None``) ----
    def span(self, trace: int, name: str, t0: float, t1: float,
             attrs: Optional[dict] = None):
        """Record one completed interval on ``trace``.  Lock-free append:
        per-trace lists are only ever appended to, and completion swaps the
        whole list out under the lock."""
        if trace in self._closed_set:
            self.n_dropped += 1
            return
        spans = self._open.get(trace)
        if spans is None:
            spans = self._open.setdefault(trace, [])
        spans.append((name, t0, t1, attrs))
        self.n_spans += 1

    def extend(self, trace: int, spans) -> None:
        """Append several completed ``(name, t0, t1, attrs)`` tuples in one
        call — the dispatch loop emits three stages per request, and one
        closed-set check + one dict lookup per *request* (not per span)
        keeps the traced hot loop inside the ≤5% budget."""
        if trace in self._closed_set:
            self.n_dropped += len(spans)
            return
        lst = self._open.get(trace)
        if lst is None:
            lst = self._open.setdefault(trace, [])
        lst.extend(spans)
        self.n_spans += len(spans)

    def stage(self, name: str, t0: float, t1: float,
              attrs: Optional[dict] = None):
        """Record one completed span of the process's own time (an engine
        state, a collector pass) in the bounded stage ring."""
        self._stages.append((name, t0, t1, attrs))

    def settle(self, trace: int, name: str, t0: float, t1: float,
               attrs: Optional[dict] = None):
        """Record the terminal span and complete the trace.  Callers gate
        this on ``ServeRequest.finish``/``fail`` returning ``True``, which
        makes a duplicate terminal structurally impossible."""
        with self._lock:
            if trace in self._closed_set:
                self.n_dropped += 1
                return
            spans = self._open.pop(trace, [])
            spans.append((name, t0, t1, attrs))
            self.n_spans += 1
            self._complete(trace, spans)

    def settle_many(self, items) -> None:
        """Settle a whole dispatch round under one lock acquisition —
        ``items`` is an iterable of ``(trace, name, t0, t1, attrs)``."""
        with self._lock:
            for trace, name, t0, t1, attrs in items:
                if trace in self._closed_set:
                    self.n_dropped += 1
                    continue
                spans = self._open.pop(trace, [])
                spans.append((name, t0, t1, attrs))
                self.n_spans += 1
                self._complete(trace, spans)

    def point(self, name: str, attrs: Optional[dict] = None):
        """A complete single-span trace for work rejected before it has an
        identity — an admission-shed submission has no rid, but the flight
        recorder should still carry one terminal record for it."""
        now = self.clock()
        with self._lock:
            self._complete(None, [(name, now, now, attrs)])

    def _complete(self, trace: Optional[int], spans: list):
        self.n_traces += 1
        self._done.append((trace, spans))
        if trace is not None:
            self._closed.append(trace)
            self._closed_set.add(trace)
            while len(self._closed) > self.capacity:
                self._closed_set.discard(self._closed.popleft())
        if self.sink is not None:
            self.sink(self.record(trace, spans))

    # -- flush / inspection --------------------------------------------------
    def record(self, trace: Optional[int], spans: list) -> dict:
        """Materialize one trace as the flight-recorder dict (`t0`-relative
        times, one span dict per tuple) — built only at completion, never
        on the span hot path."""
        base = self.t0
        out = []
        for name, a, b, attrs in spans:
            s = {"name": name, "t0": a - base, "t1": b - base}
            if attrs:
                s.update(attrs)
            out.append(s)
        return {"kind": "trace", "schema_version": SCHEMA_VERSION,
                "trace": trace, "spans": out}

    def traces(self) -> List[dict]:
        """Every completed trace still in the ring, oldest first."""
        with self._lock:
            snap = list(self._done)
        return [self.record(t, s) for t, s in snap]

    def stages(self) -> List[dict]:
        """Every stage span still in the ring, in the order recorded, on
        the same ``t0``-relative axis as ``traces``."""
        return self.record(None, list(self._stages))["spans"]

    # -- one clock with the profiler -----------------------------------------
    def anchor(self):
        """Write one ``serve.clock`` annotation into the running
        ``jax.profiler`` trace; its ``mono_ns`` stat is this tracer's time
        (``t0``-relative, in ns) at the moment the annotation opens, so the
        trace's start time minus ``mono_ns`` maps a span onto the trace."""
        from jax.profiler import TraceAnnotation
        mono_ns = int(round((self.clock() - self.t0) * 1e9))
        with TraceAnnotation(CLOCK_MARK, mono_ns=mono_ns):
            pass

    # -- the collector -------------------------------------------------------
    def watch_gc(self):
        """Record each pass of Python's collector as a ``host.gc`` stage
        span until ``close``."""
        if self._on_gc not in gc.callbacks:
            gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase: str, info: dict):
        if phase == "start":
            self._gc_t0 = self.clock()
        elif self._gc_t0 is not None:
            self.stage("host.gc", self._gc_t0, self.clock(),
                       {"generation": info.get("generation"),
                        "collected": info.get("collected")})
            self._gc_t0 = None

    def close(self):
        """Stop recording the collector (the span rings stay readable)."""
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)

    def open_traces(self) -> List[int]:
        return list(self._open)

    def stats(self) -> dict:
        return {"traces": self.n_traces, "spans": self.n_spans,
                "open": len(self._open), "dropped": self.n_dropped,
                "capacity": self.capacity}


# ---------------------------------------------------------------------------
# Completeness verification — one home, shared by the property tests and
# ``neurascope --check`` (a CI smoke failure and a test failure must agree)
# ---------------------------------------------------------------------------

def verify_trace(rec: dict) -> List[str]:
    """Problems with one ``{"kind": "trace"}`` record; empty list = a
    complete, well-formed span tree (exactly one terminal span, last; every
    span a forward interval under the versioned schema)."""
    probs: List[str] = []
    trace = rec.get("trace")
    label = f"trace {trace}"
    if rec.get("schema_version") != SCHEMA_VERSION:
        probs.append(f"{label}: schema_version "
                     f"{rec.get('schema_version')!r} != {SCHEMA_VERSION}")
    spans = rec.get("spans")
    if not spans:
        return probs + [f"{label}: no spans"]
    terminals = [s for s in spans if s.get("name") in TERMINAL_SPANS]
    if len(terminals) != 1:
        probs.append(f"{label}: {len(terminals)} terminal spans "
                     f"({[s.get('name') for s in terminals]}), want exactly 1")
    elif spans[-1].get("name") not in TERMINAL_SPANS:
        probs.append(f"{label}: terminal span is not last "
                     f"(last is {spans[-1].get('name')!r})")
    for s in spans:
        name = s.get("name")
        if not isinstance(name, str):
            probs.append(f"{label}: span without a name: {s!r}")
            continue
        t0, t1 = s.get("t0"), s.get("t1")
        if not (isinstance(t0, (int, float)) and isinstance(t1, (int, float))
                and t1 >= t0):
            probs.append(f"{label}: span {name!r} has a malformed interval "
                         f"t0={t0!r} t1={t1!r}")
    return probs


def verify_traces(records) -> List[str]:
    """Problems across a set of trace records: per-trace completeness plus
    no duplicated trace ids (a duplicate means a settled request's tree was
    flushed twice — the exactly-once contract leaking into observability)."""
    probs: List[str] = []
    seen: set = set()
    for rec in records:
        probs.extend(verify_trace(rec))
        trace = rec.get("trace")
        if trace is not None:
            if trace in seen:
                probs.append(f"trace {trace}: duplicate trace record")
            seen.add(trace)
    return probs
