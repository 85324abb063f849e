"""The GNN inference server: request + data + compute planes wired up.

``GNNServer`` owns a resident graph (host CSR for the sampler, device
``FeatureStore`` for the models) and serves seed-node requests:

1. ``submit(seeds)`` hands the request to sampler **worker threads** — one
   fanout tree per seed (``sparse.sampler``), per-request deterministic rng
   so offline replay sees identical subgraphs.  Under ``sampler="device"``
   there are no workers at all: the request carries only its seeds and two
   uint32 counter terms per tree, joins the batcher immediately, and the
   fanout sampling runs *inside the dispatched bucket step* on device
   (``serve.device_sampler`` — draw-for-draw equal to the host sampler, so
   the offline-replay parity anchor is unchanged);
2. sampled requests join the ``DynamicBatcher`` (deadline/size triggers);
3. the engine thread stacks a batch's trees into the request-count bucket
   (``bucket_for`` → power of two, bounded jit-cache key space), fetches the
   bucket's step from the ``StepCache`` and dispatches it.  JAX's async
   dispatch plus an in-flight queue of depth 2 double-buffers host sampling
   and batch assembly against device compute;
4. results scatter back per request (seed rows of the bucket output) and
   the request's latency clock stops.

``offline_inference`` is the correctness anchor: the same trees, one
request at a time through the bucket-1 step — serving output must match it
to ≤1e-5.
"""
from __future__ import annotations

import collections
import queue
import threading
import time
from typing import Dict, List, Optional, Sequence

import numpy as np

from repro.serve.batcher import DynamicBatcher, ServeRequest
from repro.serve.buckets import (all_buckets, bucket_for,
                                 build_bucket_structure, stack_trees)
from repro.serve.errors import (DeadlineExceeded, DrainTimeout,
                                RetriesExhausted, SamplerError, ServeError,
                                ServerClosed, TransientStepError)
from repro.serve.compute import (FeatureStore, StepCache, _arch_key,
                                 build_infer_step, resident)
from repro.serve.telemetry import percentiles_ms
from repro.serve.tracing import Tracer
from repro.sparse import sampler
from repro.sparse.plan import plan_cache_info

# span attrs are read-only once emitted — hot-path spans share one dict
_DEVICE_SAMPLE_ATTRS = {"mode": "device"}


def _needs_loops(arch_id: str) -> bool:
    return _arch_key(arch_id) == "gcn"


def default_tree_keys(rid: int, n: int) -> np.ndarray:
    """One counter-hash stream per (request, seed index): deterministic,
    independent of how requests group into sampling calls — the key layout
    every serving engine in the repo (single-lane and cluster) shares, so
    offline replay re-derives the exact served trees from ``rid`` alone."""
    return (np.uint64(rid) << np.uint64(16)) + np.arange(n, dtype=np.uint64)


class SamplerPool:
    """Data-plane worker pool shared by the single-lane server and the
    cluster tier: samples each submitted request's fanout trees
    (``sparse.sampler``) on daemon threads, draining whatever else is queued
    into one vectorized forest pass (the counter-based draws make grouped
    sampling identical to per-request sampling), then hands the request to
    ``on_ready``.  A failing request is isolated and reported through
    ``on_error`` without killing its groupmates or the worker."""

    def __init__(self, indptr: np.ndarray, indices: np.ndarray,
                 fanouts: Sequence[int], key: int, *,
                 on_ready, on_error, n_workers: int = 2,
                 tree_keys=default_tree_keys, group_cap: int = 64,
                 fault_hook=None):
        # the resident CSR lives in ONE tuple so a live graph swap
        # (repro.serve.live) is a single atomic reference flip: every
        # worker snapshots the tuple once per group and never sees a
        # torn (new indptr, old indices) pair
        self._graph = (np.asarray(indptr), np.asarray(indices), 0)
        self.fanouts = tuple(int(f) for f in fanouts)
        self.key = key
        self.tree_keys = tree_keys
        self.on_ready = on_ready
        self.on_error = on_error
        # chaos seam: called with each request before sampling; a raise is
        # handled exactly like a real sampling failure (isolation path)
        self.fault_hook = fault_hook
        self.group_cap = int(group_cap)
        self._q: "queue.Queue[Optional[ServeRequest]]" = queue.Queue()
        self._workers = [threading.Thread(target=self._worker, daemon=True,
                                          name=f"gnn-serve-sampler-{i}")
                         for i in range(max(int(n_workers), 1))]
        for w in self._workers:
            w.start()

    @property
    def indptr(self) -> np.ndarray:
        return self._graph[0]

    @property
    def indices(self) -> np.ndarray:
        return self._graph[1]

    @property
    def graph_epoch(self) -> int:
        return self._graph[2]

    def set_graph(self, indptr: np.ndarray, indices: np.ndarray,
                  epoch: Optional[int] = None) -> int:
        """Atomically swap the resident CSR (live graph mutation).  Groups
        already snapshotted keep sampling the old arrays; every later group
        sees the new graph whole.  Returns the new graph epoch."""
        epoch = self._graph[2] + 1 if epoch is None else int(epoch)
        self._graph = (np.asarray(indptr), np.asarray(indices), epoch)
        return epoch

    def submit(self, req: ServeRequest):
        self._q.put(req)

    def submit_block(self, reqs: Sequence[ServeRequest]):
        """Enqueue a pre-formed block as ONE queue item — a worker folds the
        whole block into a single vectorized forest pass (the bulk-ingest
        path: per-item queue overhead would otherwise dominate a burst)."""
        if reqs:
            self._q.put(list(reqs))

    def sample_for(self, seeds, rid: int) -> list:
        """The pool's sampling, re-runnable offline (parity anchor)."""
        seeds = np.atleast_1d(np.asarray(seeds, np.int64))
        indptr, indices, _ = self._graph
        return sampler.sample_forest(indptr, indices, seeds,
                                     self.fanouts, key=self.key,
                                     tree_keys=self.tree_keys(
                                         rid, seeds.shape[0]))

    def _sample_group(self, group):
        if self.fault_hook is not None:
            for r in group:
                self.fault_hook(r)
        # one snapshot per group: every request in the group samples the
        # same graph epoch, even if set_graph flips mid-pass
        indptr, indices, epoch = self._graph
        seeds_all = np.concatenate([r.seeds for r in group])
        keys = np.concatenate([self.tree_keys(r.rid, r.n_seeds)
                               for r in group])
        trees = sampler.sample_forest(indptr, indices, seeds_all,
                                      self.fanouts, key=self.key,
                                      tree_keys=keys)
        i = 0
        for req in group:                     # assign everything first so a
            req.trees = trees[i:i + req.n_seeds]  # failure submits nothing
            req.graph_epoch = epoch
            i += req.n_seeds
        for req in group:
            self.on_ready(req)

    def _sample_isolated(self, group):
        """Per-request fallback: innocent groupmates still serve."""
        for r in group:
            try:
                self._sample_group([r])
            except Exception as exc:  # noqa: BLE001
                self.on_error([r], exc)

    def _worker(self):
        while True:
            req = self._q.get()
            if req is None:
                return
            group = list(req) if isinstance(req, list) else [req]
            while len(group) < self.group_cap:
                try:
                    nxt = self._q.get_nowait()
                except queue.Empty:
                    break
                if nxt is None:           # shutdown sentinel: hand it back
                    self._q.put(None)
                    break
                group.extend(nxt) if isinstance(nxt, list) else \
                    group.append(nxt)
            try:
                self._sample_group(group)
            except Exception:  # noqa: BLE001 — isolate the bad request(s);
                # the worker (and every later request routed to it) survives
                self._sample_isolated(group)

    def close(self, timeout: Optional[float] = None):
        """Join the workers, then sample anything still queued (parked
        behind a sentinel) inline on the calling thread — everything
        submitted before ``close`` still reaches ``on_ready``."""
        for _ in self._workers:
            self._q.put(None)
        for w in self._workers:
            # unbounded by default: a worker always terminates (its group is
            # bounded and sampling is finite).  A caller tearing down over a
            # possibly-wedged stack passes ``timeout`` — a straggler's late
            # ``on_ready`` is harmless because request settlement is
            # idempotent (first transition wins).
            w.join(timeout)
        leftovers = []
        while True:
            try:
                item = self._q.get_nowait()
            except queue.Empty:
                break
            if item is not None:
                leftovers.extend(item) if isinstance(item, list) else \
                    leftovers.append(item)
        if leftovers:
            try:
                self._sample_group(leftovers)
            except Exception:  # noqa: BLE001
                self._sample_isolated(leftovers)


class GNNServer:
    """Dynamic-batching inference server over a resident graph."""

    def __init__(self, arch_id: str, cfg, params, indptr: np.ndarray,
                 indices: np.ndarray, store: FeatureStore, *,
                 fanouts: Sequence[int] = (5, 3), backend: str = "dense",
                 sampler: str = "host",
                 max_batch_seeds: int = 16, max_wait_ms: float = 5.0,
                 n_workers: int = 2, seed: int = 0,
                 step_cache_size: int = 16, inflight: int = 2,
                 chaos=None, max_retries: int = 1,
                 tracing: bool = False, trace_capacity: int = 4096,
                 metrics: bool = False, metrics_port: Optional[int] = None,
                 clock=time.monotonic):
        self.arch_id = arch_id
        self.cfg = cfg
        self.params = params
        self.indptr = np.asarray(indptr)
        self.indices = np.asarray(indices)
        # committed row-major once: the steps gather rows from it
        self.store = resident(store)
        self.fanouts = tuple(int(f) for f in fanouts)
        self.backend = backend
        self.max_batch_seeds = int(max_batch_seeds)
        self.seed = seed
        self.clock = clock
        self.inflight_depth = max(int(inflight), 1)
        self.chaos = chaos                # fault injector; None = no chaos
        self.max_retries = max(int(max_retries), 0)
        self._round_no = 0                # dispatch counter (chaos trigger)
        # NeuraScope tracing — same convention as chaos: None when off, so
        # the hot loops pay one ``is None`` test per stage and allocate
        # nothing (the property tests pin the zero-span claim)
        self.tracer = (Tracer(capacity=trace_capacity, clock=clock)
                       if tracing else None)
        if self.tracer is not None:
            self.tracer.watch_gc()

        self.batcher = DynamicBatcher(self.max_batch_seeds,
                                      max_wait_ms / 1e3, clock=clock)
        self.steps = StepCache(self._build_step, maxsize=step_cache_size)
        self._structs: Dict[int, object] = {}

        self._rid_lock = threading.Lock()
        self._next_rid = 0
        self.requests: Dict[int, ServeRequest] = {}

        # metrics — latencies keep a sliding window so a long-lived server
        # doesn't grow without bound; percentiles are over recent traffic
        self._stats_lock = threading.Lock()
        self.bucket_counts: Dict[int, int] = collections.Counter()
        self.bucket_hits = 0            # batches landing in a warm bucket
        self.n_served = 0
        self.n_deadline_failed = 0
        self.latencies: "collections.deque[float]" = collections.deque(
            maxlen=4096)

        # online metrics plane (opt-in; chaos convention — None when off,
        # one ``is None`` test on the settle path when dark)
        self.metrics = None
        self._metrics_server = None
        self._m_latency = self._m_requests = None
        if metrics or metrics_port is not None:
            from repro.serve.metrics import MetricsRegistry
            self.metrics = MetricsRegistry()
            self._m_latency = self.metrics.histogram(
                "request_latency_seconds", "end-to-end request latency")
            self._m_requests = self.metrics.counter(
                "requests_total", "settled requests by outcome")
            self._m_queue = self.metrics.gauge(
                "queue", "dynamic-batcher queue state")
            self._m_cache = self.metrics.gauge(
                "cache_hit_rate", "host plan/step cache hit rates")
            self.metrics.connect_kernel_stats()
            self.metrics.register_pull(self._pull_metrics)
            if metrics_port is not None:
                from repro.launch.metrics_server import MetricsServer
                self._metrics_server = MetricsServer(self.metrics.render,
                                                     port=metrics_port)

        # data plane: host sampler worker pool, or the device plane — where
        # sampling runs INSIDE the per-bucket jitted step (seeds + counter
        # keys in, no host node tables at all; serve.device_sampler)
        if sampler not in ("host", "device"):
            raise ValueError(f"sampler must be 'host' or 'device', "
                             f"got {sampler!r}")
        self.sampler_mode = sampler
        if sampler == "device":
            from repro.serve.device_sampler import DeviceSamplerPlane
            self._sampler = None
            self._plane = DeviceSamplerPlane(self.indptr, self.indices,
                                             self.fanouts, key=seed)
        else:
            self._plane = None
            self._sampler = SamplerPool(
                self.indptr, self.indices, self.fanouts, seed,
                # tracing picks the wrapper at construction — the untraced
                # sampler→batcher hand-off carries no branch at all
                on_ready=(self.batcher.submit if self.tracer is None
                          else self._on_sampled_traced),
                on_error=self._fail_requests, n_workers=n_workers,
                fault_hook=(chaos.sampler_hook if chaos is not None
                            else None))
        # compute plane: engine loop + in-flight double buffer
        self._closing = False
        self._close_lock = threading.Lock()
        self._stop = threading.Event()
        self._inflight: "collections.deque" = collections.deque()
        self._engine = threading.Thread(target=self._engine_loop, daemon=True,
                                        name="gnn-serve-engine")
        self._engine.start()

    # -- request plane ------------------------------------------------------
    def submit(self, seeds, *,
               deadline_ms: Optional[float] = None) -> ServeRequest:
        if self._closing:
            raise RuntimeError("server is closed; no worker will serve this")
        seeds = np.atleast_1d(np.asarray(seeds, np.int64))
        # reject malformed requests synchronously — an exception past this
        # point would land in a worker thread instead of the caller
        n_graph = self.indptr.shape[0] - 1
        if seeds.size == 0 or seeds.size > self.max_batch_seeds:
            raise ValueError(
                f"request carries {seeds.size} seeds; must be in "
                f"[1, {self.max_batch_seeds}] (the bucket cap)")
        if (seeds < 0).any() or (seeds >= n_graph).any():
            raise ValueError(
                f"seed ids {seeds[(seeds < 0) | (seeds >= n_graph)]} out of "
                f"range for the resident graph ({n_graph} nodes)")
        with self._rid_lock:
            rid = self._next_rid
            self._next_rid += 1
            now = self.clock()
            req = ServeRequest(
                rid=rid, seeds=seeds, t_submit=now,
                deadline=(now + deadline_ms / 1e3
                          if deadline_ms is not None else None))
            self.requests[rid] = req
        if self._plane is not None:
            # device sampling: the host's whole data-plane job is two uint32
            # per seed (the tree-key counter term); the request joins the
            # batcher immediately — there is no sampling queue to wait in
            from repro.serve.device_sampler import tree_key_mix
            req.tkm = tree_key_mix(default_tree_keys(rid, seeds.shape[0]))
            req.t_ready = self.clock()
            if self.tracer is not None:
                # the host's whole data-plane stage is the key mix above —
                # the span keeps the tree shape uniform across sampler modes
                self.tracer.span(rid, "sample", now, req.t_ready,
                                 _DEVICE_SAMPLE_ATTRS)
            self.batcher.submit(req)
        else:
            self._sampler.submit(req)
        return req

    def _on_sampled_traced(self, req: ServeRequest):
        """Tracing-on sampler hand-off: the sample span covers the whole
        data-plane stage (pool queue wait + the vectorized forest pass)."""
        self.tracer.span(req.rid, "sample", req.t_submit, self.clock())
        self.batcher.submit(req)

    # -- data plane ---------------------------------------------------------
    def _fail_requests(self, reqs, exc: BaseException):
        """Fail exactly ``reqs`` with a typed error carrying each request
        id; the sampler worker and the engine loop survive (the isolation
        contract — a bad request never wedges its pipeline stage)."""
        now = self.clock()
        with self._rid_lock:
            for req in reqs:
                self.requests.pop(req.rid, None)
        for req in reqs:
            err = exc if isinstance(exc, ServeError) \
                else SamplerError(req.rid, exc)
            if req.fail(err, now) and self.tracer is not None:
                self.tracer.settle(req.rid, "error", now, now,
                                   {"error": type(err).__name__})

    def sample_for(self, seeds, rid: int) -> list:
        """The data plane's sampling, re-runnable offline (parity anchor).

        Deliberately always the HOST sampler, even in device mode: the
        bit-exact counter-hash emulation makes the device draws identical,
        so host replay is the independent oracle the parity gate compares
        device-sampled serving against.
        """
        if self._sampler is not None:
            return self._sampler.sample_for(seeds, rid)
        seeds = np.atleast_1d(np.asarray(seeds, np.int64))
        return sampler.sample_forest(self.indptr, self.indices, seeds,
                                     self.fanouts, key=self.seed,
                                     tree_keys=default_tree_keys(
                                         rid, seeds.shape[0]))

    # -- compute plane ------------------------------------------------------
    def _build_step(self, key: tuple):
        (bucket,) = key
        struct = self._struct(bucket)
        if self._plane is None:
            return build_infer_step(self.arch_id, self.cfg, self.store,
                                    struct, backend=self.backend)
        # fused dispatch: sampling + feature gather + GNN forward in ONE
        # jitted program per bucket — the step's traced inputs shrink from
        # the stacked node tables to seeds + per-tree counter keys (the
        # resident CSR and features are bound arguments, not constants)
        import functools

        import jax
        body = build_infer_step(self.arch_id, self.cfg, self.store, struct,
                                backend=self.backend, jit=False)

        def fused(plane, store, params, seeds, tk_hi, tk_lo, live):
            with jax.named_scope("serve.sample"):
                node_ids, hop_valid = plane.sample_bucket(seeds, tk_hi,
                                                          tk_lo, live)
            return body(store, params, node_ids, hop_valid)

        return functools.partial(jax.jit(fused), self._plane, self.store)

    def _struct(self, bucket: int):
        if bucket not in self._structs:
            self._structs[bucket] = build_bucket_structure(
                bucket, self.fanouts, with_loops=_needs_loops(self.arch_id))
        return self._structs[bucket]

    def _device_batch(self, batch: List[ServeRequest], bucket: int):
        """Pack a batch's seeds + counter terms into the bucket's lanes
        (padding lanes: live=False ⇒ the traced sampler blanks them)."""
        seeds = np.zeros(bucket, np.int32)
        tk_hi = np.zeros(bucket, np.uint32)
        tk_lo = np.zeros(bucket, np.uint32)
        live = np.zeros(bucket, bool)
        i = 0
        for r in batch:
            k = r.n_seeds
            seeds[i:i + k] = r.seeds
            tk_hi[i:i + k], tk_lo[i:i + k] = r.tkm
            live[i:i + k] = True
            i += k
        return seeds, tk_hi, tk_lo, live

    def _dispatch(self, batch: List[ServeRequest]):
        self._round_no += 1
        if self.chaos is not None and self.chaos.step_fault(self._round_no):
            self._retry_batch(batch, TransientStepError(self._round_no))
            return
        tr = self.tracer
        t_pack0 = self.clock() if tr is not None else 0.0
        n_trees = sum(r.n_seeds for r in batch)
        bucket = bucket_for(n_trees, self.max_batch_seeds)
        warm = self.steps.builds
        step = self.steps.get((bucket,))
        if self._plane is None:
            trees = [t for r in batch for t in r.trees]
            node_ids, hop_valid = stack_trees(trees, bucket, self.fanouts)
            t_pack1 = self.clock() if tr is not None else 0.0
            out = step(self.params, node_ids, hop_valid)   # async dispatch
        else:
            packed = self._device_batch(batch, bucket)
            t_pack1 = self.clock() if tr is not None else 0.0
            out = step(self.params, *packed)
        attrs = None
        if tr is not None:
            # queue_wait ends where packing starts; engine_lag is its part
            # after the batch was ripe (or the request joined, if later);
            # dispatch is the async step call only — the device window
            # shows up as the gap between dispatch.t1 and the settle span
            t_disp = self.clock()
            attrs = {"bucket": bucket, "round": self._round_no,
                     "n_seeds": n_trees}
            t_ripe = self.batcher.t_ripe
            for r in batch:
                tr.extend(r.rid, (("queue_wait", r.t_ready, t_pack0, None),
                                  ("engine_lag", max(t_ripe, r.t_ready),
                                   t_pack0, None),
                                  ("bucket_pack", t_pack0, t_pack1, attrs),
                                  ("dispatch", t_pack1, t_disp, attrs)))
            tr.stage("engine.pack", self._t_state, t_pack1, attrs)
            tr.stage("engine.dispatch", t_pack1, t_disp, attrs)
            self._t_state = t_disp
        with self._stats_lock:
            self.bucket_counts[bucket] += 1
            self.bucket_hits += int(self.steps.builds == warm)
        self._inflight.append((batch, out, attrs))
        while len(self._inflight) > self.inflight_depth:
            self._finalize_one()

    def _finalize_one(self):
        batch, out, attrs = self._inflight.popleft()
        out = np.asarray(out)                          # device sync
        now = self.clock()
        tr = self.tracer
        if tr is not None:
            tr.stage("engine.sync", self._t_state, now, attrs)
            self._t_state = now
        settles = [] if tr is not None else None
        row = 0
        for req in batch:
            k = req.n_seeds
            if req.finish(out[row:row + k].copy(), now) and tr is not None:
                settles.append((req.rid, "settle", now, now, None))
            row += k
        if settles:
            tr.settle_many(settles)
        with self._rid_lock:
            # results live on the request objects; the server-side index
            # must not grow without bound under sustained traffic
            for req in batch:
                self.requests.pop(req.rid, None)
        with self._stats_lock:
            self.n_served += len(batch)
            self.latencies.extend(r.latency for r in batch)
        if self._m_latency is not None:
            for r in batch:      # rid = exemplar = NeuraScope trace id
                self._m_latency.observe(r.latency, exemplar=str(r.rid))
            self._m_requests.inc(len(batch), outcome="served")
        if tr is not None:
            self._mark("engine.scatter", attrs)

    def _pull_metrics(self):
        """Render-time gauge refresh — queue and cache state already lives
        in host bookkeeping, so the scrape just reads it."""
        info = self.batcher.info()
        self._m_queue.set(float(info["depth"]), field="depth")
        self._m_queue.set(float(info["depth_seeds"]), field="depth_seeds")
        sc = self.steps.info()
        tries = sc["hits"] + sc["builds"]
        self._m_cache.set(sc["hits"] / tries if tries else 0.0, cache="step")
        with self._stats_lock:
            n_batches = int(sum(self.bucket_counts.values()))
            hits = self.bucket_hits
        self._m_cache.set(hits / n_batches if n_batches else 0.0,
                          cache="bucket")

    def _retry_batch(self, batch: List[ServeRequest], exc: ServeError):
        """Transient device-step failure: re-queue each request once, fail
        it typed when its retry budget is spent.  Idempotent settlement
        makes a duplicate delivery from a raced retry impossible."""
        now = self.clock()
        tr = self.tracer
        for req in batch:
            req.attempts += 1
            if req.attempts > self.max_retries:
                with self._rid_lock:
                    self.requests.pop(req.rid, None)
                if req.fail(RetriesExhausted(req.rid, req.attempts, exc),
                            now) and tr is not None:
                    tr.settle(req.rid, "error", now, now,
                              {"error": "RetriesExhausted"})
            else:
                if tr is not None:
                    tr.span(req.rid, "retry", now, now,
                            {"attempt": req.attempts})
                self.batcher.submit(req)

    def _reap_expired(self):
        expired = self.batcher.reap_expired(self.clock())
        if expired:
            now = self.clock()
            with self._rid_lock:
                for req in expired:
                    self.requests.pop(req.rid, None)
            for req in expired:
                if req.fail(DeadlineExceeded(req.rid, req.deadline, now),
                            now) and self.tracer is not None:
                    self.tracer.settle(req.rid, "error", now, now,
                                       {"error": "DeadlineExceeded"})
            with self._stats_lock:
                self.n_deadline_failed += len(expired)

    def _mark(self, name: str, attrs: Optional[dict] = None):
        """Close the engine thread's current state span, now, as ``name``:
        each state starts where the last ended, so the ``engine.*`` stage
        spans tile the loop (tracing on only)."""
        now = self.clock()
        self.tracer.stage(name, self._t_state, now, attrs)
        self._t_state = now

    def _mark_wait(self, batch: Optional[List[ServeRequest]]):
        """The blocking wait for a ripe batch, split where its oldest
        request joined: ``engine.idle`` before (nothing pending, nothing in
        flight), ``engine.form`` after (waiting for a trigger)."""
        now = self.clock()
        t_first = batch[0].t_ready if batch else self.batcher.oldest_ready()
        t = now if t_first is None else min(max(t_first, self._t_state),
                                            now)
        if t > self._t_state:
            self.tracer.stage("engine.idle", self._t_state, t)
        if now > t:
            self.tracer.stage("engine.form", t, now)
        self._t_state = now

    def _engine_loop(self):
        tr = self.tracer
        if tr is not None:
            self._t_state = self.clock()
        while not self._stop.is_set():
            self._reap_expired()
            if tr is not None:
                self._mark("engine.reap")
            if self._inflight:
                # work is on the device: only grab a ripe batch, otherwise
                # retire the oldest in-flight batch (its sync overlaps the
                # sampler workers filling the queue)
                batch = self.batcher.poll()
                if batch is None:
                    self._finalize_one()
                    continue
            else:
                batch = self.batcher.take(timeout=0.02)
                if tr is not None:
                    self._mark_wait(batch)
            if batch:
                self._dispatch(batch)
        for batch in self.batcher.flush():
            self._dispatch(batch)
        while self._inflight:
            self._finalize_one()

    # -- lifecycle / utilities ---------------------------------------------
    def warmup(self, buckets: Optional[Sequence[int]] = None):
        """Compile the bucket ladder ahead of traffic and run one dummy
        batch through each step (jit trace + compile happen on first call)."""
        buckets = (all_buckets(self.max_batch_seeds) if buckets is None
                   else buckets)
        for b in buckets:
            step = self.steps.get((b,))
            if self._plane is not None:
                np.asarray(step(self.params, np.zeros(b, np.int32),
                                np.zeros(b, np.uint32),
                                np.zeros(b, np.uint32), np.zeros(b, bool)))
                continue
            struct = self._struct(b)
            node_ids = np.full(struct.n_nodes, -1, np.int64)
            hop_valid = np.zeros(struct.n_hop_edges, bool)
            np.asarray(step(self.params, node_ids, hop_valid))

    def drain(self, timeout: float = 60.0):
        """Block until every submitted request has *settled* (result or
        typed error — a failed request no longer aborts the drain).  On
        timeout the stragglers are failed with ``DrainTimeout`` (surfacing
        the count) and the same error is raised — no request is ever left
        silently pending."""
        deadline = time.monotonic() + timeout
        with self._rid_lock:
            pending = list(self.requests.values())
        for req in pending:
            left = deadline - time.monotonic()
            if left <= 0 or not req.wait_done(left):
                break
        stragglers = [r for r in pending if not r.done]
        if stragglers:
            err = DrainTimeout(len(stragglers), timeout,
                               [r.rid for r in stragglers])
            now = self.clock()
            with self._rid_lock:
                for r in stragglers:
                    self.requests.pop(r.rid, None)
            for r in stragglers:
                if r.fail(err, now) and self.tracer is not None:
                    self.tracer.settle(r.rid, "error", now, now,
                                       {"error": "DrainTimeout"})
            raise err

    def reset_stats(self):
        with self._stats_lock:
            self.bucket_counts.clear()
            self.bucket_hits = 0
            self.n_served = 0
            self.n_deadline_failed = 0
            self.latencies.clear()

    def stats(self) -> dict:
        with self._stats_lock:
            out = {
                "n_served": self.n_served,
                "deadline_failed": self.n_deadline_failed,
                "n_batches": int(sum(self.bucket_counts.values())),
                "bucket_counts": dict(self.bucket_counts),
                "bucket_hits": self.bucket_hits,
                "recompiles": self.steps.builds,
                "step_cache": self.steps.info(),
                "plan_cache": plan_cache_info(),
                "batcher": self.batcher.info(),
                **percentiles_ms(self.latencies),
            }
        if self.tracer is not None:
            out["tracing"] = self.tracer.stats()
        if self._metrics_server is not None:
            out["metrics_url"] = self._metrics_server.url
        return out

    def close(self, timeout: float = 30.0):
        """Graceful shutdown: everything submitted before ``close`` is still
        served.  Order matters — samplers stop FIRST, so no request can
        reach the batcher after the engine thread's final flush.

        Idempotent (a second call is a no-op), and safe over a **wedged**
        engine loop: if the engine thread does not exit within ``timeout``
        (e.g. a hung device stream), every still-pending request is failed
        with ``ServerClosed`` so no caller blocks forever."""
        with self._close_lock:
            if self._closing:
                return
            self._closing = True          # reject new submissions from here
        if self._sampler is not None:
            self._sampler.close(timeout)  # every accepted request is sampled
        self._stop.set()
        self._engine.join(timeout)        # exits within one poll interval
        if self._engine.is_alive():
            now = self.clock()
            with self._rid_lock:
                pending = list(self.requests.values())
                self.requests.clear()
            for req in pending:
                if req.fail(ServerClosed(req.rid), now) \
                        and self.tracer is not None:
                    self.tracer.settle(req.rid, "error", now, now,
                                       {"error": "ServerClosed"})
        if self._metrics_server is not None:
            self._metrics_server.close()
        if self.tracer is not None:
            self.tracer.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


def offline_inference(server: GNNServer, trees: list) -> np.ndarray:
    """One-request-at-a-time reference: each tree through the bucket-1 step.

    Uses the server's bucket-1 host-input step, so it measures exactly the
    unbatched serving path; returns the stacked (n_trees, d_out) outputs.
    Under device sampling the cached steps take (seeds, keys) instead of
    node tables, so the reference builds its own host-input bucket-1 step —
    which keeps it an INDEPENDENT program from the fused one it anchors.
    """
    if server._plane is None:
        step = server.steps.get((1,))
    else:
        step = getattr(server, "_host_step1", None)
        if step is None:
            step = build_infer_step(server.arch_id, server.cfg, server.store,
                                    server._struct(1),
                                    backend=server.backend)
            server._host_step1 = step
    out = []
    for tree in trees:
        node_ids, hop_valid = stack_trees([tree], 1, server.fanouts)
        out.append(np.asarray(step(server.params, node_ids, hop_valid)))
    return np.concatenate(out, axis=0)


def offline_replay(server: GNNServer, req: ServeRequest) -> np.ndarray:
    """The full unbatched pipeline for one request: re-sample its trees
    through the data plane's deterministic streams, then infer one tree at
    a time.  Must equal ``req.result`` to ≤1e-5 — the serving parity
    contract — and is the throughput baseline batching is measured against.
    """
    return offline_inference(server, server.sample_for(req.seeds, req.rid))
