"""GNN inference serving engine (DESIGN.md §10–§13).

Planes over the engines PRs 1–3 built:

* request plane  — ``batcher.DynamicBatcher`` (deadline/size triggers,
                   skip-ahead FIFO packing — no head-of-line blocking) on the
                   scheduler utilities shared with the LM continuous batcher;
* data plane     — per-request fanout sampling (``sparse.sampler``) on worker
                   threads, stacked into power-of-two **shape buckets** so the
                   jit/plan caches stay bounded;
* compute plane  — one jitted inference step per (arch, bucket, backend)
                   through the unified sparse-backend registry, LRU-cached
                   with an explicit recompile counter;
* control plane  — ``telemetry.TelemetryHub`` (per-lane time-series, the
                   source of truth for stats), lane supervision/failover in
                   ``ClusterServer``, typed failures (``errors``), and
                   deterministic fault injection (``chaos``) — DESIGN.md §13;
* metrics plane  — ``metrics.MetricsRegistry`` (log-bucketed mergeable
                   histograms with trace-id exemplars, gauges, counters,
                   Prometheus-style exposition) and ``slo.SLOEngine``
                   (per-class burn-rate tracking feeding the shed arm:
                   best_effort drops before batch, interactive never);
* measurement    — ``benchmarks/chip/run.py`` times ``GNNServer`` on the
                   TPU (``BENCHMARK.json``; results in ``PERF.md``).

Correctness anchors: batched-bucketed serving is parity-checked (≤1e-5)
against offline one-request-at-a-time inference on the same sampled trees;
every accepted request settles exactly once (result XOR typed error).
"""
from repro.serve.batcher import DynamicBatcher, ServeRequest
from repro.serve.buckets import (BucketStructure, bucket_for,
                                 build_bucket_structure, stack_trees)
from repro.serve.chaos import ChaosInjector, InjectedSamplerFault, LaneFault
from repro.serve.cluster import (ClusterServer, DRHMRouter,
                                 utilization_spread)
from repro.serve.compute import (FeatureStore, StepCache, build_infer_step,
                                 build_lane_infer_step)
from repro.serve.device_sampler import (DeviceSamplerPlane,
                                        sample_forest_device, tree_key_mix)
from repro.serve.engine import (GNNServer, SamplerPool, offline_inference,
                                offline_replay)
from repro.serve.errors import (DeadlineExceeded, DrainTimeout,
                                GraphMutationError, HotSwapError, LaneFailure,
                                Overloaded, RetriesExhausted, SamplerError,
                                ServeError, ServerClosed, TransientStepError)
from repro.serve.live import (FlushReport, GraphStream, SwapReport, hot_swap)
from repro.serve.metrics import (LatencyHistogram, MetricsRegistry,
                                 parse_exposition)
from repro.serve.scheduler import LaneSlotPools, SlotPool, pack_fifo
from repro.serve.slo import CLASSES, DEFAULT_SLOS, SHED_ORDER, ClassSLO, \
    SLOEngine
from repro.serve.telemetry import TelemetryHub, percentiles_ms
from repro.serve.tracing import (SCHEMA_VERSION, TERMINAL_SPANS, Tracer,
                                 verify_trace, verify_traces)

__all__ = [
    "DynamicBatcher", "ServeRequest",
    "BucketStructure", "bucket_for", "build_bucket_structure", "stack_trees",
    "ChaosInjector", "InjectedSamplerFault", "LaneFault",
    "ClusterServer", "DRHMRouter", "utilization_spread",
    "FeatureStore", "StepCache", "build_infer_step", "build_lane_infer_step",
    "DeviceSamplerPlane", "sample_forest_device", "tree_key_mix",
    "GNNServer", "SamplerPool", "offline_inference", "offline_replay",
    "ServeError", "SamplerError", "DeadlineExceeded", "DrainTimeout",
    "TransientStepError", "RetriesExhausted", "Overloaded", "LaneFailure",
    "ServerClosed", "HotSwapError", "GraphMutationError",
    "FlushReport", "GraphStream", "SwapReport", "hot_swap",
    "LaneSlotPools", "SlotPool", "pack_fifo",
    "LatencyHistogram", "MetricsRegistry", "parse_exposition",
    "CLASSES", "DEFAULT_SLOS", "SHED_ORDER", "ClassSLO", "SLOEngine",
    "TelemetryHub", "percentiles_ms",
    "SCHEMA_VERSION", "TERMINAL_SPANS", "Tracer",
    "verify_trace", "verify_traces",
]
