"""Fused step in an open cell: as ``step_ms``, over the buckets of 1 to 16
seeds that the batcher dispatches; moves the latency of every request."""
from benchmarks.chip import measures


def read(ctx):
    return measures.step_ms(ctx)
