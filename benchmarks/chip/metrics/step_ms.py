"""Fused step: device time of one call of the jitted bucket step (sampling,
feature gather, aggregation and dense layers in one program), mean over
the calls in the traced window; full 16-seed buckets in a closed cell."""
from benchmarks.chip import measures


def read(ctx):
    return measures.step_ms(ctx)
