"""Kernels in an open cell: the Gustavson kernel's share of its roofline,
as ``gustavson_roofline``, over buckets of 1 to 16 seeds."""
from benchmarks.chip import measures


def read(ctx):
    return measures.gustavson_roofline(ctx)
