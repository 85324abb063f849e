"""Kernels: the Gustavson aggregation kernel's share of its roofline
(``measures.gustavson_roofline``): padding, chunk layout and discarded
rows count as time, not as work."""
from benchmarks.chip import measures


def read(ctx):
    return measures.gustavson_roofline(ctx)
