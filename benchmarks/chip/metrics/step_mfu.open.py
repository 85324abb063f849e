"""Fused step in an open cell: the FLOPs the algorithm needs for the seeds
served, over the step's device time times the bf16 peak.  Beside the
kernel's roofline, it still reads the step if a kernel leaves the path."""
from benchmarks.chip import measures


def read(ctx):
    return measures.step_mfu(ctx)
