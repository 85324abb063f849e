"""Batcher: 95th percentile of the program's ``queue_wait`` spans (joined
the batcher -> its batch starts packing), over the window's requests."""
import numpy as np

from benchmarks.chip import measures


def read(ctx):
    return measures.percentile(np.array(measures.span_ms(ctx, "queue_wait")),
                               95)
