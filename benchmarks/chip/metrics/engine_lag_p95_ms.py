"""Engine: 95th percentile of the program's ``engine_lag`` spans, the part
of each request's queue wait after its batch was ripe (its trigger fired,
or the request joined, if later) until packing started; what the engine,
not the batching policy, added to the wait."""
import numpy as np

from benchmarks.chip import measures


def read(ctx):
    return measures.percentile(np.array(measures.span_ms(ctx, "engine_lag")),
                               95)
