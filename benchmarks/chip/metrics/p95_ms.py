"""95th percentile of the latency ``p50_ms`` takes the median of."""
from benchmarks.chip import measures


def read(ctx):
    return measures.percentile(measures.latencies_ms(ctx["requests"]), 95)
