"""Median request latency, from the time a request was due to the time it
settled, over every request due in the window (failures count as inf)."""
from benchmarks.chip import measures


def read(ctx):
    return measures.percentile(measures.latencies_ms(ctx["requests"]), 50)
