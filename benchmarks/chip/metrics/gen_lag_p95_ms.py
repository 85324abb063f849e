"""Load generator: 95th percentile of how late each request was sent
against its schedule (a starved generator is not a fast server)."""
from benchmarks.chip import measures


def read(ctx):
    req = ctx["requests"]
    return measures.percentile((req["sent"] - req["due"]) * 1e3, 95)
