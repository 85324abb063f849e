"""Seed nodes answered inside the window, over the window's length."""
from benchmarks.chip import measures


def read(ctx):
    return measures.seeds_served(ctx) / ctx["seconds"]
