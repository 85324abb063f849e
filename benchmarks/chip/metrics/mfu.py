"""Fused step: FLOPs that GraphSAGE's minibatch algorithm needs for the
seeds answered in the window (``workcount``), over the window times the
chip's bf16 peak.  An f32 matmul runs as one bf16 pass on the chip."""
from benchmarks.chip import measures, workcount


def read(ctx):
    peaks = ctx.get("peaks")
    served = measures.seeds_served(ctx)
    if not peaks or not served:
        return None
    flops = workcount.flops_per_seed(ctx["config"]["fanouts"], ctx["dims"])
    return 100.0 * flops * served / ctx["seconds"] / peaks["bf16_flops"]
