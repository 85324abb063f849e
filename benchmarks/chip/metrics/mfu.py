"""Fused step: FLOPs that the architecture's minibatch algorithm needs
for the seeds answered in the window (``measures.flops_per_seed``), over
the window times the chip's bf16 peak.  An f32 matmul runs as one bf16
pass on the chip."""
from benchmarks.chip import measures


def read(ctx):
    peaks = ctx.get("peaks")
    served = measures.seeds_served(ctx)
    if not peaks or not served:
        return None
    flops = measures.flops_per_seed(ctx)
    return 100.0 * flops * served / ctx["seconds"] / peaks["bf16_flops"]
