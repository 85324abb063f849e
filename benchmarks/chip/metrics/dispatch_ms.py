"""Engine: host time per dispatched batch, the program's ``bucket_pack``
plus ``dispatch`` spans (the async step call), mean over the batches."""
import numpy as np

from benchmarks.chip import measures


def read(ctx):
    per = measures.per_round_ms(ctx, ("bucket_pack", "dispatch"))
    return float(np.mean(per)) if per else None
