"""Kernels: the Gustavson aggregation kernel's device time per batch: its
ops' (``measures.KERNEL``) seconds over their calls in the traced window,
times the calls one batch makes (layers × the architecture's
``kernel_calls_per_layer``).  Beside ``step_ms``, the share of each
model's step that the shared kernel takes."""
from benchmarks.chip import archs, measures


def read(ctx):
    tr = ctx.get("trace")
    if tr is None:
        return None
    calls = [v for name, v in tr.ops.items()
             if measures.KERNEL.match(name.split(" = ")[0])]
    n = sum(c for c, _ in calls)
    if not n:
        return None
    cfg = ctx["config"]
    per_batch = len(cfg["fanouts"]) * archs.of(cfg).kernel_calls_per_layer(
        cfg)
    return 1e3 * sum(s for _, s in calls) / n * per_batch
