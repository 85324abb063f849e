"""Device: share of the traced window in which no operation ran."""


def read(ctx):
    tr = ctx.get("trace")
    return None if tr is None else 100.0 * tr.idle_share
