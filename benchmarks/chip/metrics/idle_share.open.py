"""Device in an open cell: share of the traced window in which no
operation ran.  The offered load is fixed, so a shorter step leaves the
device idle for longer: here higher is better."""


def read(ctx):
    tr = ctx.get("trace")
    return None if tr is None else 100.0 * tr.idle_share
