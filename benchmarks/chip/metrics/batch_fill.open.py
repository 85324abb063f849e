"""Batcher in an open cell: seeds a dispatched batch carried over the seeds
its bucket holds (the ``n_seeds`` and ``bucket`` of the program's
``bucket_pack`` span, one per round), mean over the rounds, in %."""


def read(ctx):
    rids = set(ctx["requests"]["rid"].tolist())
    fill = {}
    for tr in ctx.get("spans") or ():
        if tr["trace"] not in rids:
            continue
        for s in tr["spans"]:
            if s["name"] == "bucket_pack" and "n_seeds" in s:
                fill[s["round"]] = s["n_seeds"] / s["bucket"]
    return 100.0 * sum(fill.values()) / len(fill) if fill else None
