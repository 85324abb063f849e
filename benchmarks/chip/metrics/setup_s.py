"""Set-up: process start to the first timed request, compile included
(device data, host CSR copy, server, warm-up of the cell's buckets)."""


def read(ctx):
    return ctx["setup_s"]
