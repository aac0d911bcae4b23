"""Backend compiles (persistent-cache misses) inside the window."""


def read(ctx):
    return float(ctx.counters["compiles"])
