"""Events the gateway folds into one tick: tuples the engine ingested in
the window over gateway ticks in the window (program counters)."""


def read(ctx):
    ticks = ctx.counters["ticks"]
    return ctx.counters["tuples"] / ticks if ticks else None
