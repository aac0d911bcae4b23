"""Share of the traced window in which no operation ran on the device."""
from bench import devtrace


def read(ctx):
    if ctx.trace is None or not ctx.trace["device"]:
        return None
    return 100.0 * (1.0 - devtrace.busy_seconds(ctx.trace)
                    / devtrace.window_seconds(ctx.trace))
