"""Mean time a request waits in the gateway's queue, from its submission
to the start of the tick that takes it (``gateway.queue_wait`` spans),
over the requests whose tick started in the window."""
from bench import program_spans


def read(ctx):
    t0, t1 = ctx.window
    waits = [e - s for _, s, e, _ in program_spans.recorded(
        0.0, t1, "gateway.queue_wait") if t0 <= e < t1]
    return 1e3 * sum(waits) / len(waits) if waits else None
