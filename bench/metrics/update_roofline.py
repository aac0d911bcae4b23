"""Roofline share of the blue path's update programs: the least time the
window's ingest calls need at the chip's HBM bandwidth (``roofline.py``;
memory bound) over the device time of the update programs (``jit_fused``
in the trace; every kind's update is jitted under that name, so the share
is over all kinds together)."""
from bench import devtrace, roofline


def read(ctx):
    if ctx.trace is None:
        return None
    dev = devtrace.module_seconds(ctx.trace, "jit_fused")
    calls = ctx.spans("sde.ingest")
    if dev <= 0 or not calls:
        return None
    need = sum(roofline.update_bytes(ctx.cfg, 0 if md else t, t if md else 0)
               for _, _, _, (t, md) in calls)
    return 100.0 * need / ctx.peaks["hbm_bytes_per_s"] / dev
