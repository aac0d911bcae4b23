"""Roofline share of the CountMin update program alone: the least HBM
bytes the window's ingest calls need for the configuration's CountMin
synopses (``roofline.py`` on the configuration without its other kinds;
event counts from the program's ``sde.ingest`` spans) at the chip's HBM
rate, over the device time of the programs named
``jit_fused_update_CountMin``."""
from bench import devtrace, program_spans, roofline


def read(ctx):
    if ctx.trace is None:
        return None
    dev = devtrace.module_seconds(ctx.trace, "jit_fused_update_CountMin")
    calls = program_spans.spans(ctx, "sde.ingest")
    if dev <= 0 or not calls:
        return None
    cfg = {k: v for k, v in ctx.cfg.items() if k != "multidim"}
    cfg["synopses"] = [s for s in ctx.cfg["synopses"]
                       if s["kind"] == "countmin"]
    need = sum(roofline.update_bytes(cfg, 0 if a["multidim"] else
                                     a["events"],
                                     a["events"] if a["multidim"] else 0)
               for _, _, _, a in calls)
    return 100.0 * need / ctx.peaks["hbm_bytes_per_s"] / dev
