"""The program's own spans (``repro.tracing``), as per-layer metrics read
them.

``spans`` gives the program's spans of the window, on the harness's clock
(seconds of ``time.monotonic``). ``offset_ns`` maps that clock onto the
profiler's: the harness's ``gateway.tick`` wrapper is recorded on both
(its ``bench/gateway.tick`` annotation in the trace, its own span in
``ctx.spans``), so the median difference of their starts is the offset.
``idle_by_span`` puts each idle gap of the device down to the innermost
program span open at its midpoint. On a program without ``repro.tracing``
each of them finds nothing, and the readers leave their metric out.
"""
from __future__ import annotations

import bisect
import statistics
from typing import Dict, List, Optional, Tuple

from bench import devtrace

Span = Tuple[str, float, float, dict]

# a request's wait in the gateway queue is not host work: it overlaps the
# ticks and never says what the host was doing in a gap
NOT_WORK = ("gateway.queue_wait",)


def recorded(t0_s: float, t1_s: float, *names: str) -> List[Span]:
    """Program spans starting inside ``[t0_s, t1_s)``, in seconds."""
    try:
        from repro import tracing
    except ImportError:                  # a program without spans
        return []
    return [(n, s / 1e9, e / 1e9, a)
            for n, s, e, a in tracing.spans(t0_s, t1_s, *names)]


def spans(ctx, *names: str) -> List[Span]:
    """Program spans named ``names`` that start in the window, each
    clipped to it."""
    t0, t1 = ctx.window
    return [(n, s, min(e, t1), a)
            for n, s, e, a in recorded(t0, t1, *names)]


def busy_pct(ctx, name: str) -> Optional[float]:
    """Share of the window spent inside span ``name``, in %."""
    s = spans(ctx, name)
    return 100.0 * sum(e - b for _, b, e, _ in s) / ctx.seconds if s \
        else None


def offset_ns(ctx) -> Optional[float]:
    """Profiler clock minus the harness's clock, in ns. The traced
    window's start on both clocks gives it to within the harness's sleep
    (well under a tick); each of the window's ticks is then matched to
    the nearest traced tick, and the median of their start differences
    is the offset."""
    mono = [s[1] * 1e9 for s in ctx.spans("gateway.tick")]
    prof = sorted(h[1] for h in ctx.trace["host"]
                  if h[0] == "bench/gateway.tick")
    if not mono or not prof or not ctx.trace["window"]:
        return None
    rough = ctx.trace["window"][0] - ctx.window[0] * 1e9
    diffs = []
    for m in mono:
        i = bisect.bisect_left(prof, m + rough)
        near = min(prof[max(i - 1, 0):i + 1],
                   key=lambda p: abs(p - m - rough))
        diffs.append(near - m)
    return statistics.median(diffs)


def idle_by_span(ctx) -> Optional[List[List]]:
    """Idle device time in the traced window by program span: each gap
    (as ``devtrace.idle_gaps`` finds them, on the first chip) goes to the
    innermost program span open at its midpoint, or to ``no span``.
    None when the program recorded no span or the clocks cannot be
    mapped."""
    chips = list(ctx.trace["device"].values())
    off = offset_ns(ctx)
    if not chips or off is None or not ctx.trace["window"]:
        return None
    t0, t1 = ctx.trace["window"]
    prog = [(n, s * 1e9 + off, e * 1e9 + off)
            for n, s, e, _ in recorded(0.0, (t1 - off) / 1e9)
            if n not in NOT_WORK and e * 1e9 + off > t0]
    if not prog:
        return None
    prog.sort(key=lambda p: p[1])
    starts = [p[1] for p in prog]
    longest = max(p[2] - p[1] for p in prog)
    gaps, cur = [], t0
    for s, e in devtrace.union(devtrace._clip(chips[0]["ops"], t0, t1)):
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if t1 > cur:
        gaps.append((cur, t1))
    tot: Dict[str, float] = {}
    for s, e in gaps:
        mid = (s + e) / 2
        name = "no span"
        # the latest start at or before the midpoint that still covers it
        for i in range(bisect.bisect_right(starts, mid) - 1, -1, -1):
            if prog[i][1] < mid - longest:
                break
            if prog[i][2] >= mid:
                name = prog[i][0]
                break
        tot[name] = tot.get(name, 0.0) + (e - s) / 1e9
    return [[n, v] for n, v in sorted(tot.items(), key=lambda x: -x[1])]
