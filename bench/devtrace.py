"""From a profiler trace to plain intervals, and from those to numbers.

``collect`` reads the ``.xplane.pb`` the JAX profiler wrote and keeps
three things, in one time base (ns): the device's operations and program
(module) executions per chip, the harness's own ``bench/`` spans on the
host, and the traced window (the ``bench/window`` span). The reductions
below work on that plain form only, so a small recorded copy of it
(``testdata/``) checks them without a chip.
"""
from __future__ import annotations

import pathlib
from typing import Dict, List, Optional, Tuple

Interval = Tuple[str, float, float]

WINDOW = "bench/window"


def collect(trace_dir: pathlib.Path) -> dict:
    from jax.profiler import ProfileData
    files = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(str(files[-1]))
    device: Dict[str, Dict[str, List[Interval]]] = {}
    host: List[Interval] = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            d = device.setdefault(plane.name, dict(ops=[], modules=[]))
            for line in plane.lines:
                key = ("ops" if line.name == "XLA Ops" else
                       "modules" if line.name == "XLA Modules" else None)
                if key is None:
                    continue
                d[key].extend((e.name, float(e.start_ns),
                               float(e.start_ns + e.duration_ns))
                              for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, float(e.start_ns),
                             float(e.start_ns + e.duration_ns))
                            for e in line.events
                            if e.name.startswith("bench/"))
    wins = [h for h in host if h[0] == WINDOW]
    window = [wins[0][1], wins[0][2]] if wins else None
    return dict(device={k: v for k, v in device.items() if v["ops"]},
                host=[h for h in host if h[0] != WINDOW], window=window)


def _clip(iv: List[Interval], t0: float, t1: float) -> List[Interval]:
    return [(n, max(s, t0), min(e, t1)) for n, s, e in iv
            if e > t0 and s < t1]


def union(iv: List[Interval]) -> List[Tuple[float, float]]:
    """Merged busy intervals."""
    out: List[List[float]] = []
    for _, s, e in sorted(iv, key=lambda x: x[1]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_seconds(trace: dict) -> float:
    """Seconds in which some operation ran, averaged over the chips."""
    t0, t1 = trace["window"]
    per_chip = [sum(e - s for s, e in union(_clip(d["ops"], t0, t1)))
                for d in trace["device"].values()]
    return sum(per_chip) / len(per_chip) / 1e9 if per_chip else 0.0


def window_seconds(trace: dict) -> float:
    t0, t1 = trace["window"]
    return (t1 - t0) / 1e9


def module_seconds(trace: dict, prefix: str) -> float:
    """Device seconds of the programs whose name starts with ``prefix``,
    summed over the chips."""
    t0, t1 = trace["window"]
    return sum(e - s for d in trace["device"].values()
               for n, s, e in _clip(d["modules"], t0, t1)
               if n.startswith(prefix)) / 1e9


def top_ops(trace: dict, k: int = 10) -> List[List]:
    """The device programs that took most time in the window."""
    t0, t1 = trace["window"]
    tot: Dict[str, float] = {}
    for d in trace["device"].values():
        for n, s, e in _clip(d["modules"] or d["ops"], t0, t1):
            tot[n] = tot.get(n, 0.0) + (e - s) / 1e9
    return [[n, v] for n, v in sorted(tot.items(), key=lambda x: -x[1])[:k]]


def idle_gaps(trace: dict, k: int = 10) -> List[List]:
    """Idle device time in the window, by what the host was doing: each
    gap goes to the innermost harness span covering its midpoint, or to
    ``no span`` (the event loop between calls: reading, decoding,
    writing, waiting for requests)."""
    t0, t1 = trace["window"]
    chips = list(trace["device"].values())
    if not chips:
        return []
    busy = union(_clip(chips[0]["ops"], t0, t1))
    gaps, cur = [], t0
    for s, e in busy:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if t1 > cur:
        gaps.append((cur, t1))
    host = sorted(trace["host"], key=lambda h: h[1])
    tot: Dict[str, float] = {}
    for s, e in gaps:
        mid = (s + e) / 2
        inner: Optional[Interval] = None
        for h in host:
            if h[1] > mid:
                break
            if h[2] >= mid and (inner is None or h[1] >= inner[1]):
                inner = h
        name = inner[0][len("bench/"):] if inner else "no span"
        tot[name] = tot.get(name, 0.0) + (e - s) / 1e9
    return [[n, v] for n, v in sorted(tot.items(), key=lambda x: -x[1])[:k]]
