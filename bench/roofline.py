"""The least HBM traffic a batch's update work needs, from the sizes the
configuration states (textbook sketch sizes, not the program's padded
ones), independent of how the program does it.

Each kind stack's update program must at least read the batch's inputs
(two uint32 halves of the stream id, the uint32 item, the f32 weight and
a mask byte: 17 bytes a tuple) and read and write every counter a tuple
touches (4 bytes each way), capped at the counters that exist. Routed
stacks also read one slot of the routing table a tuple (12 bytes). The
work is memory bound: hashing is a few integer operations per counter,
far under the chip's peak rate.
"""
from __future__ import annotations

import math

IN_BYTES, PROBE_BYTES, COUNTER_BYTES = 17, 12, 4


def _counters(kind: str, params: dict):
    """(counters a tuple touches, counters of one sketch)."""
    if kind in ("countmin", "ams"):
        d = math.ceil(math.log(1.0 / params["delta"]))
        w = math.ceil(math.e / params["eps"]) if kind == "countmin" else \
            math.ceil(1.0 / params["eps"] ** 2)
        return d, d * w
    if kind == "hyperloglog":
        return 1, math.ceil((1.04 / params["rse"]) ** 2)
    if kind == "gk_quantiles":
        return 1, math.ceil(1.0 / (2 * params["eps"]))
    raise ValueError(f"no update model for kind {kind!r}")


def update_bytes(cfg: dict, t_plain: int, t_md: int) -> float:
    """Least bytes one ``SDE.ingest`` call moves: ``t_plain`` stream
    events or ``t_md`` expanded multidim tuples."""
    t = t_plain + t_md
    n_streams = int(cfg["streams"]["count"])
    total = 0.0
    stacks = {}
    for s in cfg["synopses"]:
        scope = s.get("per_stream")
        rows = (n_streams if scope == "all" else int(s["hottest"])
                if scope == "hottest" else 1)
        # a tuple of a routed stack touches its own row only when its
        # stream is one of the stack's rows
        hit = (t_plain * rows / n_streams if scope else t)
        key = (s["kind"], tuple(sorted(s["params"].items())))
        stacks.setdefault(key, []).append((rows, hit, bool(scope)))
    md = cfg.get("multidim")
    if md:
        key = (md["kind"], tuple(sorted(md["params"].items())))
        groups = 1
        for vals in md["dims"].values():
            groups *= len(vals) + 1
        stacks.setdefault(key, []).append((groups, t_md, True))
    for (kind, params), members in stacks.items():
        touch, size = _counters(kind, dict(params))
        routed = any(m[2] for m in members)
        total += t * (IN_BYTES + (PROBE_BYTES if routed else 0))
        for rows, hit, _ in members:
            total += 2 * COUNTER_BYTES * min(hit * touch, rows * size)
    return total
