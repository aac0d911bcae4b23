"""Durability check: the engine recovered from checkpoint + WAL must equal
the engine that acked every request, byte for byte (the image helpers of
the repository's first chip smoke test, copied here so the yardstick
does not move with that script)."""
from __future__ import annotations

import numpy as np


def engine_image(sde) -> dict:
    """Host copy of everything that makes up an engine's state."""
    import jax
    out = dict(tuples=sde.tuples_ingested, batches=sde.batches_ingested,
               entries={k: (e.row, e.stream_id)
                        for k, e in sde.entries.items()},
               stacks={})
    for kind, st in sde.stacks.items():
        out["stacks"][kind] = dict(
            state=[np.asarray(x) for x in jax.tree.leaves(st.state)],
            used=list(st.used), source=list(st.source_rows),
            keys=np.asarray(st.table.keys), rows=np.asarray(st.table.rows))
    return out


def image_diff(a: dict, b: dict) -> int:
    """Number of parts in which two images differ (0: identical)."""
    n = sum(a[k] != b[k] for k in ("tuples", "batches", "entries"))
    if set(a["stacks"]) != set(b["stacks"]):
        return n + 1
    for kind, x in a["stacks"].items():
        y = b["stacks"][kind]
        n += (x["used"], x["source"]) != (y["used"], y["source"])
        n += not (np.array_equal(x["keys"], y["keys"])
                  and np.array_equal(x["rows"], y["rows"]))
        if len(x["state"]) != len(y["state"]):
            n += 1
            continue
        n += sum(not np.array_equal(p, q)
                 for p, q in zip(x["state"], y["state"]))
    return int(n)
