"""End-to-end metrics over the measured window, from the load generator's
records (all on one host clock, ``time.monotonic``).

A request belongs to the window when it was due inside it; one that
failed or never got an answer counts in ``failed``.
"""
from __future__ import annotations

from typing import Dict, Optional

INGEST = ("ingest", "ingest_multidim")


def _in_window(rec: dict, t0: float, t1: float) -> bool:
    return rec["phase"] == "window" and t0 <= rec["due"] < t1


def end_to_end(results: dict) -> Dict[str, Optional[float]]:
    """``events_per_s``: the events of every request acked inside the
    window over the time from the window's start to the last of those
    acks. Acks come a whole tick at a time (hundreds of thousands of
    events), so dividing by the window's full length would step by one
    tick's share of it; ending at the last ack counts the same work over
    the time it really took."""
    t0, t1 = results["window"]
    acks = [(r["done"], r["size"]) for r in results["records"].values()
            if r["kind"] in INGEST and r["ok"] and r["done"] is not None
            and t0 <= r["done"] < t1]
    if not acks:
        return dict(events_per_s=None)
    last = max(t for t, _ in acks)
    return dict(events_per_s=sum(n for _, n in acks) / (last - t0))


def failed(results: dict) -> int:
    """Requests due in the window that failed or never got an answer."""
    t0, t1 = results["window"]
    return sum(1 for r in results["records"].values()
               if _in_window(r, t0, t1) and not r["ok"])


def attempted(results: dict) -> int:
    t0, t1 = results["window"]
    return sum(1 for r in results["records"].values()
               if _in_window(r, t0, t1))
