"""The configuration module of the tiny price-walk test deployment: its
events (each ingest request one walk of integer prices, in cents, over
the stocks in round-robin order) and its ``turnover`` query template
(each of the first ``rows`` stocks' CountMin asked for its own id, whose
weight is the stock's traded value), with the exact answer and the
number that judges it: ``turnover_gap``, the widest distance of an
answer from the exact traded value; and its control, the traded values
kept in bf16 counters. Numpy only."""
from __future__ import annotations

import numpy as np

from bench import traffic as tr


def ingest_events(plan, tag, index, size):
    v = plan.values
    rng = tr._rng(plan.seed, tag, index, 2)
    start = rng.integers(int(v["start_low"]), int(v["start_high"]) + 1)
    steps = rng.integers(-int(v["max_step"]), int(v["max_step"]) + 1, size)
    return plan.ranks(tag, index, size), np.maximum(start + np.cumsum(steps),
                                                    1)


def _rows(plan, qtype):
    return int(plan.cfg["queries"][qtype]["rows"])


def query(plan, qtype, j):
    sid = plan.cfg["queries"][qtype]["synopsis"]
    return dict(type="query_many", queries=[
        dict(synopsis_id=f"{sid}/{int(s)}", query=dict(items=[int(s)]))
        for s in plan.ids[:_rows(plan, qtype)]])


def _turnover(req, qtype):
    """The traded value each acked request adds to each queried stock:
    ``[requests, rows]``."""
    n = _rows(req.plan, qtype)
    out = np.zeros((len(req.recs), n))
    for i, (rk, v) in enumerate(zip(req.ranks, req.vals)):
        hit = rk < n
        out[i] = np.bincount(rk[hit], v[hit], minlength=n)
    return out


def exact(req, qtype, k):
    return [dict(value=[float(x)]) for x in _turnover(req, qtype).sum(0)]


def judge(j, req, qtype, k, answer):
    want = [w["value"][0] for w in exact(req, qtype, k)]
    got = [a["value"][0] for a in answer]
    got += [0.0] * (len(want) - len(got))   # a stock left unanswered
    for g, w in zip(got, want):
        j.put("turnover_gap", abs(g - w))


def control(req, qtype, k, order):
    if qtype != "turnover":
        return None                 # the harness rounds the exact answer
    from bench.control import bf16_running
    return [dict(value=[float(x)])
            for x in bf16_running(_turnover(req, qtype)[order])]
