"""Fig 13 — multidim subpopulation queries: covering sets beat scans.

A 2-dimensional family (32 x 32 -> 1024 leaf groups, plus the level
fixing only dimension ``a`` and the population group) ingests a uniform
attribute stream, then answers the same subpopulation predicate
(``a in {v0..v7}``) two ways:

  * subpop  — ``subpop_query``: the predicate resolves to its covering
    key set (8 groups at level ``(a,)``), gathered + merged + estimated
    in ONE fused dispatch (``kernels.ops.estimate_subpop``).
  * scan    — the pre-multidim serving story: the client fetches ALL
    1024 leaf synopses through ``query_many`` (one stacked-estimate
    dispatch over the full leaf level) and combines the predicate's
    slice host-side.

Both answer the same question off the same maintained state, so the
estimates must agree within the sketch's own error — asserted — while
the covering-set path touches 8 rows instead of 1024. ``--check`` gates
CI on the serving claim: subpop query cost <= 0.25x of the scan-all
baseline at 1024 leaf synopses, and exactly one fused dispatch answers
the predicate (``DISPATCH_COUNT``).
"""
from __future__ import annotations

import numpy as np

from repro import tracing
from repro.service import SDE, api
from .common import csv_row, time_fn

_DIM = 32                    # per-dimension domain -> 32*32 leaf groups
_COVER = 8                   # predicate: a in {v0..v7}
_CM = {"eps": 0.01, "delta": 0.05, "weighted": False}


def _build_engine() -> SDE:
    eng = SDE()
    r = eng.handle({
        "type": "build_multidim", "request_id": "b0", "synopsis_id": "md",
        "kind": "countmin", "params": _CM,
        "dims": {"a": [f"a{i}" for i in range(_DIM)],
                 "b": [f"b{i}" for i in range(_DIM)]},
        # the predicate's level, the scan's leaf level, the mandatory
        # population group — the middle levels of the full family would
        # only slow the build down without being measured
        "levels": [["a"], ["a", "b"]]})
    assert r.ok, r.error
    return eng


def run(full: bool = False, check: bool = False):
    rng = np.random.RandomState(0)
    eng = _build_engine()
    spec = eng.multidim["md"]
    n_batches, batch = (8, 4096) if full else (2, 2048)
    for i in range(n_batches):
        a = rng.randint(0, _DIM, batch)
        b = rng.randint(0, _DIM, batch)
        recs = [{"a": f"a{x}", "b": f"b{y}"} for x, y in zip(a, b)]
        r = eng.handle({"type": "ingest_multidim", "request_id": f"i{i}",
                        "synopsis_id": "md", "records": recs,
                        "values": [1.0] * batch})
        assert r.ok, r.error
    eng.flush()

    # the question: how many records landed in leaf (a0, b0), within the
    # subpopulation a in {a0..a7}? (an item-count CM point query — the
    # same item probed through both paths)
    item = spec.leaf_key({"a": "a0", "b": "b0"})
    where = {"a": [f"a{i}" for i in range(_COVER)]}
    subpop_req = {"type": "subpop_query", "request_id": "q",
                  "synopsis_id": "md", "where": where,
                  "query": {"items": [item]}}

    # scan baseline: every leaf synopsis, one query_many (itself ONE
    # stacked dispatch — the fairest possible scan), combined host-side
    leaf_assign = spec.level_assignments(("a", "b"))
    leaf_keys = [spec.group_key(asg) for asg in leaf_assign]
    scan_qs = [api.AdHocQuery(request_id=f"s{i}",
                              synopsis_id=f"md/{k}",
                              query={"items": [item]})
               for i, k in enumerate(leaf_keys)]
    in_pred = np.asarray([asg["a"] in set(where["a"])
                          for asg in leaf_assign])

    def subpop():
        r = eng.handle(subpop_req)
        assert r.ok, r.error
        return float(np.asarray(r.value).ravel()[0])

    def scan():
        rs = eng.query_many(scan_qs)
        vals = np.asarray([float(np.asarray(r.value).ravel()[0])
                           for r in rs])
        return float(vals[in_pred].sum())

    est_sub, est_scan = subpop(), scan()
    # both paths estimate the count of leaf (a0, b0) — agreement within
    # the CM overcount budget (eps * subpop mass per covering row)
    tol = _CM["eps"] * n_batches * batch + 1.0
    assert abs(est_sub - est_scan) <= tol, \
        f"subpop {est_sub} vs scan {est_scan} (tol {tol})"

    before = int(tracing.DISPATCH_COUNT["CountMin"])
    subpop()
    n_disp = int(tracing.DISPATCH_COUNT["CountMin"]) - before

    iters = 10 if full else 3
    t_sub = time_fn(subpop, warmup=1, iters=iters)
    t_scan = time_fn(scan, warmup=1, iters=iters)
    ratio = t_sub / t_scan
    rows = [csv_row(
        f"fig13_subpop_g{len(leaf_keys)}_cover{_COVER}", t_sub,
        f"scan_us={t_scan*1e6:.1f} ratio={ratio:.3f} "
        f"dispatches={n_disp} est_subpop={est_sub:.0f} "
        f"est_scan={est_scan:.0f}")]
    if check:
        assert n_disp == 1, \
            f"subpop_query cost {n_disp} dispatches, acceptance is 1"
        assert ratio <= 0.25, \
            f"subpop query at {ratio:.3f}x of the scan baseline; " \
            "acceptance is <= 0.25x at 1024 leaf synopses"
    eng.close()
    return rows


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--check", action="store_true",
                    help="assert the acceptance gates (CI mode)")
    ap.add_argument("--full", action="store_true")
    args = ap.parse_args()
    for row in run(full=args.full, check=args.check):
        print(row)
