"""Elasticity reconciler tests (PR 8): sample -> plan -> diff -> migrate.

The contract under test:

  * ``worst_fit_decreasing`` is fully deterministic: heaviest piece
    first (input order breaks load ties), equally loaded bins hand out
    the LOWEST worker id — the tie rule the reconciler's no-flap
    behavior depends on, locked here.
  * ``Placement.diff`` relabels the target's workers to maximally
    overlap the previous placement (Hungarian on the overlap matrix) and
    returns the minimal move set; applying the delta to ``prev``
    reproduces the target assignment exactly (property-tested).
  * a ``Reconciler.step`` against a skewed stream improves the max/mean
    imbalance, migrates rows between worker slices of the row axis
    (single engine) or synopses between sites (federation), and the
    reconciled engine is BYTE-identical to a from-scratch engine built
    directly at the target placement — migration is invisible to state.
  * hysteresis damps: a balanced stream reconciles to zero moves.
  * probes (``RECONCILE_COUNT`` / ``MIGRATED_ROWS`` /
    ``REBALANCE_IMBALANCE``) surface through the JSON ``status``
    response; the gateway tick and ``serve_lines`` drive
    ``maybe_step`` and survive a raising reconciler.
"""
import json

import jax
import numpy as np
import pytest

from repro import tracing
from repro.launch import sde_server
from repro.service import (SDE, Federation, Placement, Reconciler,
                           SynopsisGateway, worst_fit_decreasing)

CM = {"eps": 0.05, "delta": 0.1, "weighted": False}


def _mk_engine(streams, n_est_eps=0.01):
    """Engine with per-stream CountMins (prefix ``pt``) plus the two
    estimator synopses the reconciler samples. The estimator CM uses a
    different eps so it lives in its OWN kind stack — placement moves
    only the per-stream stack."""
    eng = SDE()
    for req in (
        {"type": "build", "request_id": "b1", "synopsis_id": "pt",
         "kind": "countmin", "params": CM,
         "per_stream_of_source": True, "stream_ids": list(streams)},
        {"type": "build", "request_id": "b2", "synopsis_id": "rhll",
         "kind": "hyperloglog", "params": {"rse": 0.05}},
        {"type": "build", "request_id": "b3", "synopsis_id": "rcm",
         "kind": "countmin", "params": {"eps": n_est_eps, "delta": 0.01,
                                        "weighted": False}},
    ):
        r = eng.handle(req)
        assert r.ok, r.error
    return eng


def _skewed(streams, hot, n=512, seed=0, frac=0.8):
    """80% of the traffic on ``hot``, integer values (exact f32 sums)."""
    rng = np.random.RandomState(seed)
    pick = np.where(rng.rand(n) < frac,
                    rng.choice(hot, n), rng.choice(streams, n))
    return pick.astype(np.int64), np.ones(n, np.float32)


def _stack_bytes(eng):
    eng.flush()
    return {str(k): [np.asarray(x).tobytes()
                     for x in jax.tree.leaves(s.state)]
            for k, s in eng.stacks.items()}


# ---------------------------------------------------------------------------
# satellite: the WFD tie rule, locked
# ---------------------------------------------------------------------------
@pytest.mark.smoke
def test_wfd_lowest_worker_id_tie_rule():
    # all-equal loads: the heap must hand out 0, 1, 2, 0, 1, 2, ...
    p = worst_fit_decreasing([10, 11, 12, 13, 14, 15],
                             [2.0] * 6, 3)
    assert p.assignments == {10: 0, 11: 1, 12: 2, 13: 0, 14: 1, 15: 2}
    # load ties between bins resolve to the LOWEST id even mid-pack
    p = worst_fit_decreasing([1, 2, 3], [4.0, 2.0, 2.0], 2)
    assert p.assignments == {1: 0, 2: 1, 3: 1}
    assert p.loads == [4.0, 4.0]
    # equal stream loads keep input order (stable sort)
    p = worst_fit_decreasing([9, 4, 7], [1.0, 1.0, 1.0], 2)
    assert p.assignments == {9: 0, 4: 1, 7: 0}
    # and the whole thing is reproducible
    args = (list(range(40)), list(np.random.RandomState(0).rand(40)), 5)
    assert worst_fit_decreasing(*args).assignments \
        == worst_fit_decreasing(*args).assignments


@pytest.mark.smoke
def test_wfd_duplicate_candidates_coalesce():
    # an estimator can list the same stream twice; packing the
    # duplicates separately left the dict assignment holding only the
    # LAST bin while BOTH loads stayed in the bin totals, so
    # sum(p.loads) drifted above the load of the streams assigned
    p = worst_fit_decreasing([7, 7, 9], [3.0, 3.0, 4.0], 2)
    assert p.assignments == {7: 0, 9: 1}              # 7 is ONE piece
    assert sorted(p.loads) == [4.0, 6.0]
    # the invariant the bug broke: bin totals == assigned stream loads
    rng = np.random.RandomState(2)
    ids = rng.randint(0, 20, 64)                      # heavy duplication
    loads = rng.rand(64) + 0.01
    p = worst_fit_decreasing(ids, loads, 4)
    assert np.isclose(sum(p.loads), loads.sum())
    per_stream = {}
    for s, load in zip(ids, loads):
        per_stream[int(s)] = per_stream.get(int(s), 0.0) + float(load)
    assert set(p.assignments) == set(per_stream)
    for w in range(4):
        assert np.isclose(
            p.loads[w], sum(load for s, load in per_stream.items()
                            if p.assignments[s] == w))
    with pytest.raises(ValueError, match="align 1:1"):
        worst_fit_decreasing([1, 2], [1.0], 2)


def test_wfd_imbalance_sane():
    rng = np.random.RandomState(1)
    loads = rng.pareto(1.5, 64) + 0.01
    p = worst_fit_decreasing(list(range(64)), loads, 8)
    assert np.isclose(sum(p.loads), loads.sum())
    # WFD never exceeds mean + the heaviest piece
    assert max(p.loads) <= loads.sum() / 8 + loads.max() + 1e-9


# ---------------------------------------------------------------------------
# satellite: Placement.diff — apply(delta, prev) == target, moves minimal
# ---------------------------------------------------------------------------
def _random_placement(rng, streams, w):
    assign = {s: int(rng.randint(0, w)) for s in streams}
    loads = [0.0] * w
    for s in assign:
        loads[assign[s]] += 1.0
    return Placement(assignments=assign, loads=loads, n_workers=w)


def test_diff_apply_reproduces_target_property():
    rng = np.random.RandomState(7)
    for trial in range(30):
        w = int(rng.randint(1, 6))
        n = int(rng.randint(1, 40))
        streams = list(rng.choice(10_000, n, replace=False))
        prev = _random_placement(rng, streams[:int(rng.randint(0, n + 1))],
                                 w)
        target = _random_placement(rng, streams, w)
        delta = target.diff(prev)
        got = delta.apply(prev)
        assert got == delta.target.assignments, trial
        # relabeling permutes labels, it never regroups streams
        groups = lambda p: sorted(
            tuple(sorted(s for s, ww in p.assignments.items() if ww == k))
            for k in range(p.n_workers))
        assert groups(delta.target) == groups(target)
        # every listed move is a real move
        for s, pw, dw in delta.moves:
            assert prev.assignments.get(s) == pw and pw != dw


def test_diff_relabel_minimizes_moves():
    # identical placement under permuted labels: ZERO moves after the
    # Hungarian relabel (a naive label-wise diff would move everything)
    prev = Placement(assignments={s: s % 4 for s in range(32)},
                     loads=[8.0] * 4, n_workers=4)
    perm = [2, 3, 1, 0]
    tgt = Placement(assignments={s: perm[s % 4] for s in range(32)},
                    loads=[8.0] * 4, n_workers=4)
    delta = tgt.diff(prev)
    assert delta.moves == [] and delta.dropped == []
    assert delta.target.assignments == prev.assignments
    # one genuinely misplaced stream -> exactly one move
    shifted = {s: perm[s % 4] for s in range(32)}
    shifted[5] = perm[2]
    tgt2 = Placement(assignments=shifted,
                     loads=[8.0, 7.0, 9.0, 8.0], n_workers=4)
    d2 = tgt2.diff(prev)
    assert [(s, pw) for s, pw, _ in d2.moves] == [(5, 1)]


# ---------------------------------------------------------------------------
# the loop, single engine: skew in, rebalanced rows out
# ---------------------------------------------------------------------------
@pytest.mark.smoke
def test_reconcile_single_engine_rebalances():
    streams = list(range(32))
    eng = _mk_engine(streams)
    rec = Reconciler(eng, "rhll", "rcm", n_workers=4, min_gain=0.0)
    count0 = tracing.RECONCILE_COUNT[eng.site]

    eng.ingest(*_skewed(streams, hot=[0, 1]))
    rep = rec.step()
    assert rep["applied"], rep
    assert rep["moves"] == rep["migrated_rows"] > 0
    assert rep["imbalance_after"] < rep["imbalance_before"]

    # rows landed inside their assigned workers' slices of the row axis
    kind = eng.entries["pt/0"].kind_key
    cap = eng.stacks[kind].capacity
    assign = {s: eng.entries[f"pt/{s}"].row * 4 // cap for s in streams}
    assert assign[0] != assign[1]         # the two heavy streams split

    # probes reached the JSON status response
    st = eng.handle({"type": "status", "request_id": "s"})
    assert st.params["reconcile_count"] \
        == tracing.RECONCILE_COUNT[eng.site] > count0
    assert st.params["migrated_rows"] >= rep["migrated_rows"]
    assert st.params["rebalance_imbalance"] \
        == pytest.approx(rep["imbalance_after"])
    json.loads(st.to_json())              # and serializes

    # queries and further ingest survive the move
    q = eng.handle({"type": "adhoc", "request_id": "q",
                    "synopsis_id": "pt/0", "query": {"items": [0]}})
    assert q.ok
    eng.ingest(np.full(50, 0, np.int64), np.ones(50, np.float32))
    eng.flush()
    q2 = eng.handle({"type": "adhoc", "request_id": "q2",
                     "synopsis_id": "pt/0", "query": {"items": [0]}})
    assert float(np.asarray(q2.value)[0]) \
        == float(np.asarray(q.value)[0]) + 50


def test_reconcile_non_pow2_worker_count_terminates():
    # regression: the capacity search used to double a pow2 capacity
    # forever looking for divisibility by 3 — plan directly instead
    streams = list(range(12))
    eng = _mk_engine(streams)
    rec = Reconciler(eng, "rhll", "rcm", n_workers=3, min_gain=0.0)
    eng.ingest(*_skewed(streams, hot=[0, 1]))
    rep = rec.step()
    assert rep["applied"], rep
    kind = eng.entries["pt/0"].kind_key
    cap = eng.stacks[kind].capacity
    ss = cap // 3
    assert cap % 3 == 0 and ss & (ss - 1) == 0           # pow2 slices
    # every row landed inside its worker's slice, heavy streams split
    assign = {s: eng.entries[f"pt/{s}"].row * 3 // cap for s in streams}
    assert set(assign.values()) <= {0, 1, 2}
    assert assign[0] != assign[1]


def test_reconcile_skips_are_quiet():
    streams = list(range(8))
    eng = SDE()
    rec = Reconciler(eng, "rhll", "rcm", n_workers=2)
    rep = rec.step()
    assert rep["reason"] == "estimator synopses not built yet"
    # skip reports carry the SAME schema as applied ones — consumers
    # index imbalance_before/after without guarding on the path
    assert rep["imbalance_before"] is None
    assert rep["imbalance_after"] is None
    eng2 = _mk_engine(streams)
    rec2 = Reconciler(eng2, "rhll", "rcm", n_workers=2)
    assert rec2.step()["reason"] == "no traffic since last pass"
    # first pass spreads the (all-in-slice-0) rows; a second pass over
    # equally balanced traffic is within hysteresis — reconcilers damp
    sids = np.asarray(streams * 64, np.int64)
    eng2.ingest(sids, np.ones(len(sids), np.float32))
    assert rec2.step()["applied"]
    eng2.ingest(sids, np.ones(len(sids), np.float32))
    rep = rec2.step()
    assert not rep["applied"] and rep["reason"] == "within hysteresis"
    assert rep["migrated_rows"] == 0
    # windowing: no NEW traffic means "no traffic since last pass"
    assert rec2.step()["reason"] == "no traffic since last pass"


def test_reconciler_needs_a_worker_count():
    with pytest.raises(ValueError, match="n_workers"):
        Reconciler(SDE(), "h", "c")          # no mesh to infer from


# ---------------------------------------------------------------------------
# the acceptance oracle: reconciled state == from-scratch build at the
# target placement, byte for byte
# ---------------------------------------------------------------------------
def test_reconcile_byte_identical_to_rebuild_at_target():
    streams = list(range(16))
    phase_a = _skewed(streams, hot=[0, 1], seed=3)
    phase_b = _skewed(streams, hot=[14, 15], seed=4)

    live = _mk_engine(streams)
    live.ingest(*phase_a)
    rec = Reconciler(live, "rhll", "rcm", n_workers=4, min_gain=0.0)
    rep = rec.step()
    assert rep["applied"]
    live.ingest(*phase_b)
    live.flush()

    # rebuild from scratch: same builds (same rows), then jump STRAIGHT
    # to the reconciled engine's final placement, then ALL the traffic
    fresh = _mk_engine(streams)
    kind = fresh.entries["pt/0"].kind_key
    fresh.resize_stack(kind, live.stacks[kind].capacity)
    mapping = {fresh.entries[f"pt/{s}"].row: live.entries[f"pt/{s}"].row
               for s in streams}
    fresh.migrate_rows(kind, mapping)
    fresh.ingest(*phase_a)
    fresh.ingest(*phase_b)
    fresh.flush()

    for s in streams:
        assert fresh.entries[f"pt/{s}"].row == live.entries[f"pt/{s}"].row
    assert _stack_bytes(live) == _stack_bytes(fresh)


# ---------------------------------------------------------------------------
# federation: synopses ship between sites through the migration plane
# ---------------------------------------------------------------------------
def test_reconcile_federated_ships_synopses():
    streams = list(range(8))
    fed = Federation(["eu", "us"])
    for rid, (sid, kind, params) in enumerate([
            ("rhll", "hyperloglog", {"rse": 0.05}),
            ("rcm", "countmin", {"eps": 0.01, "delta": 0.01,
                                 "weighted": False})]):
        rs = fed.broadcast({"type": "build", "request_id": f"b{rid}",
                            "synopsis_id": sid, "kind": kind,
                            "params": params})
        assert all(r.ok for r in rs.values())
    r = fed.sdes["eu"].handle({"type": "build", "request_id": "p",
                               "synopsis_id": "pt", "kind": "countmin",
                               "params": CM, "per_stream_of_source": True,
                               "stream_ids": streams})
    assert r.ok, r.error

    sids, vals = _skewed(streams, hot=[0], seed=5, frac=0.5)
    fed.sdes["eu"].ingest(sids, vals)
    counts = {s: int(np.count_nonzero(sids == s)) for s in streams}

    rec = Reconciler(fed, "rhll", "rcm", min_gain=0.0)
    rep = rec.step()
    assert rep["applied"] and rep["migrated_rows"] > 0

    moved = [s for s in streams if f"pt/{s}" in fed.sdes["us"].entries]
    stayed = [s for s in streams if f"pt/{s}" in fed.sdes["eu"].entries]
    assert sorted(moved + stayed) == streams and moved
    assert tracing.RECONCILE_COUNT["federation"] > 0
    # federated passes are ALSO tagged per member site, so each site's
    # JSON status (keyed by its own site tag) shows the loop's activity
    for site in ("eu", "us"):
        st = fed.sdes[site].handle({"type": "status", "request_id": "st"})
        assert st.params["reconcile_count"] > 0
        assert st.params["rebalance_imbalance"] \
            == pytest.approx(rep["imbalance_after"])

    # shipped synopses answer exactly at the new site, then keep counting
    for s in moved:
        q = fed.sdes["us"].handle({"type": "adhoc", "request_id": "q",
                                   "synopsis_id": f"pt/{s}",
                                   "query": {"items": [s]}})
        assert q.ok and float(np.asarray(q.value)[0]) == counts[s]
    s0 = moved[0]
    fed.sdes["us"].ingest(np.full(10, s0, np.int64),
                          np.ones(10, np.float32))
    fed.sdes["us"].flush()
    q = fed.sdes["us"].handle({"type": "adhoc", "request_id": "q2",
                               "synopsis_id": f"pt/{s0}",
                               "query": {"items": [s0]}})
    assert float(np.asarray(q.value)[0]) == counts[s0] + 10


# ---------------------------------------------------------------------------
# drive wires: gateway tick and serve_lines
# ---------------------------------------------------------------------------
def test_gateway_tick_drives_reconciler():
    streams = list(range(16))
    eng = _mk_engine(streams)
    rec = Reconciler(eng, "rhll", "rcm", n_workers=4, min_gain=0.0)
    gw = SynopsisGateway(eng, reconciler=rec)
    c = gw.connect("c0")
    sids, vals = _skewed(streams, hot=[2, 3], seed=6)
    f = gw.submit_nowait(c, {"type": "ingest", "request_id": "i",
                             "stream_ids": [int(s) for s in sids],
                             "values": [float(v) for v in vals]})
    gw.tick()
    assert f.result().ok
    assert gw.reconcile_error is None
    assert rec.last_report is not None and rec.last_report["applied"]
    # an empty tick still drives the loop (a quiet window skips cheaply)
    gw.tick()
    assert rec.last_report["reason"] == "no traffic since last pass"

    # a raising reconciler must not take the gateway down
    class Boom:
        def maybe_step(self):
            raise RuntimeError("boom")
    gw.reconciler = Boom()
    gw.tick()
    assert gw.reconcile_error == "RuntimeError('boom')"
    f2 = gw.submit_nowait(c, {"type": "status", "request_id": "s"})
    gw.tick()
    assert f2.result().ok


def test_serve_lines_drives_reconciler():
    streams = list(range(8))
    eng = _mk_engine(streams)
    rec = Reconciler(eng, "rhll", "rcm", n_workers=2, min_gain=0.0)
    sids, _ = _skewed(streams, hot=[0], seed=8, frac=0.9)
    lines = [json.dumps({"type": "ingest", "request_id": "i",
                         "stream_ids": [int(s) for s in sids],
                         "values": [1.0] * len(sids)})]
    import io
    out = io.StringIO()
    n = sde_server.serve_lines(lines, eng, out=out, reconciler=rec)
    assert n == 1
    assert rec.last_report is not None and rec.last_report["applied"]


def test_server_flags_construct_reconciler():
    # --reconcile-interval wires a Reconciler into JSON-lines mode; an
    # empty-input run proves the flag path end to end
    import io as _io
    import sys as _sys
    old = _sys.stdin
    _sys.stdin = _io.StringIO("")
    try:
        n = sde_server.main(["--reconcile-interval", "0.5",
                             "--reconcile-workers", "2"])
    finally:
        _sys.stdin = old
    assert n == 0
