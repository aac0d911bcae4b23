"""Fig 11 — elasticity under drift: the reconciler keeps throughput.

A drifting Zipf workload over 64 per-stream CountMins on a 4-worker row
axis, ingested in chunks. Three placements race:

  * static     — WFD planned ONCE on the first phase's loads (what an
    offline planner ships), never revisited. When the hot set drifts —
    phase B concentrates 90% of the traffic on exactly the streams the
    static plan packed onto worker 0 — its bottleneck worker eats the
    whole phase.
  * reconciled — the live loop (``service/reconciler.py``): after every
    chunk the engine samples its own estimator synopses, re-plans WFD,
    and migrates rows through the migration plane. It chases the drift
    with one chunk of lag and the CM's estimation noise — this is the
    REAL engine reconciling, placements read back from row positions.
  * optimal    — per-chunk WFD on the true counts (oracle): the
    statically-optimal bound nothing adaptive can beat.

The metric is bottleneck work: a chunk costs its most-loaded worker's
tuple count (workers drain in parallel), a run costs the sum over
chunks, and modeled throughput is ``total_tuples / (W * cost)`` — 1.0
at perfect balance. ``--check`` gates CI on the paper's elasticity
claim (Section 7): reconciled stays within 1.2x of optimal while static
degrades by >= 2x, and the reconciler actually migrated rows.
"""
from __future__ import annotations

import time

import numpy as np

from repro import tracing
from repro.service import SDE, Reconciler, worst_fit_decreasing
from .common import csv_row

_W = 4
_N_STREAMS = 64
_CHUNKS_PER_PHASE = 16
_CHUNK = 256
_ZIPF_A = 0.9
_CM = {"eps": 0.05, "delta": 0.1, "weighted": False}
_EST_CM = {"eps": 0.01, "delta": 0.01, "weighted": False}


def _build_engine() -> SDE:
    eng = SDE()
    for req in (
        {"type": "build", "request_id": "b1", "synopsis_id": "pt",
         "kind": "countmin", "params": _CM,
         "per_stream_of_source": True, "n_streams": _N_STREAMS},
        {"type": "build", "request_id": "b2", "synopsis_id": "rhll",
         "kind": "hyperloglog", "params": {"rse": 0.05}},
        {"type": "build", "request_id": "b3", "synopsis_id": "rcm",
         "kind": "countmin", "params": _EST_CM},
    ):
        r = eng.handle(req)
        assert r.ok, r.error
    return eng


def _phase_probs(hot=None, hot_mass=0.9):
    """Zipf(a) over stream ranks; with ``hot``, that stream set takes
    ``hot_mass`` of the total (uniformly) and the rest stays Zipf."""
    p = 1.0 / np.arange(1, _N_STREAMS + 1) ** _ZIPF_A
    p /= p.sum()
    if hot is not None:
        mask = np.zeros(_N_STREAMS, bool)
        mask[list(hot)] = True
        p = np.where(mask, 0.0, p)
        p *= (1.0 - hot_mass) / p.sum()
        p[mask] = hot_mass / mask.sum()
    return p


def _engine_assign(eng) -> dict:
    kind = eng.entries["pt/0"].kind_key
    cap = eng.stacks[kind].capacity
    return {s: eng.entries[f"pt/{s}"].row * _W // cap
            for s in range(_N_STREAMS)}


def _chunk_cost(assign, counts) -> float:
    """Bottleneck work for one chunk under ``assign``: the most-loaded
    worker's tuple count (workers drain in parallel)."""
    loads = np.zeros(_W)
    for s in range(_N_STREAMS):
        loads[assign[s]] += counts[s]
    return float(loads.max())


def run(full: bool = False, check: bool = False):
    rng = np.random.RandomState(0)
    eng = _build_engine()
    rec = Reconciler(eng, "rhll", "rcm", n_workers=_W, min_gain=0.02)
    ones = np.ones(_CHUNK, np.float32)

    state = dict(cost_rec=0.0, cost_opt=0.0, cost_static=0.0,
                 t_reconcile=0.0, n_chunks=0, static=None)

    def run_phase(probs):
        chunk_counts = []
        for _ in range(_CHUNKS_PER_PHASE):
            sids = rng.choice(_N_STREAMS, _CHUNK, p=probs).astype(np.int64)
            counts = np.bincount(sids, minlength=_N_STREAMS)
            chunk_counts.append(counts)
            # placement DURING the chunk: the real engine's row layout
            state["cost_rec"] += _chunk_cost(_engine_assign(eng), counts)
            if state["static"] is not None:
                state["cost_static"] += _chunk_cost(
                    state["static"].assignments, counts)
            eng.ingest(sids, ones)
            t0 = time.perf_counter()
            rec.maybe_step()
            state["t_reconcile"] += time.perf_counter() - t0
            state["n_chunks"] += 1
        # the oracle: the best STATIC placement for this phase, planned
        # on the phase's true totals (per-chunk re-planning would just
        # chase sampling noise no real scheduler sees)
        phase_counts = np.sum(chunk_counts, axis=0)
        opt = worst_fit_decreasing(list(range(_N_STREAMS)),
                                   phase_counts, _W)
        for counts in chunk_counts:
            state["cost_opt"] += _chunk_cost(opt.assignments, counts)
        return phase_counts

    # warmup (uncounted): let the reconciler pull the fresh engine's
    # rows — all allocated into worker 0's slice — apart before the
    # measurement window opens, so the race starts from a warmed system
    for _ in range(2):
        sids = rng.choice(_N_STREAMS, _CHUNK,
                          p=_phase_probs()).astype(np.int64)
        eng.ingest(sids, ones)
        rec.maybe_step()

    # phase A: plain Zipf — this is also where the static plan is fitted
    # (it pays the same cost the reconciler does while both converge)
    counts_a = run_phase(_phase_probs())
    state["static"] = worst_fit_decreasing(
        list(range(_N_STREAMS)), counts_a, _W)
    state["cost_static"] = state["cost_rec"]

    # drift: each phase's hot set is EXACTLY one static worker's stream
    # set — maximally adversarial for a placement that cannot move. Pick
    # the workers holding the MOST streams (WFD isolates the Zipf head
    # on its own worker; a one-stream hot set is indivisible for
    # everyone, which would measure nothing)
    by_count = sorted(range(_W), key=lambda w: -sum(
        1 for ww in state["static"].assignments.values() if ww == w))
    n_drift = 2 if full else 1
    for w in by_count[:n_drift]:
        hot = [s for s, ww in state["static"].assignments.items()
               if ww == w]
        run_phase(_phase_probs(hot=hot))

    total = state["n_chunks"] * _CHUNK
    thr = {name: total / (_W * state[f"cost_{key}"])
           for name, key in (("reconciled", "rec"), ("static", "static"),
                             ("optimal", "opt"))}
    rec_vs_opt = state["cost_rec"] / state["cost_opt"]
    static_vs_opt = state["cost_static"] / state["cost_opt"]
    migrated = int(tracing.MIGRATED_ROWS[eng.site])
    rows = [csv_row(
        f"fig11_elasticity_w{_W}_s{_N_STREAMS}",
        state["t_reconcile"] / state["n_chunks"],
        f"thr_reconciled={thr['reconciled']:.3f} "
        f"thr_static={thr['static']:.3f} thr_optimal={thr['optimal']:.3f} "
        f"rec_vs_opt={rec_vs_opt:.2f}x "
        f"static_vs_opt={static_vs_opt:.2f}x migrated_rows={migrated}")]
    if check:
        assert rec_vs_opt <= 1.2, \
            f"reconciled {rec_vs_opt:.2f}x of optimal, acceptance is 1.2x"
        assert static_vs_opt >= 2.0, \
            f"static only degraded {static_vs_opt:.2f}x; the drift must " \
            "cost a frozen placement >= 2x"
        assert migrated > 0, "reconciler never migrated a row"
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
