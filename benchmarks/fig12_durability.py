"""Fig 12 — checkpointing off the hot path: dirty-row deltas vs full.

The durability question: what does it COST to make the engine
crash-safe? 1024 per-stream CountMins ingest skewed traffic (each
8-batch interval touches a rotating ~19% window of the streams — the
hot set real workloads have) under three regimes:

  * none — no checkpointing: the throughput ceiling.
  * incr — ``SDE.snapshot(incremental=True, async_=True)`` every 8
    batches: a dirty-row delta chained on one full base. No pipeline
    fence (the bounded pull syncs only dirty slices), npz write + fsync
    on a background thread — only the host copy of the touched rows
    rides the hot path.
  * full — the pre-delta baseline: synchronous full snapshots at the
    same cadence. Every one fences the pipeline, pulls the whole stack
    and blocks on the write.

``--check`` gates CI on the three acceptance claims: incr keeps
>= 0.9x of the no-checkpoint throughput, full drops below 0.7x, and a
delta with <= 20% dirty rows ships <= 0.25x the bytes of a full
snapshot (measured by the CHECKPOINT_BYTES probe, which counts payload
bytes whether or not the write already retired).
"""
from __future__ import annotations

import os
import tempfile
import time

import numpy as np

from repro import tracing
from repro.service import SDE
from .common import csv_row

_N_SYNOPSES = 1024
_BATCH = 49152                 # tuples per ingest batch
_INTERVAL = 8                  # batches between snapshots
_WINDOW = 24                   # streams hot per interval (~2% dirty)
# wide, shallow CM rows: ingest cost scales with depth x batch, full
# snapshot cost with width x capacity — the realistic regime where the
# state dwarfs what one interval touches
_CM = {"eps": 0.001, "delta": 0.01, "weighted": False}


def _build_engine() -> SDE:
    eng = SDE(pipelined=True)
    r = eng.handle({"type": "build", "request_id": "b",
                    "synopsis_id": "cm", "kind": "countmin",
                    "params": _CM, "per_stream_of_source": True,
                    "n_streams": _N_SYNOPSES})
    assert r.ok, r.error
    return eng


def _interval_traffic(rng, offset):
    """One snapshot interval's batches, all drawn from a _WINDOW-wide
    stream window at ``offset`` — the rotating hot set."""
    out = []
    for _ in range(_INTERVAL):
        sids = ((offset + rng.randint(0, _WINDOW, _BATCH))
                % _N_SYNOPSES).astype(np.int64)
        out.append((sids, rng.uniform(0.5, 2.0, _BATCH)
                    .astype(np.float32)))
    return out


def _timed_run(eng, traffic, snap) -> float:
    """Wall seconds to ingest ``traffic`` (a list of intervals), calling
    ``snap()`` after each interval, ending on a drained pipeline."""
    t0 = time.perf_counter()
    for interval in traffic:
        for sids, vals in interval:
            eng.ingest(sids, vals)
        snap()
    eng.flush()
    return time.perf_counter() - t0


def run(full: bool = False, check: bool = False):
    rng = np.random.RandomState(0)
    intervals = 6 if full else 3
    repeats = 5
    step = dict(n=0)

    def next_step() -> int:
        step["n"] += 1
        return step["n"]

    modes = ("none", "incr", "full")
    engines = {}
    snaps = {}
    times = {m: [] for m in modes}
    # snapshots land on tmpfs when the host has one: the figure measures
    # the ENGINE's checkpoint overhead (fence, host pull, serialization),
    # and routing it through a spinning disk would gate CI on that
    # machine's fsync latency instead (the durability tests exercise
    # real files; this benchmark isolates the hot-path cost)
    base = "/dev/shm" if os.path.isdir("/dev/shm") else None
    with tempfile.TemporaryDirectory(dir=base) as tmp:
        for mode in modes:
            eng = _build_engine()
            engines[mode] = eng
            ck = f"{tmp}/{mode}"
            # warmup: compile the fused paths before the clock starts
            for sids, vals in _interval_traffic(rng, 0):
                eng.ingest(sids, vals)
            eng.flush()
            if mode != "none":
                eng.snapshot(ck, 0, incremental=False)
            if mode == "incr":
                # one untimed delta compiles the dirty-row gather and
                # leaves the chain the timed deltas extend
                for sids, vals in _interval_traffic(rng, _WINDOW):
                    eng.ingest(sids, vals)
                eng.snapshot(ck, next_step(), incremental=True,
                             async_=True, rebase_every=1_000_000)
            if mode == "none":
                snaps[mode] = lambda: None
            elif mode == "incr":
                # rebase_every sys-large: the timed window measures the
                # steady delta cadence, not a rebase spike
                snaps[mode] = lambda e=eng, c=ck: e.snapshot(
                    c, next_step(), incremental=True, async_=True,
                    rebase_every=1_000_000)
            else:
                snaps[mode] = lambda e=eng, c=ck: e.snapshot(
                    c, next_step(), incremental=False, async_=False)
        # repeats interleave round-robin across regimes: the process
        # slows slightly over its lifetime (allocator growth), and a
        # sequential schedule would bill all of that drift to whichever
        # regime ran last
        for rep in range(repeats):
            for mode in modes:
                traffic = [_interval_traffic(rng, (rep * intervals + i)
                                             * _WINDOW)
                           for i in range(intervals)]
                times[mode].append(
                    _timed_run(engines[mode], traffic, snaps[mode]))
        for eng in engines.values():
            eng.wait_for_snapshot()
        # best-of-N: the min is the interference-free estimate of each
        # regime's intrinsic cost (snapshot work is in-loop, so it stays
        # in the incr/full minima); medians of noisy wall times would
        # make the ratio gates flaky
        regimes = {m: float(np.min(ts)) for m, ts in times.items()}

        # bytes claim: one full vs one delta with <= 20% dirty rows,
        # measured through the CHECKPOINT_BYTES probe
        eng = engines["incr"]
        ck = f"{tmp}/incr"
        b0 = tracing.CHECKPOINT_BYTES[eng.site]
        eng.snapshot(ck, next_step(), incremental=False)
        bytes_full = tracing.CHECKPOINT_BYTES[eng.site] - b0
        for sids, vals in _interval_traffic(rng, 0):
            eng.ingest(sids, vals)
        b0 = tracing.CHECKPOINT_BYTES[eng.site]
        eng.snapshot(ck, next_step(), incremental=True)
        bytes_delta = tracing.CHECKPOINT_BYTES[eng.site] - b0
        dirty = int(tracing.DIRTY_ROWS[eng.site])
        for e in engines.values():
            e.close()

    tuples = intervals * _INTERVAL * _BATCH
    thr = {m: tuples / t for m, t in regimes.items()}
    r_incr = thr["incr"] / thr["none"]
    r_full = thr["full"] / thr["none"]
    r_bytes = bytes_delta / bytes_full
    rows = [csv_row(
        f"fig12_durability_k{_N_SYNOPSES}_i{_INTERVAL}",
        regimes["incr"] / (intervals * _INTERVAL),
        f"thr_none={thr['none']:,.0f}t/s thr_incr={thr['incr']:,.0f}t/s "
        f"thr_full={thr['full']:,.0f}t/s incr_vs_none={r_incr:.3f}x "
        f"full_vs_none={r_full:.3f}x delta_bytes={bytes_delta} "
        f"full_bytes={bytes_full} bytes_ratio={r_bytes:.3f}x "
        f"dirty_rows={dirty}")]
    if check:
        assert r_incr >= 0.9, \
            f"incremental async checkpointing kept only {r_incr:.3f}x " \
            "of no-checkpoint throughput, acceptance floor is 0.9x"
        assert r_full < 0.7, \
            f"sync full snapshots kept {r_full:.3f}x — the old path " \
            "must visibly stall (< 0.7x) or the figure measures nothing"
        assert dirty <= 0.20 * _N_SYNOPSES + 1, \
            f"delta dirtied {dirty} rows; the bytes claim needs <= 20%"
        assert r_bytes <= 0.25, \
            f"delta shipped {r_bytes:.3f}x of full-snapshot bytes at " \
            f"{dirty}/{_N_SYNOPSES} dirty rows, acceptance is 0.25x"
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
