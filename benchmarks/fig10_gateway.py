"""Fig 10 — gateway coalescing: N concurrent clients, one dispatch.

The serving question fig 8 left open: fig 8 shows ONE call amortizing
device work across synopses (blue path) and across queries (red path);
this figure shows the ``SynopsisGateway`` amortizing across CLIENTS.
64 clients each push 64-tuple ingest batches against an engine
maintaining 1024 CountMin synopses:

  * serial   — the pre-gateway front door: one ``SDE.ingest`` call per
    client per tick (64 fused dispatches per tick, one per client).
  * gateway  — 64 ``submit_nowait`` + ONE ``tick()``: the micro-batcher
    concatenates all 64 batches into one ``SDE.ingest`` (ONE fused
    dispatch per kind per tick), then fans the acks back out.

Both paths ingest identical traffic; the speedup is pure per-dispatch
overhead (trace-cache lookup, donation bookkeeping, kernel launch)
recovered by coalescing — the same effect as fig 8's ``query_many``
but on the write path, driven by concurrency instead of batch size.

``--check`` gates CI: speedup >= 4x AND the probe-verified invariant
that one gateway tick costs exactly ONE blue-path dispatch.
"""
from __future__ import annotations

import numpy as np

from repro import tracing
from repro.service import SDE, SynopsisGateway
from .common import time_fn, csv_row

_N_SYNOPSES = 1024
_N_CLIENTS = 64
_TUPLES_PER_CLIENT = 64
_CM = {"eps": 0.02, "delta": 0.1, "weighted": False}


def _build_engine() -> SDE:
    eng = SDE()
    r = eng.handle({"type": "build", "request_id": "b",
                    "synopsis_id": "cm", "kind": "countmin",
                    "params": _CM, "per_stream_of_source": True,
                    "n_streams": _N_SYNOPSES})
    assert r.ok, r.error
    return eng


def _client_batches(rng):
    return [(rng.randint(0, _N_SYNOPSES, _TUPLES_PER_CLIENT)
             .astype(np.int64),
             rng.uniform(0.5, 2.0, _TUPLES_PER_CLIENT)
             .astype(np.float32))
            for _ in range(_N_CLIENTS)]


def run(full: bool = False, check: bool = False):
    rng = np.random.RandomState(0)
    batches = _client_batches(rng)
    reqs = [{"type": "ingest", "request_id": f"i{j}",
             "stream_ids": sids.tolist(), "values": vals.tolist()}
            for j, (sids, vals) in enumerate(batches)]

    serial = _build_engine()

    def serial_tick():
        for sids, vals in batches:       # one dispatch PER CLIENT
            serial.ingest(sids, vals)
        return [serial.stacks[k].state for k in serial.stacks]

    gw = SynopsisGateway(_build_engine())
    clients = [gw.connect(f"c{j}") for j in range(_N_CLIENTS)]

    def gateway_tick():
        futs = [gw.submit_nowait(c, r) for c, r in zip(clients, reqs)]
        gw.tick()                        # ONE dispatch for all clients
        for f in futs:
            assert f.result().ok, f.result().error
        return [gw.sde.stacks[k].state for k in gw.sde.stacks]

    t_serial = time_fn(serial_tick, warmup=1, iters=5)
    t_gateway = time_fn(gateway_tick, warmup=1, iters=5)

    # probe the invariant on one extra tick: 64 clients, ONE dispatch
    d0 = tracing.DISPATCH_COUNT.get("update:CountMin", 0)
    c0 = tracing.GATEWAY_COALESCED.get("ingest", 0)
    gateway_tick()
    dispatches = tracing.DISPATCH_COUNT["update:CountMin"] - d0
    coalesced = tracing.GATEWAY_COALESCED["ingest"] - c0

    tuples = _N_CLIENTS * _TUPLES_PER_CLIENT
    speedup = t_serial / t_gateway
    rows = [csv_row(
        f"fig10_gateway_c{_N_CLIENTS}_k{_N_SYNOPSES}", t_gateway,
        f"gateway={tuples / t_gateway:,.0f}t/s "
        f"serial={tuples / t_serial:,.0f}t/s "
        f"speedup={speedup:.1f}x "
        f"dispatches_per_tick={dispatches} coalesced={coalesced}")]
    if check:
        assert dispatches == 1, \
            f"expected ONE blue dispatch per tick, saw {dispatches}"
        assert coalesced == _N_CLIENTS, \
            f"expected {_N_CLIENTS} coalesced requests, saw {coalesced}"
        assert speedup >= 4.0, \
            f"gateway speedup {speedup:.2f}x < 4x acceptance floor"
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
