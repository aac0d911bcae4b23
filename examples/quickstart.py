"""Quickstart: the Synopses Data Engine in 60 seconds.

Builds synopses over a synthetic stock stream through the SDEaaS JSON API,
queries them, merges federated states, and shows the DFT correlation
bucketing — the paper's core loop end to end on one CPU.

  PYTHONPATH=src python examples/quickstart.py
"""
import asyncio
import json

import numpy as np

from repro.service import SDE, Federation
from repro.streams import StockStream


def main():
    sde = SDE()

    # 1. Build synopses on-the-fly (paper Section 3: Build Synopsis).
    #    One request maintains a CountMin per stock for 500 stocks.
    #    Stream ids are ARBITRARY non-negative ints (< 2**63): routing is
    #    hashed, so 64-bit hashed user ids / sensor UUIDs work as-is —
    #    no re-keying to a dense range. Pass `stream_ids=[...]` on a
    #    per-stream build to cover a sparse/hashed id population.
    for req in [
        {"type": "build", "request_id": "r1", "synopsis_id": "bids",
         "kind": "countmin", "params": {"eps": 0.01, "delta": 0.05},
         "per_stream_of_source": True, "n_streams": 500,
         "source_id": "stocks"},
        {"type": "build", "request_id": "r2", "synopsis_id": "cardinality",
         "kind": "hyperloglog", "params": {"rse": 0.02}},
        {"type": "build", "request_id": "r3", "synopsis_id": "dft",
         "kind": "dft", "params": {"window": 64, "n_coeffs": 8,
                                   "threshold": 0.9},
         "per_stream_of_source": True, "n_streams": 500},
    ]:
        resp = sde.handle(req)
        assert resp.ok, resp.error
        print(f"built {resp.synopsis_id}: {resp.params}")

    # 2. Ingest the stream (blue path) — one call updates EVERYTHING.
    stock = StockStream(n_streams=500, group_size=10, seed=0)
    for _ in range(200):
        sids, vals = stock.level1_batch(2000)
        sde.ingest(sids, vals)
    print(f"\ningested {sde.tuples_ingested:,} tuples; engine state = "
          f"{sde.memory_bytes()/1e6:.1f} MB for "
          f"{len(sde.entries)} synopses")

    # 2b. Pipelined ingest: `SDE(pipelined=True)` parks each batch's
    #     continuous-query outputs on a bounded (depth-2) queue instead
    #     of syncing device->host inside ingest, so host prep for the
    #     next batch overlaps the device work of the previous one.
    #     Syncs happen ONLY when (a) a newer batch pushes an old one off
    #     the queue, (b) you call flush() — the explicit barrier — or
    #     (c) the engine fences itself before a query/stop/build/
    #     snapshot, which is why both modes return identical results.
    psde = SDE(pipelined=True)
    resp = psde.handle({"type": "build", "request_id": "p1",
                        "synopsis_id": "live", "kind": "hyperloglog",
                        "params": {"rse": 0.02}, "continuous": True})
    assert resp.ok, resp.error
    pstock = StockStream(n_streams=500, group_size=10, seed=1)
    for _ in range(8):
        sids, vals = pstock.level1_batch(2000)
        batch = psde.ingest(sids, vals)       # returns without syncing
    print(f"\npipelined ingest: batch {batch} acked, "
          f"{psde.pending_batches} batches still in flight")
    drained = psde.flush()                    # the explicit barrier
    print(f"flush() drained {drained} batches -> "
          f"{len(psde.continuous_out)} continuous responses "
          f"(latest cardinality "
          f"{float(psde.continuous_out[-1].value):,.0f})")

    # 2c. Pallas backend: `SDE(backend="pallas")` (or SDE_BACKEND=pallas)
    #     runs the blue path through hand-written Pallas kernels instead
    #     of XLA's scatter lowering. Every scatter kind covers it —
    #     countmin, ams, hyperloglog, bloom, fm and rhp each declare
    #     `update_kernel = "<name>"` resolved from the kernels.ops
    #     registry at dispatch (no isinstance ladder); scan kinds and the
    #     DFT step path fall back to the same XLA programs as backend=
    #     "xla". The routing probe runs ahead of the kernel in the same
    #     jitted dispatch, and states stay byte-identical to the XLA
    #     backend — CI-gated by `python -m benchmarks.roofline --check`.
    #     On a TPU the kernels are compiled by Mosaic; elsewhere they run
    #     in interpret mode. A plugged kind reuses
    #     a stock kernel by declaring its name, or brings its own via
    #     `kernels.ops.register_update_kernel(name, builder)`.
    ksde = SDE(backend="pallas")
    resp = ksde.handle({"type": "build", "request_id": "k1",
                        "synopsis_id": "kbids", "kind": "countmin",
                        "params": {"eps": 0.1, "delta": 0.1},
                        "per_stream_of_source": True, "n_streams": 500,
                        "source_id": "stocks"})
    assert resp.ok, resp.error
    kstock = StockStream(n_streams=500, group_size=10, seed=0)
    for _ in range(4):
        ksde.ingest(*kstock.level1_batch(2000))
    q = ksde.handle({"type": "adhoc", "request_id": "kq",
                     "synopsis_id": "kbids/42", "query": {"items": [42]}})
    print(f"\npallas backend: stock 42 bid volume (CM) "
          f"{float(q.value[0]):,.1f} via the Pallas update kernel")

    # 2d. Serving many clients: the `SynopsisGateway` front door
    #     multiplexes N concurrent clients onto ONE engine. Per tick it
    #     concatenates every client's ingest into one fused blue-path
    #     dispatch per kind (the acks below all carry the same batch id
    #     and coalesced=8) and folds concurrent ad-hoc queries into one
    #     `query_many` dispatch. A request's `tenant` namespaces its
    #     synopsis keys ("acme::cm" vs "globex::cm" in the engine) while
    #     STREAM ids stay shared — many workflows, same streams — which
    #     is exactly what makes their traffic coalescible.
    #     `python -m repro.launch.sde_server --port 7077` serves this
    #     over TCP with per-client backpressure.
    async def serve_clients():
        from repro.service import SynopsisGateway
        gw = SynopsisGateway(SDE(), tick_interval=0.001)
        await gw.start()

        async def one_client(j):
            tenant = "acme" if j % 2 else "globex"
            c = gw.connect(f"client-{j}", tenant=tenant)
            r = await gw.submit(c, {
                "type": "build", "request_id": f"b{j}",
                "synopsis_id": f"cm{j}", "kind": "countmin",
                "params": {"eps": 0.05, "delta": 0.1, "weighted": False}})
            assert r.ok, r.error
            rng = np.random.RandomState(j)
            r = await gw.submit(c, {
                "type": "ingest", "request_id": f"i{j}",
                "stream_ids": rng.randint(0, 500, 64).tolist(),
                "values": [1.0] * 64})
            return r.value

        acks = await asyncio.gather(*(one_client(j) for j in range(8)))
        await gw.stop()
        return acks

    acks = asyncio.run(serve_clients())
    coalesced = max(a["coalesced"] for a in acks)
    print(f"\ngateway: 8 clients' ingest coalesced "
          f"{coalesced}-wide into {len({a['batch'] for a in acks})} "
          f"fused batch(es) — dispatch cost amortizes across clients")

    # 2e. Elastic rebalancing: the `Reconciler` closes the loop the
    #     balancer opens (paper Section 7). The engine's OWN estimator
    #     synopses measure the load — HLL says how many streams are
    #     active, CountMin says how heavy each one is — then WFD plans a
    #     target placement over `n_workers` row slices, `Placement.diff`
    #     reduces it to minimal moves, and the migration plane
    #     (`service/migration.py`) relocates exactly those rows, routing
    #     entries remapped atomically so fused programs never retrace.
    #     Live it rides the gateway tick or `sde_server
    #     --reconcile-interval`; here one explicit `step()` after skewed
    #     traffic shows the mechanism.
    from repro.service import Reconciler
    esde = SDE()
    for req in [
        {"type": "build", "request_id": "e1", "synopsis_id": "load",
         "kind": "countmin", "params": {"eps": 0.05, "delta": 0.1,
                                        "weighted": False},
         "per_stream_of_source": True, "n_streams": 64},
        {"type": "build", "request_id": "e2", "synopsis_id": "ehll",
         "kind": "hyperloglog", "params": {"rse": 0.05}},
        {"type": "build", "request_id": "e3", "synopsis_id": "ecm",
         "kind": "countmin", "params": {"eps": 0.01, "delta": 0.01,
                                        "weighted": False}},
    ]:
        assert esde.handle(req).ok
    rng = np.random.RandomState(7)
    hot = rng.choice(64, 4096, p=np.where(np.arange(64) < 8,
                                          0.9 / 8, 0.1 / 56))
    esde.ingest(hot.astype(np.int64), np.ones(4096, np.float32))
    rep = Reconciler(esde, "ehll", "ecm", n_workers=4).step()
    assert rep["applied"], rep       # skewed traffic always rebalances
    print(f"\nreconciler: applied={rep['applied']} "
          f"moves={rep['moves']} rows={rep['migrated_rows']} "
          f"imbalance {rep['imbalance_before']:.2f} -> "
          f"{rep['imbalance_after']:.2f} — hot streams spread across "
          f"4 workers, state moved byte-exactly")

    # 2f. Durable mode: checkpointing off the hot path. A `WriteAheadLog`
    #     records every state-mutating call durably before its ack
    #     (lifecycle requests pre-apply; ingest batches after a
    #     successful apply, keyed by the engine-assigned batch id, so a
    #     refused batch never lands in the log), and a `Checkpointer`
    #     takes an
    #     incremental snapshot every `interval` ingested batches — a
    #     dirty-row DELTA chained on the last full base, written by a
    #     background thread, so the steady-state cost is O(rows touched),
    #     not O(engine state). `recover` = restore the latest snapshot +
    #     replay the WAL tail through the normal ingest path; monotonic
    #     seq/batch watermarks make the replay exactly-once, so the
    #     recovered engine is byte-identical to the acked pre-crash
    #     state. Serving processes get all of this with
    #     `python -m repro.launch.sde_server --wal ingest.wal
    #     --checkpoint-dir ckpt --checkpoint-interval 8` (add `--recover`
    #     on restart; `--full-snapshots` opts back into the old
    #     synchronous full path).
    import tempfile
    from repro.service import Checkpointer, WriteAheadLog, recover
    ck_dir = tempfile.mkdtemp()
    wal_path = ck_dir + "/ingest.wal"
    dsde = SDE()
    wal = WriteAheadLog(wal_path, tag=dsde.site)
    ckp = Checkpointer(dsde, ck_dir, interval=4)   # delta every 4 batches
    breq = {"type": "build", "request_id": "d1", "synopsis_id": "dcm",
            "kind": "countmin", "params": {"eps": 0.05, "delta": 0.1},
            "per_stream_of_source": True, "n_streams": 64}
    wal.append_request(breq)
    assert dsde.handle(breq).ok
    dsde.wal_seq = wal.seq
    drng = np.random.RandomState(3)
    for _ in range(10):                  # 2 deltas + a 2-batch WAL tail
        sids = drng.randint(0, 64, 256).astype(np.int64)
        vals = np.ones(256, np.float32)
        batch = dsde.ingest(sids, vals)
        wal.append_ingest(batch, sids, vals)   # post-apply: acked id
        wal.sync()                       # durable-before-ack point
        dsde.wal_seq = wal.seq
        ckp.maybe_snapshot()
    wal.close()
    dsde.wait_for_snapshot()
    back = recover(ck_dir, wal_path)     # "kill -9", then this
    q1 = dsde.handle({"type": "adhoc", "request_id": "dq",
                      "synopsis_id": "dcm/9", "query": {"items": [9]}})
    q2 = back.handle({"type": "adhoc", "request_id": "dq",
                      "synopsis_id": "dcm/9", "query": {"items": [9]}})
    assert float(q1.value[0]) == float(q2.value[0])
    print(f"\ndurable mode: {ckp.snapshots} incremental snapshots + "
          f"{back.batches_ingested - 8}-batch WAL tail -> recovered "
          f"engine matches (stream 9 count {float(q2.value[0]):,.0f})")

    # 2g. Multidim subpopulations + the continuous outlier workflow.
    #     `build_multidim` declares attribute dimensions with finite
    #     domains; every subset of dimensions (a "level") gets one
    #     synopsis per value combination, all encoded into the SAME
    #     63-bit stream-id space the router already speaks — so multidim
    #     groups are ordinary routed streams and ingest stays ONE fused
    #     dispatch per kind. `subpop_query` answers a conjunction of
    #     per-dimension predicates by merging the minimal covering key
    #     set in one fused gather+merge+estimate dispatch (vs scanning
    #     every leaf synopsis — fig13 gates the >= 4x win). A tracked
    #     outlier workflow re-scores one level against the population
    #     every ingest tick off the SAME synopses (zero extra builds),
    #     flagging robust-z outliers through the continuous channel.
    msde = SDE()
    assert msde.handle({
        "type": "build_multidim", "request_id": "m1", "synopsis_id":
        "trades", "kind": "countmin",
        "params": {"eps": 0.005, "delta": 0.01, "weighted": False},
        "dims": {"region": ["EU", "US", "APAC"],
                 "venue": ["lit", "dark"]}}).ok
    assert msde.handle({
        "type": "track_outliers", "request_id": "m2", "workflow_id":
        "hot-venues", "synopsis_id": "trades", "level": ["region"],
        "query": {"items": [1]}, "threshold": 2.0}).ok
    mrng = np.random.RandomState(11)
    recs = [{"region": str(r), "venue": str(v)} for r, v in zip(
        mrng.choice(["EU", "US", "APAC"], 3000, p=[0.7, 0.2, 0.1]),
        mrng.choice(["lit", "dark"], 3000))]
    assert msde.handle({
        "type": "ingest_multidim", "request_id": "m3", "synopsis_id":
        "trades", "records": recs, "values": [1.0] * len(recs),
        "items": [1] * len(recs)}).ok
    sq = msde.handle({"type": "subpop_query", "request_id": "m4",
                      "synopsis_id": "trades",
                      "where": {"region": ["EU", "US"], "venue": "lit"},
                      "query": {"items": [1]}})
    msde.flush()
    ow = [r for r in msde.continuous_out.drain()
          if r.synopsis_id == "hot-venues"][-1]
    print(f"\nsubpop EU|US x lit trades: {float(sq.value[0]):,.0f} "
          f"(covering {sq.params['cover_keys']} keys, one dispatch); "
          f"outlier tick flagged {[o['group'] for o in ow.value['outliers']]}")
    msde.close()

    # 3. Ad-hoc queries (red path).
    q = sde.handle({"type": "adhoc", "request_id": "q1",
                    "synopsis_id": "cardinality"})
    print(f"\ndistinct stocks (HLL):   {float(q.value):,.0f}  (true 500)")
    q = sde.handle({"type": "adhoc", "request_id": "q2",
                    "synopsis_id": "bids/42", "query": {"items": [42]}})
    print(f"stock 42 bid volume (CM): {float(q.value[0]):,.1f}")
    q = sde.handle({"type": "adhoc", "request_id": "q3",
                    "synopsis_id": "dft/7"})
    print(f"stock 7 DFT bucket:       {int(q.value['bucket'])} "
          f"(coeffs {q.value['coeffs'].shape})")

    # 3b. Querying at scale: N ad-hoc queries of one kind are answered by
    #     ONE jitted stacked-estimate dispatch (the batched red path) —
    #     this is what keeps thousands of concurrent SDEaaS queries from
    #     serializing on host round trips (paper Fig. 8).
    batch = sde.handle({
        "type": "query_many", "request_id": "qm",
        "queries": [{"synopsis_id": f"bids/{s}", "query": {"items": [s]}}
                    for s in range(100)]})
    vols = [float(r["value"][0]) for r in batch.value]
    print(f"\n100 bid volumes in one dispatch: "
          f"min={min(vols):,.0f} max={max(vols):,.0f}")

    # 4. Federated queries across two 'sites' (yellow path). With one
    #    device per site available, pass a mesh whose `site` axis plays
    #    the DCN between clusters: each site's state lives on its own
    #    device, site ingest runs site-locally, and a federated query is
    #    ONE compiled collective program — `federated.merge_over_axis`
    #    psum/pmax-merges the partial synopses ACROSS the axis and the
    #    estimate executes at the responsible site. Without enough
    #    devices the same API answers via the host-merge path; results
    #    are byte-identical either way.
    from repro.launch.mesh import try_federation_mesh
    fed = Federation(["eu", "us"], mesh=try_federation_mesh(2))
    fed.broadcast({"type": "build", "request_id": "f", "synopsis_id": "h",
                   "kind": "hyperloglog", "params": {"rse": 0.02},
                   "federated": True, "responsible_site": "eu"})
    fed.sdes["eu"].ingest(np.arange(0, 3000, dtype=np.uint32),
                          np.ones(3000, np.float32))
    fed.sdes["us"].ingest(np.arange(2000, 5000, dtype=np.uint32),
                          np.ones(3000, np.float32))
    #    The JSON `federated_query` request reports the fig 5d metrics:
    #    what the collective shipped across the site axis vs what
    #    gathering every site's state to the responsible host would ship.
    resp = fed.handle({"type": "federated_query", "request_id": "fq",
                       "synopsis_id": "h", "responsible_site": "eu"})
    print(f"\nfederated distinct count: {float(resp.value):,.0f} "
          f"(true 5,000) via the {resp.params['path']} path — shipped "
          f"{resp.params['collective_operand_bytes']:,} bytes "
          f"(host-merge would ship "
          f"{resp.params['host_merge_bytes']:,})")

    # 5. Status report.
    st = sde.handle({"type": "status", "request_id": "s"})
    print(f"\nSDE status: {len(st.value)} synopses live; sample entry:")
    k = sorted(st.value)[0]
    print(" ", k, "->", json.dumps(st.value[k], default=str)[:100])


if __name__ == "__main__":
    main()
