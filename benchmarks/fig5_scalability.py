"""Fig 5 — SDEaaS scalability study (paper Section 8.1).

(a) throughput vs parallelization degree [2..10 workers]
(b) throughput vs ingestion rate multiplier [1,2,5,10]
(c) throughput vs number of summarized streams [50,500,5000]
(d) federated communication vs #sites: collective site merges
    (`merge_over_axis` operand bytes) vs host-merge state shipping vs
    raw streams, plus a live mesh-collective query on multi-device hosts
(e) routing scale: ingest throughput at 1M distinct hashed 64-bit
    stream ids vs the 65k that used to be the dense-table cap
(f) pipelined vs eager blue path: ingest throughput with 1024
    continuous synopses — the bounded async queue (deferred emission)
    against per-batch inline sync. Acceptance: pipelined >= 1.2x.

(a) runs on the ENGINE's fused blue path (one jitted, donated-buffer
dispatch per kind per batch, routing + routed + data-source rows in one
program). This container has ONE core, so the multi-worker aggregate is
simulated the way the paper's mechanism works: streams are
hash-partitioned into P shards, each shard is one SDE engine, per-shard
ingest time is measured, and aggregate throughput = batch_tuples /
max-shard-time (workers run concurrently on a real cluster). On a
multi-device host the same measurement also runs with ONE engine whose
kind stacks are sharded over the `synopsis` mesh axis (true scale-out).
(b), (c), (d) are direct measurements.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from repro import core
from repro.core import batched, federated
from repro.service import SDE
from repro.streams import StockStream
from .common import time_fn, csv_row

_KINDS = lambda: dict(
    cm=core.CountMin(eps=0.002, delta=0.01),      # paper's parameters
    hll=core.HyperLogLog(rse=0.03),
    dft=core.DFT(window=64, n_coeffs=8, threshold=0.9),
)


def _ingest_sync(eng: SDE, sids, vals):
    """Ingest and hand the updated stack states to time_fn so its
    block_until_ready waits for the dispatched update, not just the
    host-side enqueue (ingest itself returns None)."""
    eng.ingest(sids, vals)
    return [s.state for s in eng.stacks.values()]


def _update_fns(kinds):
    fns = {}
    for name, kind in kinds.items():
        if name == "dft":
            fns[name] = jax.jit(
                lambda st, vals, msk, k=kind: batched.stacked_step(
                    k, st, vals, msk))
        else:
            fns[name] = jax.jit(
                lambda st, syn, it, v, m, k=kind: batched.stacked_add_batch(
                    k, st, syn, it, v, m))
    return fns


def run(batch_tuples: int = 262144, full: bool = False):
    rows = []
    kinds = _KINDS()
    fns = _update_fns(kinds)

    # ---------------- (a) parallelization degree ----------------
    # fused blue path: each worker is one SDE maintaining one routed CM
    # synopsis PER STREAM + 1 data-source HLL over the full stream-id
    # population (paper setting); ingest is ONE dispatch per kind.
    n_streams = 1000 if not full else 5000
    stock = StockStream(n_streams=n_streams, seed=1)
    sids, vals = stock.level1_batch(batch_tuples)
    for p in [2, 4, 6, 8, 10]:
        shard_of = sids % p
        shard_times = []
        for w in range(p):
            sel = shard_of == w
            eng = SDE()
            eng.handle({"type": "build", "request_id": "b",
                        "synopsis_id": "cm", "kind": "countmin",
                        "params": {"eps": 0.002, "delta": 0.01,
                                   "weighted": False},
                        "per_stream_of_source": True,
                        "n_streams": n_streams})
            eng.handle({"type": "build", "request_id": "b2",
                        "synopsis_id": "card", "kind": "hyperloglog",
                        "params": {"rse": 0.03}})
            w_sids = sids[sel].astype(np.uint32)
            w_vals = vals[sel].astype(np.float32)
            t = time_fn(lambda s=w_sids, v=w_vals, e=eng: _ingest_sync(e, s, v))
            shard_times.append(t)
        thr = batch_tuples / max(shard_times)
        rows.append(csv_row(f"fig5a_parallelism_{p}", max(shard_times),
                            f"throughput={thr:,.0f}tuples/s"))

    # ---- (a') synopsis-axis sharding: one engine, stacks partitioned
    # across devices (requires a multi-device host; skipped on 1 device)
    if len(jax.devices()) > 1:
        n_dev = len(jax.devices())
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((n_dev, 1), ("data", "model"))
        eng = SDE(mesh=mesh)
        eng.handle({"type": "build", "request_id": "b", "synopsis_id":
                    "cm", "kind": "countmin",
                    "params": {"eps": 0.002, "delta": 0.01,
                               "weighted": False},
                    "per_stream_of_source": True, "n_streams": n_streams})
        sh_sids = sids.astype(np.uint32)
        sh_vals = vals.astype(np.float32)
        t = time_fn(lambda: _ingest_sync(eng, sh_sids, sh_vals))
        rows.append(csv_row(
            f"fig5a_sharded_{n_dev}dev", t,
            f"throughput={batch_tuples / t:,.0f}tuples/s"))

    # ---------------- (b) ingestion rate ----------------
    base_sids, base_vals = stock.level1_batch(batch_tuples // 16)
    cm_state = batched.stacked_init(kinds["cm"], 64)
    for rate in [1, 2, 5, 10]:
        sids_r = np.tile(base_sids, rate)
        vals_r = np.tile(base_vals, rate)
        syn = jnp.asarray((sids_r % 64).astype(np.int32))
        items = jnp.asarray(sids_r.astype(np.uint32))
        v = jnp.asarray(vals_r)
        m = jnp.ones(len(sids_r), bool)
        t = time_fn(fns["cm"], cm_state, syn, items, v, m)
        thr = len(sids_r) / t
        rows.append(csv_row(f"fig5b_rate_x{rate}", t,
                            f"throughput={thr:,.0f}tuples/s"))

    # ---------------- (c) number of streams ----------------
    for ns in ([50, 500, 5000] if full else [50, 500, 2000]):
        st = StockStream(n_streams=ns, seed=2)
        dft_states = batched.stacked_init(kinds["dft"], ns)
        ticks = st.ticks(1)[0]
        v = jnp.asarray(ticks)
        m = jnp.ones(ns, bool)
        t = time_fn(fns["dft"], dft_states, v, m)
        thr = ns / t
        rows.append(csv_row(f"fig5c_streams_{ns}", t,
                            f"throughput={thr:,.0f}streams-ticks/s"))

    # ---------------- (e) routing scale: hashed 64-bit stream ids ----
    # vertical scalability past the old 65536-slot dense route table:
    # per-stream synopses over 65k vs 1M DISTINCT hashed 63-bit ids,
    # ingest still one fused dispatch (probe included). Acceptance:
    # 1M-stream throughput within 2x of the 65k baseline.
    thr_by_ns = {}
    for ns in [1 << 16, 1 << 20]:
        rng = np.random.RandomState(7)
        sid_pop = np.unique(rng.randint(0, 2**63 - 1, ns, dtype=np.int64))
        eng = SDE()
        eng.handle({"type": "build", "request_id": "b", "synopsis_id":
                    "cm", "kind": "countmin",
                    "params": {"eps": 0.5, "delta": 0.5,
                               "weighted": False},
                    "per_stream_of_source": True,
                    "stream_ids": [int(s) for s in sid_pop]})
        stack = next(iter(eng.stacks.values()))
        e_sids = sid_pop[rng.randint(0, len(sid_pop), batch_tuples)]
        e_vals = np.ones(batch_tuples, np.float32)
        t = time_fn(lambda s=e_sids, v=e_vals, e=eng: _ingest_sync(e, s, v))
        thr_by_ns[ns] = batch_tuples / t
        rows.append(csv_row(
            f"fig5e_hashed_routing_{ns}streams", t,
            f"throughput={batch_tuples / t:,.0f}tuples/s "
            f"table={stack.table.size}slots "
            f"probe<={stack.n_probe}"))
    rows.append(csv_row(
        "fig5e_1M_vs_65k_slowdown", 0.0,
        f"ratio={thr_by_ns[1 << 16] / thr_by_ns[1 << 20]:.2f}x "
        "(acceptance <= 2x)"))

    # ---------------- (f) pipelined vs eager blue path ----------------
    # 1024 continuous per-stream synopses: the eager engine pays a
    # device->host sync per batch inside continuous emission, idling the
    # host while the device finishes and the device while the host preps
    # the next batch. The pipelined engine (bounded depth-2 queue) keeps
    # both busy; a final flush() + block makes the comparison fair.
    import time as _time
    n_syn = 1024
    n_batches = 16
    pipe_stock = StockStream(n_streams=n_syn, seed=3)
    pipe_batches = [pipe_stock.level1_batch(16384) for _ in range(n_batches)]
    pipe_build = {"type": "build", "request_id": "b", "synopsis_id": "cm",
                  "kind": "countmin",
                  "params": {"eps": 0.01, "delta": 0.05,
                             "weighted": False},
                  "per_stream_of_source": True, "n_streams": n_syn,
                  "continuous": True}
    thr_by_mode = {}
    for mode in ("eager", "pipelined"):
        def run_once(mode=mode):
            eng = SDE(pipelined=(mode == "pipelined"))
            assert eng.handle(pipe_build).ok
            eng.ingest(*pipe_batches[0])     # warmup: trace + compile
            eng.flush()
            t0 = _time.perf_counter()
            for sids, vals in pipe_batches:
                eng.ingest(sids, vals)
            eng.flush()
            jax.block_until_ready([s.state for s in eng.stacks.values()])
            return _time.perf_counter() - t0
        t = float(np.median([run_once() for _ in range(3)]))
        thr_by_mode[mode] = n_batches * len(pipe_batches[0][0]) / t
        rows.append(csv_row(
            f"fig5f_{mode}_{n_syn}syn", t,
            f"throughput={thr_by_mode[mode]:,.0f}tuples/s"))
    rows.append(csv_row(
        "fig5f_pipelined_speedup", 0.0,
        f"speedup={thr_by_mode['pipelined'] / thr_by_mode['eager']:.2f}x "
        "(acceptance >= 1.2x)"))

    # ---------------- (d) federated communication ----------------
    # Per 5-minute ad-hoc query (paper setting), three ways of answering
    # the same (count, cardinality, correlation) queries globally:
    #  collective — the mesh path: `federated.merge_over_axis` runs the
    #  site merge as psum/pmax/selection collectives, which combine
    #  in-network; operand bytes via `collective_operand_bytes`.
    #  host-merge — the legacy path: every site ships its full synopsis
    #  state to the responsible host (`Federation.query_bytes`).
    #  raw        — every Level-1/2 tuple of the window (16B).
    per_site_streams = 250
    ticks_per_window = 300          # 1 tick/s x 5 min per stream
    cm_st = kinds["cm"].init(None)
    hll_st = kinds["hll"].init(None)
    dft_st = kinds["dft"].init(None)
    site_state = (federated.communication_bytes(kinds["cm"], cm_st)
                  + federated.communication_bytes(kinds["hll"], hll_st)
                  + per_site_streams * federated.communication_bytes(
                      kinds["dft"], dft_st))
    raw_site = per_site_streams * ticks_per_window * 16
    for n_sites in [2, 4, 8, 16]:
        coll_total = (
            federated.collective_operand_bytes(kinds["cm"], cm_st, n_sites)
            + federated.collective_operand_bytes(kinds["hll"], hll_st,
                                                 n_sites)
            + per_site_streams * federated.collective_operand_bytes(
                kinds["dft"], dft_st, n_sites))
        host_total = site_state * n_sites
        raw_total = raw_site * n_sites
        assert coll_total <= host_total     # acceptance: never worse
        rows.append(csv_row(
            f"fig5d_federated_{n_sites}sites", 0.0,
            f"collective={coll_total/1e6:.2f}MB "
            f"host={host_total/1e6:.2f}MB raw={raw_total/1e6:.2f}MB "
            f"gain_vs_raw={raw_total/max(coll_total,1):.0f}x "
            f"gain_vs_host={host_total/max(coll_total,1):.1f}x"))

    # live collective measurement when the host has the devices for it:
    # a mesh federation answers one federated query as ONE compiled
    # collective program; the response reports the fig5d byte metrics
    if len(jax.devices()) >= 2:
        from repro.launch.mesh import try_federation_mesh
        from repro.service import Federation
        ns = min(4, len(jax.devices()))
        fed = Federation([f"s{i}" for i in range(ns)],
                         mesh=try_federation_mesh(ns))
        fed.broadcast({"type": "build", "request_id": "b",
                       "synopsis_id": "card", "kind": "hyperloglog",
                       "params": {"rse": 0.03}, "federated": True,
                       "responsible_site": "s0"})
        rng = np.random.RandomState(5)
        for i in range(ns):
            sids = rng.randint(i << 20, (i + 1) << 20, 65536)
            fed.sdes[f"s{i}"].ingest(sids.astype(np.int64),
                                     np.ones(65536, np.float32))
        req = {"type": "federated_query", "request_id": "q",
               "synopsis_id": "card", "responsible_site": "s0"}
        last = {}

        def timed_query():
            last["resp"] = fed.handle(req)
            return np.asarray(last["resp"].value)

        t = time_fn(timed_query)             # time_fn warms up first
        resp = last["resp"]
        rows.append(csv_row(
            f"fig5d_live_collective_{ns}sites", t,
            f"path={resp.params['path']} est={float(resp.value):,.0f} "
            f"collective={resp.params['collective_operand_bytes']}B "
            f"host={resp.params['host_merge_bytes']}B"))
    return rows


if __name__ == "__main__":
    for r in run():
        print(r)
