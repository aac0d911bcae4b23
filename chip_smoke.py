#!/usr/bin/env python3
"""Chip smoke test: the SDE's served path on one TPU, its sharded and
federated paths on four.

    python chip_smoke.py              # one chip: served path + Pallas phase
    python chip_smoke.py --chips 4    # four chips: sharded SDE + Federation

One chip, served path. A multi-tenant SDE-as-a-service deployment (the
shape of ROADMAP R-D4), everything generated from ``--seed``:
131,072 hashed 63-bit stream ids with Zipf(0.99) popularity, a per-stream
CountMin (eps=delta=0.01: 5x512 f32) and a per-stream HyperLogLog
(rse=0.0325: 1,024 registers) on every stream, data-source CountMin /
HyperLogLog / GK quantiles over all traffic, 256 continuous per-stream AMS
queries on the hottest streams and a multidim HyperLogLog family. The
script starts ``launch.sde_server.serve_socket`` in this process on an
ephemeral port, on an SDE built as ``sde_server.main`` builds it (WAL and
checkpoints on), and drives it with asyncio TCP clients in the same event
loop: 8 windows of 262,144 events over 4 clients, then ``query_many`` over
1,024 CountMin rows, ad-hoc HyperLogLog / GK / CountMin queries and a
``subpop_query``, then ``shutdown``. It recovers a second engine from the
checkpoint + WAL (``service.wal.recover``) and requires it byte-equal to
the engine before shutdown. Every answer is checked against an exact
host reference computed from the same events, within the sketch's bound.

One chip, Pallas phase: ``SDE(backend="pallas")`` against
``SDE(backend="xla")`` on the six scatter kinds at their real widths;
states must be byte-equal.

Four chips (``--chips 4``): the same build/ingest/query sequence on
``SDE(mesh=<4-chip synopsis mesh>)`` byte-compared with a one-device
``SDE``, and a 4-site ``Federation(mesh=make_federation_mesh(4))``
compared with the host-merge federation. Nothing else runs.

The script fails (non-zero exit, no result line) when JAX's first device
is not a TPU, when any response is not ok, when the gateway recorded a
checkpoint/reconcile error, or when any check fails. Its last stdout
line is ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import asyncio
import dataclasses
import json
import pathlib
import shutil
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE / "src"))

import numpy as np  # noqa: E402


@dataclasses.dataclass(frozen=True)
class Deployment:
    """Scale of one smoke run. ``FULL`` is what the chip runs; tests and
    CPU rehearsals shrink it."""
    n_streams: int = 131072
    window: int = 262144
    n_windows: int = 8
    n_clients: int = 4
    n_continuous: int = 256
    n_queried_rows: int = 1024
    md_records: int = 4096            # multidim records per window
    pallas_rows: int = 512
    pallas_tuples: int = 4096


FULL = Deployment()

ZIPF_S = 0.99
CM = dict(eps=0.01, delta=0.01)            # per-stream CountMin, 5 x 512
HLL = dict(rse=0.0325)                     # per-stream HLL, 1024 regs
SRC_CM = dict(eps=0.001, delta=0.01)       # data-source CountMin
SRC_HLL = dict(rse=0.01)                   # data-source HLL
SRC_GK = dict(eps=0.01)                    # data-source GK quantiles
CQ_AMS = dict(eps=0.05, delta=0.05)        # continuous per-stream F2
MD_HLL = dict(rse=0.02)                    # multidim distinct users
MD_DIMS = {"region": [f"r{i}" for i in range(8)],
           "device": [f"d{i}" for i in range(4)]}
GK_QS = [0.01, 0.05, 0.25, 0.5, 0.75, 0.95, 0.99]
# the six scatter kinds at their real widths (Pallas vs XLA phase)
PALLAS_KINDS = [("countmin", CM), ("ams", dict(eps=0.05, delta=0.05)),
                ("hyperloglog", HLL), ("bloom", dict(n_elements=1000,
                                                     fpr=0.01)),
                ("fm", dict(nmaps=64, bitmap_size=32)),
                ("rhp", dict(n_bits=64))]


class SmokeFailure(AssertionError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def log(**kw) -> None:
    print(json.dumps(kw, default=float), flush=True)


_COMPILE_SECONDS = [0.0]        # JAX backend compile time, this process


def _on_duration(event: str, duration: float, **_) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        _COMPILE_SECONDS[0] += duration


def clock():
    """Wall and backend-compile seconds so far (``since`` diffs them). A
    persistent compilation-cache hit skips the backend compile."""
    if not _COMPILE_SECONDS[1:]:
        import jax
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _COMPILE_SECONDS.append(True)
    return time.perf_counter(), _COMPILE_SECONDS[0]


def since(t0) -> dict:
    return dict(seconds=time.perf_counter() - t0[0],
                compile_seconds=_COMPILE_SECONDS[0] - t0[1])


# ---------------------------------------------------------------------------
# traffic, generated from the seed
# ---------------------------------------------------------------------------
class Traffic:
    """Stream ids, Zipf popularity and the event windows of one run,
    plus the exact host reference every answer is checked against."""

    def __init__(self, dep: Deployment, seed: int):
        from repro.service import routing
        self.dep = dep
        rng = np.random.default_rng(seed)
        ids = np.unique(rng.integers(1, 2**63 - 1, int(dep.n_streams * 1.01),
                                     dtype=np.int64))
        rng.shuffle(ids)
        self.ids = ids[:dep.n_streams]                 # popularity order
        p = 1.0 / np.arange(1, dep.n_streams + 1) ** ZIPF_S
        self.p = p / p.sum()
        self.rng = rng
        self.fold = routing.fold64
        # exact reference: every tuple that reached the engine
        self.sids, self.vals = [], []          # plain ingest
        self.md_items, self.md_vals, self.md_region = [], [], []

    def window(self):
        """One window: (stream ids, integer-valued f32 weights). Integer
        weights keep every f32 sum exact, so CountMin bounds are exact."""
        rank = self.rng.choice(self.dep.n_streams, self.dep.window, p=self.p)
        sids = self.ids[rank]
        vals = self.rng.integers(1, 65, self.dep.window).astype(np.float32)
        self.sids.append(sids)
        self.vals.append(vals)
        return sids, vals

    def md_window(self):
        n = self.dep.md_records
        region = self.rng.integers(0, len(MD_DIMS["region"]), n)
        device = self.rng.integers(0, len(MD_DIMS["device"]), n)
        users = self.rng.zipf(1.3, n).astype(np.int64) + 10**12
        vals = self.rng.integers(1, 65, n).astype(np.float32)
        records = [{"region": MD_DIMS["region"][r],
                    "device": MD_DIMS["device"][d]}
                   for r, d in zip(region, device)]
        self.md_items.append(users)
        self.md_vals.append(vals)
        self.md_region.append(region)
        return records, vals, users

    # -- exact answers ----------------------------------------------------
    def finish(self, n_levels: int) -> None:
        self.all_sids = np.concatenate(self.sids)
        self.all_vals = np.concatenate(self.vals)
        md_items = np.concatenate(self.md_items)
        md_vals = np.concatenate(self.md_vals)
        self.md_users = md_items
        self.md_regions = np.concatenate(self.md_region)
        # data-source sketches see every tuple: plain events hash their
        # stream id, each multidim record reaches them once per level
        # hashing its user id
        self.src_items = np.concatenate(
            [self.fold(self.all_sids), np.repeat(self.fold(md_items),
                                                 n_levels)])
        self.src_vals = np.concatenate(
            [self.all_vals, np.repeat(md_vals, n_levels)])
        order = np.argsort(self.all_sids, kind="stable")
        s, v = self.all_sids[order], self.all_vals[order]
        uniq, start = np.unique(s, return_index=True)
        self.stream_weight = dict(zip(uniq.tolist(),
                                      np.add.reduceat(v, start).tolist()))
        items, inv = np.unique(self.src_items, return_inverse=True)
        self.src_item_weight = dict(zip(
            items.tolist(), np.bincount(inv, self.src_vals).tolist()))
        self.src_total = float(self.src_vals.sum())
        self.src_sorted = np.sort(self.src_vals)

    def weight(self, sid: int) -> float:
        return self.stream_weight.get(int(sid), 0.0)


def rank_error(sorted_vals: np.ndarray, x: float, q: float) -> float:
    """Distance from q to the rank interval of x (tie-aware)."""
    n = len(sorted_vals)
    lo = np.searchsorted(sorted_vals, x, side="left") / n
    hi = np.searchsorted(sorted_vals, x, side="right") / n
    return 0.0 if lo <= q <= hi else min(abs(q - lo), abs(q - hi))


def check_cm(est: float, exact: float, n_row: float, eps: float,
             what: str) -> float:
    """CountMin bound; returns the share of the bound the error used."""
    check(exact <= est <= exact + eps * n_row,
          f"{what}: CountMin {est} outside [{exact}, {exact} + {eps}*"
          f"{n_row}]")
    return (est - exact) / (eps * n_row) if n_row else 0.0


def check_hll(est: float, exact: float, rse: float, what: str) -> float:
    """HLL within 4 relative standard errors; returns the bound share."""
    check(abs(est - exact) <= 4 * rse * exact,
          f"{what}: HLL {est} vs exact {exact} beyond 4*rse={4 * rse}")
    return abs(est - exact) / (4 * rse * exact) if exact else 0.0


# ---------------------------------------------------------------------------
# a newline-delimited-JSON client (no JAX)
# ---------------------------------------------------------------------------
class Client:
    def __init__(self, name: str):
        self.name = name
        self.seq = 0
        self.pushed = []            # continuous responses routed to us

    async def open(self, port: int) -> None:
        self.reader, self.writer = await asyncio.open_connection(
            "127.0.0.1", port, limit=1 << 30)

    async def call(self, req: dict) -> dict:
        self.seq += 1
        rid = f"{self.name}-{self.seq}"
        req = dict(req, request_id=rid)
        self.writer.write((json.dumps(req) + "\n").encode())
        await self.writer.drain()
        while True:
            line = await self.reader.readline()
            check(bool(line), f"{self.name}: connection closed before "
                              f"the ack of {req['type']}")
            resp = json.loads(line)
            if resp["request_id"] == rid:
                check(resp["ok"], f"{req['type']} failed: {resp['error']}")
                return resp
            self.pushed.append(resp)

    async def close(self) -> None:
        while True:                      # drain what the server pushes
            line = await self.reader.readline()
            if not line:
                break
            self.pushed.append(json.loads(line))
        self.writer.close()


# ---------------------------------------------------------------------------
# one chip: the served path
# ---------------------------------------------------------------------------
def build_requests(tr: Traffic):
    ids = [int(s) for s in tr.ids]
    hot = ids[:tr.dep.n_continuous]
    return [
        dict(type="build", synopsis_id="cm", kind="countmin", params=CM,
             per_stream_of_source=True, stream_ids=ids),
        dict(type="build", synopsis_id="hll", kind="hyperloglog",
             params=HLL, per_stream_of_source=True, stream_ids=ids),
        dict(type="build", synopsis_id="src_cm", kind="countmin",
             params=SRC_CM),
        dict(type="build", synopsis_id="src_hll", kind="hyperloglog",
             params=SRC_HLL),
        dict(type="build", synopsis_id="src_gk", kind="gk_quantiles",
             params=SRC_GK),
        dict(type="build", synopsis_id="f2", kind="ams", params=CQ_AMS,
             per_stream_of_source=True, stream_ids=hot, continuous=True),
        dict(type="build_multidim", synopsis_id="md", kind="hyperloglog",
             params=MD_HLL, dims=MD_DIMS),
    ]


def query_requests(tr: Traffic):
    """The red-path requests, each paired with its checker."""
    dep = tr.dep
    rng = np.random.default_rng(7)
    hot = tr.ids[:dep.n_queried_rows // 2]
    cold = rng.choice(tr.ids, dep.n_queried_rows - len(hot), replace=False)
    rows = [int(s) for s in np.concatenate([hot, cold])]
    absent = [int(s) for s in np.roll(np.asarray(rows), 1)]
    eps_cm = CM["eps"]

    def check_rows(resp):
        share = 0.0
        for sid, other, r in zip(rows, absent, resp["value"]):
            n_row = tr.weight(sid)
            est_self, est_other = r["value"]
            exact_other = n_row if tr.fold(np.int64([other]))[0] == \
                tr.fold(np.int64([sid]))[0] else 0.0
            share = max(share,
                        check_cm(est_self, n_row, n_row, eps_cm,
                                 f"cm/{sid}"),
                        check_cm(est_other, exact_other, n_row, eps_cm,
                                 f"cm/{sid} item {other}"))
        return share

    src_items = rows[:256]

    def check_src_cm(resp):
        folded = tr.fold(np.asarray(src_items, np.int64))
        return max(check_cm(est, tr.src_item_weight.get(int(it), 0.0),
                            tr.src_total, SRC_CM["eps"], f"src_cm item {it}")
                   for it, est in zip(folded, resp["value"]))

    def check_src_hll(resp):
        return check_hll(resp["value"], float(len(tr.src_item_weight)),
                         SRC_HLL["rse"], "src_hll")

    def check_gk(resp):
        share = 0.0
        for q, x in zip(GK_QS, resp["value"]):
            err = rank_error(tr.src_sorted, x, q)
            check(err <= SRC_GK["eps"],
                  f"src_gk q={q}: {x} has rank error {err}")
            share = max(share, err / SRC_GK["eps"])
        return share

    def check_hll_row(sid):
        def chk(resp):
            exact = 1.0 if tr.weight(sid) > 0 else 0.0
            return check_hll(resp["value"], exact, HLL["rse"], f"hll/{sid}")
        return chk

    def check_subpop(resp):
        users = tr.md_users[tr.md_regions == 3]
        exact = float(np.unique(tr.fold(users)).size)
        return check_hll(resp["value"], exact, MD_HLL["rse"],
                         "md region=r3")

    reqs = [
        (dict(type="query_many", queries=[
            dict(synopsis_id=f"cm/{s}", query=dict(items=[s, o]))
            for s, o in zip(rows, absent)]), check_rows),
        (dict(type="adhoc", synopsis_id="src_cm",
              query=dict(items=src_items)), check_src_cm),
        (dict(type="adhoc", synopsis_id="src_hll"), check_src_hll),
        (dict(type="adhoc", synopsis_id="src_gk", query=dict(qs=GK_QS)),
         check_gk),
        (dict(type="subpop_query", synopsis_id="md",
              where=dict(region="r3")), check_subpop),
    ]
    for sid in rows[:8] + rows[-8:]:
        reqs.append((dict(type="adhoc", synopsis_id=f"hll/{sid}"),
                     check_hll_row(sid)))
    return reqs


def check_continuous(tr: Traffic, pushed, n_batches: int) -> int:
    hot = tr.ids[:tr.dep.n_continuous]
    last = {}
    for r in pushed:
        if r["request_id"].startswith("cq/"):
            check(r["ok"], f"continuous {r['request_id']}: {r['error']}")
            batch = int(r["request_id"].rsplit("/", 1)[1])
            if batch >= last.get(r["synopsis_id"], (0, None))[0]:
                last[r["synopsis_id"]] = (batch, r["value"])
    n = sum(r["request_id"].startswith("cq/") for r in pushed)
    check(n == len(hot) * n_batches,
          f"{n} continuous responses, want {len(hot)} x {n_batches}")
    for sid in hot:
        batch, est = last[f"f2/{int(sid)}"]
        exact = tr.weight(sid) ** 2              # one item per stream
        check(abs(est - exact) <= CQ_AMS["eps"] * exact,
              f"f2/{sid}: AMS F2 {est} vs exact {exact}")
    return n


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


def same_image(a: dict, b: dict) -> bool:
    if (a["tuples"], a["batches"], a["entries"]) != \
            (b["tuples"], b["batches"], b["entries"]):
        return False
    if set(a["stacks"]) != set(b["stacks"]):
        return False
    for kind, x in a["stacks"].items():
        y = b["stacks"][kind]
        if (x["used"], x["source"]) != (y["used"], y["source"]):
            return False
        if not (np.array_equal(x["keys"], y["keys"])
                and np.array_equal(x["rows"], y["rows"])):
            return False
        if len(x["state"]) != len(y["state"]) or not all(
                np.array_equal(p, q) for p, q in zip(x["state"],
                                                     y["state"])):
            return False
    return True


async def _serve_and_drive(dep: Deployment, seed: int, run_dir: pathlib.Path):
    from repro import tracing
    from repro.launch import sde_server
    from repro.service import SDE, wal as wal_mod

    wal_path, ckpt_dir = run_dir / "wal.jsonl", run_dir / "ckpt"
    # as sde_server.main builds it: eager engine, WAL, incremental async
    # checkpoints every 8 ingested batches
    sde = SDE(pipelined=False, pipeline_depth=2)
    wal = wal_mod.WriteAheadLog(str(wal_path), tag=sde.site)
    checkpointer = wal_mod.Checkpointer(
        sde, str(ckpt_dir), interval=8, keep=3, rebase_every=8,
        incremental=True, async_=True, wal=wal)
    ready = asyncio.get_running_loop().create_future()
    server = asyncio.create_task(sde_server.serve_socket(
        sde, "127.0.0.1", 0, ready=ready, wal=wal,
        checkpointer=checkpointer, err=sys.stdout))
    port = await ready
    clients = [Client(f"c{i}") for i in range(dep.n_clients)]
    for c in clients:
        await c.open(port)

    tr = Traffic(dep, seed)
    t0 = clock()
    for req in build_requests(tr):
        await clients[0].call(req)
    log(phase="build", **since(t0),
        synopses=len(sde.entries))

    per_client = dep.window // dep.n_clients
    trace0 = sum(tracing.TRACE_COUNT.values())
    t0 = clock()
    for _ in range(dep.n_windows):
        sids, vals = tr.window()
        await asyncio.gather(*(
            c.call(dict(type="ingest",
                        stream_ids=sids[i * per_client:(i + 1) * per_client]
                        .tolist(),
                        values=vals[i * per_client:(i + 1) * per_client]
                        .tolist()))
            for i, c in enumerate(clients)))
        records, md_vals, users = tr.md_window()
        await clients[1].call(dict(type="ingest_multidim", synopsis_id="md",
                                   records=records, values=md_vals.tolist(),
                                   items=users.tolist()))
    n_batches = sde.batches_ingested
    log(phase="ingest", **since(t0), windows=dep.n_windows,
        events=dep.window * dep.n_windows, batches=n_batches,
        traces=sum(tracing.TRACE_COUNT.values()) - trace0,
        state_bytes=sde.memory_bytes(), checkpoints=checkpointer.snapshots)

    tr.finish(n_levels=len(sde.multidim["md"].levels))
    t0 = clock()
    share = {}
    for req, chk in query_requests(tr):
        key = req.get("synopsis_id", req["type"]).split("/")[0]
        share[key] = max(share.get(key, 0.0),
                         chk(await clients[2].call(req)))
    # worst error per query family, as a share of its sketch bound
    log(phase="query", **since(t0), bound_share=share)

    await clients[0].call(dict(type="flush"))
    sde.wait_for_snapshot()
    before = engine_image(sde)
    await clients[0].call(dict(type="shutdown"))
    for c in clients:
        await c.close()
    gw = await server
    check(gw.checkpoint_error is None,
          f"gateway checkpoint error: {gw.checkpoint_error}")
    check(gw.reconcile_error is None,
          f"gateway reconcile error: {gw.reconcile_error}")
    n_cq = check_continuous(tr, clients[0].pushed, n_batches)
    wal.close()
    sde.wait_for_snapshot()
    sde.close()
    return before, n_cq, wal_path, ckpt_dir


def run_served(dep: Deployment, seed: int, run_dir: pathlib.Path) -> None:
    import jax
    from repro.service import wal as wal_mod
    shutil.rmtree(run_dir, ignore_errors=True)
    run_dir.mkdir(parents=True)
    try:
        t0 = clock()
        before, n_cq, wal_path, ckpt_dir = asyncio.run(
            _serve_and_drive(dep, seed, run_dir))
        log(phase="served", **since(t0),
            continuous_responses=n_cq)
        t0 = clock()
        rec = wal_mod.recover(str(ckpt_dir), str(wal_path))
        after = engine_image(rec)
        check(same_image(before, after),
              "recovered engine differs from the engine before shutdown")
        log(phase="recover", **since(t0),
            batches=rec.batches_ingested, synopses=len(rec.entries))
        rec.close()
        stats = jax.devices()[0].memory_stats() or {}
        log(phase="memory", peak_bytes_in_use=stats.get("peak_bytes_in_use"))
    finally:
        shutil.rmtree(run_dir, ignore_errors=True)


def run_pallas(dep: Deployment, seed: int) -> None:
    """Pallas registry kernels vs the XLA scatter, byte for byte."""
    import jax
    from repro.kernels import ops as kops
    from repro.service import SDE
    rng = np.random.default_rng(seed + 1)
    pop = np.unique(rng.integers(1, 2**63 - 1, 2 * dep.pallas_rows,
                                 dtype=np.int64))[:dep.pallas_rows]
    batches = []
    for _ in range(2):
        sids = pop[rng.integers(0, len(pop), dep.pallas_tuples)]
        sids[::17] = 1                          # unrouted ids
        batches.append((sids, rng.integers(1, 9, dep.pallas_tuples)
                        .astype(np.float32)))
    if jax.default_backend() == "tpu":
        check(kops._interpret() is False, "pallas interpret mode on TPU")
    for name, params in PALLAS_KINDS:
        states = {}
        t0 = clock()
        for backend in ("xla", "pallas"):
            eng = SDE(backend=backend)
            for req in (dict(type="build", request_id="b", synopsis_id="s",
                             kind=name, params=params,
                             per_stream_of_source=True,
                             stream_ids=[int(s) for s in pop]),
                        dict(type="build", request_id="b2",
                             synopsis_id="src", kind=name, params=params)):
                r = eng.handle(req)
                check(r.ok, f"{name} {backend} build: {r.error}")
            for sids, vals in batches:
                eng.ingest(sids, vals)
            stack = next(iter(eng.stacks.values()))
            states[backend] = [np.asarray(x)
                               for x in jax.tree.leaves(stack.state)]
            eng.close()
        equal = all(np.array_equal(a, b)
                    for a, b in zip(states["xla"], states["pallas"]))
        check(equal, f"{name}: pallas state != xla state")
        log(phase="pallas", kind=name, byte_equal=equal,
            **since(t0))


# ---------------------------------------------------------------------------
# four chips: sharded SDE and Federation
# ---------------------------------------------------------------------------
def _drive_engine(eng, tr: Traffic, windows) -> list:
    """The served sequence, through ``SDE.handle``: builds, ingest
    windows, then every query; returns the query responses' values."""
    for req in build_requests(tr):
        r = eng.handle(dict(req, request_id="b"))
        check(r.ok, f"{req['type']} {req['synopsis_id']}: {r.error}")
    for (sids, vals), (records, md_vals, users) in windows:
        r = eng.handle(dict(type="ingest", request_id="i",
                            stream_ids=sids, values=vals))
        check(r.ok, f"ingest: {r.error}")
        r = eng.handle(dict(type="ingest_multidim", request_id="m",
                            synopsis_id="md", records=records,
                            values=md_vals, items=users))
        check(r.ok, f"ingest_multidim: {r.error}")
    out = []
    for req, chk in query_requests(tr):
        r = eng.handle(dict(req, request_id="q"))
        check(r.ok, f"{req['type']}: {r.error}")
        resp = json.loads(r.to_json())
        chk(resp)
        out.append(resp["value"])
    return out


def run_sharded(dep: Deployment, seed: int, n_dev: int) -> None:
    import jax
    from repro.core.multidim import MultidimSpec
    from repro.launch.mesh import make_mesh
    from repro.service import SDE
    tr = Traffic(dep, seed)
    windows = [(tr.window(), tr.md_window()) for _ in range(dep.n_windows)]
    tr.finish(n_levels=len(MultidimSpec(MD_DIMS).levels))
    images, answers = {}, {}
    for label, mesh in (("one_device", None),
                        ("sharded", make_mesh((n_dev, 1),
                                              ("data", "model")))):
        t0 = clock()
        eng = SDE(mesh=mesh)
        answers[label] = _drive_engine(eng, tr, windows)
        if mesh is not None:
            for stack in eng.stacks.values():
                n = len(jax.tree.leaves(stack.state)[0].sharding.device_set)
                check(n == n_dev, f"a stack spans {n} of {n_dev} devices")
        images[label] = engine_image(eng)
        eng.close()
        log(phase=f"mesh_{label}", **since(t0))
    check(answers["one_device"] == answers["sharded"],
          "sharded answers differ from one-device answers")
    check(same_image(images["one_device"], images["sharded"]),
          "sharded state differs from one-device state")
    log(phase="mesh", devices=n_dev, byte_equal=True)


def run_federation(dep: Deployment, seed: int, n_sites: int) -> None:
    from repro.launch.mesh import make_federation_mesh
    from repro.service import Federation
    sites = [f"site{i}" for i in range(n_sites)]
    rng = np.random.default_rng(seed + 2)
    tr = Traffic(dep, seed)
    sids = [tr.window() for _ in range(n_sites)]
    streams = [int(s) for s in tr.ids[:dep.n_queried_rows]]
    builds = [
        dict(type="build", synopsis_id="cm", kind="countmin", params=CM,
             per_stream_of_source=True, stream_ids=streams, federated=True),
        dict(type="build", synopsis_id="src_cm", kind="countmin",
             params=SRC_CM, federated=True),
        dict(type="build", synopsis_id="src_hll", kind="hyperloglog",
             params=SRC_HLL, federated=True),
        dict(type="build", synopsis_id="src_gk", kind="gk_quantiles",
             params=SRC_GK, federated=True),
        dict(type="build", synopsis_id="f2", kind="ams", params=CQ_AMS,
             federated=True),
    ]
    queries = [("src_cm", dict(items=streams[:256])), ("src_hll", {}),
               ("src_gk", dict(qs=GK_QS)), ("f2", {})]
    queries += [(f"cm/{s}", dict(items=[s])) for s in
                rng.choice(streams, 16, replace=False).tolist()]
    answers = {}
    for label, mesh in (("host_merge", None),
                        ("collective", make_federation_mesh(n_sites))):
        t0 = clock()
        fed = Federation(sites, mesh=mesh)
        for req in builds:
            for site, r in fed.handle(dict(req, request_id="b")).items():
                check(r.ok, f"{site} build {req['synopsis_id']}: {r.error}")
        for site, (s, v) in zip(sites, sids):
            fed.sdes[site].ingest(s, v)
        vals = []
        for syn, q in queries:
            r = fed.handle(dict(type="federated_query", request_id="q",
                                synopsis_id=syn, query=q,
                                responsible_site=sites[0]))
            check(r.ok, f"federated {syn}: {r.error}")
            want = "collective" if mesh is not None else "host"
            check(r.params["path"] == want,
                  f"federated {syn} took the {r.params['path']} path")
            vals.append(json.loads(r.to_json())["value"])
        answers[label] = vals
        for sde in fed.sdes.values():
            sde.close()
        log(phase=f"federation_{label}", **since(t0))
    check(answers["host_merge"] == answers["collective"],
          "collective federation differs from the host-merge oracle")
    # the merged answers obey the sketch bounds over all sites' traffic
    all_items = tr.fold(np.concatenate([s for s, _ in sids]))
    all_vals = np.concatenate([v for _, v in sids])
    items, inv = np.unique(all_items, return_inverse=True)
    weight = dict(zip(items.tolist(), np.bincount(inv, all_vals).tolist()))
    folded = tr.fold(np.asarray(streams[:256], np.int64))
    for it, est in zip(folded, answers["collective"][0]):
        check_cm(est, weight.get(int(it), 0.0), float(all_vals.sum()),
                 SRC_CM["eps"], f"federated src_cm item {it}")
    check_hll(answers["collective"][1], float(len(items)), SRC_HLL["rse"],
              "federated src_hll")
    log(phase="federation", sites=n_sites, byte_equal=True)


# ---------------------------------------------------------------------------
def device_report() -> dict:
    import jax
    d = jax.devices()
    return dict(platform=d[0].platform, kind=d[0].device_kind, count=len(d))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: served path + Pallas phase; 4: sharded SDE "
                         "and Federation only")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compilation_cache
    enable_compilation_cache()
    import jax
    dev = device_report()
    if dev["platform"] != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev}", file=sys.stderr)
        return 2
    if dev["count"] < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found {dev}",
              file=sys.stderr)
        return 2
    log(phase="start", device=dev, jax=jax.__version__)
    t0 = clock()
    if args.chips == 1:
        run_served(FULL, args.seed, HERE / ".chip_smoke_run")
        run_pallas(FULL, args.seed)
    else:
        run_sharded(FULL, args.seed, args.chips)
        run_federation(FULL, args.seed, args.chips)
    log(phase="done", **since(t0))
    print(json.dumps(dict(ok=True, device=dev)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
