"""Cross-tenant micro-batching gateway — N clients, one fused dispatch
per kind per tick.

The engine already amortizes device work per CALL: one fused blue-path
program per kind per ingest batch, ``query_many`` answering N queries in
one dispatch, the pipelined ingest queue overlapping host prep with
device work. What it lacked was a front door that turns N concurrent
CLIENTS into few calls — a serial per-client loop pays one dispatch per
client per batch, so serving cost scales with client count instead of
tick count. This module is that front door:

  * ``SynopsisGateway`` — a single-threaded asyncio actor owning one
    ``SDE``. Clients submit JSON request dicts; the ticked micro-batcher
    drains the arrival queue once per tick and coalesces it in
    arrival-preserving runs:

      - a run of ``ingest`` requests (any mix of clients/tenants) is
        ``np.concatenate``d into ONE ``SDE.ingest`` call — one fused
        blue-path dispatch per kind per tick, riding the existing
        ``IngestPipeline`` when the engine is pipelined. Every client's
        ack carries the same coalesced batch id.
      - a run of ``adhoc``/``query_many`` requests flattens into ONE
        ``SDE.query_many`` call (one stacked-estimate dispatch per kind
        touched); answers are demultiplexed back to their submitters.
      - every other request (build/stop/load/status/flush/shutdown)
        executes alone, exactly where it arrived — so per-client
        submission order is the engine's execution order, and the whole
        committed sequence is replayable (see ``replay_log``).

  * **Per-tenant namespaces** — a request's ``tenant`` prefixes every
    ``synopsis_id`` with ``"<tenant>::"`` before it reaches the engine
    (and is stripped from responses), so tenants can neither address nor
    collide with each other's synopses. Stream ids stay SHARED across
    tenants by design: the paper's claim (e) is many concurrent
    workflows maintaining synopses over the same streams, and shared
    stream ids are what lets their ingest coalesce into one dispatch.
    (Corollary: a data-source synopsis — ``stream_id=None`` — observes
    the engine's whole coalesced traffic, not one tenant's slice.)

  * **Per-client response logs** — continuous-query responses route to
    the BUILDING client's bounded ``BoundedResponseLog`` (the engine's
    single global deque generalized per client); responses whose
    subscriber is gone land in the gateway's bounded ``unrouted`` log.

  * **Admission control** — at most ``max_in_flight`` unacknowledged
    requests per client (an ``asyncio.Semaphore``); ``submit`` does not
    enqueue until a slot frees, so a socket server that awaits admission
    before reading the next line gets real backpressure via delayed
    acks (the client's TCP window fills instead of the engine's queue).

Observability (``repro.tracing``): ``GATEWAY_TICKS`` counts micro-batcher
ticks per gateway tag and ``GATEWAY_COALESCED`` counts client requests
folded into coalesced calls — paired with ``DISPATCH_COUNT``, tests
assert the invariant this module exists for: 64 clients ingesting
concurrently cost ONE blue-path dispatch per kind per tick, not 64. With
a profiler session running, spans time each request's wait in the queue
(``gateway.queue_wait``), the linger before a tick, the tick and its
coalescing.
"""
from __future__ import annotations

import asyncio
import concurrent.futures
import dataclasses
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from repro import tracing
from . import api, pipeline
from .engine import SDE

NS_SEP = "::"


def namespaced(tenant: str, synopsis_id: str) -> str:
    """Tenant-prefixed synopsis key (identity for the empty tenant)."""
    return f"{tenant}{NS_SEP}{synopsis_id}" if tenant else synopsis_id


def check_tenant(tenant: str) -> str:
    """Reject tenant names carrying the namespace separator: tenant
    "a" + synopsis "b::c" would be indistinguishable from tenant
    "a::b" + synopsis "c", silently collapsing two tenants' namespaces
    (synopsis ids may contain "::" freely — only the LEFT side of the
    prefix must be separator-clean for the split to stay unambiguous)."""
    if NS_SEP in tenant:
        raise ValueError(
            f"tenant name {tenant!r} contains the reserved namespace "
            f"separator {NS_SEP!r}")
    return tenant


def strip_ns(tenant: str, synopsis_id: str) -> str:
    prefix = tenant + NS_SEP
    if tenant and synopsis_id.startswith(prefix):
        return synopsis_id[len(prefix):]
    return synopsis_id


class GatewayClient:
    """One connected client: its tenant default, bounded response log
    for continuous output, and the admission-control semaphore."""

    def __init__(self, client_id: str, tenant: str = "", *,
                 max_in_flight: int = 8, log_cap: Optional[int] = 1024):
        self.client_id = client_id
        self.tenant = tenant
        self.log = pipeline.BoundedResponseLog(log_cap)
        # set whenever continuous responses land in ``log`` — a socket
        # server's per-connection pusher task waits on it
        self.wakeup = asyncio.Event()
        self._slots = asyncio.Semaphore(max_in_flight)

    async def admit(self) -> None:
        """Block until an in-flight slot frees (admission control)."""
        await self._slots.acquire()

    def release(self) -> None:
        self._slots.release()


@dataclasses.dataclass
class _Item:
    """One queued request: submitting client, resolved tenant, the raw
    request dict, the future its response resolves, and when it was
    submitted (``time.monotonic_ns``)."""
    client: GatewayClient
    tenant: str
    req: Dict[str, Any]
    fut: Any
    t_submit: int


def _future():
    """An awaitable/result-able future that also works without a running
    event loop (synchronous benchmark/test drivers call
    ``submit_nowait`` + ``tick`` and read ``.result()``)."""
    try:
        return asyncio.get_running_loop().create_future()
    except RuntimeError:
        return concurrent.futures.Future()


class SynopsisGateway:
    """Multi-client micro-batching front door over one ``SDE``.

    Async use (the socket server, concurrent test clients)::

        gw = SynopsisGateway(SDE(), tick_interval=0.001)
        await gw.start()
        client = gw.connect("c0", tenant="acme")
        resp = await gw.submit(client, {"type": "ingest", ...})

    Synchronous use (benchmarks, deterministic tests): skip ``start``,
    enqueue with ``submit_nowait`` and drive ticks explicitly::

        futs = [gw.submit_nowait(c, req) for c, req in traffic]
        gw.tick()                       # ONE fused dispatch per kind
        acks = [f.result() for f in futs]
    """

    def __init__(self, sde: Optional[SDE] = None, *,
                 tick_interval: float = 0.001, max_in_flight: int = 8,
                 client_log_cap: Optional[int] = 1024,
                 tag: str = "gateway", reconciler=None,
                 wal=None, checkpointer=None):
        self.sde = sde if sde is not None else SDE()
        self.tag = tag
        # durability (service/wal.py): lifecycle requests are appended
        # to ``wal`` BEFORE they apply, ingest batches AFTER a
        # successful apply (keyed by the engine-assigned batch id, so a
        # refused batch never reaches the log), and the tick fsyncs
        # before any of its acks can leave the process (tick is
        # synchronous; conn handlers resolve futures only after it
        # returns) — acked implies recoverable. ``checkpointer`` rides
        # the tick too, taking an incremental snapshot every N batches.
        self.wal = wal
        self.checkpointer = checkpointer
        self.checkpoint_error: Optional[str] = None
        # optional elasticity loop (service/reconciler.py): rides the
        # micro-batcher tick — after each tick's coalesced dispatches,
        # ``maybe_step`` reconciles placement when its interval elapsed.
        # A reconcile failure must never take down serving; the last
        # error is kept for inspection instead.
        self.reconciler = reconciler
        self.reconcile_error: Optional[str] = None
        self.tick_interval = tick_interval
        self.max_in_flight = max_in_flight
        self.client_log_cap = client_log_cap
        self.clients: Dict[str, GatewayClient] = {}
        # continuous-query subscriptions: namespaced build synopsis_id
        # -> (client_id, tenant). Entry ids extend the build id with
        # "/<stream>", so routing walks the "/" prefix chain.
        self._subs: Dict[str, Tuple[str, str]] = {}
        # continuous responses whose subscriber disconnected
        self.unrouted = pipeline.BoundedResponseLog(client_log_cap)
        # execution-order record of every state-mutating engine call:
        # ("ingest", sids, vals, mask) for coalesced blue-path batches,
        # ("request", dict) for build/stop/load. ``replay_log`` replays
        # it serially — the oracle the equivalence tests compare against.
        self.commit_log: List[Tuple[Any, ...]] = []
        self.ticks = 0
        self.requests = 0
        self.closed = False
        self.closed_event = asyncio.Event()
        self._queue: List[_Item] = []
        self._arrival = asyncio.Event()
        self._task: Optional[asyncio.Task] = None

    # ------------------------------------------------------------------
    # clients
    # ------------------------------------------------------------------
    def connect(self, client_id: str, tenant: str = "") -> GatewayClient:
        if client_id in self.clients:
            raise ValueError(f"client id {client_id!r} already connected")
        check_tenant(tenant)
        client = GatewayClient(client_id, tenant,
                               max_in_flight=self.max_in_flight,
                               log_cap=self.client_log_cap)
        self.clients[client_id] = client
        return client

    def disconnect(self, client: GatewayClient) -> None:
        """Drop a client. Its subscriptions stay registered — later
        continuous responses fall into the bounded ``unrouted`` log."""
        self.clients.pop(client.client_id, None)

    # ------------------------------------------------------------------
    # submission
    # ------------------------------------------------------------------
    def submit_nowait(self, client: GatewayClient,
                      req: Dict[str, Any]):
        """Enqueue one request for the next tick; returns the future its
        response will resolve. The tenant is resolved per request
        (``req["tenant"]``, falling back to the client's default)."""
        fut = _future()
        if self.closed:
            fut.set_result(api.Response(
                request_id=str(req.get("request_id", "")), ok=False,
                error="gateway is shut down"))
            return fut
        tenant = str(req.get("tenant") or client.tenant)
        if NS_SEP in tenant:
            # per-request tenant overrides bypass ``connect`` — validate
            # here too, or "a::b" would silently alias tenant "a"'s
            # namespace (see ``check_tenant``)
            fut.set_result(api.Response(
                request_id=str(req.get("request_id", "")), ok=False,
                error=f"tenant name {tenant!r} contains the reserved "
                      f"namespace separator {NS_SEP!r}"))
            return fut
        self._queue.append(_Item(client, tenant, dict(req), fut,
                                 time.monotonic_ns()))
        self._arrival.set()
        return fut

    async def submit(self, client: GatewayClient,
                     req: Dict[str, Any]) -> api.Response:
        """Admission-controlled submit: blocks while the client already
        has ``max_in_flight`` unacknowledged requests, then enqueues and
        awaits the (possibly coalesced) response."""
        await client.admit()
        try:
            return await self.submit_nowait(client, req)
        finally:
            client.release()

    @property
    def queued(self) -> int:
        return len(self._queue)

    # ------------------------------------------------------------------
    # the ticked micro-batcher
    # ------------------------------------------------------------------
    async def start(self) -> None:
        self._task = asyncio.create_task(self._run())

    async def _run(self) -> None:
        while not self.closed:
            await self._arrival.wait()
            if self.tick_interval > 0:
                # let a tick's worth of concurrent traffic accumulate
                with tracing.span("gateway.linger"):
                    await asyncio.sleep(self.tick_interval)
            self._arrival.clear()
            self.tick()

    async def stop(self) -> None:
        """Stop the batcher; queued requests still resolve (with errors
        once the gateway is closed). Idempotent."""
        self.tick()                      # drain what already arrived
        self.closed = True
        self.closed_event.set()
        self._arrival.set()
        if self._task is not None:
            await self._task
            self._task = None
        self.tick()                      # error-out any stragglers

    def tick(self) -> int:
        """Process everything queued right now as ONE tick: coalesce
        arrival-order runs, dispatch, demultiplex, route continuous
        output. Returns the number of requests processed."""
        batch, self._queue = self._queue, []
        if not batch:
            # still route: a pipelined engine may have retired batches
            # (and emitted continuous output) since the last tick
            self._route_continuous()
            self._maybe_reconcile()
            return 0
        if tracing.enabled():
            now = time.monotonic_ns()
            for item in batch:
                tracing.record("gateway.queue_wait", item.t_submit, now,
                               type=str(item.req.get("type")))
        with tracing.span("gateway.tick", requests=len(batch)):
            self.ticks += 1
            self.requests += len(batch)
            tracing.GATEWAY_TICKS[self.tag] += 1
            runs: List[Tuple[str, List[_Item]]] = []
            for item in batch:
                klass = self._class_of(item.req)
                if (runs and runs[-1][0] == klass
                        and klass in ("ingest", "query")):
                    runs[-1][1].append(item)
                else:
                    runs.append((klass, [item]))
            for klass, items in runs:
                if self.closed:
                    for it in items:
                        it.fut.set_result(api.Response(
                            request_id=str(it.req.get("request_id", "")),
                            ok=False, error="gateway is shut down"))
                    continue
                if klass == "ingest":
                    self._do_ingest(items)
                elif klass == "query":
                    self._do_query(items)
                else:
                    self._do_one(items[0])
            if self.wal is not None:
                # durable-before-ack: one fsync per tick covers every
                # record this tick appended, before its futures are
                # awaited
                self.wal.sync()
            if self.checkpointer is not None and not self.closed:
                try:
                    self.checkpointer.maybe_snapshot()
                except Exception as e:  # noqa: BLE001 - keep serving
                    self.checkpoint_error = repr(e)
            self._route_continuous()
            self._maybe_reconcile()
            return len(batch)

    def _maybe_reconcile(self) -> None:
        if self.reconciler is None or self.closed:
            return
        try:
            self.reconciler.maybe_step()
        except Exception as e:  # noqa: BLE001 - serving must survive
            self.reconcile_error = repr(e)

    @staticmethod
    def _class_of(req: Dict[str, Any]) -> str:
        t = req.get("type")
        if t == "ingest":
            return "ingest"
        if t in ("adhoc", "query_many"):
            return "query"
        return "other"

    # ------------------------------------------------------------------
    # coalesced blue path: one SDE.ingest per run
    # ------------------------------------------------------------------
    def _do_ingest(self, items: List[_Item]) -> None:
        with tracing.span("gateway.coalesce", requests=len(items)) as sp:
            parts = []                   # (item, sids, vals, mask)
            for item in items:
                try:
                    sids = np.asarray(item.req.get("stream_ids", []),
                                      np.int64).ravel()
                    vals = np.asarray(item.req.get("values", []),
                                      np.float32).ravel()
                    if len(sids) != len(vals):
                        raise ValueError(
                            f"ingest batch mismatch: {len(sids)} "
                            f"stream_ids vs {len(vals)} values")
                    raw_mask = item.req.get("mask")
                    mask = (np.ones(len(sids), bool) if raw_mask is None
                            else np.asarray(raw_mask, bool).ravel())
                    if len(mask) != len(sids):
                        raise ValueError(
                            f"ingest batch mismatch: {len(sids)} "
                            f"stream_ids vs {len(mask)} mask entries")
                    parts.append((item, sids, vals, mask))
                except Exception as e:  # noqa: BLE001 - fails alone
                    item.fut.set_result(api.Response(
                        request_id=str(item.req.get("request_id", "")),
                        ok=False, error=repr(e)))
            if not parts:
                return
            sids = np.concatenate([p[1] for p in parts])
            vals = np.concatenate([p[2] for p in parts])
            mask = np.concatenate([p[3] for p in parts])
            sp.note(events=len(sids))
        try:
            batch_id = self.sde.ingest(sids, vals, mask)
        except Exception as e:  # noqa: BLE001 - service returns errors
            for item, *_ in parts:
                item.fut.set_result(api.Response(
                    request_id=str(item.req.get("request_id", "")),
                    ok=False, error=repr(e)))
            return
        if self.wal is not None:
            # logged POST-apply, keyed by the batch id the engine really
            # assigned: a batch the engine refuses never reaches the
            # log, so replay cannot be poisoned or steal an acked id.
            # Durable-before-ack still holds — the tick fsyncs before
            # any future's awaiter runs.
            try:
                self.sde.wal_seq = self.wal.append_ingest(
                    batch_id, sids, vals, mask)
            except Exception as e:  # noqa: BLE001 - serving must survive
                # applied but not durable: tell the clients so none of
                # them counts on this batch surviving a crash
                self.commit_log.append(("ingest", sids, vals, mask))
                for item, *_ in parts:
                    item.fut.set_result(api.Response(
                        request_id=str(item.req.get("request_id", "")),
                        ok=False,
                        error=f"ingested but WAL append failed: {e!r}"))
                return
        self.commit_log.append(("ingest", sids, vals, mask))
        tracing.note_coalesced("ingest", len(parts))
        for item, part_sids, _, part_mask in parts:
            item.fut.set_result(api.Response(
                request_id=str(item.req.get("request_id", "")),
                value=dict(batch=batch_id, coalesced=len(parts),
                           tuples=int(part_mask.sum()),
                           in_flight=self.sde.pending_batches)))

    # ------------------------------------------------------------------
    # coalesced red path: one SDE.query_many per run
    # ------------------------------------------------------------------
    def _do_query(self, items: List[_Item]) -> None:
        flat: List[api.AdHocQuery] = []
        # (item, start, prefail) — for query_many, prefail maps entry
        # index -> pre-built error response (malformed entries fail
        # alone, mirroring the engine's own query_many semantics)
        slices = []
        for item in items:
            rid = str(item.req.get("request_id", ""))
            if item.req.get("type") == "adhoc":
                start = len(flat)
                flat.append(api.AdHocQuery(
                    request_id=rid,
                    synopsis_id=namespaced(
                        item.tenant, str(item.req.get("synopsis_id", ""))),
                    query=item.req.get("query")))
                slices.append((item, start, None))
            else:                        # query_many: flatten entries
                start = len(flat)
                prefail: Dict[int, api.Response] = {}
                queries = item.req.get("queries") or []
                for i, q in enumerate(queries):
                    sub_rid = f"{rid}/{i}"
                    if isinstance(q, dict):
                        flat.append(api.AdHocQuery(
                            request_id=sub_rid,
                            synopsis_id=namespaced(
                                item.tenant, str(q.get("synopsis_id", ""))),
                            query=q["query"] if "query" in q else {}))
                    else:
                        prefail[i] = api.Response(
                            request_id=sub_rid, ok=False,
                            error="query entry must be an object, got "
                                  f"{type(q).__name__}")
                slices.append((item, start, (len(queries), prefail)))
        try:
            answers = self.sde.query_many(flat) if flat else []
        except Exception as e:  # noqa: BLE001 - service returns errors
            for item, *_ in slices:
                item.fut.set_result(api.Response(
                    request_id=str(item.req.get("request_id", "")),
                    ok=False, error=repr(e)))
            return
        tracing.note_coalesced("query", len(items))
        for item, start, many in slices:
            if many is None:             # adhoc: one answer, un-prefixed
                resp = answers[start]
                resp.synopsis_id = strip_ns(item.tenant, resp.synopsis_id)
                item.fut.set_result(resp)
                continue
            n_entries, prefail = many
            sub, cursor = [], start
            for i in range(n_entries):
                if i in prefail:
                    r = prefail[i]
                else:
                    r = answers[cursor]
                    cursor += 1
                    r.synopsis_id = strip_ns(item.tenant, r.synopsis_id)
                sub.append(r)
            n_fail = sum(1 for r in sub if not r.ok)
            item.fut.set_result(api.Response(
                request_id=str(item.req.get("request_id", "")),
                ok=n_fail == 0,
                error=(f"{n_fail}/{len(sub)} queries failed"
                       if n_fail else ""),
                value=[dataclasses.asdict(r) for r in sub]))

    # ------------------------------------------------------------------
    # everything else: serial, in place
    # ------------------------------------------------------------------
    def _do_one(self, item: _Item) -> None:
        req = dict(item.req)
        rtype = req.get("type")
        if item.tenant and isinstance(req.get("synopsis_id"), str):
            req["synopsis_id"] = namespaced(item.tenant,
                                            req["synopsis_id"])
        if item.tenant and isinstance(req.get("workflow_id"), str):
            # outlier workflow ids live in the same per-tenant namespace
            # as synopsis ids (their continuous responses route by them)
            req["workflow_id"] = namespaced(item.tenant,
                                            req["workflow_id"])
        seq = None
        if self.wal is not None and rtype in api.MUTATING_REQUESTS:
            # write-ahead, post-namespacing — replay sees exactly what
            # the engine saw (a request that fails live fails on replay
            # too, changing nothing). A WAL write error refuses the
            # request instead of killing the tick.
            try:
                seq = self.wal.append_request(req)
            except Exception as e:  # noqa: BLE001 - serving must survive
                item.fut.set_result(api.Response(
                    request_id=str(item.req.get("request_id", "")),
                    ok=False, error=f"WAL append failed: {e!r}"))
                return
        resp = self.sde.handle(req)
        if seq is not None:
            self.sde.wal_seq = seq
        if resp.ok and rtype == "ingest_multidim" and self.wal is not None:
            # data path: logged POST-apply keyed by the engine-assigned
            # batch id, like coalesced ingest above
            try:
                self.sde.wal_seq = self.wal.append_ingest_multidim(
                    int(resp.value["batch"]), req)
            except Exception as e:  # noqa: BLE001 - serving must survive
                self.commit_log.append(("request", req))
                item.fut.set_result(api.Response(
                    request_id=str(item.req.get("request_id", "")),
                    ok=False,
                    error=f"ingested but WAL append failed: {e!r}"))
                return
        if resp.ok and (rtype in api.MUTATING_REQUESTS
                        or rtype == "ingest_multidim"):
            self.commit_log.append(("request", req))
            if (rtype in ("build", "build_multidim")
                    and req.get("continuous")):
                cid = str(req.get("client_id") or item.client.client_id)
                self._subs[req.get("synopsis_id", "")] = (cid, item.tenant)
            elif rtype == "track_outliers":
                cid = str(req.get("client_id") or item.client.client_id)
                self._subs[req.get("workflow_id", "")] = (cid, item.tenant)
            elif rtype == "untrack_outliers":
                self._subs.pop(req.get("workflow_id", ""), None)
            elif rtype == "stop":
                dead = req.get("synopsis_id", "")
                self._subs = {k: v for k, v in self._subs.items()
                              if not (k == dead
                                      or k.startswith(dead + "/"))}
        if resp.ok and rtype == "shutdown":
            self.closed = True
            self.closed_event.set()
        if resp.ok and rtype == "status" and item.tenant \
                and isinstance(resp.value, dict):
            # a tenant's status sees ONLY its own namespace (the empty
            # tenant is the admin view over everything)
            prefix = item.tenant + NS_SEP
            resp.value = {k[len(prefix):]: v
                          for k, v in resp.value.items()
                          if k.startswith(prefix)}
        resp.synopsis_id = strip_ns(item.tenant, resp.synopsis_id)
        item.fut.set_result(resp)

    # ------------------------------------------------------------------
    # continuous output: per-client routing
    # ------------------------------------------------------------------
    def _route_continuous(self) -> None:
        """Move every retired continuous response from the engine's
        global log to its subscriber's bounded per-client log, with the
        tenant prefix stripped from both id fields."""
        for r in self.sde.continuous_out.drain():
            owner = self._owner_of(r.synopsis_id)
            if owner is None:
                self.unrouted.append(r)
                continue
            cid, tenant = owner
            client = self.clients.get(cid)
            if client is None:
                self.unrouted.append(r)
                continue
            if tenant:
                r = dataclasses.replace(
                    r,
                    synopsis_id=strip_ns(tenant, r.synopsis_id),
                    request_id=r.request_id.replace(tenant + NS_SEP,
                                                    "", 1))
            client.log.append(r)
            client.wakeup.set()

    def _owner_of(self, synopsis_id: str
                  ) -> Optional[Tuple[str, str]]:
        """Resolve a continuous entry id (``<build id>`` or
        ``<build id>/<stream>``) to its subscriber via the "/" prefix
        chain."""
        p = synopsis_id
        while True:
            if p in self._subs:
                return self._subs[p]
            if "/" not in p:
                return None
            p = p.rsplit("/", 1)[0]


def replay_log(commit_log, sde: Optional[SDE] = None) -> SDE:
    """The serialized oracle: replay a gateway's ``commit_log`` into a
    fresh single-client engine, serially, in commit order. Coalescing
    must be state-invisible — a gateway-driven engine's stacks are
    byte-identical to this replay (float scatter order WITHIN a
    coalesced batch is part of the committed record, which is why the
    log stores the concatenated arrays, not the per-client pieces)."""
    sde = sde if sde is not None else SDE()
    for entry in commit_log:
        if entry[0] == "ingest":
            _, sids, vals, mask = entry
            sde.ingest(sids, vals, mask)
        else:
            sde.handle(entry[1])
    sde.flush()
    return sde
