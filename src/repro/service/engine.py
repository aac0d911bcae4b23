"""The SDEaaS engine — one always-on service maintaining thousands of
synopses for thousands of streams (paper Section 4).

Structure mirrors the paper's architecture, adapted to JAX:

  * blue path  : ``ingest(stream_ids, values)`` — ONE jitted update per
    synopsis *kind* updates every synopsis of that kind (stacked state =
    slot sharing). Stream routing (stream -> row) is a hashed open-
    addressing table (``service/routing.py``): stream ids are arbitrary
    63-bit ints, and the linear probe runs INSIDE the fused program
    (``kernels.ops.route_probe``), the analogue of
    RegisterSynopsis/HashData key creation.
  * red path   : ``handle(request_json)`` / ``query_many(requests)`` —
    queries read the same stacked state in place through ONE cached jitted
    stacked-estimate program per kind (``kernels.ops.estimate_all``): N
    ad-hoc queries against a kind are one dispatch, and all continuous
    queries of a kind are re-evaluated per ingest batch in one program.
    Queries never enter (or back-pressure) the update path.
  * yellow path: federated synopses — ``Federation`` keeps one SDE per
    site and synthesizes global estimates at the responsible site. On a
    mesh with a ``site``/``pod`` axis each site's state is pinned to its
    own device and the merge runs as a REAL collective inside one
    shard_map-ped program (``kernels.ops.estimate_collective`` driving
    ``core.federated.merge_over_axis``: psum/pmax/all_gather over the
    axis); off-mesh, host copies are gathered and tree-merged
    (``kernels.ops.estimate_merged`` — the equivalence oracle).

Capacity management: kind stacks grow by doubling (amortized re-jit),
"a request for a new synopsis assigns new tasks, not task slots"; the
routing tables grow-and-rehash independently of stack capacity.

Execution modes: the blue path runs **eager** (continuous-query outputs
are materialized to host before ``ingest`` returns — the pre-PR-4
behaviour) or **pipelined** (``SDE(pipelined=True)``, or env
``SDE_PIPELINED=1``): ingest dispatches the fused update and
stacked-estimate programs and returns immediately, parking the batch's
continuous outputs as device futures on a bounded depth-2 queue
(``service/pipeline.py``). Host prep for batch N+1 then overlaps batch
N's device work. Futures materialize into ``continuous_out`` when the
queue retires the batch (a newer submission exceeds the depth), on an
explicit ``flush()``, or at a fence — ``query_many``/``handle`` reads,
build/stop/grow, snapshot and elastic merge all drain the pipeline
first, so both modes produce byte-identical synopsis state and
identical continuous responses (ids and values) in the same order.
"""
from __future__ import annotations

import dataclasses
import importlib
import json
import os
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from repro import core, tracing
from repro.core import batched, federated
from repro.core.multidim import MultidimSpec
from repro.core.synopsis import Synopsis, kind_params
from repro.kernels import ops as kops
from repro.sharding import specs
from . import api, migration, outliers, pipeline, routing

# dense route size of pre-hashed-routing snapshots (the old _MAX_STREAMS);
# restore migrates these into a RouteTable
_LEGACY_ROUTE_SLOTS = 1 << 16

# fold the parked ingest-window stream ids into per-stack dirty sets every
# this many batches — bounds _pending_dirty even when nobody snapshots
_DIRTY_RESOLVE_EVERY = 64


@dataclasses.dataclass
class _Entry:
    synopsis_id: str
    kind_key: Any                 # the frozen kind dataclass
    row: int
    stream_id: Optional[int]      # None => data-source synopsis
    federated: bool = False
    responsible_site: Optional[str] = None
    continuous: bool = False
    source_id: Optional[str] = None


class _KindStack:
    """All synopses of one kind: stacked state + hashed routing table.

    On a multi-device mesh the stacked state's leading [capacity] row
    axis is partitioned over the ``synopsis`` logical axis (horizontal
    scale-out, paper Fig. 5); the routing table's device mirror is
    replicated (like the old dense route array).
    """

    def __init__(self, kind: Synopsis, capacity: int = 64,
                 mesh: Optional[Mesh] = None,
                 rules: Optional[specs.MeshRules] = None,
                 device=None):
        self.kind = kind
        self.capacity = capacity
        self.mesh = mesh
        self.device = device            # pin to ONE device (federation site)
        self.rules = rules or specs.DEFAULT_RULES
        self.state = batched.stacked_init(kind, capacity)
        self.table = routing.RouteTable()  # stream id -> row (host side)
        self.source_rows: List[int] = []   # rows fed by ALL tuples
        self.used: List[bool] = [False] * capacity
        self.is_timeseries = hasattr(kind, "step")
        self._source_idx = None            # device cache, source_rows_idx()
        self._free: Optional[List[int]] = None   # alloc free list (lazy)
        self._dev_table = None             # device mirror of self.table
        self._dev_table_version = -1
        # rows whose bytes changed since the last snapshot — what an
        # incremental checkpoint ships. Bounded by capacity; a superset
        # is always safe (extra rows ship unchanged bytes), a miss never
        # is, so every mutation path marks here: alloc/free, the
        # migration plane (implant/move), merge and the deferred
        # ingest-window resolver (SDE._resolve_dirty)
        self.dirty: set[int] = set()
        self._place()

    def mark_dirty(self, rows) -> None:
        """Record rows whose state bytes (or lifecycle) changed since the
        last snapshot."""
        self.dirty.update(int(r) for r in rows)

    @property
    def sharding(self) -> Optional[NamedSharding]:
        if self.mesh is None or self.mesh.empty:
            return None
        return specs.stack_sharding(self.rules, self.mesh, self.capacity)

    def _place(self):
        """Pin state rows over the synopsis axis — or, for a federation
        site, to the site's own device, so ingest's jitted programs run
        where the site lives (the routing table's device mirror is placed
        lazily by ``device_table``)."""
        target = self.sharding if self.device is None else self.device
        if target is None:
            return
        self.state = jax.tree.map(
            lambda x: jax.device_put(x, target), self.state)

    def device_table(self):
        """(keys_lo, keys_hi, rows) device mirror of the routing table —
        the arrays ``kernels.ops.route_probe`` gathers from inside the
        fused programs. Rebuilt only when the host table mutated
        (build/stop/merge — the rare path); replicated on a mesh."""
        if (self._dev_table is None
                or self._dev_table_version != self.table.version):
            lo, hi = routing.split64(self.table.keys)
            arrs = (lo, hi, self.table.rows)
            if self.device is not None:
                self._dev_table = tuple(
                    jax.device_put(a, self.device) for a in arrs)
            elif self.mesh is not None and not self.mesh.empty:
                rep = NamedSharding(self.mesh, P())
                self._dev_table = tuple(
                    jax.device_put(a, rep) for a in arrs)
            else:
                self._dev_table = tuple(jnp.asarray(a) for a in arrs)
            self._dev_table_version = self.table.version
        return self._dev_table

    @property
    def n_probe(self) -> int:
        """Static probe bound for the fused programs: the table's longest
        displacement + 1, pow2-rounded so jit retraces are bounded by
        log(PROBE_CAP) distinct values, not one per table state."""
        return _next_pow2(self.table.max_probe)

    def source_rows_idx(self) -> Optional[jax.Array]:
        """int32 index vector of data-source rows; None when there are
        none (lets the no-source fused path skip the merge branch at
        trace time). Cached on device; invalidated on lifecycle changes."""
        if not self.source_rows:
            return None
        if self._source_idx is None:
            self._source_idx = jnp.asarray(
                np.asarray(self.source_rows, np.int32))
        return self._source_idx

    def mark_source(self, row: int):
        self.source_rows.append(row)
        self._source_idx = None

    def out_sharding(self) -> Optional[NamedSharding]:
        """Replicate the (small) estimate outputs of a red-path dispatch
        when the stack is mesh-sharded; None off-mesh."""
        if self.mesh is None or self.mesh.empty:
            return None
        return NamedSharding(self.mesh, P())

    def row_bytes(self) -> int:
        """Actual device bytes of ONE row slice of the stacked state — the
        per-synopsis footprint. ``kind.memory_bytes()`` reports the
        abstract sketch size, which drifts from the stacked dtypes (e.g.
        Bloom bits are int32 lanes here, not packed bits)."""
        return sum((x.size // self.capacity) * x.dtype.itemsize
                   for x in jax.tree.leaves(self.state))

    def alloc(self) -> int:
        """Hand out the lowest free row (free-list backed: a 1M-stream
        per-source build is 1M O(1) pops, not 1M O(capacity) scans)."""
        if self._free is None:
            self._free = [i for i, u in enumerate(self.used)
                          if not u][::-1]
        if not self._free:
            old_cap = self.capacity
            self.capacity *= 2
            self.state = batched.grow(self.kind, self.state, self.capacity)
            self.used.extend([False] * old_cap)
            self._free = list(range(self.capacity - 1, old_cap - 1, -1))
            self._source_idx = None
            self._place()
        row = self._free.pop()
        self.used[row] = True
        # a freshly built synopsis differs from the base snapshot even
        # before its first tuple (build-without-ingest must still ship)
        self.dirty.add(row)
        return row

    def free(self, row: int):
        self.free_rows([row])

    def free_rows(self, rows: List[int]):
        """Release rows AND re-initialize their state: the next alloc of
        these slots must hand out fresh synopses, not the dead ones'
        counts (freed-row reuse corruption). Batched — stopping a
        per-stream group of thousands is ONE scatter, not one full-state
        copy per row. The routing table compacts by re-insert
        (tombstone-free), and the source-row index cache is ALWAYS
        dropped so a stopped data-source row cannot keep absorbing
        tuples through a stale cached vector."""
        for row in rows:
            self.used[row] = False
            if row in self.source_rows:
                self.source_rows.remove(row)
        self._source_idx = None
        self._free = None
        # freed rows are re-initialized below — changed bytes the next
        # delta must carry so a restored engine matches byte-for-byte
        self.dirty.update(int(r) for r in rows)
        self.table.remove_rows(np.asarray(rows, np.int32))
        idx = jnp.asarray(rows, jnp.int32)
        fresh = batched.stacked_init(self.kind, len(rows))
        self.state = jax.tree.map(
            lambda x, f: x.at[idx].set(f), self.state, fresh)
        if self.sharding is not None:
            self.state = jax.tree.map(
                lambda x: jax.device_put(x, self.sharding), self.state)


class SDE:
    """One SDEaaS instance (one site/cluster in federated settings).

    Pass a ``mesh`` to shard every kind stack's row axis across devices
    (the ``synopsis`` logical axis of ``sharding/specs.py``); omit it for
    single-device operation.
    """

    def __init__(self, site: str = "site-0",
                 backend: Optional[str] = None,
                 mesh: Optional[Mesh] = None,
                 rules: Optional[specs.MeshRules] = None,
                 pipelined: Optional[bool] = None, pipeline_depth: int = 2,
                 continuous_out_cap: Optional[int] = 65536,
                 device=None):
        self.site = site
        # backend=None defers to the SDE_BACKEND env toggle (default
        # "xla"), so whole suites flip to the Pallas registry kernels
        # untouched — the same pattern as SDE_PIPELINED below
        if backend is None:
            backend = os.environ.get("SDE_BACKEND", "") or "xla"
        self.backend = backend
        if mesh is not None and AxisType.Explicit in mesh.axis_types:
            raise ValueError(
                f"SDE needs a mesh with Auto axes, got axis types "
                f"{mesh.axis_types} (jax.make_mesh defaults to Explicit); "
                "build it with repro.launch.mesh.make_mesh")
        self.mesh = mesh
        if device is not None and mesh is not None:
            raise ValueError(
                "pass mesh= (shard stacks over devices) OR device= (pin a "
                "federation site to one device), not both")
        self.device = device
        self.rules = rules or specs.DEFAULT_RULES
        self.stacks: Dict[Any, _KindStack] = {}
        self.entries: Dict[str, _Entry] = {}
        # bounded: a consumer that falls behind loses the OLDEST
        # responses (counted in .dropped), never stalls ingest
        self.continuous_out = pipeline.BoundedResponseLog(continuous_out_cap)
        # pipelined=None defers to the SDE_PIPELINED env toggle, so whole
        # suites (CI's pipelined smoke job) flip execution mode untouched
        if pipelined is None:
            pipelined = os.environ.get("SDE_PIPELINED", "") not in ("", "0")
        self.pipelined = bool(pipelined)
        self._pipeline = (pipeline.IngestPipeline(
            self._retire_batch, depth=pipeline_depth, tag=site)
            if self.pipelined else None)
        self.tuples_ingested = 0
        self.batches_ingested = 0   # monotonic; keys continuous responses
        # continuous queries grouped by kind: {kind: (ids, rows)} — rebuilt
        # lazily after any lifecycle change so _emit_continuous issues one
        # stacked-estimate dispatch per kind, not one gather per entry
        self._cq_groups: Optional[Dict[Any, Any]] = None
        # multidim synopsis families (family id -> key-encoding spec) and
        # the continuous outlier workflows riding them; the per-tick
        # outlier plans invalidate together with _cq_groups (every
        # lifecycle mutation clears both through _invalidate_plans)
        self.multidim: Dict[str, MultidimSpec] = {}
        self.outliers: Dict[str, outliers.OutlierWorkflow] = {}
        self._ow_plans: Optional[List[outliers.OutlierPlan]] = None
        # durability plumbing. Ingest routes ON DEVICE (the probe runs
        # inside the fused program), so the hot path cannot know which
        # rows a batch touched; it appends the batch's stream ids here
        # instead, and _resolve_dirty folds whole windows into per-stack
        # dirty sets with one vectorized table lookup (deferred dirty
        # tracking — O(0) device work, O(batch) host append per ingest).
        self._pending_dirty: List[np.ndarray] = []
        # incremental-snapshot lineage: the full base step and the delta
        # steps stacked on it (oldest first), valid for _ckpt_dir
        self._ckpt_dir: Optional[str] = None
        self._ckpt_base: Optional[int] = None
        self._ckpt_chain: List[int] = []
        # background (async_=True) saves that never landed — detected at
        # the next snapshot, which then rebuilds from a fresh full base
        self.ckpt_failures = 0
        # highest write-ahead-log sequence number already folded into
        # this engine's state — snapshots persist it so recovery replays
        # only the WAL tail (exactly-once; see service/wal.py)
        self.wal_seq = 0

    def _new_stack(self, kind: Synopsis, capacity: int = 64) -> _KindStack:
        return _KindStack(kind, capacity, mesh=self.mesh, rules=self.rules,
                          device=self.device)

    # ------------------------------------------------------------------
    # red path: requests
    # ------------------------------------------------------------------
    def handle(self, snippet: str | dict) -> api.Response:
        try:
            req = api.parse_request(snippet)
            if isinstance(req, api.BuildSynopsis):
                return self._build(req)
            if isinstance(req, api.StopSynopsis):
                return self._stop(req)
            if isinstance(req, api.LoadSynopsis):
                return self._load(req)
            if isinstance(req, api.AdHocQuery):
                return self._query(req)
            if isinstance(req, api.QueryMany):
                return self._query_many_req(req)
            if isinstance(req, api.Ingest):
                return self._ingest_req(req)
            if isinstance(req, api.BuildMultidim):
                return self._build_multidim(req)
            if isinstance(req, api.IngestMultidim):
                return self._ingest_multidim_req(req)
            if isinstance(req, api.SubpopQuery):
                return self._subpop_query(req)
            if isinstance(req, api.TrackOutliers):
                return self._track_outliers(req)
            if isinstance(req, api.UntrackOutliers):
                return self._untrack_outliers(req)
            if isinstance(req, api.Flush):
                return self._flush_req(req)
            if isinstance(req, api.Shutdown):
                return self._shutdown_req(req)
            if isinstance(req, api.StatusReport):
                return self._status(req)
            raise ValueError(f"unhandled request {req}")
        except Exception as e:  # noqa: BLE001 - service returns errors
            rid = ""
            try:
                rid = json.loads(snippet)["request_id"] if isinstance(
                    snippet, str) else snippet.get("request_id", "")
            except Exception:
                pass
            return api.Response(request_id=rid, ok=False, error=repr(e))

    def _build(self, req: api.BuildSynopsis) -> api.Response:
        # fence: builds can grow stacks (capacity doubling) and mutate
        # routing tables; pending continuous batches retire first
        self.flush()
        kind = core.make_kind(req.kind, **req.params)
        # validate EVERY routed stream id before any allocation: a failed
        # build must not commit partial entries. Ids are arbitrary 63-bit
        # ints (hashed routing) — only unrepresentable ids (negative or
        # >= 2**63) are rejected.
        if req.per_stream_of_source:
            sid_list = (req.stream_ids if req.stream_ids is not None
                        else range(req.n_streams))
            for sid in sid_list:
                _check_stream_id(sid)
            # canonicalize + dedupe: the entry id (f"{syn}/{sid}") and the
            # routed key must agree, or non-canonical forms (7.0 vs 7)
            # would commit shadow entries that never receive updates
            sid_list = list(dict.fromkeys(int(s) for s in sid_list))
        else:
            sid_list = None
            _check_stream_id(req.stream_id)
        stack = self.stacks.get(kind)
        if stack is None:
            cap = 64
            if sid_list:
                cap = max(64, _next_pow2(len(sid_list)))
            stack = self._new_stack(kind, cap)
            self.stacks[kind] = stack

        def add_one(sid: Optional[int], syn_id: str, routed: list):
            # reuse: same id => same synopsis shared across workflows
            if syn_id in self.entries:
                return
            row = stack.alloc()
            if sid is None:
                stack.mark_source(row)
            else:
                routed.append((int(sid), row))
            self.entries[syn_id] = _Entry(
                synopsis_id=syn_id, kind_key=kind, row=row, stream_id=sid,
                federated=req.federated,
                responsible_site=req.responsible_site,
                continuous=req.continuous, source_id=req.source_id)

        routed: List[tuple] = []
        if sid_list is not None:
            for sid in sid_list:
                add_one(int(sid), f"{req.synopsis_id}/{sid}", routed)
        else:
            add_one(req.stream_id, req.synopsis_id, routed)
        if routed:
            # one vectorized table insert for the whole build
            stack.table.insert_many(
                np.asarray([s for s, _ in routed], np.int64),
                np.asarray([r for _, r in routed], np.int32))
        self._invalidate_plans()
        return api.Response(request_id=req.request_id,
                            synopsis_id=req.synopsis_id,
                            params=kind_params(kind))

    def _stop(self, req: api.StopSynopsis) -> api.Response:
        # fence: stopping frees + re-initializes rows and compacts the
        # routing table; the stopped synopses' final continuous responses
        # (already dispatched) must land in continuous_out first
        self.flush()
        ids = [k for k in self.entries
               if k == req.synopsis_id or k.startswith(req.synopsis_id + "/")]
        if not ids:
            return api.Response(request_id=req.request_id, ok=False,
                                error=f"unknown synopsis {req.synopsis_id!r}")
        freed: Dict[Any, List[int]] = {}
        for k in ids:
            e = self.entries.pop(k)
            freed.setdefault(e.kind_key, []).append(e.row)
        for kind, rows in freed.items():
            self.stacks[kind].free_rows(rows)
            # a kind nothing references anymore releases BOTH its stack
            # state and its compiled programs (the KindCaches are bounded
            # by engine lifecycle, not append-only). Kind instances are
            # value-equal across engines, so another engine still serving
            # the same parameters merely re-jits on its next batch.
            if not any(e.kind_key == kind for e in self.entries.values()):
                del self.stacks[kind]
                kops.evict_kind_caches(kind)
        # a stopped multidim family takes its key spec with it; workflows
        # watching it go dormant (the planner skips missing families)
        self.multidim.pop(req.synopsis_id, None)
        self._invalidate_plans()
        return api.Response(request_id=req.request_id,
                            synopsis_id=req.synopsis_id, value=len(ids))

    def _load(self, req: api.LoadSynopsis) -> api.Response:
        """Dynamic pluggability: import factory while the service runs."""
        mod_name, _, attr = req.factory_path.partition(":")
        factory = getattr(importlib.import_module(mod_name), attr)
        core.register_kind(req.kind_name, factory, overwrite=True)
        return api.Response(request_id=req.request_id, value=req.kind_name)

    def _query(self, req: api.AdHocQuery) -> api.Response:
        return self.query_many([req])[0]

    def query_many(self, requests: Sequence[api.AdHocQuery]
                   ) -> List[api.Response]:
        """Answer N ad-hoc queries with ONE jitted stacked-estimate
        dispatch per kind touched (the batched red path, paper Fig. 8):
        queries are grouped by kind, their args batched into padded device
        arrays, and each group reads the `synopsis`-sharded stack state in
        place — no per-query host round trip."""
        with tracing.span("sde.query_many", queries=len(requests)):
            # fence: pending continuous batches were dispatched against
            # earlier state; they retire before ad-hoc reads answer, so the
            # response stream stays in ingest order
            self.flush()
            responses: List[Optional[api.Response]] = [None] * len(requests)
            groups: Dict[Any, List[int]] = {}
            for i, req in enumerate(requests):
                e = self.entries.get(req.synopsis_id)
                if e is None:
                    responses[i] = api.Response(
                        request_id=req.request_id, ok=False,
                        error=f"unknown synopsis {req.synopsis_id!r}")
                elif (req.query is not None
                      and not isinstance(req.query, dict)):
                    # fails alone — never poisons the rest of the batch
                    responses[i] = api.Response(
                        request_id=req.request_id, ok=False,
                        error="query must be an object, got "
                              f"{type(req.query).__name__}")
                else:
                    groups.setdefault(e.kind_key, []).append(i)
            for kind, idxs in groups.items():
                stack = self.stacks[kind]
                rows = [self.entries[requests[i].synopsis_id].row
                        for i in idxs]
                vals, errs = self._estimate_rows(
                    kind, stack, rows,
                    [requests[i].query or {} for i in idxs])
                for i, val, err in zip(idxs, vals, errs):
                    if err is not None:
                        responses[i] = api.Response(
                            request_id=requests[i].request_id,
                            synopsis_id=requests[i].synopsis_id,
                            ok=False, error=err)
                    else:
                        responses[i] = api.Response(
                            request_id=requests[i].request_id,
                            synopsis_id=requests[i].synopsis_id, value=val,
                            params=kind_params(kind))
            return responses

    def _query_many_req(self, req: api.QueryMany) -> api.Response:
        subs: List[Optional[api.AdHocQuery]] = []
        prefail: Dict[int, api.Response] = {}
        for i, q in enumerate(req.queries):
            rid = f"{req.request_id}/{i}"
            if isinstance(q, dict):
                # pass the query field through untouched (no `or {}`):
                # query_many rejects non-dict values uniformly, including
                # falsy ones like 0 or ""
                subs.append(api.AdHocQuery(
                    request_id=rid, synopsis_id=q.get("synopsis_id", ""),
                    query=q["query"] if "query" in q else {}))
            else:
                # a malformed entry fails alone; the rest of the batch runs
                prefail[i] = api.Response(
                    request_id=rid, ok=False,
                    error="query entry must be an object, got "
                          f"{type(q).__name__}")
                subs.append(None)
        answered = iter(self.query_many([s for s in subs if s is not None]))
        rs = [prefail[i] if s is None else next(answered)
              for i, s in enumerate(subs)]
        n_fail = sum(1 for r in rs if not r.ok)
        return api.Response(request_id=req.request_id, ok=n_fail == 0,
                            error=(f"{n_fail}/{len(rs)} queries failed"
                                   if n_fail else ""),
                            value=[dataclasses.asdict(r) for r in rs])

    def _ingest_req(self, req: api.Ingest) -> api.Response:
        """JSON blue path: the ack carries the monotonic batch counter
        (keys this batch's ``cq/<id>/<batch>`` continuous responses) and
        the pipeline depth at return time."""
        batch = self.ingest(req.stream_ids, req.values, req.mask)
        return api.Response(
            request_id=req.request_id,
            value=dict(batch=batch, tuples_ingested=self.tuples_ingested,
                       in_flight=self.pending_batches))

    # ------------------------------------------------------------------
    # multidim subpopulations (tentpole): a family id maps to a
    # MultidimSpec; every group is an ORDINARY per-stream entry
    # (f"<family>/<group key>") on the fused blue path, so maintenance
    # costs exactly what the same number of scalar streams would.
    # ------------------------------------------------------------------
    def _build_multidim(self, req: api.BuildMultidim) -> api.Response:
        if not req.synopsis_id:
            raise ValueError("build_multidim needs a synopsis_id")
        if req.synopsis_id in self.multidim:
            raise ValueError(
                f"multidim family {req.synopsis_id!r} already exists")
        spec = MultidimSpec(
            req.dims,
            levels=None if req.levels is None
            else [tuple(lvl) for lvl in req.levels])
        keys = spec.all_keys()
        if len(set(keys)) != len(keys):
            # birthday-bound 63-bit collision across the family's groups
            # (~n^2/2^64): astronomically rare, but aliased groups would
            # silently share one synopsis — fail loudly instead
            raise ValueError(
                "group-key collision inside the family; rename a "
                "dimension or value to re-roll the hashes")
        resp = self._build(api.BuildSynopsis(
            request_id=req.request_id, synopsis_id=req.synopsis_id,
            kind=req.kind, params=req.params, per_stream_of_source=True,
            stream_ids=keys, continuous=req.continuous))
        if resp.ok:
            self.multidim[req.synopsis_id] = spec
            resp.params = dict(resp.params, n_groups=spec.n_groups(),
                               n_levels=len(spec.levels))
        return resp

    def _ingest_multidim_req(self, req: api.IngestMultidim) -> api.Response:
        batch = self.ingest_multidim(req.synopsis_id, req.records,
                                     req.values, req.mask, req.items)
        return api.Response(
            request_id=req.request_id, synopsis_id=req.synopsis_id,
            value=dict(batch=batch, tuples_ingested=self.tuples_ingested,
                       in_flight=self.pending_batches))

    def ingest_multidim(self, synopsis_id: str, records, values,
                        mask=None, items=None) -> int:
        """Blue path for attribute-tagged records: expand each record to
        its per-level group keys host-side and feed the expansion through
        the NORMAL ``ingest`` — one fused dispatch per kind, the probe
        untouched. ``items`` optionally carries per-record item
        identities for item-hashing sketches; default is the record's
        leaf-group key (so coarse groups count distinct leaf
        subpopulations). Returns the (single) batch id."""
        spec = self.multidim.get(synopsis_id)
        if spec is None:
            raise KeyError(f"unknown multidim family {synopsis_id!r}")
        n = len(records)
        vals = np.asarray(values, np.float32)
        if len(vals) != n:
            raise ValueError(
                f"ingest_multidim mismatch: {n} records vs "
                f"{len(vals)} values — the two must align 1:1")
        msk = (np.ones(n, bool) if mask is None
               else np.asarray(mask, bool))
        if len(msk) != n:
            raise ValueError(
                f"ingest_multidim mismatch: {n} records vs "
                f"{len(msk)} mask entries — the two must align 1:1")
        if items is None:
            its = np.asarray([spec.leaf_key(r) for r in records], np.int64)
        else:
            its = np.asarray(items, np.int64)
            if len(its) != n:
                raise ValueError(
                    f"ingest_multidim mismatch: {n} records vs "
                    f"{len(its)} items — the two must align 1:1")
        lvl = len(spec.levels)
        sids = np.fromiter(
            (k for rec in records for k in spec.expand(rec)),
            np.int64, count=n * lvl)
        return self.ingest(sids, np.repeat(vals, lvl),
                           np.repeat(msk, lvl), items=np.repeat(its, lvl))

    def _subpop_query(self, req: api.SubpopQuery) -> api.Response:
        """Estimate over an arbitrary subpopulation — the covering key
        set of the predicate's level, merged + estimated in ONE fused
        dispatch (``kernels.ops.estimate_subpop``)."""
        # fence: a subpop read observes every ingested batch
        self.flush()
        spec = self.multidim.get(req.synopsis_id)
        if spec is None:
            raise KeyError(f"unknown multidim family {req.synopsis_id!r}")
        level, keys = spec.covering_keys(req.where)
        entries = [self.entries[f"{req.synopsis_id}/{k}"] for k in keys]
        kind = entries[0].kind_key
        if getattr(kind, "merge_mode", "gather") == "fresh":
            raise ValueError(
                f"{type(kind).__name__} replicas are exchanged, not "
                "merged — subpop_query needs a mergeable kind")
        args, take, errors = _plan_queries(kind, [req.query or {}])
        if errors[0] is not None:
            raise ValueError(errors[0])
        stack = self.stacks[kind]
        rows = jnp.asarray(np.asarray([e.row for e in entries], np.int32))
        out = kops.estimate_subpop(kind, stack.state, rows, *args,
                                   out_sharding=stack.out_sharding())
        tracing.note_subpop(self.site, len(keys))
        return api.Response(
            request_id=req.request_id, synopsis_id=req.synopsis_id,
            value=take(jax.tree.map(np.asarray, out), 0),
            params=dict(kind_params(kind), cover_keys=len(keys),
                        level=list(level)))

    def _track_outliers(self, req: api.TrackOutliers) -> api.Response:
        if not req.workflow_id:
            raise ValueError("track_outliers needs a workflow_id")
        if req.workflow_id in self.outliers:
            raise ValueError(
                f"workflow {req.workflow_id!r} is already tracked")
        spec = self.multidim.get(req.synopsis_id)
        if spec is None:
            raise KeyError(f"unknown multidim family {req.synopsis_id!r}")
        if req.level is None:
            level = tuple(spec.dim_names)        # the leaf level
        else:
            level = tuple(n for n in spec.dim_names if n in set(req.level))
            for name in req.level:
                spec._check_dim(name)
        if level not in spec.levels:
            raise ValueError(
                f"level {level} is not materialized; available: "
                f"{spec.levels}")
        # the kind + query must plan cleanly NOW, not fail every tick
        kind = self.entries[
            f"{req.synopsis_id}/{spec.population_key()}"].kind_key
        if getattr(kind, "merge_mode", "gather") == "fresh":
            raise ValueError(
                f"{type(kind).__name__} cannot back an outlier workflow "
                "(non-mergeable replicas)")
        _, _, errors = _plan_queries(kind, [dict(req.query or {})])
        if errors[0] is not None:
            raise ValueError(errors[0])
        wf = outliers.OutlierWorkflow(
            workflow_id=req.workflow_id, synopsis_id=req.synopsis_id,
            level=level, query=dict(req.query or {}),
            threshold=float(req.threshold), min_dev=float(req.min_dev))
        self.outliers[req.workflow_id] = wf
        self._ow_plans = None
        return api.Response(
            request_id=req.request_id, synopsis_id=req.workflow_id,
            value=dict(level=list(level),
                       n_groups=len(spec.level_assignments(level))))

    def _untrack_outliers(self, req: api.UntrackOutliers) -> api.Response:
        if req.workflow_id not in self.outliers:
            raise KeyError(f"unknown workflow {req.workflow_id!r}")
        del self.outliers[req.workflow_id]
        self._ow_plans = None
        return api.Response(request_id=req.request_id,
                            synopsis_id=req.workflow_id, value=1)

    def _plan_outliers(self) -> List[outliers.OutlierPlan]:
        """One dispatch plan per live workflow: the level's group rows
        plus the population row (LAST), padded like any red-path batch,
        with the workflow's query planned once for every row. Workflows
        whose family or entries were stopped underneath them go dormant
        (skipped) instead of failing ingest."""
        plans: List[outliers.OutlierPlan] = []
        for wf in self.outliers.values():
            spec = self.multidim.get(wf.synopsis_id)
            if spec is None:
                continue
            assignments = spec.level_assignments(wf.level)
            ids = [f"{wf.synopsis_id}/{spec.group_key(a)}"
                   for a in assignments]
            ids.append(f"{wf.synopsis_id}/{spec.population_key()}")
            if any(i not in self.entries for i in ids):
                continue
            kind = self.entries[ids[0]].kind_key
            rows_arr = _pad_rows([self.entries[i].row for i in ids])
            args, take, _ = _plan_queries(
                kind, [dict(wf.query)] * len(rows_arr))
            plans.append(outliers.OutlierPlan(
                workflow=wf, kind_key=kind, assignments=assignments,
                rows=jnp.asarray(rows_arr), args=args, take=take,
                out_sharding=self.stacks[kind].out_sharding()))
        return plans

    def _flush_req(self, req: api.Flush) -> api.Response:
        drained = self.flush()
        return api.Response(
            request_id=req.request_id,
            value=dict(drained=drained,
                       batches_ingested=self.batches_ingested,
                       continuous_unread=len(self.continuous_out),
                       continuous_dropped=self.continuous_out.dropped))

    def _shutdown_req(self, req: api.Shutdown) -> api.Response:
        """Clean stop: flush (the pending continuous batches land in
        ``continuous_out`` before the ack), then ``close()`` — stacks and
        this engine's compiled-program cache entries are released. The
        ack carries the final counters; the engine object stays usable
        (a later build simply re-allocates)."""
        drained = self.flush()
        value = dict(drained=drained,
                     tuples_ingested=self.tuples_ingested,
                     batches_ingested=self.batches_ingested,
                     synopses=len(self.entries),
                     continuous_unread=len(self.continuous_out),
                     continuous_dropped=self.continuous_out.dropped)
        self.close()
        return api.Response(request_id=req.request_id, value=value)

    def _status(self, req: api.StatusReport) -> api.Response:
        per_row = {k: s.row_bytes() for k, s in self.stacks.items()}
        info = {
            sid: dict(kind=type(e.kind_key).__name__,
                      params=kind_params(e.kind_key),
                      stream=e.stream_id, federated=e.federated,
                      memory_bytes=per_row[e.kind_key])
            for sid, e in self.entries.items()}
        # elasticity probes ride ``params`` (the JSON status response
        # surfaces them) so ``value`` keeps its per-synopsis shape — the
        # gateway's tenant filtering and len(status.value) stay intact
        return api.Response(
            request_id=req.request_id, value=info,
            params=dict(
                site=self.site,
                reconcile_count=int(tracing.RECONCILE_COUNT[self.site]),
                migrated_rows=int(tracing.MIGRATED_ROWS[self.site]),
                rebalance_imbalance=float(
                    tracing.REBALANCE_IMBALANCE[self.site]),
                checkpoint_bytes=int(tracing.CHECKPOINT_BYTES[self.site]),
                dirty_rows=int(tracing.DIRTY_ROWS[self.site]),
                wal_appends=int(tracing.WAL_APPENDS[self.site]),
                subpop_cover_keys=int(tracing.SUBPOP_COVER_KEYS[self.site]),
                outlier_emits=int(tracing.OUTLIER_EMITS[self.site])))

    # ------------------------------------------------------------------
    # blue path: data
    # ------------------------------------------------------------------
    def ingest(self, stream_ids, values, mask=None, items=None) -> int:
        """One batch of (stream, value) tuples; updates EVERY maintained
        synopsis of every kind with EXACTLY ONE jitted, donated-buffer
        dispatch per kind stack — hashed routing probe, routed rows and
        data-source rows are fused into that single program.

        ``stream_ids``/``values`` accept anything ``np.asarray`` takes
        (the JSON/service path hands in plain Python lists). Stream ids
        are arbitrary ints in ``[0, 2**63)``; only unrepresentable ids
        (negative, or uint64 values >= 2**63) are masked out.

        ``items`` optionally decouples each tuple's ITEM identity (what
        the item-hashing sketches — HLL/Bloom/FM/CM/AMS — hash) from its
        ROUTING key; default is the stream id itself, the pre-multidim
        behaviour. The multidim path threads per-record item ids through
        here so a record's 2**d group copies all hash the same identity.

        Returns the batch's monotonic id — the counter that keys this
        batch's continuous responses (``cq/<synopsis>/<id>``). Eager
        engines materialize those responses before returning; pipelined
        engines park them on the bounded queue and return immediately
        (see ``flush``)."""
        with tracing.span("sde.ingest", events=len(stream_ids),
                          multidim=items is not None):
            sid_arr = np.asarray(stream_ids)
            # np.asarray(values, float32) is a NO-OP when the caller
            # already hands in float32 (the hot path) — .astype would
            # always copy
            vals_np = np.asarray(values, np.float32)
            if len(vals_np) != len(sid_arr):
                raise ValueError(
                    f"ingest batch mismatch: {len(sid_arr)} stream_ids vs "
                    f"{len(vals_np)} values — the two must align 1:1")
            t = len(sid_arr)
            if mask is None:
                mask = np.ones(t, bool)
            else:
                mask = np.asarray(mask, bool)
                if len(mask) != t:
                    raise ValueError(
                        f"ingest batch mismatch: {t} stream_ids vs "
                        f"{len(mask)} mask entries — the two must align "
                        "1:1")
            items64 = None
            if items is not None:
                items64 = np.asarray(items, np.int64)
                if len(items64) != t:
                    raise ValueError(
                        f"ingest batch mismatch: {t} stream_ids vs "
                        f"{len(items64)} items — the two must align 1:1")
            sid64 = sid_arr.astype(np.int64)
            mask = mask & (sid64 >= 0)
            self.tuples_ingested += int(mask.sum())
            self.batches_ingested += 1
            # deferred dirty tracking: park this batch's surviving ids;
            # the window resolves to rows in one vectorized lookup per
            # stack (data-source rows absorb every batch, so an
            # all-unroutable batch still has to be parked to mark them)
            self._pending_dirty.append(sid64[mask])
            if len(self._pending_dirty) >= _DIRTY_RESOLVE_EVERY:
                self._resolve_dirty()
            batch_id = self.batches_ingested
            lo, hi = routing.split64(sid64)
            items_np = routing.fold64(sid64 if items64 is None else items64)
            with tracing.span("ingest.h2d", bytes=lo.nbytes + hi.nbytes
                              + items_np.nbytes + vals_np.nbytes
                              + mask.nbytes):
                sid_lo = jnp.asarray(lo)
                sid_hi = jnp.asarray(hi)
                items = jnp.asarray(items_np)
                vals = jnp.asarray(vals_np)
                msk = jnp.asarray(mask)
            for kind, stack in self.stacks.items():
                with tracing.span("ingest.update", kind=type(kind).__name__,
                                  n_probe=stack.n_probe):
                    if stack.is_timeseries:
                        self._ingest_timeseries(stack, sid_lo, sid_hi, vals,
                                                msk)
                    else:
                        self._ingest_stack(stack, sid_lo, sid_hi, items,
                                           vals, msk)
            pending = self._dispatch_continuous(batch_id)
            if pending is not None:
                if self._pipeline is not None:
                    self._pipeline.submit(pending)
                else:
                    self._retire_batch(pending)
            return batch_id

    def flush(self) -> int:
        """Pipeline barrier: materialize every pending continuous batch
        into ``continuous_out`` (oldest first — the order eager emission
        would have produced). Returns the number of batches drained; 0
        on an eager engine or an idle pipeline. This is the ONLY point a
        pipelined blue path syncs device→host; the engine calls it as a
        fence before query reads, build/stop/grow, snapshot and merge."""
        if self._pipeline is None:
            return 0
        return self._pipeline.flush()

    def close(self) -> None:
        """Retire the engine: drain the pipeline, then release every kind
        stack and this engine's share of the compiled-program caches
        (update/step/estimate entries keyed by its kinds). Idempotent;
        the engine stays usable — a later build simply re-allocates."""
        self.flush()
        for kind in list(self.stacks):
            kops.evict_kind_caches(kind)
        self.stacks.clear()
        self.entries.clear()
        self.multidim.clear()
        self.outliers.clear()
        self._invalidate_plans()

    def _invalidate_plans(self) -> None:
        """Drop the cached per-tick dispatch plans (continuous-query
        groups + outlier plans) — called by EVERY lifecycle mutation;
        both replan lazily on the next ingest."""
        self._cq_groups = None
        self._ow_plans = None

    @property
    def pending_batches(self) -> int:
        """Ingest batches whose continuous output is still in flight."""
        return self._pipeline.in_flight if self._pipeline else 0

    def _ingest_stack(self, stack: _KindStack, sid_lo, sid_hi, items,
                      vals, msk):
        klo, khi, trows = stack.device_table()
        stack.state = _update(
            stack.kind, self.backend, stack.sharding, stack.n_probe,
            stack.state, klo, khi, trows, sid_lo, sid_hi, items, vals,
            msk, stack.source_rows_idx())

    def _ingest_timeseries(self, stack: _KindStack, sid_lo, sid_hi,
                           vals, msk):
        """Time-series kinds (DFT): one tick per stream per batch — the
        batch is a StatStream 'basic window'; the last value per stream
        wins (documented resolution reduction). Route probe + step are
        one fused dispatch."""
        klo, khi, trows = stack.device_table()
        stack.state = _step_all(stack.kind, stack.sharding, stack.n_probe,
                                stack.state, klo, khi, trows, sid_lo,
                                sid_hi, vals, msk)

    def _dispatch_continuous(self, batch_id: int
                             ) -> Optional[pipeline.PendingBatch]:
        """Evaluate ALL continuous queries of a kind per ingest batch in a
        single stacked-estimate program — no per-entry row gather. The
        padded rows array, planned (default) args and output sharding are
        byte-identical between lifecycle changes, so they are cached with
        the grouping: per-ingest host work is O(1) plus the dispatch.
        Response ids key on the monotonic batch counter — a batch whose
        tuples are all masked out must still emit FRESH request ids, not
        collide with the previous batch's.

        Returns the batch's un-materialized emissions (device futures) —
        NO host sync happens here; ``_retire_batch`` materializes them
        either immediately (eager) or when the pipeline retires the
        batch. None when no continuous queries OR outlier workflows are
        registered. Outlier ticks dispatch here too (one extra
        ``estimate_all`` per workflow, same maintained state — zero
        extra builds) and score host-side at retirement."""
        if self._cq_groups is None:
            self._cq_groups = self._plan_continuous()
        if self._ow_plans is None:
            self._ow_plans = self._plan_outliers()
        if not self._cq_groups and not self._ow_plans:
            return None
        emissions = []
        for kind, (ids, rows_dev, args, take, out_sh) in \
                self._cq_groups.items():
            out = kops.estimate_all(kind, self.stacks[kind].state,
                                    rows_dev, *args, out_sharding=out_sh)
            emissions.append((ids, take, out))
        extras = []
        for plan in self._ow_plans:
            out = kops.estimate_all(
                plan.kind_key, self.stacks[plan.kind_key].state,
                plan.rows, *plan.args, out_sharding=plan.out_sharding)
            extras.append((plan, out))
        return pipeline.PendingBatch(batch_id, emissions, extras)

    def _retire_batch(self, pending: pipeline.PendingBatch) -> None:
        """Materialize one batch's continuous outputs (the only
        device→host sync of the blue path) into ``continuous_out``,
        then score the batch's outlier ticks (``ow/<wf>/<batch>``)."""
        with tracing.span("sde.retire"):
            for ids, take, out in pending.emissions:
                out = jax.tree.map(np.asarray, out)
                for i, sid in enumerate(ids):
                    self.continuous_out.append(api.Response(
                        request_id=f"cq/{sid}/{pending.batch_id}",
                        synopsis_id=sid, value=take(out, i)))
            for plan, out in pending.extras:
                out = jax.tree.map(np.asarray, out)
                ests = [plan.take(out, i)
                        for i in range(len(plan.assignments) + 1)]
                payload = outliers.evaluate_tick(plan, ests)
                tracing.note_outlier(self.site, len(payload["outliers"]))
                self.continuous_out.append(api.Response(
                    request_id=(f"ow/{plan.workflow.workflow_id}"
                                f"/{pending.batch_id}"),
                    synopsis_id=plan.workflow.workflow_id, value=payload))

    def _plan_continuous(self) -> Dict[Any, Any]:
        by_kind: Dict[Any, List[Any]] = {}
        for sid, e in self.entries.items():
            if e.continuous:
                by_kind.setdefault(e.kind_key, []).append((sid, e.row))
        groups: Dict[Any, Any] = {}
        for kind, members in by_kind.items():
            ids = [sid for sid, _ in members]
            rows_arr = _pad_rows([row for _, row in members])
            args, take, _ = _plan_queries(kind, [{}] * len(rows_arr))
            groups[kind] = (ids, jnp.asarray(rows_arr), args, take,
                            self.stacks[kind].out_sharding())
        return groups

    # ------------------------------------------------------------------
    def _estimate_rows(self, kind, stack: _KindStack, rows: Sequence[int],
                       queries: Sequence[Dict[str, Any]]):
        """Answer ``len(rows)`` queries against one kind stack with ONE
        jitted dispatch. Rows and per-query args are padded to the next
        power of two so repeated batch sizes reuse the cached program."""
        n = len(rows)
        rows_arr = _pad_rows(rows)
        args, take, errors = _plan_queries(
            kind, list(queries) + [{}] * (len(rows_arr) - n))
        out = kops.estimate_all(kind, stack.state, jnp.asarray(rows_arr),
                                *args, out_sharding=stack.out_sharding())
        out = jax.tree.map(np.asarray, out)
        return [take(out, i) for i in range(n)], errors[:n]

    def state_of(self, synopsis_id: str):
        self.flush()   # fence: a state read observes all ingested batches
        e = self.entries[synopsis_id]
        return batched.stacked_row(self.stacks[e.kind_key].state, e.row)

    def memory_bytes(self) -> int:
        return sum(
            x.size * x.dtype.itemsize
            for s in self.stacks.values() for x in jax.tree.leaves(s.state))

    # ------------------------------------------------------------------
    # fault tolerance + elasticity (all state movement rides the
    # migration plane: service/migration.py)
    # ------------------------------------------------------------------
    def migrate_rows(self, kind: Any, mapping: Dict[int, int]) -> int:
        """Live intra-stack migration: relocate row ``src`` to
        ``mapping[src]`` for a whole batch of rows at once — the
        reconciler's mover for rebalancing across the ``synopsis`` mesh
        axis (a row's position picks its device shard). Fences the
        pipeline first (at most the in-flight batches retire), then one
        on-device gather/scatter plus an atomic routing remap; the probe
        layout is untouched, so nothing retraces. Returns rows moved."""
        self.flush()
        stack = self.stacks[kind]
        mapping = {int(s): int(d) for s, d in mapping.items()
                   if int(s) != int(d)}
        if not mapping:
            return 0
        for s in mapping:
            if not stack.used[s]:
                raise ValueError(f"migrate_rows: source row {s} is free")
        migration.move_rows(stack, mapping)
        for e in self.entries.values():
            if e.kind_key == kind and e.row in mapping:
                e.row = mapping[e.row]
        self._invalidate_plans()
        tracing.note_migrated(self.site, len(mapping))
        return len(mapping)

    def resize_stack(self, kind: Any, new_capacity: int) -> int:
        """Grow or shrink a kind stack to ``new_capacity`` rows (the
        reconciler's capacity knob; alloc's doubling keeps working
        independently). Growth pads with the kind's init prototype;
        shrink requires every live row below the cut — ``compact``
        packs them down first. Returns the new capacity."""
        self.flush()
        stack = self.stacks[kind]
        new_capacity = int(new_capacity)
        if new_capacity < 1:
            raise ValueError(f"resize_stack: capacity {new_capacity} < 1")
        if new_capacity == stack.capacity:
            return stack.capacity
        if new_capacity > stack.capacity:
            stack.state = batched.grow(stack.kind, stack.state,
                                       new_capacity)
            stack.used.extend([False] * (new_capacity - stack.capacity))
        else:
            if any(stack.used[new_capacity:]):
                raise ValueError(
                    f"resize_stack: live rows at/above {new_capacity}; "
                    "compact (migrate them down) first")
            stack.state = batched.shrink(stack.state, new_capacity)
            stack.used = stack.used[:new_capacity]
        stack.capacity = new_capacity
        stack._free = None
        stack._source_idx = None
        stack._place()
        self._invalidate_plans()
        return stack.capacity

    def compact(self, kind: Any, min_capacity: int = 64) -> int:
        """Free-list compaction on the migration plane: pack live rows
        to the low end (ONE ``move_rows`` batch) and shrink capacity to
        the smallest power of two holding them — the scale-down half of
        elasticity. Returns the resulting capacity."""
        stack = self.stacks[kind]
        live = [r for r, u in enumerate(stack.used) if u]
        mapping = {r: i for i, r in enumerate(live) if r != i}
        if mapping:
            self.migrate_rows(kind, mapping)
        new_cap = max(min_capacity, _next_pow2(max(len(live), 1)))
        if new_cap < stack.capacity:
            self.resize_stack(kind, new_cap)
        return stack.capacity

    def extract_synopses(self, synopsis_ids: Sequence[str], *,
                         remove: bool = True) -> List[tuple]:
        """Package synopses for a cross-engine move: one
        ``(kind, entry_metas, RowPayload)`` per kind touched — host
        payloads that implant into any engine on any device or site.
        With ``remove=True`` (a true migration) the rows are freed here
        once extracted: state re-initialized, routes dropped, entries
        gone."""
        self.flush()
        by_kind: Dict[Any, List[_Entry]] = {}
        for sid in synopsis_ids:
            e = self.entries[sid]
            by_kind.setdefault(e.kind_key, []).append(e)
        package = []
        for kind, es in by_kind.items():
            payload = migration.extract_rows(
                self.stacks[kind], [e.row for e in es])
            metas = [dict(synopsis_id=e.synopsis_id,
                          stream_id=e.stream_id, federated=e.federated,
                          responsible_site=e.responsible_site,
                          continuous=e.continuous, source_id=e.source_id)
                     for e in es]
            package.append((kind, metas, payload))
        if remove:
            for kind, metas, _ in package:
                rows = [self.entries[m["synopsis_id"]].row for m in metas]
                for m in metas:
                    del self.entries[m["synopsis_id"]]
                self.stacks[kind].free_rows(rows)
                if not any(e.kind_key == kind
                           for e in self.entries.values()):
                    del self.stacks[kind]
                    kops.evict_kind_caches(kind)
            self._invalidate_plans()
        return package

    def implant_synopses(self, package: Sequence[tuple]) -> int:
        """Absorb ``extract_synopses`` output: per kind, allocate rows,
        scatter the payload in (one dispatch per state leaf) and commit
        its routing keys with one table insert. The receiving half of a
        cross-site migration; returns synopses implanted."""
        self.flush()
        # validate BEFORE any allocation: a failed implant must not
        # commit partial state (same contract as _build)
        for _, metas, _ in package:
            for m in metas:
                if m["synopsis_id"] in self.entries:
                    raise ValueError(
                        f"implant_synopses: {m['synopsis_id']!r} already "
                        "lives here (matched ids merge via merge_from)")
        n = 0
        for kind, metas, payload in package:
            if kind not in self.stacks:
                self.stacks[kind] = self._new_stack(
                    kind, max(64, _next_pow2(len(metas))))
            stack = self.stacks[kind]
            rows = [stack.alloc() for _ in metas]
            migration.implant_rows(stack, rows, payload)
            for m, row in zip(metas, rows):
                self.entries[m["synopsis_id"]] = _Entry(
                    kind_key=kind, row=row, **m)
            n += len(metas)
            tracing.note_migrated(self.site, len(metas))
        self._invalidate_plans()
        return n

    def _resolve_dirty(self) -> None:
        """Fold the parked ingest-window stream ids into each stack's
        dirty set: one vectorized table lookup per stack over the
        deduped window. Rows that moved or were freed AFTER a parked
        batch are already dirty (the plane and ``free_rows`` mark both
        ends), so resolving against the CURRENT table is exact — at
        worst a superset, never a miss."""
        if not self._pending_dirty:
            return
        sids = np.unique(np.concatenate(self._pending_dirty))
        self._pending_dirty.clear()
        for stack in self.stacks.values():
            rows = stack.table.lookup_many(sids)
            stack.mark_dirty(rows[rows >= 0])
            # data-source rows absorb EVERY batch of the window
            if stack.source_rows:
                stack.mark_dirty(stack.source_rows)

    def _manifest(self, kinds: List[Any]) -> Dict[str, Any]:
        """The restore-authoritative engine metadata every snapshot
        (full or delta) carries: per-stack lifecycle (used/source/table
        layout), the entry registry and the counters."""
        from repro.core.synopsis import name_of_kind
        return dict(
            site=self.site, backend=self.backend,
            tuples_ingested=self.tuples_ingested,
            batches_ingested=self.batches_ingested,
            wal_seq=self.wal_seq,
            stacks=[dict(kind=name_of_kind(k),
                         params=_json_params(kind_params(k)),
                         capacity=self.stacks[k].capacity,
                         used=self.stacks[k].used,
                         source_rows=self.stacks[k].source_rows,
                         table=dict(size=self.stacks[k].table.size,
                                    count=self.stacks[k].table.count,
                                    max_probe=self.stacks[k].table.max_probe))
                    for k in kinds],
            entries={sid: dict(kind_index=kinds.index(e.kind_key),
                               row=e.row, stream_id=e.stream_id,
                               federated=e.federated,
                               responsible_site=e.responsible_site,
                               continuous=e.continuous,
                               source_id=e.source_id)
                     for sid, e in self.entries.items()},
            multidim={sid: spec.to_json_dict()
                      for sid, spec in self.multidim.items()},
            outlier_workflows=[wf.to_json_dict()
                               for wf in self.outliers.values()],
        )

    def snapshot(self, directory: str, step: int = 0, *,
                 incremental: bool = False, keep: int = 3,
                 async_: bool = False, rebase_every: int = 8) -> str:
        """Engine checkpoint (state + routing + registry). The routing
        table ships as its uint32 (keys_lo, keys_hi) halves plus the
        int32 rows array — byte-identical probe layout on restore,
        independent of the target device count (the mirror is
        replicated).

        ``incremental=True`` ships a **delta**: only the rows dirtied
        since the previous snapshot into this directory (plus the full —
        small — route export and manifest), chained onto the last full
        base via ``base_step``/``delta_chain`` lineage; after
        ``rebase_every`` deltas the chain folds into a fresh full base.
        A delta does NOT fence the pipeline — pulling a dirty slice
        waits only for that stack's dispatched updates, so checkpoint
        cost is O(rows touched), fully overlapped with pipelined ingest.
        ``async_=True`` moves the npz write + fsync to a background
        thread (the save's host copy is still synchronous — state may be
        mutated immediately after return); a concurrent save into the
        same directory waits for the previous one instead of racing its
        GC. Returns ``"full"`` or ``"delta"`` — which mode was taken."""
        from repro.training import checkpoint as ckpt
        self._resolve_dirty()
        if ckpt.take_error(directory) is not None:
            # the previous background save into this directory never
            # landed (its step is not on disk), so the lineage the chain
            # bookkeeping recorded is broken and the dirty rows it
            # cleared were never shipped. Drop the chain and take a
            # fresh FULL base — it re-ships every row, so nothing the
            # failed delta covered is lost.
            self.ckpt_failures += 1
            if self._ckpt_dir == directory:
                self._ckpt_base = None
                self._ckpt_chain = []
        chain_ok = (self._ckpt_dir == directory
                    and self._ckpt_base is not None
                    and len(self._ckpt_chain) < rebase_every)
        if not incremental or not chain_ok:
            return self._snapshot_full(directory, step, keep=keep,
                                       async_=async_)
        kinds = list(self.stacks)
        arrays: Dict[str, Any] = {}
        n_rows = 0
        manifest = self._manifest(kinds)
        manifest.update(snapshot_kind="delta", base_step=self._ckpt_base,
                        delta_chain=self._ckpt_chain + [step])
        for i, k in enumerate(kinds):
            stack = self.stacks[k]
            # rows past a shrink no longer exist (the restore-side
            # capacity adjust drops them the same way)
            rows = np.asarray(
                sorted(r for r in stack.dirty if r < stack.capacity),
                np.int32)
            payload = migration.extract_rows(stack, rows)
            arrays[f"stack{i}"] = dict(
                rows=rows, state=payload.state,
                keys_lo=payload.keys_lo, keys_hi=payload.keys_hi,
                source=payload.source,
                route=migration.export_route(stack.table))
            manifest["stacks"][i]["dirty_rows"] = int(rows.size)
            n_rows += int(rows.size)
        ckpt.save(arrays, directory, step, extra_manifest=manifest,
                  keep=keep, async_=async_)
        self._ckpt_chain.append(step)
        for k in kinds:
            self.stacks[k].dirty.clear()
        tracing.note_checkpoint(self.site, _tree_nbytes(arrays), n_rows)
        return "delta"

    def _snapshot_full(self, directory: str, step: int, *,
                       keep: int = 3, async_: bool = False) -> str:
        from repro.training import checkpoint as ckpt
        # fence: every pending continuous batch retires before a full
        # checkpoint — a restore must not resurrect an engine that still
        # owes responses it can no longer produce (a delta skips this:
        # its bounded pull syncs only the dirty stacks' device work)
        self.flush()
        kinds = list(self.stacks)
        arrays = {}
        for i, k in enumerate(kinds):
            stack = self.stacks[k]
            arrays[f"stack{i}"] = dict(
                state=stack.state,
                route=migration.export_route(stack.table))
        manifest = self._manifest(kinds)
        manifest.update(snapshot_kind="full", base_step=None,
                        delta_chain=[])
        ckpt.save(arrays, directory, step, extra_manifest=manifest,
                  keep=keep, async_=async_)
        self._ckpt_dir = directory
        self._ckpt_base = step
        self._ckpt_chain = []
        n_rows = 0
        for k in kinds:
            self.stacks[k].dirty.clear()
            n_rows += self.stacks[k].capacity
        tracing.note_checkpoint(self.site, _tree_nbytes(arrays), n_rows)
        return "full"

    def wait_for_snapshot(self) -> None:
        """Join the in-flight background (``async_=True``) save, if any —
        the durability barrier a server takes before acking a clean
        shutdown."""
        from repro.training import checkpoint as ckpt
        if self._ckpt_dir is not None:
            ckpt.wait(self._ckpt_dir)

    @classmethod
    def restore(cls, directory: str, step: Optional[int] = None, *,
                mesh: Optional[Mesh] = None,
                rules: Optional[specs.MeshRules] = None,
                pipelined: Optional[bool] = None) -> "SDE":
        """Rebuild a running engine from a snapshot (restart path). Pass
        a ``mesh`` to restore onto a (possibly different) device mesh —
        the elastic repartition path. A delta snapshot restores its full
        base first, then replays every chained delta through the
        migration plane (``implant_rows``), landing byte-identical to a
        full snapshot of the same moment."""
        import repro.core as core_mod
        from repro.training import checkpoint as ckpt
        # structure: rebuild kinds first, then load arrays into shape
        import json as _json
        import os
        ckpt.wait(directory)
        step_ = step if step is not None else ckpt.latest_step(directory)
        with open(os.path.join(directory, f"step-{step_:08d}",
                               "manifest.json")) as f:
            man = _json.load(f)
        if man.get("snapshot_kind") == "delta":
            eng = cls.restore(directory, int(man["base_step"]), mesh=mesh,
                              rules=rules, pipelined=pipelined)
            for s in man["delta_chain"]:
                eng._apply_delta(directory, int(s))
            eng._ckpt_chain = [int(s) for s in man["delta_chain"]]
            return eng
        eng = cls(site=man["site"], backend=man["backend"], mesh=mesh,
                  rules=rules, pipelined=pipelined)
        eng.tuples_ingested = man["tuples_ingested"]
        eng.batches_ingested = man.get("batches_ingested",
                                       man["tuples_ingested"])
        eng.wal_seq = man.get("wal_seq", 0)
        kinds = []
        like = {}
        for i, sk in enumerate(man["stacks"]):
            kind = core_mod.make_kind(sk["kind"], **sk["params"])
            stack = eng._new_stack(kind, sk["capacity"])
            stack.used = list(sk["used"])
            stack.source_rows = list(sk["source_rows"])
            eng.stacks[kind] = stack
            kinds.append(kind)
            if "table" in sk:
                route_like = migration.route_like(sk["table"]["size"])
            else:
                # pre-hashed-routing snapshot: one dense int32 route array
                route_like = np.zeros(_LEGACY_ROUTE_SLOTS, np.int32)
            like[f"stack{i}"] = dict(state=stack.state, route=route_like)
        arrays, _ = ckpt.restore(like, directory, step_)
        for i, kind in enumerate(kinds):
            stack = eng.stacks[kind]
            stack.state = arrays[f"stack{i}"]["state"]
            r = arrays[f"stack{i}"]["route"]
            sk = man["stacks"][i]
            if isinstance(r, dict):
                table = migration.import_route(r, sk["table"])
            else:
                # migrate the legacy dense route into a hash table
                dense = np.asarray(r, np.int32)
                occ = np.nonzero(dense >= 0)[0]
                table = routing.RouteTable()
                table.insert_many(occ.astype(np.int64), dense[occ])
            stack.table = table
            stack.dirty.clear()    # alloc-free rebuild; snapshot-clean
            stack._place()
        for sid, e in man["entries"].items():
            eng.entries[sid] = _Entry(
                synopsis_id=sid, kind_key=kinds[e["kind_index"]],
                row=e["row"], stream_id=e["stream_id"],
                federated=e["federated"],
                responsible_site=e["responsible_site"],
                continuous=e["continuous"], source_id=e["source_id"])
        eng.multidim = {sid: MultidimSpec.from_json_dict(o)
                        for sid, o in man.get("multidim", {}).items()}
        eng.outliers = {
            o["workflow_id"]: outliers.OutlierWorkflow.from_json_dict(o)
            for o in man.get("outlier_workflows", [])}
        eng._ckpt_dir = directory
        eng._ckpt_base = step_
        eng._ckpt_chain = []
        return eng

    def _apply_delta(self, directory: str, step: int) -> None:
        """Replay one delta snapshot onto this engine: adjust each
        stack's capacity, implant the dirty-row payload through the
        migration plane, then adopt the manifest's authoritative
        lifecycle metadata (used/source rows, the EXACT exported routing
        layout — implant's insert side effects are discarded so probe
        chains land where the saver had them) and counters. Stacks
        absent from the delta were stopped before it was taken."""
        import json as _json
        import os
        import repro.core as core_mod
        from repro.training import checkpoint as ckpt
        with open(os.path.join(directory, f"step-{step:08d}",
                               "manifest.json")) as f:
            man = _json.load(f)
        kinds = []
        like = {}
        for i, sk in enumerate(man["stacks"]):
            kind = core_mod.make_kind(sk["kind"], **sk["params"])
            kinds.append(kind)
            # the template only fixes tree structure + leaf dtypes;
            # shapes come from the stored blob
            proto = jax.tree.map(np.asarray, batched.stacked_init(kind, 1))
            like[f"stack{i}"] = dict(
                rows=np.zeros(0, np.int32), state=proto,
                keys_lo=np.zeros(0, np.uint32),
                keys_hi=np.zeros(0, np.uint32),
                source=np.zeros(0, bool),
                route=migration.route_like(sk["table"]["size"]))
        arrays, _ = ckpt.restore(like, directory, step)
        for k in list(self.stacks):
            if k not in kinds:
                del self.stacks[k]
                kops.evict_kind_caches(k)
        for i, (kind, sk) in enumerate(zip(kinds, man["stacks"])):
            cap = int(sk["capacity"])
            stack = self.stacks.get(kind)
            if stack is None:
                stack = self._new_stack(kind, cap)
                self.stacks[kind] = stack
            if cap > stack.capacity:
                stack.state = batched.grow(kind, stack.state, cap)
                stack.used.extend([False] * (cap - stack.capacity))
            elif cap < stack.capacity:
                stack.state = batched.shrink(stack.state, cap)
                stack.used = stack.used[:cap]
            stack.capacity = cap
            a = arrays[f"stack{i}"]
            rows = np.asarray(a["rows"], np.int32)
            migration.implant_rows(stack, rows, migration.RowPayload(
                state=a["state"],
                keys_lo=np.asarray(a["keys_lo"], np.uint32),
                keys_hi=np.asarray(a["keys_hi"], np.uint32),
                source=np.asarray(a["source"], bool)))
            stack.used = list(sk["used"])
            stack.source_rows = list(sk["source_rows"])
            stack.table = migration.import_route(a["route"], sk["table"])
            stack.dirty.clear()
            stack._source_idx = None
            stack._free = None
            stack._dev_table = None
            stack._dev_table_version = -1
            stack._place()
        self.entries = {
            sid: _Entry(synopsis_id=sid, kind_key=kinds[e["kind_index"]],
                        row=e["row"], stream_id=e["stream_id"],
                        federated=e["federated"],
                        responsible_site=e["responsible_site"],
                        continuous=e["continuous"],
                        source_id=e["source_id"])
            for sid, e in man["entries"].items()}
        self.tuples_ingested = man["tuples_ingested"]
        self.batches_ingested = man["batches_ingested"]
        self.wal_seq = man.get("wal_seq", 0)
        self.multidim = {sid: MultidimSpec.from_json_dict(o)
                         for sid, o in man.get("multidim", {}).items()}
        self.outliers = {
            o["workflow_id"]: outliers.OutlierWorkflow.from_json_dict(o)
            for o in man.get("outlier_workflows", [])}
        self._invalidate_plans()

    def merge_from(self, other: "SDE") -> None:
        """Elastic scale-down: absorb another engine's synopses.
        Matching synopsis ids merge (mergeability) — vectorized into ONE
        row-wise merge dispatch per kind; new ids ride the migration
        plane (one extract+implant payload per kind, routing keys
        carried alongside the state — no per-row copies)."""
        # fence BOTH engines: this engine's stacks are about to mutate,
        # and the absorbed engine's pending responses must surface on its
        # own log before its state is read (state_of fences `other` too)
        self.flush()
        other.flush()
        # engines pinned to different federation sites hold committed
        # arrays on different devices, which cannot mix in one dispatch:
        # pull the absorbed engine's contributions through host numpy
        # (uncommitted) so the merge programs run where THIS engine lives
        cross = ((self.device is not None or other.device is not None)
                 and self.device is not other.device)

        def pull(state):
            return jax.tree.map(np.asarray, state) if cross else state

        matches: Dict[Any, tuple[list[int], list[int]]] = {}
        transfers = []
        for sid, oe in other.entries.items():
            if sid in self.entries:
                e = self.entries[sid]
                if oe.kind_key != e.kind_key:
                    raise ValueError(
                        f"synopsis {sid!r} is {type(e.kind_key).__name__} "
                        f"here but {type(oe.kind_key).__name__} on "
                        f"{other.site!r}; cannot merge")
                rows_a, rows_b = matches.setdefault(e.kind_key, ([], []))
                rows_a.append(e.row)
                rows_b.append(oe.row)
            else:
                transfers.append(sid)
        for kind, (rows_a, rows_b) in matches.items():
            stack = self.stacks[kind]
            stack.state = federated.merge_rows(
                kind, stack.state, jnp.asarray(rows_a, jnp.int32),
                pull(other.stacks[kind].state),
                jnp.asarray(rows_b, jnp.int32))
            stack.mark_dirty(rows_a)
        if transfers:
            self.implant_synopses(
                other.extract_synopses(transfers, remove=False))
        self.tuples_ingested += other.tuples_ingested
        self.batches_ingested += other.batches_ingested
        self._invalidate_plans()


def _json_params(params):
    return {k: v for k, v in params.items()
            if isinstance(v, (int, float, str, bool))}


def _tree_nbytes(tree) -> int:
    """Bytes a snapshot's array pytree ships (device or host leaves)."""
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


# ---------------------------------------------------------------------------
# jitted update/step dispatch, cached per (kind, backend, sharding,
# has_sources, n_probe). The cached program is the WHOLE blue path for one
# kind: hashed routing probe, routed update and data-source update fused
# into one dispatch; the state buffer is donated (in-place on device), and
# — on a mesh — pinned to the stack's `synopsis`-axis sharding while the
# routing-table mirror stays replicated.
#
# Kernel choice is the REGISTRY's, not the engine's: under
# ``backend="pallas"`` the kind's declared ``update_kernel`` resolves to a
# Pallas scatter program fed by the probe; kinds without a declaration —
# and the ``backend="xla"`` path — run probe-then-``batched.stacked_update``.
# The caches are bounded KindCaches: engines evict their kinds' entries on
# stop/close (``tracing.KERNEL_CACHE_SIZE`` gauges them). Each program is
# named by its kind (``fused_update_<Kind>``, ``fused_step_<Kind>``), so a
# device trace shows ``jit_fused_update_CountMin`` and not a hash.
# ---------------------------------------------------------------------------

_UPDATE_CACHE = kops.KindCache("update")
_STEP_CACHE = kops.KindCache("step")


def _update_fn(kind, backend: str, sharding, has_sources: bool,
               n_probe: int):
    def build():
        name = f"update:{type(kind).__name__}"
        kernel = (kops.resolve_update_kernel(kind)
                  if backend == "pallas" else None)

        def fused(state, klo, khi, trows, sid_lo, sid_hi, items, vals, msk,
                  *src):
            tracing.TRACE_COUNT[name] += 1     # runs only when jit (re)traces
            src_rows = src[0] if has_sources else None
            syn_idx = kops.route_probe(klo, khi, trows, sid_lo, sid_hi,
                                       n_probe=n_probe)   # [-1 => unrouted]
            if kernel is not None:
                return kernel(state, syn_idx, items, vals, msk, src_rows)
            return batched.stacked_update(kind, state, syn_idx, items,
                                          vals, msk, src_rows)

        kw = dict(donate_argnums=0)
        if sharding is not None:
            kw["out_shardings"] = sharding
        return jax.jit(tracing.named(
            fused, f"fused_update_{type(kind).__name__}"), **kw)

    return _UPDATE_CACHE.get(
        (kind, backend, sharding, has_sources, n_probe), build)


def _update(kind, backend, sharding, n_probe, state, klo, khi, trows,
            sid_lo, sid_hi, items, vals, msk, src_rows=None):
    tracing.DISPATCH_COUNT[f"update:{type(kind).__name__}"] += 1
    fn = _update_fn(kind, backend, sharding, src_rows is not None, n_probe)
    if src_rows is None:
        return fn(state, klo, khi, trows, sid_lo, sid_hi, items, vals, msk)
    return fn(state, klo, khi, trows, sid_lo, sid_hi, items, vals, msk,
              src_rows)


def _step_fn(kind, sharding, n_probe: int):
    def build():
        def fused(state, klo, khi, trows, sid_lo, sid_hi, vals, msk):
            capacity = jax.tree.leaves(state)[0].shape[0]
            syn_idx = kops.route_probe(klo, khi, trows, sid_lo, sid_hi,
                                       n_probe=n_probe)
            routed = msk & (syn_idx >= 0)
            rows = jnp.where(routed, syn_idx, capacity)    # overflow slot
            # LAST routed tuple per row wins, deterministically:
            # scatter-max the tuple order, then gather each winner's value
            # (.at[].set with duplicate indices applies in
            # implementation-defined order)
            order = jnp.arange(sid_lo.shape[0], dtype=jnp.int32)
            winner = jnp.full((capacity + 1,), -1, jnp.int32)
            winner = winner.at[rows].max(jnp.where(routed, order, -1))[:-1]
            hit = winner >= 0
            per_row = jnp.where(hit, vals[jnp.maximum(winner, 0)], 0.0)
            return batched.stacked_step(kind, state, per_row, hit)

        kw = dict(donate_argnums=0)
        if sharding is not None:
            kw["out_shardings"] = sharding
        return jax.jit(tracing.named(
            fused, f"fused_step_{type(kind).__name__}"), **kw)

    return _STEP_CACHE.get((kind, sharding, n_probe), build)


def _step_all(kind, sharding, n_probe, state, klo, khi, trows, sid_lo,
              sid_hi, vals, msk):
    return _step_fn(kind, sharding, n_probe)(state, klo, khi, trows,
                                             sid_lo, sid_hi, vals, msk)


# ---------------------------------------------------------------------------
# red-path query planning: normalize N query dicts for one kind into padded
# batched device args + a per-query result slicer. Kinds taking per-query
# ``items`` (CM, Bloom, Lossy, Sticky) or ``qs`` (GK) get ONE [N, L] arg
# (L = padded max arg length); every other kind is arg-free and returns its
# full estimation pytree per row.
# ---------------------------------------------------------------------------

_ITEM_KINDS = (core.CountMin, core.BloomFilter, core.LossyCounting,
               core.StickySampling)


_next_pow2 = routing.next_pow2


def _pad_rows(rows: Sequence[int]) -> np.ndarray:
    """Pad a row-index batch to the next power of two (padding rows point
    at row 0 — reads are side-effect free — and their results are sliced
    off) so repeated batch sizes reuse one compiled program."""
    padded = np.zeros((_next_pow2(len(rows)),), np.int32)
    padded[:len(rows)] = rows
    return padded


def _check_stream_id(sid: Optional[int]) -> None:
    """Reject stream ids the engine cannot represent. None (data-source
    synopses) is always valid; anything in [0, 2**63) routes (hashed
    routing — no table-size cap)."""
    if sid is not None and not (0 <= int(sid) <= routing.MAX_STREAM_ID):
        raise ValueError(
            f"stream id {sid} outside [0, 2**63); stream ids must be "
            "non-negative 63-bit ints")


def _coerce_items(raw, default) -> np.ndarray:
    """Per-query ``items`` arg -> uint32 identities, folding 64-bit item
    ids the same way ingest folds stream ids (``routing.fold64`` is the
    identity below 2**32, so small-id queries are unchanged)."""
    arr = np.asarray(raw if raw is not None else default, np.int64).ravel()
    if arr.size and (arr.min() < 0):
        raise ValueError(f"negative item id {int(arr.min())}")
    return routing.fold64(arr)


def _plan_queries(kind, queries: Sequence[Dict[str, Any]]):
    """Returns ``(args, take, errors)``: ``args`` are the batched device
    arrays to pass to ``kernels.ops.estimate_all`` after the rows
    argument, ``take(out, i)`` slices query ``i``'s value out of the
    (host-side) batched output — dropping arg padding for argful kinds —
    and ``errors[i]`` is an error string when query ``i``'s args failed
    to coerce (that query gets default args so ONE bad query never
    poisons the rest of the batch)."""
    errors: List[Optional[str]] = [None] * len(queries)
    if isinstance(kind, core.GKQuantiles):
        key, default, np_dtype = "qs", [0.5], np.float32
    elif isinstance(kind, _ITEM_KINDS):
        key, default, np_dtype = "items", [0], np.uint32
    else:
        def take(out, i):
            return jax.tree.map(lambda x: x[i], out)
        return (), take, errors
    lists = []
    for i, q in enumerate(queries):
        try:
            if key == "items":
                lists.append(_coerce_items(q.get(key), default))
            else:
                lists.append(
                    np.asarray(q.get(key, default), np_dtype).ravel())
        except (TypeError, ValueError, OverflowError) as e:
            lists.append(np.asarray(default, np_dtype).ravel())
            errors[i] = f"bad {key!r} in query: {e!r}"
    lens = [len(lst) for lst in lists]
    width = _next_pow2(max(max(lens), 1))
    arg = np.zeros((len(queries), width), np_dtype)
    for i, lst in enumerate(lists):
        arg[i, :len(lst)] = lst

    def take(out, i):
        return out[i, :lens[i]]
    return (jnp.asarray(arg),), take, errors


# ---------------------------------------------------------------------------
# Federation (yellow path): one SDE per geo-dispersed site
# ---------------------------------------------------------------------------
class Federation:
    """The paper's multi-cluster deployment: each site runs its own SDE;
    federated queries are synthesized at the responsible site.

    Pass a ``mesh`` carrying a ``site`` axis (``launch.mesh.
    make_federation_mesh``) — or a production multi-pod mesh, whose
    ``pod`` axis plays the site role over DCN — to run federation as a
    REAL collective: each site's SDE state is pinned to its slice of the
    axis (ingest executes site-locally on that device), and
    ``query_federated`` runs ONE shard_map-ped program in which
    ``federated.merge_over_axis`` merges the partial states via
    psum/pmax/all_gather and the stacked estimate executes on the merged
    result (``kernels.ops.estimate_collective``). Without a mesh the
    legacy single-device path gathers host copies and merges them at the
    responsible site (``kernels.ops.estimate_merged``) — the oracle the
    collective path is tested byte-identical against.

    The bytes a federated answer ships are reported per query (fig 5d):
    ``query_bytes`` (host-merge: every site's state) and
    ``collective_query_bytes`` (the collective's operand bytes)."""

    def __init__(self, sites: List[str], backend: Optional[str] = None,
                 mesh: Optional[Mesh] = None):
        self.sites = list(sites)
        self.mesh = mesh
        self.site_axis: Optional[str] = None
        self.fed_mesh: Optional[Mesh] = None    # 1-D lead-device submesh
        self._site_devices = None
        if mesh is not None and not mesh.empty:
            for ax in ("site", "pod"):
                if ax in mesh.axis_names:
                    self.site_axis = ax
                    break
            if self.site_axis is None:
                raise ValueError(
                    "federation mesh needs a 'site' or 'pod' axis (use "
                    "launch.mesh.make_federation_mesh, or a multi-pod "
                    f"production mesh); got axes {mesh.axis_names}")
            n = mesh.shape[self.site_axis]
            if n != len(self.sites):
                raise ValueError(
                    f"mesh axis {self.site_axis!r} has {n} slices for "
                    f"{len(self.sites)} sites; one slice per site")
            # one lead device per site slice: the DCN endpoint of the site
            idx = mesh.axis_names.index(self.site_axis)
            dev_nd = np.moveaxis(np.asarray(mesh.devices), idx, 0)
            self._site_devices = list(dev_nd.reshape(n, -1)[:, 0])
            self.fed_mesh = Mesh(np.asarray(self._site_devices),
                                 (self.site_axis,))
            self.sdes = {s: SDE(site=s, backend=backend, device=d)
                         for s, d in zip(self.sites, self._site_devices)}
        else:
            self.sdes = {s: SDE(site=s, backend=backend)
                         for s in self.sites}

    def broadcast(self, snippet: str | dict) -> Dict[str, api.Response]:
        return {s: sde.handle(snippet) for s, sde in self.sdes.items()}

    def handle(self, snippet: str | dict):
        """JSON entry point for federated workflows: ``federated_query``
        requests are answered once at the responsible site (collective
        merge on a mesh federation, host merge otherwise), with the
        fig 5d byte metrics in the response's ``params``; every other
        request type — including anything that fails to parse — is
        broadcast to all sites (returns ``{site: Response}``, per-site
        error responses for malformed snippets, so the return shape only
        depends on the request type, never on validity)."""
        try:
            req = api.parse_request(snippet)
        except Exception:  # noqa: BLE001 - malformed: keep broadcast shape
            return self.broadcast(snippet)
        if not isinstance(req, api.FederatedQuery):
            return self.broadcast(snippet)
        try:
            value, info = self._query_federated(
                req.synopsis_id, req.query, req.responsible_site)
            return api.Response(request_id=req.request_id,
                                synopsis_id=req.synopsis_id,
                                value=value, params=info)
        except Exception as e:  # noqa: BLE001 - service returns errors
            return api.Response(request_id=req.request_id,
                                synopsis_id=req.synopsis_id,
                                ok=False, error=repr(e))

    def _partial_states(self, synopsis_id: str):
        """(kind, per-site partial states, full-coverage flag). Reading a
        site's state fences its pipeline first (``state_of`` flushes), so
        a federated answer observes every ingested batch even under
        pipelined blue paths."""
        states, kind = [], None
        for sde in self.sdes.values():
            if synopsis_id in sde.entries:
                kind = sde.entries[synopsis_id].kind_key
                states.append(sde.state_of(synopsis_id))
        if kind is None:
            raise KeyError(synopsis_id)
        return kind, states, len(states) == len(self.sdes)

    def _site_stacked(self, states: List[Any]) -> Any:
        """Stack per-site partial states into ONE [S, ...] pytree sharded
        over the site axis — zero-copy: shard s is site s's already
        device-resident state, so building the collective's operand ships
        nothing before the program runs."""
        sharding = NamedSharding(self.fed_mesh, P(self.site_axis))

        def stack(*leaves):
            shards = [jax.device_put(leaf[None], d)
                      for leaf, d in zip(leaves, self._site_devices)]
            return jax.make_array_from_single_device_arrays(
                (len(leaves),) + leaves[0].shape, sharding, shards)

        return jax.tree.map(stack, *states)

    def query_federated(self, synopsis_id: str, query: Dict[str, Any],
                        responsible: str):
        """Case 2/3: merge every site's partial synopsis and estimate
        once at the responsible site. On a mesh federation the merge is a
        real collective over the site axis fused with the estimate into
        ONE compiled program (``kernels.ops.estimate_collective``); off
        mesh, the partials are gathered and tree-merged on the
        responsible host (``kernels.ops.estimate_merged``). Both paths
        ride the same stacked-estimate entry point as the local red path
        and return byte-identical results."""
        value, _ = self._query_federated(synopsis_id, query, responsible)
        return value

    def _query_federated(self, synopsis_id: str, query: Dict[str, Any],
                         responsible: str):
        kind, states, covered = self._partial_states(synopsis_id)
        args, take, errors = _plan_queries(kind, [query or {}])
        if errors[0] is not None:
            raise ValueError(errors[0])
        host_bytes = sum(
            federated.communication_bytes(kind, s) for s in states)
        info = dict(sites=len(states), responsible_site=responsible,
                    host_merge_bytes=host_bytes)
        if self.fed_mesh is not None and covered:
            # the collective path spans the WHOLE axis: it needs one
            # partial per slice (a federated build is broadcast, so this
            # is the common case)
            out = kops.estimate_collective(
                kind, self._site_stacked(states), *args,
                mesh=self.fed_mesh, axis_name=self.site_axis)
            info.update(path="collective",
                        collective_operand_bytes=federated.
                        collective_operand_bytes(kind, states[0],
                                                 len(states)))
        else:
            if self.fed_mesh is not None:
                # partial coverage: fall back to the host merge — pull
                # the site-committed partials through host numpy so one
                # device can fold them
                states = [jax.tree.map(np.asarray, s) for s in states]
            out = kops.estimate_merged(
                kind, federated.stack_states(states), *args)
            info.update(path="host", collective_operand_bytes=host_bytes)
        return take(jax.tree.map(np.asarray, out), 0), info

    def query_bytes(self, synopsis_id: str) -> int:
        """Host-merge shipped bytes: every site sends its state to the
        responsible site (what the legacy path actually ships, and the
        fig 5d baseline the collective is compared against)."""
        total = 0
        for sde in self.sdes.values():
            if synopsis_id in sde.entries:
                total += federated.communication_bytes(
                    sde.entries[synopsis_id].kind_key,
                    sde.state_of(synopsis_id))
        return total

    def collective_query_bytes(self, synopsis_id: str) -> int:
        """Operand bytes the collective path ships across the site axis
        for one federated estimate (fig 5d): in-network psum/pmax
        reduction makes this independent of the site count for sum/max
        kinds. Never exceeds ``query_bytes``."""
        kind, states, _ = self._partial_states(synopsis_id)
        return federated.collective_operand_bytes(kind, states[0],
                                                  len(states))
