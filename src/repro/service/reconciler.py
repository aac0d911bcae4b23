"""The elasticity loop: declared-vs-actual reconciliation of synopsis
placement (paper Section 7 made live).

``service/balancer.py`` computes the paper's placement plan — HLL counts
the pieces of work, CountMin sizes them, WFD packs them — and this
module ACTS on it. A :class:`Reconciler` periodically:

  1. **samples** the ingest rate and ``balancer.estimate_workload``
     (one batched red-path call — the engine estimates its own load),
     windowed: each pass balances the load since the LAST pass, so a
     drifting skew re-plans on what the stream is doing *now*;
  2. **plans** a WFD target placement over the worker pool — the slices
     of the ``synopsis`` mesh axis (a row's position picks its device
     shard) for a single engine, or the member sites of a
     :class:`~repro.service.engine.Federation`;
  3. **diffs** declared against actual via ``Placement.diff`` (worker
     labels matched to the current placement first, so only genuinely
     misplaced streams move);
  4. **applies** the delta through the migration plane
     (``service/migration.py``): intra-engine, ``SDE.migrate_rows``
     relocates rows between mesh-axis slices (growing stacks first when
     a slice would overflow); across a federation,
     ``extract_synopses``/``implant_synopses`` ship per-stream synopses
     between sites. Every mover fences through the ingest pipeline — at
     most the in-flight batches retire per pass — and ingest resumes
     against the new routing immediately after the atomic remap.

Hysteresis: a pass applies only when it would improve the max/mean load
imbalance by at least ``min_gain`` (reconcilers must damp, not flap).
Skips are cheap — one ``query_many`` dispatch — so tight intervals are
fine. Probes: ``tracing.RECONCILE_COUNT`` / ``MIGRATED_ROWS`` /
``REBALANCE_IMBALANCE``, surfaced by the JSON ``status`` response.

Drive it off the gateway tick (``SynopsisGateway(reconciler=...)``),
the server flag (``sde_server --reconcile-interval``), or directly
(``step()``) in tests and benchmarks.
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional, Sequence

from repro import tracing
from . import balancer
from .engine import SDE, Federation
from .routing import next_pow2


class Reconciler:
    """Close the loop: sample -> plan -> diff -> migrate.

    ``target`` is an :class:`SDE` (workers = slices of the ``synopsis``
    mesh axis; ``n_workers`` defaults to the axis size and may be set
    explicitly for single-device runs) or a :class:`Federation`
    (workers = member sites). ``hll_id``/``cm_id`` name the estimator
    synopses (a data-source HLL and CountMin); until both exist the
    reconciler skips quietly, so it can be wired up before any client
    builds them. ``placed`` names the per-stream build prefixes whose
    rows move (default: every per-stream build discovered in the
    engine). ``interval`` throttles :meth:`maybe_step`."""

    def __init__(self, target, hll_id: str, cm_id: str, *,
                 streams: Optional[Sequence[int]] = None,
                 placed: Optional[Sequence[str]] = None,
                 n_workers: Optional[int] = None,
                 interval: float = 0.0, min_gain: float = 0.05,
                 tag: Optional[str] = None):
        self.target = target
        self.federated = isinstance(target, Federation)
        self.hll_id = hll_id
        self.cm_id = cm_id
        self.streams = list(streams) if streams is not None else None
        self.placed = list(placed) if placed is not None else None
        self.interval = float(interval)
        self.min_gain = float(min_gain)
        if self.federated:
            self.n_workers = len(target.sites)
            self.tag = tag or "federation"
        else:
            if n_workers is None:
                n_workers = self._mesh_workers(target)
            if n_workers is None or n_workers < 1:
                raise ValueError(
                    "n_workers: pass it explicitly, or give the engine a "
                    "mesh with a synopsis axis to infer it from")
            self.n_workers = int(n_workers)
            self.tag = tag or target.site
        self._last_loads: Optional[Dict[int, float]] = None
        self._last_tuples = 0
        self._next_due: Optional[float] = None
        self.last_report: Optional[dict] = None

    @staticmethod
    def _mesh_workers(sde: SDE) -> Optional[int]:
        if sde.mesh is None or sde.mesh.empty:
            return None
        ax = sde.rules.synopsis
        if ax is None or ax not in sde.mesh.axis_names:
            return None
        return int(sde.mesh.shape[ax])

    # ------------------------------------------------------------------
    # scheduling
    # ------------------------------------------------------------------
    def maybe_step(self, now: Optional[float] = None) -> Optional[dict]:
        """Run :meth:`step` when ``interval`` has elapsed since the last
        pass (always, for ``interval<=0``). The gateway tick and the
        server loop call this — reconciling rides existing wakeups, no
        thread of its own."""
        if self.interval > 0:
            now = time.monotonic() if now is None else now
            if self._next_due is not None and now < self._next_due:
                return None
            self._next_due = now + self.interval
        return self.step()

    # ------------------------------------------------------------------
    # one reconcile pass
    # ------------------------------------------------------------------
    def step(self) -> dict:
        """Sample, plan, diff, migrate. Returns a report dict; its
        ``applied`` field tells whether anything moved (skips record
        their reason). Never raises for an incomplete world — missing
        estimators or zero traffic are normal early states."""
        sdes = (list(self.target.sdes.values()) if self.federated
                else [self.target])
        estimators = [s for s in sdes
                      if self.hll_id in s.entries and self.cm_id in s.entries]
        if not estimators:
            return self._skip("estimator synopses not built yet")
        tuples = sum(s.tuples_ingested for s in sdes)
        if tuples == self._last_tuples:
            return self._skip("no traffic since last pass")
        placed = self._discover_placed(sdes)
        if not placed:
            self._last_tuples = tuples
            return self._skip("no per-stream builds to place")
        streams = (self.streams if self.streams is not None
                   else sorted({s for m in placed.values() for s in m}))
        window = self._sample_window(estimators, streams)
        self._last_tuples = tuples
        if sum(window.values()) <= 0.0:
            return self._skip("no load in window")
        current = self._current_placement(placed, window)
        plan = balancer.worst_fit_decreasing(
            streams, [window[s] for s in streams], self.n_workers)
        delta = plan.diff(current)
        before, after = current.imbalance, delta.target.imbalance
        if not delta.moves or before - after < self.min_gain:
            self._note(before)
            self.last_report = dict(
                applied=False, reason="within hysteresis", moves=0,
                migrated_rows=0, imbalance_before=before,
                imbalance_after=before)
            return self.last_report
        moved = self._apply(delta, placed)
        self._note(after)
        self.last_report = dict(
            applied=True, reason="", moves=len(delta.moves),
            migrated_rows=moved, imbalance_before=before,
            imbalance_after=after)
        return self.last_report

    def _note(self, imbalance: float) -> None:
        """Record the pass under this reconciler's tag — and, for a
        federation, under every member site too, so each site's JSON
        ``status`` (which reads the probes by its own site tag) shows
        the control loop's activity."""
        tracing.note_reconcile(self.tag, imbalance)
        if self.federated:
            for site in self.target.sites:
                if site != self.tag:
                    tracing.note_reconcile(site, imbalance)

    def _skip(self, reason: str) -> dict:
        # same schema as the hysteresis/applied paths — consumers index
        # the report without guarding on which path produced it
        self.last_report = dict(applied=False, reason=reason, moves=0,
                                migrated_rows=0, imbalance_before=None,
                                imbalance_after=None)
        return self.last_report

    # ------------------------------------------------------------------
    # sampling
    # ------------------------------------------------------------------
    def _sample_window(self, estimators: List[SDE],
                       streams: List[int]) -> Dict[int, float]:
        """Per-stream load landed since the LAST pass: cumulative CM
        estimates (summed over federation sites — each site's CM saw its
        own traffic) minus the previous sample, clipped at zero (sketch
        noise must not produce negative work)."""
        totals = {s: 0.0 for s in streams}
        for sde in estimators:
            _, loads = balancer.estimate_workload(
                sde, self.hll_id, self.cm_id, streams)
            for s, ld in zip(streams, loads):
                totals[s] += float(ld)
        prev = self._last_loads or {}
        self._last_loads = totals
        return {s: max(totals[s] - prev.get(s, 0.0), 0.0) for s in streams}

    def _discover_placed(self, sdes: List[SDE]
                         ) -> Dict[str, Dict[int, List[SDE]]]:
        """{build prefix: {stream id: engines holding its entry}} for
        every per-stream build (entry id ``<prefix>/<stream>``),
        restricted to ``self.placed`` when given."""
        out: Dict[str, Dict[int, List[SDE]]] = {}
        for sde in sdes:
            for full, e in sde.entries.items():
                if e.stream_id is None or "/" not in full:
                    continue
                prefix, _, tail = full.rpartition("/")
                if tail != str(e.stream_id):
                    continue
                if self.placed is not None and prefix not in self.placed:
                    continue
                out.setdefault(prefix, {}).setdefault(
                    int(e.stream_id), []).append(sde)
        return out

    # ------------------------------------------------------------------
    # actual placement
    # ------------------------------------------------------------------
    def _current_placement(self, placed, window) -> balancer.Placement:
        """Derive the ACTUAL stream->worker map from engine state — row
        positions (mesh mode: worker = the row's slice of the synopsis
        axis) or entry residency (federation mode: worker = site index).
        Declared state is never trusted over what the engine holds."""
        assign: Dict[int, int] = {}
        prefix = sorted(placed)[0]       # placed stacks move in lockstep
        if self.federated:
            order = {s: i for i, s in enumerate(self.target.sites)}
            for stream, holders in placed[prefix].items():
                assign[stream] = order[holders[0].site]
        else:
            sde = self.target
            for stream, _ in placed[prefix].items():
                e = sde.entries[f"{prefix}/{stream}"]
                cap = sde.stacks[e.kind_key].capacity
                assign[stream] = e.row * self.n_workers // cap
        loads = [0.0] * self.n_workers
        for s, w in assign.items():
            loads[w] += window.get(s, 0.0)
        return balancer.Placement(assignments=assign, loads=loads,
                                  n_workers=self.n_workers)

    # ------------------------------------------------------------------
    # applying the delta
    # ------------------------------------------------------------------
    def _apply(self, delta: balancer.PlacementDelta, placed) -> int:
        assign = delta.target.assignments
        if self.federated:
            return self._apply_federated(assign, placed)
        moved = 0
        for prefix, members in placed.items():
            sde = self.target
            kinds = {}
            for stream in members:
                e = sde.entries[f"{prefix}/{stream}"]
                kinds.setdefault(e.kind_key, {})[stream] = e.row
            for kind, rows_by_stream in kinds.items():
                mapping = self._plan_stack(sde, kind, rows_by_stream,
                                           assign)
                moved += sde.migrate_rows(kind, mapping)
        return moved

    def _plan_stack(self, sde: SDE, kind, rows_by_stream: Dict[int, int],
                    assign: Dict[int, int]) -> Dict[int, int]:
        """Row moves realizing ``assign`` on one kind stack: every row
        lands inside its worker's contiguous slice of the row axis.
        Stacks grow (pow2 slices) when a slice would overflow; rows
        already in place stay put, movers fill each slice's lowest free
        rows — deterministic, minimal."""
        stack = sde.stacks[kind]
        W = self.n_workers
        desired: Dict[int, int] = {}
        for stream, row in sorted(rows_by_stream.items()):
            w = assign.get(stream)
            if w is not None:
                desired[row] = w
        for r, used in enumerate(stack.used):
            if used and r not in desired:
                # non-candidate rows (sources, other builds) stay where
                # they are — their current slice is their declared one
                desired[r] = min(r * W // stack.capacity, W - 1)
        demand = [0] * W
        for w in desired.values():
            demand[w] += 1
        # slice size: the smallest pow2 fitting both the demand and the
        # current rows (ceil-div keeps cap >= capacity for ANY W — a
        # doubling search can never make cap divisible by a non-pow2 W)
        ss = next_pow2(max(-(-stack.capacity // W), max(demand), 1))
        cap = W * ss
        if cap != stack.capacity:
            sde.resize_stack(kind, cap)
        stay = {r for r, w in desired.items()
                if w * ss <= r < (w + 1) * ss}
        free = {w: [r for r in range((w + 1) * ss - 1, w * ss - 1, -1)
                    if r not in stay] for w in range(W)}
        mapping: Dict[int, int] = {}
        for row in sorted(desired):
            if row in stay:
                continue
            mapping[row] = free[desired[row]].pop()
        return mapping

    def _apply_federated(self, assign: Dict[int, int], placed) -> int:
        """Ship per-stream synopses between sites: one
        ``extract_synopses`` payload per (source, destination) pair —
        routing keys travel inside the payloads, state through host
        numpy (the DCN of this reproduction)."""
        sites = self.target.sites
        order = {s: i for i, s in enumerate(sites)}
        moves: Dict[tuple, List[str]] = {}
        for prefix, members in placed.items():
            for stream, holders in members.items():
                w = assign.get(stream)
                if w is None:
                    continue
                src = order[holders[0].site]
                if src != w:
                    moves.setdefault((src, w), []).append(
                        f"{prefix}/{stream}")
        moved = 0
        for (src, dst), ids in sorted(moves.items()):
            package = self.target.sdes[sites[src]].extract_synopses(
                ids, remove=True)
            moved += self.target.sdes[sites[dst]].implant_synopses(package)
        return moved
