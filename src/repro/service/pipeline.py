"""Pipelined blue path — the bounded async ingest queue.

``SDE.ingest`` dispatches one fused update program per kind and one
stacked-estimate program per kind with continuous queries; JAX async
dispatch lets all of them run un-awaited. What used to serialize the
caller was ``_emit_continuous`` materializing the estimate outputs to
host (``np.asarray``) before ``ingest`` returned — a forced device→host
sync per batch, so host-side prep for batch N+1 (np normalization,
``split64``/``fold64``, mask work) could never overlap batch N's device
work.

This module decouples emission from ingestion:

  * ``PendingBatch`` — one ingest batch's un-materialized continuous
    outputs: per-kind device futures plus the monotonic batch id that
    keys their response ids.
  * ``IngestPipeline`` — a bounded (default depth-2, double-buffered)
    queue of pending batches. Submitting batch N+1 while N is in flight
    is free; submitting past the depth retires the oldest batch
    (materializes its futures into the engine's continuous output,
    oldest first, so response order is identical to eager execution).
    ``flush()`` is the explicit barrier: it drains everything, and the
    engine fences (flushes) before any operation that reads or mutates
    engine state — ``query_many``, stop/grow/build, snapshot, merge.
  * ``BoundedResponseLog`` — the ``continuous_out`` sink: a deque with a
    configurable cap and a dropped-count stat, so unread continuous
    responses cannot grow without bound.

The pipeline never re-orders or re-dispatches device work: programs are
dispatched in ingest order by the engine; this queue only defers the
host-side materialization. Retirement depth is observable through
``tracing.PIPELINE_IN_FLIGHT`` / ``PIPELINE_MAX_IN_FLIGHT``.
"""
from __future__ import annotations

import collections
import dataclasses
from typing import Any, Callable, List, Optional, Tuple

from repro import tracing


class BoundedResponseLog(collections.deque):
    """A bounded response sink: the engine's global ``continuous_out``
    and — through the gateway — one log per connected client. A deque
    bounded at ``cap`` responses; when full, appending evicts the oldest
    response and counts it in ``dropped`` (the continuous stream keeps
    flowing; a consumer that falls behind loses the oldest results,
    never the newest)."""

    def __init__(self, cap: Optional[int] = 65536):
        super().__init__(maxlen=cap if cap and cap > 0 else None)
        self.dropped = 0

    def append(self, response) -> None:
        if self.maxlen is not None and len(self) == self.maxlen:
            self.dropped += 1        # deque(maxlen) evicts from the left
        super().append(response)

    def drain(self) -> List[Any]:
        """Pop EVERY unread response, oldest first — one call per
        consumer wake-up, so a server writes a whole backlog with one
        syscall instead of one write per response."""
        out = []
        while self:
            out.append(self.popleft())
        return out


@dataclasses.dataclass
class PendingBatch:
    """One ingest batch's deferred continuous emission.

    ``emissions`` holds ``(ids, take, out)`` per kind: the continuous
    synopsis ids, the per-query result slicer from ``_plan_queries``,
    and the (device-future) ``estimate_all`` output. ``extras`` holds
    ``(plan, out)`` pairs for the continuous OUTLIER workflows
    (service/outliers.py): each plan finishes host-side at retirement —
    scoring the deferred estimates and emitting flagged groups — so
    outlier ticks pipeline exactly like continuous queries. Nothing here
    pins the engine's mutable state — lifecycle changes after dispatch
    cannot corrupt a pending batch, only delay its materialization.
    """
    batch_id: int
    emissions: List[Tuple[List[str], Callable[..., Any], Any]]
    extras: List[Tuple[Any, Any]] = dataclasses.field(default_factory=list)


class IngestPipeline:
    """Bounded queue of in-flight ingest batches (double-buffered at the
    default ``depth=2``): the engine submits each batch's pending
    emission right after dispatching its update programs and returns to
    the caller without waiting. The queue retires (materializes) the
    oldest batch only when a new submission would exceed the depth, or
    on an explicit ``flush()``.
    """

    def __init__(self, retire: Callable[[PendingBatch], None],
                 depth: int = 2, tag: str = ""):
        if depth < 1:
            raise ValueError(f"pipeline depth must be >= 1, got {depth}")
        self.depth = depth
        self.tag = tag
        self._retire = retire
        self._queue: collections.deque[PendingBatch] = collections.deque()
        self.batches_retired = 0

    @property
    def in_flight(self) -> int:
        return len(self._queue)

    @property
    def in_flight_ids(self) -> List[int]:
        """Batch ids still pending materialization, oldest first — the
        durability layer reads this to know which acked batches a crash
        right now would owe to WAL replay."""
        return [p.batch_id for p in self._queue]

    def submit(self, pending: PendingBatch) -> None:
        """Enqueue one batch's deferred emission; retires the oldest
        batch(es) beyond the depth bound so at most ``depth`` batches
        are ever pending materialization."""
        self._queue.append(pending)
        while len(self._queue) > self.depth:
            self._retire_oldest()
        tracing.note_in_flight(self.tag, len(self._queue))

    def flush(self) -> int:
        """Explicit barrier: materialize EVERY pending batch, oldest
        first. Returns the number of batches drained."""
        n = 0
        while self._queue:
            self._retire_oldest()
            n += 1
        if n:
            tracing.note_in_flight(self.tag, 0)
        return n

    def _retire_oldest(self) -> None:
        self._retire(self._queue.popleft())
        self.batches_retired += 1
