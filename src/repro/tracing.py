"""The SDE's counters and spans, in one module.

**Counters** are plain ``collections.Counter`` module globals keyed by a
tag (an engine site, a gateway tag, a kind name), bumped where the work
happens and read by tests, by ``SDE._status`` (the operator's JSON
status response) and by benchmarks. They are always on.

**Spans** time the served path from inside: ``with span("sde.ingest",
events=n): ...``. A span records nothing unless a JAX profiler session
is running (``jax.profiler.TraceAnnotation.is_enabled()``), so with the
profiler off its whole cost is that one check. To turn tracing on,
start a session in the serving process: ``jax.profiler.start_trace(dir)``
... ``stop_trace()``, or ``jax.profiler.start_server(port)`` and capture
from a profiler client. While it runs, each span

* opens ``TraceAnnotation("sde/<name>")``, so it lies on the device
  trace's own timeline next to the programs it dispatched, and
* appends ``(name, t0_ns, t1_ns, attrs)`` on ``time.monotonic_ns()`` to
  a bounded in-memory ring (the oldest records fall out first), which
  ``spans()`` reads.

A span never reads a device value and never waits on the device;
attributes are host ints, bools and strings. Spans recorded after the
fact (``record``: a request's wait in the gateway queue) go to the ring
only.

**Clocks.** Profiler event timestamps are relative to the profiler
session's start, not to ``time.monotonic_ns()``, so the two cannot be
compared directly. A reader maps one onto the other through a call
recorded on both clocks: the median difference of its start times over
many calls is the offset.

Program names: every jitted program is named by what it runs and its
kind (``jit_fused_update_CountMin``, ``jit_fused_step_DFT``,
``jit_estimate_all_HyperLogLog``, ...), so the device trace says which
kind's program ran.
"""
from __future__ import annotations

import collections
import time
from typing import Callable, Deque, List, Tuple

from jax.profiler import TraceAnnotation

# ---------------------------------------------------------------------------
# counters
# ---------------------------------------------------------------------------

# Red path and update programs (kernels/ops.py, service/engine.py):
# ``TRACE_COUNT`` increments at trace time only and ``DISPATCH_COUNT`` on
# every call — tests use them to assert "one dispatch, one compiled program
# per kind per query-batch shape". ``KERNEL_CACHE_SIZE`` gauges how many
# compiled entries each KindCache holds (the caches are BOUNDED: engines
# evict their kinds' entries on stop/close instead of growing forever).
TRACE_COUNT: collections.Counter = collections.Counter()
DISPATCH_COUNT: collections.Counter = collections.Counter()
KERNEL_CACHE_SIZE: collections.Counter = collections.Counter()

# Blue-path pipeline probes: the engine's bounded ingest queue
# (service/pipeline.py) reports how many dispatched-but-unmaterialized
# batches are in flight, keyed by engine site. ``PIPELINE_IN_FLIGHT`` is
# the current gauge, ``PIPELINE_MAX_IN_FLIGHT`` the high-water mark —
# tests and benchmarks assert batches actually overlap (depth reached)
# and that fences drain back to zero, without reaching into internals.
PIPELINE_IN_FLIGHT: collections.Counter = collections.Counter()
PIPELINE_MAX_IN_FLIGHT: collections.Counter = collections.Counter()


def note_in_flight(tag: str, depth: int) -> None:
    """Record a pipeline's current in-flight batch depth."""
    PIPELINE_IN_FLIGHT[tag] = depth
    if depth > PIPELINE_MAX_IN_FLIGHT[tag]:
        PIPELINE_MAX_IN_FLIGHT[tag] = depth


# Gateway coalescing probes: the multi-client micro-batcher
# (service/gateway.py) reports how many client requests each fused
# dispatch absorbed. ``GATEWAY_TICKS`` counts micro-batcher ticks per
# gateway tag; ``GATEWAY_COALESCED`` counts client requests folded into
# coalesced engine calls, keyed by request class ("ingest" / "query").
# Tests pair these with TRACE_COUNT/DISPATCH_COUNT to assert that N
# concurrent clients cost ONE blue-path dispatch per kind per tick —
# serving cost scales with tick count, not client count.
GATEWAY_TICKS: collections.Counter = collections.Counter()
GATEWAY_COALESCED: collections.Counter = collections.Counter()


def note_coalesced(klass: str, n: int) -> None:
    """Record ``n`` client requests coalesced into one engine call."""
    GATEWAY_COALESCED[klass] += n


# Elasticity probes: the reconciler (service/reconciler.py) and the
# migration plane (service/migration.py via SDE.migrate_rows /
# implant_synopses) report the control loop's work. ``RECONCILE_COUNT``
# counts reconcile passes per tag (engine site or federation),
# ``MIGRATED_ROWS`` totals rows moved by the plane per engine site, and
# ``REBALANCE_IMBALANCE`` gauges the latest max/mean worker-load ratio a
# reconcile observed (1.0 = perfectly balanced). All three surface
# through ``SDE._status`` into the JSON status response.
RECONCILE_COUNT: collections.Counter = collections.Counter()
MIGRATED_ROWS: collections.Counter = collections.Counter()
REBALANCE_IMBALANCE: collections.Counter = collections.Counter()


def note_migrated(site: str, n_rows: int) -> None:
    """Record ``n_rows`` rows moved by the migration plane."""
    MIGRATED_ROWS[site] += n_rows


def note_reconcile(tag: str, imbalance: float) -> None:
    """Record one reconcile pass and the imbalance it measured."""
    RECONCILE_COUNT[tag] += 1
    REBALANCE_IMBALANCE[tag] = float(imbalance)


# Durability probes: the checkpoint path (SDE.snapshot) and the
# write-ahead ingest log (service/wal.py) report what persistence ships.
# ``CHECKPOINT_BYTES`` accumulates bytes handed to ``checkpoint.save``
# per engine site — benchmarks diff it around one save to compare a
# dirty-row delta against a full snapshot (the fig12 byte gate).
# ``DIRTY_ROWS`` gauges the row count the LATEST snapshot shipped (full:
# every capacity row; delta: only rows touched since the previous
# snapshot). ``WAL_APPENDS`` counts records appended to the write-ahead
# log per tag. All three surface through ``SDE._status``.
CHECKPOINT_BYTES: collections.Counter = collections.Counter()
DIRTY_ROWS: collections.Counter = collections.Counter()
WAL_APPENDS: collections.Counter = collections.Counter()


def note_checkpoint(site: str, n_bytes: int, n_rows: int) -> None:
    """Record one snapshot: bytes shipped (cumulative) and rows shipped
    (latest-snapshot gauge)."""
    CHECKPOINT_BYTES[site] += int(n_bytes)
    DIRTY_ROWS[site] = int(n_rows)


def note_wal_append(tag: str, n: int = 1) -> None:
    """Record ``n`` records appended to a write-ahead log."""
    WAL_APPENDS[tag] += n


# Subpopulation / outlier-workflow probes: ``SUBPOP_COVER_KEYS``
# accumulates how many covering-set group keys ``subpop_query`` merged
# per engine site — paired with DISPATCH_COUNT it pins "K maintained
# groups answered in ONE fused dispatch". ``OUTLIER_EMITS`` counts
# flagged subpopulations the continuous outlier workflow emitted per
# site; tests also use it (with the entry counters) to pin that the
# workflow rides the ALREADY-maintained synopses — zero extra builds.
SUBPOP_COVER_KEYS: collections.Counter = collections.Counter()
OUTLIER_EMITS: collections.Counter = collections.Counter()


def note_subpop(site: str, n_keys: int) -> None:
    """Record one subpop query's covering-set size."""
    SUBPOP_COVER_KEYS[site] += int(n_keys)


def note_outlier(site: str, n_flagged: int) -> None:
    """Record flagged subpopulations emitted by an outlier tick."""
    OUTLIER_EMITS[site] += int(n_flagged)


def named(fn: Callable, name: str) -> Callable:
    """``fn`` renamed, so ``jax.jit(fn)`` names its program
    ``jit_<name>`` in compiled modules and device traces."""
    fn.__name__ = fn.__qualname__ = name
    return fn


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------

Span = Tuple[str, int, int, dict]

RING_SIZE = 1 << 20
_RING: Deque[Span] = collections.deque(maxlen=RING_SIZE)

enabled = TraceAnnotation.is_enabled
"""True while a JAX profiler session runs: spans record only then."""


class _Span:
    __slots__ = ("name", "attrs", "_t0", "_annotation")

    def __init__(self, name: str, attrs: dict):
        self.name, self.attrs = name, attrs

    def __enter__(self) -> "_Span":
        self._annotation = TraceAnnotation("sde/" + self.name)
        self._annotation.__enter__()
        self._t0 = time.monotonic_ns()
        return self

    def __exit__(self, *exc) -> None:
        t1 = time.monotonic_ns()
        self._annotation.__exit__(*exc)
        _RING.append((self.name, self._t0, t1, self.attrs))

    def note(self, **attrs) -> None:
        """Attributes known only once the span's work has run."""
        self.attrs.update(attrs)


class _Off:
    """What ``span`` returns while tracing is off: does nothing."""
    __slots__ = ()

    def __enter__(self) -> "_Off":
        return self

    def __exit__(self, *exc) -> None:
        return None

    def note(self, **attrs) -> None:
        return None


_OFF = _Off()


def span(name: str, **attrs):
    """A context manager timing the work in its body as span ``name``
    (see the module docstring); ``.note(**attrs)`` on what ``with``
    binds adds attributes before it closes."""
    if not enabled():
        return _OFF
    return _Span(name, attrs)


def record(name: str, t0_ns: int, t1_ns: int, **attrs) -> None:
    """Record a span whose interval is already known (on
    ``time.monotonic_ns()``), if tracing is on. It goes to the ring only:
    the profiler cannot take an annotation after the fact."""
    if enabled():
        _RING.append((name, t0_ns, t1_ns, attrs))


def spans(t0_s: float, t1_s: float, *names: str) -> List[Span]:
    """Recorded spans named ``names`` (every name when none is given)
    that start inside ``[t0_s, t1_s)``, in seconds of
    ``time.monotonic()``."""
    lo, hi = int(t0_s * 1e9), int(t1_s * 1e9)
    return [s for s in tuple(_RING)
            if lo <= s[1] < hi and (not names or s[0] in names)]
