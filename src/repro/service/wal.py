"""Write-ahead ingest log + crash recovery for the serving layer.

The engine's snapshots (``SDE.snapshot``, incremental or full) bound
recovery work to the last checkpoint; this module covers the tail —
everything acked AFTER it. The serving front ends
(``launch/sde_server.py`` JSON-lines mode and the
``SynopsisGateway`` micro-batcher) append lifecycle requests BEFORE
applying them (replay re-executes verbatim; a request that failed live
fails identically on replay) and ingest batches AFTER a successful
apply, keyed by the batch id the engine actually assigned — an ingest
that fails live never reaches the log, so replay can never consume a
batch id an acked batch owns. Either way the record is fsynced before
the ack leaves the process, so the durability contract is::

    acked  =>  in the WAL  =>  recoverable

Recovery (:func:`recover`) = restore the latest snapshot + replay the
WAL tail through the NORMAL ingest/request path. Exactly-once holds by
two independent watermarks, both persisted in every snapshot manifest:

  * ``seq``   — every WAL record carries a monotonic sequence number;
    replay skips records with ``seq <= sde.wal_seq`` (also what makes
    replay idempotent under duplicate or overlapping tails).
  * ``batch`` — ingest records additionally carry the monotonic engine
    batch id they became; replay skips batches
    ``<= sde.batches_ingested`` (belt-and-braces for snapshots taken by
    other writers into the same lineage).

Records are JSON lines (one fsync per serving tick, not per record); a
torn FINAL line — the signature of a crash mid-append — is tolerated
and dropped, torn interior lines are corruption and raise.
"""
from __future__ import annotations

import json
import os
import sys
from typing import Any, Dict, Iterator, Optional

import numpy as np

from repro import tracing
from .api import MUTATING_REQUESTS  # noqa: F401 — the replay set lives
#     with the request schemata now; re-exported here for compatibility
from .engine import SDE


class WriteAheadLog:
    """Append-only JSON-lines log of state-mutating engine calls.

    One instance per serving process; ``append_*`` buffers, ``sync``
    makes everything appended so far durable (flush + fsync — the
    serving loop calls it once per tick, before acks go out). Reopening
    an existing log resumes its sequence numbering, so a recovered
    server appends where the crashed one stopped."""

    def __init__(self, path: str, tag: str = "wal"):
        self.path = path
        self.tag = tag
        self.seq = 0
        # highest seq dropped by a truncation (a "trunc" marker record
        # persists it, so numbering never restarts inside a lineage)
        self._trunc_seq = 0
        if os.path.exists(path):
            for rec in read_records(path):
                self.seq = max(self.seq, int(rec.get("seq", 0)))
                if rec.get("kind") == "trunc":
                    self._trunc_seq = max(self._trunc_seq,
                                          int(rec.get("seq", 0)))
        parent = os.path.dirname(os.path.abspath(path))
        os.makedirs(parent, exist_ok=True)
        self._fh = open(path, "a", encoding="utf-8")
        self._dirty = False

    def append_ingest(self, batch: int, stream_ids, values,
                      mask=None) -> int:
        """Log one ingest batch. The serving front ends call this right
        AFTER a successful ``sde.ingest`` with the batch id the engine
        actually assigned (the second idempotence watermark), and fsync
        before the ack leaves — so the WAL never holds a record for an
        ingest that failed live, and batch ids in the log are exactly
        the acked ones."""
        with tracing.span("wal.encode", events=len(stream_ids)):
            return self._append(dict(
                kind="ingest", batch=int(batch),
                sids=np.asarray(stream_ids, np.int64).ravel().tolist(),
                vals=np.asarray(values, np.float32).ravel().tolist(),
                mask=(None if mask is None
                      else np.asarray(mask, bool).ravel().tolist())))

    def append_ingest_multidim(self, batch: int,
                               req: Dict[str, Any]) -> int:
        """Log one multidim ingest batch (the attribute-tagged form of
        ``append_ingest``): the raw request replays through the engine's
        normal ``ingest_multidim`` path, which re-derives the expanded
        group keys deterministically. Logged POST-apply with the engine
        batch id, same contract as ``append_ingest``."""
        return self._append(dict(kind="ingest_md", batch=int(batch),
                                 req=dict(req)))

    def append_request(self, req: Dict[str, Any]) -> int:
        """Log one lifecycle request (``api.MUTATING_REQUESTS``), already
        namespaced exactly as the engine will see it."""
        return self._append(dict(kind="req", req=dict(req)))

    def _append(self, rec: Dict[str, Any]) -> int:
        self.seq += 1
        rec["seq"] = self.seq
        self._fh.write(json.dumps(rec) + "\n")
        self._dirty = True
        tracing.note_wal_append(self.tag)
        return self.seq

    def sync(self) -> None:
        """Make every appended record durable (flush + fsync). The
        serving loop's durable-before-ack point."""
        if not self._dirty:
            return
        with tracing.span("wal.sync"):
            self._fh.flush()
            os.fsync(self._fh.fileno())
        self._dirty = False

    def truncate_through(self, seq: int) -> None:
        """Drop every record with ``seq <=`` the watermark — they are
        folded into a snapshot that durably landed, so replay will never
        need them. Atomic (tmp + fsync + rename); a ``trunc`` marker
        record persists the watermark so a reopened log resumes its
        sequence numbering past the dropped records instead of reusing
        them (which would make replay skip genuinely new appends)."""
        seq = int(seq)
        if seq <= self._trunc_seq:
            return                       # nothing new to drop
        self.sync()
        tmp = self.path + ".tmp"
        with open(tmp, "w", encoding="utf-8") as f:
            f.write(json.dumps(dict(kind="trunc", seq=seq)) + "\n")
            # stream old -> new: never materializes the kept tail
            for r in read_records(self.path):
                if int(r.get("seq", 0)) > seq:
                    f.write(json.dumps(r) + "\n")
            f.flush()
            os.fsync(f.fileno())
        self._fh.close()
        os.replace(tmp, self.path)
        self._fh = open(self.path, "a", encoding="utf-8")
        self._trunc_seq = seq
        self.seq = max(self.seq, seq)

    def close(self) -> None:
        if self._fh.closed:
            return
        self.sync()
        self._fh.close()


def read_records(path: str) -> Iterator[Dict[str, Any]]:
    """Parse a WAL file, STREAMING: yields records one line at a time so
    recovery of a long un-truncated tail is O(1) in memory, never
    O(log size). A torn FINAL record (crash mid-append, fsync never
    completed — the ack for it never left either) is dropped; a torn
    interior record means real corruption and raises — detection is
    deferred one record (an unparseable line is held until the NEXT
    non-empty line proves it interior), so the error surfaces during
    iteration, not at generator creation."""
    with open(path, encoding="utf-8") as f:
        bad_line = None
        for lineno, raw in enumerate(f, 1):
            line = raw.strip()
            if not line:
                continue
            if bad_line is not None:
                raise ValueError(
                    f"corrupt WAL record at {path}:{bad_line} (not the "
                    "final line — this is not a torn append)")
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                bad_line = lineno        # torn tail unless more follows
                continue
            yield rec


def replay(sde: SDE, path: str) -> int:
    """Replay a WAL tail through the engine's normal paths. Skips
    records already folded into ``sde`` (``seq <= sde.wal_seq``; ingest
    batches ``<= sde.batches_ingested``), so replay is idempotent under
    duplicate/overlapping tails and exactly-once on top of any snapshot
    of the same lineage. Reads stream (``read_records`` is a generator),
    so replaying an arbitrarily long tail holds one record at a time.
    Returns the number of records applied."""
    if not os.path.exists(path):
        return 0
    n = 0
    for rec in read_records(path):
        seq = int(rec.get("seq", 0))
        if seq <= sde.wal_seq:
            continue
        kind = rec.get("kind")
        if kind == "ingest":
            batch = rec.get("batch")
            if batch is not None and int(batch) <= sde.batches_ingested:
                sde.wal_seq = seq        # snapshot already folded it
                continue
            try:
                sde.ingest(np.asarray(rec["sids"], np.int64),
                           np.asarray(rec["vals"], np.float32),
                           None if rec.get("mask") is None
                           else np.asarray(rec["mask"], bool))
            except Exception as e:  # noqa: BLE001 - poisoned record
                # ingest records are logged post-apply, so a record that
                # fails here came from a pre-fix log (logged before
                # validation) — the live call failed too, no batch id
                # was consumed, and recovery must not die on it
                print(f"[wal] skipping unreplayable ingest record "
                      f"seq={seq}: {e!r}", file=sys.stderr)
                sde.wal_seq = seq
                continue
        elif kind == "ingest_md":
            batch = rec.get("batch")
            if batch is not None and int(batch) <= sde.batches_ingested:
                sde.wal_seq = seq        # snapshot already folded it
                continue
            sde.handle(rec["req"])       # normal ingest_multidim path
        elif kind == "req":
            # lifecycle requests re-execute verbatim; a request that
            # failed live fails identically here (no state change)
            sde.handle(rec["req"])
        else:
            # e.g. the "trunc" watermark marker: carries no state —
            # just advance the seq cursor past it
            sde.wal_seq = seq
            continue
        sde.wal_seq = seq
        n += 1
    return n


class Checkpointer:
    """Periodic off-hot-path snapshots, paced by ingest batches: call
    ``maybe_snapshot()`` once per serving tick and every ``interval``
    ingested batches it takes one ``SDE.snapshot`` — incremental (a
    dirty-row delta chained on the last full base, rebasing every
    ``rebase_every`` deltas) and asynchronous (background npz write) by
    default. Steps continue from whatever the directory already holds,
    so a recovered server extends the existing lineage."""

    def __init__(self, sde: SDE, directory: str, *, interval: int = 8,
                 keep: int = 3, rebase_every: int = 8,
                 incremental: bool = True, async_: bool = True,
                 wal: Optional[WriteAheadLog] = None):
        from repro.training import checkpoint as ckpt
        self.sde = sde
        self.directory = directory
        self.interval = max(1, int(interval))
        self.keep = keep
        self.rebase_every = rebase_every
        self.incremental = incremental
        self.async_ = async_
        # when given the serving WAL, records folded into a snapshot
        # that durably landed are truncated away, bounding log growth
        # and restart re-parse time
        self.wal = wal
        last = ckpt.latest_step(directory)
        self.next_step = 0 if last is None else last + 1
        self._last_batches = sde.batches_ingested
        # wal_seq covered by the previous snapshot REQUEST — promoted to
        # a truncation watermark only once that save is known durable
        self._last_snap_seq: Optional[int] = None
        self.snapshots = 0

    def maybe_snapshot(self) -> Optional[str]:
        """Snapshot iff ``interval`` batches landed since the last one.
        Returns the mode taken ("full"/"delta") or None."""
        if self.sde.batches_ingested - self._last_batches < self.interval:
            return None
        return self.snapshot()

    def snapshot(self) -> str:
        with tracing.span("checkpoint.snapshot"):
            failures = self.sde.ckpt_failures
            seq_now = self.sde.wal_seq
            mode = self.sde.snapshot(
                self.directory, self.next_step,
                incremental=self.incremental, keep=self.keep,
                async_=self.async_, rebase_every=self.rebase_every)
            # Truncate only through a snapshot KNOWN durable. Sync saves
            # land before SDE.snapshot returns (a failure raises above);
            # async saves lag one snapshot — SDE.snapshot joined the
            # previous background write and bumped ckpt_failures if it
            # never landed, so an unchanged counter certifies it.
            durable_seq = None
            if not self.async_:
                durable_seq = seq_now
            elif self._last_snap_seq and self.sde.ckpt_failures == failures:
                durable_seq = self._last_snap_seq
            if self.wal is not None and durable_seq:
                try:
                    self.wal.truncate_through(durable_seq)
                except OSError:
                    pass                     # rotation is best-effort only
            self._last_snap_seq = seq_now
            self.next_step += 1
            self._last_batches = self.sde.batches_ingested
            self.snapshots += 1
            return mode


def recover(checkpoint_dir: Optional[str], wal_path: Optional[str], *,
            pipelined: Optional[bool] = None, mesh=None,
            rules=None) -> SDE:
    """The restart path: restore the latest snapshot (a fresh engine
    when there is none) and replay the WAL tail. The result is
    byte-identical to the pre-crash engine's acked state."""
    from repro.training import checkpoint as ckpt
    if (checkpoint_dir is not None
            and ckpt.latest_step(checkpoint_dir) is not None):
        sde = SDE.restore(checkpoint_dir, mesh=mesh, rules=rules,
                          pipelined=pipelined)
    else:
        sde = SDE(mesh=mesh, rules=rules, pipelined=pipelined)
    if wal_path is not None:
        replay(sde, wal_path)
    return sde
