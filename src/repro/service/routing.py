"""Hashed stream routing: arbitrary 63-bit stream ids -> kind-stack rows.

Replaces the fixed ``route[_MAX_STREAMS]`` dense table that silently
dropped every tuple with a stream id >= 2**16 (and rejected such builds).
A :class:`RouteTable` is an open-addressing hash table with linear
probing: pow2-sized ``keys``/``rows`` arrays, tombstone-free inserts,
compaction by a full rebuild on stop, and grow-and-rehash past
~``_MAX_LOAD`` load factor. Stream ids are arbitrary ints in
``[0, 2**63)`` — nothing is ever clamped, rejected or dropped for being
"too big".

Split of responsibilities:

  * HOST (this module, numpy): the authoritative table. Inserts/removes
    happen on the rare lifecycle path (build/stop/merge), so they are
    plain vectorized numpy — no device round trip per synopsis.
  * DEVICE (``kernels.ops.route_probe``): the per-batch lookup, a
    fixed-bound linear-probe gather chain that runs *inside* the fused
    blue-path programs (one dispatch per kind per batch, PR 1 contract).
    The device mirror stores keys split into uint32 lo/hi halves so the
    probe needs no 64-bit lanes (``jax_enable_x64`` stays off); it is
    replicated over multi-device meshes exactly like the old dense route.

**Layout (Robin Hood).** Along every cluster keys sit in order of home
slot (``slot_hash``), ties broken by key: each key's displacement from
its home is at most one more than its predecessor's. The layout thus
depends only on the set of keys, never on the order they came in, and it
spreads displacement evenly: the ids 0..4,999 in 8,192 slots probe at
most 8 slots, where first-come placement of the same ids probes 30. Lookup,
on the host or the device, is plain linear probing to the key or to an
empty slot, which is right for any layout with no empty slot inside a
key's chain; tables written with first-come placement (older
snapshots) are read as they are.

The probe loop's trip count must be static under jit, so the table
keeps ``max_probe``, its longest displacement + 1, and grows whenever
that would pass :data:`PROBE_CAP` — this bounds the fused gather chain
(and jit retraces: the engine rounds ``max_probe`` up to a power of two)
independent of table occupancy. 2^20 random ids fit 2^21 slots with
probes of at most 11; first-come placement passed the cap there and
doubled the table.
"""
from __future__ import annotations

from typing import Tuple

import numpy as np

_MAX_LOAD = 0.7          # grow-and-rehash past this occupancy
PROBE_CAP = 32           # grow instead of probing longer than this
_MIN_SIZE = 64           # smallest table (pow2)
_GOLDEN = np.uint32(0x9E3779B9)

# host sentinel for an empty slot; its uint32 halves are both 0xFFFFFFFF,
# unreachable by valid ids (hi <= 0x7FFFFFFF for ids < 2**63) — the
# device probe detects empty slots from the hi half alone.
EMPTY = np.int64(-1)

MAX_STREAM_ID = (1 << 63) - 1


def _mix32(x: np.ndarray) -> np.ndarray:
    """murmur3 fmix32 on uint32 arrays — bit-identical to
    ``core.hashing.mix32`` (the device side of the probe)."""
    x = np.atleast_1d(np.asarray(x)).astype(np.uint32)  # uint32 wraps; the
    with np.errstate(over="ignore"):                    # scalar path warns
        x ^= x >> np.uint32(16)
        x = (x * np.uint32(0x85EBCA6B)).astype(np.uint32)
        x ^= x >> np.uint32(13)
        x = (x * np.uint32(0xC2B2AE35)).astype(np.uint32)
        x ^= x >> np.uint32(16)
    return x


def split64(sids: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """int64 stream ids -> (lo, hi) uint32 halves."""
    s = np.asarray(sids, np.int64)
    lo = (s & np.int64(0xFFFFFFFF)).astype(np.uint32)
    hi = ((s >> np.int64(32)) & np.int64(0xFFFFFFFF)).astype(np.uint32)
    return lo, hi


def fold64(sids: np.ndarray) -> np.ndarray:
    """Fold a 64-bit stream id into the uint32 item identity the sketches
    hash. Identity for ids < 2**32 (``hi == 0``), so sketch contents are
    bit-identical to the pre-hashed-routing engine on small id spaces."""
    lo, hi = split64(sids)
    return (lo ^ (_mix32(hi) * _GOLDEN)).astype(np.uint32)


def slot_hash(lo: np.ndarray, hi: np.ndarray, size: int) -> np.ndarray:
    """Initial probe slot for keys given as uint32 halves. Must stay in
    lockstep with the jnp twin inside ``kernels.ops.route_probe``."""
    h = _mix32(lo.astype(np.uint32) ^ _mix32(hi.astype(np.uint32)
                                             ^ _GOLDEN))
    return (h & np.uint32(size - 1)).astype(np.int64)


class RouteTable:
    """Host-side open-addressing stream->row map: linear probing over a
    Robin Hood layout (see the module docstring)."""

    def __init__(self, size: int = _MIN_SIZE):
        size = max(_MIN_SIZE, next_pow2(size))
        self.keys = np.full((size,), EMPTY, np.int64)
        self.rows = np.full((size,), -1, np.int32)
        self.count = 0
        self.max_probe = 1      # longest displacement + 1
        self.version = 0        # bumped on any mutation (device cache key)

    # -- read ----------------------------------------------------------
    @property
    def size(self) -> int:
        return int(self.keys.shape[0])

    @property
    def load(self) -> float:
        return self.count / self.size

    def lookup(self, sid: int) -> int:
        """Row for ``sid`` or -1 (host-side twin of the device probe)."""
        sid = int(sid)
        slot = int(slot_hash(*split64(np.int64(sid)), self.size).ravel()[0])
        mask = self.size - 1
        for _ in range(self.max_probe):
            k = self.keys[slot]
            if k == sid:
                return int(self.rows[slot])
            if k == EMPTY:
                return -1
            slot = (slot + 1) & mask
        return -1

    def lookup_many(self, sids: np.ndarray) -> np.ndarray:
        """Vectorized ``lookup``: int32 rows, -1 where a key is absent.
        The dirty-tracking resolver runs a whole ingest window of stream
        ids through this in a handful of numpy passes."""
        sids = np.asarray(sids, np.int64).ravel()
        out = np.full(sids.shape, -1, np.int32)
        slot = self._slots(sids)
        hit = slot >= 0
        out[hit] = self.rows[slot[hit]]
        return out

    def items(self) -> Tuple[np.ndarray, np.ndarray]:
        """(stream_ids, rows) of every occupied slot."""
        occ = self.keys != EMPTY
        return self.keys[occ].copy(), self.rows[occ].copy()

    # -- write ---------------------------------------------------------
    def insert(self, sid: int, row: int) -> None:
        self.insert_many([sid], [row])

    def insert_many(self, sids: np.ndarray, rows: np.ndarray) -> None:
        """Insert or re-route keys; the layout stays the Robin Hood one.
        Re-inserting an existing key updates its row in place. New keys
        re-lay out only the clusters they land in, unless the table grows
        (then every key is placed afresh): a 1M-stream build is a handful
        of numpy passes, not 1M Python probes."""
        try:
            sids = np.asarray(sids, np.int64)
        except OverflowError as e:
            raise ValueError(
                "stream id outside [0, 2**63) — ids must be non-negative "
                "63-bit ints") from e
        rows = np.asarray(rows, np.int32)
        if sids.size == 0:
            return
        if sids.size > 1:
            # intra-batch duplicates: LAST occurrence wins, matching the
            # sequential-insert semantics (a tie-losing duplicate must
            # not land in a second slot and orphan a row mapping)
            _, idx = np.unique(sids[::-1], return_index=True)
            keep = np.sort(sids.size - 1 - idx)
            sids, rows = sids[keep], rows[keep]
        if np.any((sids < 0) | (sids > MAX_STREAM_ID)):
            bad = sids[(sids < 0) | (sids > MAX_STREAM_ID)][0]
            raise ValueError(
                f"stream id {int(bad)} outside [0, 2**63) — ids must be "
                "non-negative 63-bit ints")
        slot = self._slots(sids)
        old = slot >= 0
        self.rows[slot[old]] = rows[old]
        # only genuinely NEW keys count toward load and may grow the table
        sids, rows = sids[~old], rows[~old]
        if sids.size:
            size = self.size
            while self.count + sids.size > _MAX_LOAD * size:
                size *= 2
            if size == self.size:
                self._merge_in(sids, rows)
                if self.max_probe > PROBE_CAP:
                    self._rebuild(*self.items(), 2 * self.size)
            else:
                keys, old_rows = self.items()
                self._rebuild(np.concatenate([keys, sids]),
                              np.concatenate([old_rows, rows]), size)
        self.version += 1

    def remap_rows(self, old_rows: np.ndarray, new_rows: np.ndarray) -> None:
        """Atomically rewrite row targets: every key routed to
        ``old_rows[i]`` now routes to ``new_rows[i]``. Keys never move —
        slot layout, ``count`` and ``max_probe`` are untouched, so the
        fused probe programs need no retrace — and the single version
        bump republishes the device mirror in one step (the migration
        plane's routing commit: a reader sees the old mapping or the new
        one, never a half-moved table)."""
        old = np.asarray(old_rows, np.int32)
        new = np.asarray(new_rows, np.int32)
        if old.shape != new.shape:
            raise ValueError(
                f"remap_rows: {old.size} old rows vs {new.size} new rows")
        if old.size == 0:
            return
        top = int(max(old.max(), new.max(), self.rows.max(initial=0)))
        rowmap = np.arange(top + 1, dtype=np.int32)
        rowmap[old] = new
        occ = self.rows >= 0
        self.rows[occ] = rowmap[self.rows[occ]]
        self.version += 1

    def remove_rows(self, dead_rows: np.ndarray) -> None:
        """Drop every key routed to ``dead_rows`` and compact by a full
        rebuild (tombstone-free: stop is the rare path, and the rebuild
        lays the remaining keys out as if they alone had been inserted)."""
        dead = np.asarray(dead_rows, np.int32)
        keys, rows = self.items()
        keep = ~np.isin(rows, dead)
        if keep.all():
            # nothing routed to the dead rows (e.g. a source-only stop):
            # skip the rebuild and the device-mirror re-upload
            return
        self._rebuild(keys[keep], rows[keep], self.size)
        self.version += 1

    # -- internals -----------------------------------------------------
    def _slots(self, sids: np.ndarray) -> np.ndarray:
        """Slot of each key, -1 where it is absent: ``lookup``'s probe,
        one numpy pass per probe step."""
        out = np.full(sids.shape, -1, np.int64)
        if sids.size == 0 or self.count == 0:
            return out
        slot = slot_hash(*split64(sids), self.size)
        mask = self.size - 1
        active = np.ones(sids.shape, bool)
        for _ in range(self.max_probe):
            k = self.keys[slot]
            hit = active & (k == sids)
            out[hit] = slot[hit]
            active &= ~hit & (k != EMPTY)
            if not active.any():
                break
            slot = (slot + 1) & mask
        return out

    def _merge_in(self, sids: np.ndarray, rows: np.ndarray) -> None:
        """Robin Hood insert of absent keys into a table with room. Only
        the blocks they are homed in are laid out again, with any block
        those grow into; every other key keeps its slot. A block is a
        cluster with the empty slot that closes it, so it can be laid out
        on its own. In a Robin Hood table keys only move away from home
        here, so ``max_probe`` stays exact as a running maximum (over a
        first-come table from an older snapshot, an upper bound)."""
        mask = self.size - 1
        home = slot_hash(*split64(sids), self.size)
        lo = (_empty_from(self.keys, (home - 1) & mask, -1) + 1) & mask
        while True:
            lo = np.unique(lo)
            n = (_empty_from(self.keys, lo, 1) - lo) & mask
            old = (np.repeat(lo - np.cumsum(n) + n, n)
                   + np.arange(n.sum())) & mask
            keys = np.concatenate([self.keys[old], sids])
            slot, disp = _robin_hood(keys, self.size)
            spilled = slot[(self.keys[slot] != EMPTY) & ~np.isin(slot, old)]
            if not spilled.size:
                break
            # grown into the next block: lay that one out too
            lo = np.concatenate([lo, (_empty_from(
                self.keys, (spilled - 1) & mask, -1) + 1) & mask])
        held = np.concatenate([self.rows[old], rows])
        self.keys[old], self.rows[old] = EMPTY, -1
        self.keys[slot], self.rows[slot] = keys, held
        self.count += int(sids.size)
        self.max_probe = max(self.max_probe, int(disp.max()) + 1)

    def _rebuild(self, keys: np.ndarray, rows: np.ndarray,
                 size: int) -> None:
        """Lay ``keys`` out afresh at ``size`` slots, or at the smallest
        power of two above it whose layout probes no further than
        PROBE_CAP."""
        size = max(_MIN_SIZE, next_pow2(size))
        while True:
            slot, disp = _robin_hood(keys, size)
            max_probe = int(disp.max(initial=0)) + 1
            if max_probe <= PROBE_CAP:
                break
            size *= 2
        self.keys = np.full((size,), EMPTY, np.int64)
        self.rows = np.full((size,), -1, np.int32)
        self.keys[slot], self.rows[slot] = keys, rows
        self.count = int(keys.size)
        self.max_probe = max_probe


def _empty_from(keys: np.ndarray, slots: np.ndarray, step: int
                ) -> np.ndarray:
    """The first empty slot of ``keys`` at or after each of ``slots``
    (``step`` 1) or at or before it (``step`` -1), wrapping round."""
    mask = keys.size - 1
    out = np.empty_like(slots)
    todo, at, width = np.arange(slots.size), slots.copy(), 16
    while todo.size:
        run = (at[todo, None] + step * np.arange(width)) & mask
        empty = keys[run] == EMPTY
        hit = empty.any(axis=1)
        out[todo[hit]] = run[hit, empty[hit].argmax(axis=1)]
        at[todo] += step * width
        todo, width = todo[~hit], 2 * width
    return out


def _robin_hood(keys: np.ndarray, size: int
                ) -> Tuple[np.ndarray, np.ndarray]:
    """(slot, displacement) of each of ``keys`` (distinct, fewer than
    ``size``) in the Robin Hood layout of a ``size``-slot table. Sorted
    by (home slot, key), each key takes the first slot at or after its
    home that the keys before it left free: slot ``i`` of the sort order
    is ``i + max(home[j] - j for j <= i)``. The last keys of the order
    may run past the end; they wrap onto slots 0, 1, ... ahead of every
    key homed there, which can push more keys past the end, so the
    placement repeats until nothing more wraps (once or twice)."""
    home = slot_hash(*split64(keys), size)
    order = np.lexsort((keys, home))
    h = home[order]
    i = np.arange(h.size)
    wrap = 0
    while True:
        earliest = np.concatenate([np.zeros(wrap, np.int64),
                                   h[:h.size - wrap]])
        pos = i + np.maximum.accumulate(earliest - i)
        over = int(np.count_nonzero(pos[wrap:] >= size))
        if not over:
            break
        wrap += over
    slot = np.empty(h.size, np.int64)
    slot[np.roll(order, wrap)] = pos
    return slot, (slot - home) & (size - 1)


def next_pow2(n: int) -> int:
    return 1 << max(0, (int(n) - 1).bit_length())
