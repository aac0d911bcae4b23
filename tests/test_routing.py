"""Hashed stream routing: the open-addressing table, the fused device
probe, arbitrary-63-bit-id ingest, and snapshot/restore of the table —
including restore onto a different device count.

The contract under test (ISSUE 3): stream ids are arbitrary ints in
[0, 2**63); nothing is clamped, rejected or dropped for being "too big";
the probe runs inside the fused blue-path programs so ingest stays ONE
jitted dispatch per kind per batch.
"""
import os
import subprocess
import sys
import tempfile
import textwrap

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops as kops
from repro.service import SDE, routing
from repro.service import engine as engine_mod


# ---------------------------------------------------------------------------
# RouteTable host-side unit behaviour
# ---------------------------------------------------------------------------
@pytest.mark.smoke
def test_table_insert_lookup_roundtrip_63bit():
    t = routing.RouteTable()
    rng = np.random.RandomState(0)
    ids = np.unique(rng.randint(0, 2**63 - 1, 4096, dtype=np.int64))
    rows = np.arange(len(ids), dtype=np.int32)
    t.insert_many(ids, rows)
    assert t.count == len(ids)
    for i in rng.choice(len(ids), 64, replace=False):
        assert t.lookup(int(ids[i])) == int(rows[i])
    # misses miss (ids drawn outside the inserted set)
    present = set(int(x) for x in ids)
    for probe in (7, 2**40 + 1, 2**62 + 11):
        if probe not in present:
            assert t.lookup(probe) == -1
    # invariants: pow2 size, bounded load, bounded probe chains
    assert t.size & (t.size - 1) == 0
    assert t.load <= 0.7
    assert t.max_probe <= routing.PROBE_CAP


def test_table_duplicate_insert_updates_row():
    t = routing.RouteTable()
    t.insert(2**50 + 3, 1)
    t.insert(2**50 + 3, 9)
    assert t.lookup(2**50 + 3) == 9
    assert t.count == 1


def test_table_remove_rows_compacts_without_tombstones():
    t = routing.RouteTable()
    ids = np.arange(100, dtype=np.int64) * (2**33)   # all > 2**32
    t.insert_many(ids, np.arange(100, dtype=np.int32))
    t.remove_rows(np.arange(0, 100, 2, dtype=np.int32))
    assert t.count == 50
    for i in range(100):
        want = -1 if i % 2 == 0 else i
        assert t.lookup(int(ids[i])) == want, i
    # freed capacity is reusable: re-insert the removed half
    t.insert_many(ids[::2], np.arange(0, 100, 2, dtype=np.int32))
    assert all(t.lookup(int(ids[i])) == i for i in range(100))


def test_table_intra_batch_duplicates_last_wins():
    """A key appearing twice in ONE insert_many must end up in one slot
    with the last row mapping (sequential-insert semantics) — not two
    copies inflating count."""
    t = routing.RouteTable()
    t.insert_many([5, 5, 2**40, 5], [1, 2, 7, 3])
    assert t.count == 2
    assert t.lookup(5) == 3
    assert t.lookup(2**40) == 7
    assert int((t.keys == 5).sum()) == 1


def test_table_remove_rows_noop_keeps_layout():
    """Removing rows nothing routes to (a source-only stop) must not
    rebuild the table or invalidate the device mirror."""
    t = routing.RouteTable()
    t.insert_many([1, 2, 3], [0, 1, 2])
    version, keys = t.version, t.keys.copy()
    t.remove_rows(np.asarray([50, 51], np.int32))
    assert t.version == version
    np.testing.assert_array_equal(t.keys, keys)


def test_build_canonicalizes_duplicate_id_forms():
    """Non-canonical numeric forms of the same id (7 vs 7.0) must not
    commit shadow entries that never receive updates."""
    eng = SDE()
    r = eng.handle({"type": "build", "request_id": "b", "synopsis_id":
                    "cm", "kind": "countmin",
                    "params": {"eps": 0.02, "delta": 0.1,
                               "weighted": False},
                    "per_stream_of_source": True,
                    "stream_ids": [7, 7.0, 2**40]})
    assert r.ok, r.error
    assert set(eng.entries) == {"cm/7", f"cm/{2**40}"}
    eng.ingest(np.asarray([7, 7], np.int64), np.ones(2, np.float32))
    q = eng.handle({"type": "adhoc", "request_id": "q", "synopsis_id":
                    "cm/7", "query": {"items": [7]}})
    assert float(q.value[0]) == 2.0


def test_table_rejects_unrepresentable_ids():
    t = routing.RouteTable()
    for bad in (-1, 1 << 63):
        with pytest.raises(ValueError, match="2\\*\\*63"):
            t.insert(bad, 0)


def test_table_grow_keeps_probe_bound_at_scale():
    """A large id population must settle with probe chains <= PROBE_CAP
    (the fused loop's static bound) — clustering triggers growth."""
    t = routing.RouteTable()
    rng = np.random.RandomState(1)
    ids = np.unique(rng.randint(0, 2**63 - 1, 100_000, dtype=np.int64))
    t.insert_many(ids, np.arange(len(ids), dtype=np.int32))
    assert t.max_probe <= routing.PROBE_CAP
    assert t.load <= 0.7
    sample = rng.choice(len(ids), 32, replace=False)
    assert all(t.lookup(int(ids[i])) == int(i) for i in sample)


# ---------------------------------------------------------------------------
# Robin Hood layout: invariant, order independence, probe bound
# ---------------------------------------------------------------------------
def _first_come(keys, size):
    """Plain sequential linear-probing placement, first come first
    served: the layout older engines wrote into snapshots. Returns the
    ``keys`` array of a ``size``-slot table and its longest displacement
    + 1."""
    keys = [int(k) for k in keys]
    homes = routing.slot_hash(*routing.split64(np.asarray(keys, np.int64)),
                              size).tolist()
    table, longest = [int(routing.EMPTY)] * size, 0
    for k, h in zip(keys, homes):
        d = 0
        while table[(h + d) % size] != routing.EMPTY:
            d += 1
        table[(h + d) % size] = k
        longest = max(longest, d)
    return np.asarray(table, np.int64), longest + 1


def _displacements(t):
    """(occupied slots, displacement of each) recomputed from ``t``."""
    occ = np.flatnonzero(t.keys != routing.EMPTY)
    home = routing.slot_hash(*routing.split64(t.keys[occ]), t.size)
    return occ, (occ - home) & (t.size - 1)


def _assert_robin_hood(t):
    """Every cluster in order of home slot, ties by key; no empty slot
    inside a key's chain; ``max_probe`` exact; ``count`` right."""
    occ, disp = _displacements(t)
    assert t.count == occ.size
    assert t.max_probe == (int(disp.max()) + 1 if occ.size else 1)
    d = np.full(t.size, -1)
    d[occ] = disp
    prev = (occ - 1) & (t.size - 1)
    # a displaced key's previous slot is taken, by a key at most one
    # slot further from home (one further: same home, smaller key)
    assert np.all((disp == 0) | (d[prev] >= 0))
    assert np.all(disp <= d[prev] + 1)
    tie = (disp > 0) & (disp == d[prev] + 1)
    assert np.all(t.keys[prev[tie]] < t.keys[occ[tie]])


def _wrapping_ids(size, n, low):
    """``n`` ids whose home slot in a ``size``-slot table is ``>= low``:
    their cluster runs past the last slot onto the first ones."""
    pool = np.arange(50 * size, dtype=np.int64)
    home = routing.slot_hash(*routing.split64(pool), size)
    return pool[home >= low][:n]


def _ids(n, seed=11):
    rng = np.random.RandomState(seed)
    return np.unique(rng.randint(0, 2**63 - 1, n, dtype=np.int64))


def _robin_hood_via(path):
    """A table built through one write path, and the keys it should
    hold with their rows."""
    ids = _ids(3000)
    rows = np.arange(ids.size, dtype=np.int32)
    t = routing.RouteTable()
    if path == "bulk":
        t.insert_many(ids, rows)
    elif path == "incremental":
        t.insert_many(ids[:2000], rows[:2000])
        for lo in range(2000, ids.size, 7):
            t.insert_many(ids[lo:lo + 7], rows[lo:lo + 7])
    elif path == "grow":
        t = routing.RouteTable(64)
        for lo in range(0, ids.size, 250):    # every call past load grows
            t.insert_many(ids[lo:lo + 250], rows[lo:lo + 250])
        assert t.size > 4096
    elif path == "remove_rows":
        t.insert_many(ids, rows)
        t.remove_rows(rows[::3])
        keep = np.ones(ids.size, bool)
        keep[::3] = False
        ids, rows = ids[keep], rows[keep]
    elif path == "wrap":
        t = routing.RouteTable(64)
        ids = _wrapping_ids(64, 40, 56)
        rows = np.arange(ids.size, dtype=np.int32)
        for k, r in zip(ids, rows):
            t.insert(int(k), int(r))
        assert t.keys[0] != routing.EMPTY and t.keys[-1] != routing.EMPTY
    return t, ids, rows


@pytest.mark.parametrize("path", ["bulk", "incremental", "grow",
                                  "remove_rows", "wrap"])
def test_every_write_path_keeps_the_robin_hood_layout(path):
    t, ids, rows = _robin_hood_via(path)
    _assert_robin_hood(t)
    np.testing.assert_array_equal(t.lookup_many(ids), rows)
    assert all(t.lookup(int(k)) == int(r) for k, r in
               zip(ids[::97], rows[::97]))


@pytest.mark.parametrize("ids", [_ids(4000), _wrapping_ids(64, 44, 50)],
                         ids=["random", "wrapping"])
@pytest.mark.parametrize("order", ["one_call", "shuffled_chunks",
                                   "one_at_a_time"])
def test_layout_depends_only_on_the_key_set(ids, order):
    size = 64 if ids.size < 64 else routing._MIN_SIZE
    rows = (ids % 1000).astype(np.int32)
    want = routing.RouteTable(size)
    want.insert_many(ids, rows)
    t = routing.RouteTable(size)
    perm = np.random.RandomState(ids.size).permutation(ids.size)
    if order == "one_call":
        t.insert_many(ids[perm], rows[perm])
    elif order == "shuffled_chunks":
        for chunk in np.array_split(perm, 9):
            t.insert_many(ids[chunk], rows[chunk])
    else:
        for i in perm[:300]:
            t.insert(int(ids[i]), int(rows[i]))
        t.insert_many(ids[perm[300:]], rows[perm[300:]])
    np.testing.assert_array_equal(t.keys, want.keys)
    np.testing.assert_array_equal(t.rows, want.rows)
    assert (t.size, t.count, t.max_probe) == \
        (want.size, want.count, want.max_probe)
    _assert_robin_hood(t)


@pytest.mark.smoke
def test_stocks5k_ids_probe_at_most_8():
    """The ids 0..4,999 (one CountMin stack of the 5,000-stock
    deployment): first-come placement probes up to 30 slots, so the
    fused programs ran 32 gather passes; the Robin Hood layout of the
    same table probes at most 8."""
    ids = np.arange(5000, dtype=np.int64)
    t = routing.RouteTable()
    t.insert_many(ids, np.arange(5000, dtype=np.int32))
    assert t.size == 8192
    assert t.max_probe <= 8 < _first_come(ids, t.size)[1]
    assert engine_mod._next_pow2(t.max_probe) == 8


def test_probe_bound_no_worse_than_first_come_at_scale():
    ids = _ids(100_000, seed=12)
    t = routing.RouteTable()
    t.insert_many(ids, np.arange(ids.size, dtype=np.int32))
    assert t.max_probe <= _first_come(ids, t.size)[1]
    _assert_robin_hood(t)


# ---------------------------------------------------------------------------
# device probe == host table
# ---------------------------------------------------------------------------
@pytest.mark.smoke
def test_route_probe_matches_host_lookup():
    t = routing.RouteTable()
    rng = np.random.RandomState(2)
    ids = np.unique(rng.randint(0, 2**63 - 1, 2000, dtype=np.int64))
    t.insert_many(ids, np.arange(len(ids), dtype=np.int32))
    # half hits, half misses
    queries = np.concatenate([
        ids[rng.choice(len(ids), 500)],
        rng.randint(0, 2**63 - 1, 500, dtype=np.int64)])
    lo, hi = routing.split64(t.keys)
    qlo, qhi = routing.split64(queries)
    got = np.asarray(kops.route_probe(
        jnp.asarray(lo), jnp.asarray(hi), jnp.asarray(t.rows),
        jnp.asarray(qlo), jnp.asarray(qhi),
        n_probe=engine_mod._next_pow2(t.max_probe)))
    want = np.asarray([t.lookup(int(q)) for q in queries], np.int32)
    np.testing.assert_array_equal(got, want)


def _probe(t, queries):
    lo, hi = routing.split64(t.keys)
    qlo, qhi = routing.split64(queries)
    return np.asarray(kops.route_probe(
        jnp.asarray(lo), jnp.asarray(hi), jnp.asarray(t.rows),
        jnp.asarray(qlo), jnp.asarray(qhi),
        n_probe=engine_mod._next_pow2(t.max_probe)))


@pytest.mark.parametrize("path", ["bulk", "remove_rows", "wrap"])
def test_route_probe_matches_host_lookup_on_robin_hood_tables(path):
    t, ids, rows = _robin_hood_via(path)
    misses = np.arange(2**40, 2**40 + 64, dtype=np.int64)
    queries = np.concatenate([ids, misses])
    want = np.concatenate([rows, np.full(misses.size, -1, np.int32)])
    np.testing.assert_array_equal(_probe(t, queries), want)
    np.testing.assert_array_equal(t.lookup_many(queries), want)


def _first_come_route(ids, rows, size):
    """``export_route``-shaped arrays and manifest entry of a first-come
    table holding ``ids`` -> ``rows``."""
    keys, max_probe = _first_come(ids, size)
    row_of = dict(zip(np.asarray(ids).tolist(), np.asarray(rows).tolist()))
    lo, hi = routing.split64(keys)
    arrays = dict(keys_lo=lo, keys_hi=hi, rows=np.asarray(
        [row_of.get(int(k), -1) for k in keys], np.int32))
    return arrays, dict(size=size, count=len(row_of), max_probe=max_probe)


def test_first_come_table_from_an_older_snapshot_still_routes():
    """Tables written with first-come placement come back through
    ``import_route`` as they are, and every key is still found by the
    host lookups and the device probe, before and after new inserts."""
    from repro.service import migration
    ids = np.concatenate([_ids(1500, seed=13),
                          _wrapping_ids(4096, 30, 4080)])
    rows = np.arange(ids.size, dtype=np.int32)
    arrays, meta = _first_come_route(ids, rows, 4096)
    t = migration.import_route(arrays, meta)
    np.testing.assert_array_equal(t.keys, _first_come(ids, 4096)[0])
    assert t.max_probe == meta["max_probe"]

    def routes_every_key():
        assert all(t.lookup(int(k)) == int(r) for k, r in zip(ids, rows))
        np.testing.assert_array_equal(t.lookup_many(ids), rows)
        np.testing.assert_array_equal(_probe(t, ids), rows)

    routes_every_key()
    more = _ids(200, seed=14)
    t.insert_many(more, np.arange(200, dtype=np.int32) + ids.size)
    ids = np.concatenate([ids, more])
    rows = np.arange(ids.size, dtype=np.int32)
    routes_every_key()


def test_restore_routes_a_snapshot_with_a_first_come_table():
    """A snapshot whose routing table was laid out first come (as older
    engines wrote them) restores, answers as the live engine does, and
    keeps routing new ingests."""
    import json as _json
    eng, sid_pop, sids = _build_big_id_engine(n_streams=300)
    table = next(iter(eng.stacks.values())).table
    keys, rows = table.items()
    with tempfile.TemporaryDirectory() as d:
        eng.snapshot(d, 1)
        step_dir = os.path.join(d, "step-00000001")
        arrays, meta = _first_come_route(keys, rows, table.size)
        blob = dict(np.load(os.path.join(step_dir, "leaves.npz")))
        for name, arr in arrays.items():
            blob[f"stack0__route__{name}"] = arr
        np.savez(os.path.join(step_dir, "leaves.npz"), **blob)
        with open(os.path.join(step_dir, "manifest.json")) as f:
            man = _json.load(f)
        man["stacks"][0]["table"] = meta
        with open(os.path.join(step_dir, "manifest.json"), "w") as f:
            _json.dump(man, f)
        eng2 = SDE.restore(d)
    t2 = next(iter(eng2.stacks.values())).table
    np.testing.assert_array_equal(t2.keys, _first_come(keys, table.size)[0])
    np.testing.assert_array_equal(t2.lookup_many(sid_pop),
                                  table.lookup_many(sid_pop))
    eng2.ingest(sids, np.ones(len(sids), np.float32))
    for sid in sid_pop[::37]:
        q = eng2.handle({"type": "adhoc", "request_id": "q",
                         "synopsis_id": f"cm/{sid}",
                         "query": {"items": [int(sid)]}})
        assert float(q.value[0]) == 2 * float((sids == sid).sum())


def test_slot_hash_host_device_lockstep():
    """The host inserter and the jitted probe MUST hash to the same
    slots — otherwise lookups silently miss."""
    from repro.core import hashing
    rng = np.random.RandomState(3)
    ids = rng.randint(0, 2**63 - 1, 256, dtype=np.int64)
    lo, hi = routing.split64(ids)
    size = 1 << 14
    host = routing.slot_hash(lo, hi, size)
    dev_h = hashing.mix32(jnp.asarray(lo)
                          ^ hashing.mix32(jnp.asarray(hi)
                                          ^ jnp.uint32(0x9E3779B9)))
    dev = np.asarray(dev_h).astype(np.int64) & (size - 1)
    np.testing.assert_array_equal(host, dev)


# ---------------------------------------------------------------------------
# engine: arbitrary ids, exactness, single fused dispatch
# ---------------------------------------------------------------------------
@pytest.mark.smoke
def test_high_stream_ids_exact_and_single_dispatch(monkeypatch):
    calls = []
    orig = engine_mod._update

    def counting(kind, *a, **k):
        calls.append(kind)
        return orig(kind, *a, **k)

    monkeypatch.setattr(engine_mod, "_update", counting)
    eng = SDE()
    rng = np.random.RandomState(0)
    sid_pop = np.unique(rng.randint(0, 2**63 - 1, 64, dtype=np.int64))
    r = eng.handle({"type": "build", "request_id": "b", "synopsis_id":
                    "cm", "kind": "countmin",
                    "params": {"eps": 0.02, "delta": 0.1,
                               "weighted": False},
                    "per_stream_of_source": True,
                    "stream_ids": [int(s) for s in sid_pop]})
    assert r.ok, r.error
    eng.handle({"type": "build", "request_id": "b2", "synopsis_id":
                "card", "kind": "hyperloglog", "params": {"rse": 0.03}})
    n_batches = 3
    sids = sid_pop[rng.randint(0, len(sid_pop), 1024)]
    for _ in range(n_batches):
        eng.ingest(sids, np.ones(len(sids), np.float32))
    # one fused dispatch per kind per batch, probe included
    assert len(calls) == n_batches * len(eng.stacks)
    # zero dropped tuples
    assert eng.tuples_ingested == n_batches * len(sids)
    # exact per-stream counts on ids far beyond the old 2**16 cap
    for sid in sid_pop[:8]:
        q = eng.handle({"type": "adhoc", "request_id": "q",
                        "synopsis_id": f"cm/{sid}",
                        "query": {"items": [int(sid)]}})
        assert q.ok, q.error
        assert float(q.value[0]) == n_batches * float((sids == sid).sum())
    # the data-source HLL sees the whole (folded) id population
    q = eng.handle({"type": "adhoc", "request_id": "qh",
                    "synopsis_id": "card"})
    assert abs(float(q.value) - len(sid_pop)) / len(sid_pop) < 0.25


def test_high_ids_pallas_backend_matches_xla():
    out = {}
    rng = np.random.RandomState(4)
    sid_pop = np.unique(rng.randint(0, 2**63 - 1, 32, dtype=np.int64))
    sids = sid_pop[rng.randint(0, len(sid_pop), 512)]
    for backend in ("xla", "pallas"):
        eng = SDE(backend=backend)
        eng.handle({"type": "build", "request_id": "b", "synopsis_id":
                    "cm", "kind": "countmin",
                    "params": {"eps": 0.02, "delta": 0.1,
                               "weighted": False},
                    "per_stream_of_source": True,
                    "stream_ids": [int(s) for s in sid_pop]})
        eng.ingest(sids, np.ones(len(sids), np.float32))
        q = eng.handle({"type": "adhoc", "request_id": "q",
                        "synopsis_id": f"cm/{sid_pop[3]}",
                        "query": {"items": [int(sid_pop[3])]}})
        assert q.ok, q.error
        out[backend] = float(q.value[0])
    assert out["xla"] == out["pallas"] == float((sids == sid_pop[3]).sum())


def test_timeseries_kind_routes_hashed_ids():
    def fresh():
        eng = SDE()
        r = eng.handle({"type": "build", "request_id": "b", "synopsis_id":
                        "dft", "kind": "dft",
                        "params": {"window": 16, "n_coeffs": 4},
                        "stream_id": 2**45 + 17})
        assert r.ok, r.error
        return eng

    eng = fresh()
    sid = 2**45 + 17
    for v in (1.0, -1.0, 0.5):
        eng.ingest(np.asarray([sid], np.int64),
                   np.asarray([v], np.float32))
    q = eng.handle({"type": "adhoc", "request_id": "q",
                    "synopsis_id": "dft"})
    assert q.ok, q.error
    # duplicate ids inside one batch: the LAST tuple's value ticks the
    # stream, deterministically — equivalent to a single-tuple batch
    dup, single = fresh(), fresh()
    dup.ingest(np.asarray([sid, 123, sid], np.int64),
               np.asarray([1.0, 9.0, 2.0], np.float32))
    single.ingest(np.asarray([sid], np.int64),
                  np.asarray([2.0], np.float32))
    for a, b in zip(jax.tree.leaves(dup.state_of("dft")),
                    jax.tree.leaves(single.state_of("dft"))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# satellite: snapshot -> restore round-trips of the hashed routing table
# ---------------------------------------------------------------------------
def _build_big_id_engine(n_streams=96, n_tuples=2048, seed=0):
    rng = np.random.RandomState(seed)
    sid_pop = np.unique(rng.randint(0, 2**63 - 1, n_streams,
                                    dtype=np.int64))
    eng = SDE()
    eng.handle({"type": "build", "request_id": "b", "synopsis_id": "cm",
                "kind": "countmin",
                "params": {"eps": 0.02, "delta": 0.1, "weighted": False},
                "per_stream_of_source": True,
                "stream_ids": [int(s) for s in sid_pop]})
    sids = sid_pop[rng.randint(0, len(sid_pop), n_tuples)]
    eng.ingest(sids, np.ones(n_tuples, np.float32))
    return eng, sid_pop, sids


def test_snapshot_restore_roundtrips_hashed_routing():
    eng, sid_pop, sids = _build_big_id_engine()
    with tempfile.TemporaryDirectory() as d:
        eng.snapshot(d, 1)
        eng2 = SDE.restore(d)
    # the table restored byte-identical (layout, occupancy, probe bound)
    t1 = next(iter(eng.stacks.values())).table
    t2 = next(iter(eng2.stacks.values())).table
    np.testing.assert_array_equal(t1.keys, t2.keys)
    np.testing.assert_array_equal(t1.rows, t2.rows)
    assert (t1.count, t1.max_probe) == (t2.count, t2.max_probe)
    assert eng2.batches_ingested == eng.batches_ingested
    # query equivalence pre/post restore
    for sid in sid_pop[:6]:
        qs = [e.handle({"type": "adhoc", "request_id": "q",
                        "synopsis_id": f"cm/{sid}",
                        "query": {"items": [int(sid)]}})
              for e in (eng, eng2)]
        assert qs[0].ok and qs[1].ok
        assert float(qs[0].value[0]) == float(qs[1].value[0])
    # post-restore ingest keeps routing: counts double on a re-ingest
    sid = int(sid_pop[3])
    before = float(eng2.handle(
        {"type": "adhoc", "request_id": "q", "synopsis_id": f"cm/{sid}",
         "query": {"items": [sid]}}).value[0])
    eng2.ingest(sids, np.ones(len(sids), np.float32))
    after = float(eng2.handle(
        {"type": "adhoc", "request_id": "q", "synopsis_id": f"cm/{sid}",
         "query": {"items": [sid]}}).value[0])
    assert after == 2 * before and before == float((sids == sid).sum())


def test_table_reinsert_does_not_grow():
    """Re-inserting existing keys (row updates) must not count toward
    load or trigger a pointless grow-and-rehash."""
    t = routing.RouteTable()
    ids = np.arange(40, dtype=np.int64)
    t.insert_many(ids, np.arange(40, dtype=np.int32))
    size = t.size
    t.insert_many(ids, np.arange(40, dtype=np.int32)[::-1])
    assert t.size == size and t.count == 40
    assert t.lookup(0) == 39


def test_restore_migrates_legacy_dense_route_snapshot():
    """Snapshots written by the pre-hashed-routing engine (one dense
    int32 ``route`` array per stack, no ``table`` manifest entry) must
    restore: the dense route is migrated into a RouteTable."""
    import json as _json
    eng, sid_pop, sids = None, None, None
    rng = np.random.RandomState(5)
    eng = SDE()
    eng.handle({"type": "build", "request_id": "b", "synopsis_id": "cm",
                "kind": "countmin",
                "params": {"eps": 0.02, "delta": 0.1, "weighted": False},
                "per_stream_of_source": True, "n_streams": 50})
    sids = rng.randint(0, 50, 512).astype(np.uint32)
    eng.ingest(sids, np.ones(512, np.float32))
    with tempfile.TemporaryDirectory() as d:
        eng.snapshot(d, 1)
        # rewrite the snapshot into the LEGACY layout: dense route array,
        # no table metadata, no batch counter
        step_dir = os.path.join(d, "step-00000001")
        blob = dict(np.load(os.path.join(step_dir, "leaves.npz")))
        table = next(iter(eng.stacks.values())).table
        dense = np.full(1 << 16, -1, np.int32)
        keys, rows = table.items()
        dense[keys] = rows
        for k in list(blob):
            if "__route__" in k:
                del blob[k]
        blob["stack0__route"] = dense
        np.savez(os.path.join(step_dir, "leaves.npz"), **blob)
        with open(os.path.join(step_dir, "manifest.json")) as f:
            man = _json.load(f)
        del man["batches_ingested"]
        for sk in man["stacks"]:
            del sk["table"]
        with open(os.path.join(step_dir, "manifest.json"), "w") as f:
            _json.dump(man, f)
        eng2 = SDE.restore(d)
    for sid in (3, 17, 49):
        q1 = eng.handle({"type": "adhoc", "request_id": "q",
                         "synopsis_id": f"cm/{sid}",
                         "query": {"items": [sid]}})
        q2 = eng2.handle({"type": "adhoc", "request_id": "q",
                          "synopsis_id": f"cm/{sid}",
                          "query": {"items": [sid]}})
        assert q1.ok and q2.ok
        assert float(q1.value[0]) == float(q2.value[0])
    # the migrated table keeps routing new ingests
    eng2.ingest(sids, np.ones(512, np.float32))
    q3 = eng2.handle({"type": "adhoc", "request_id": "q",
                      "synopsis_id": "cm/3", "query": {"items": [3]}})
    assert float(q3.value[0]) == 2 * float((sids == 3).sum())


_RESTORE_MESH_SCRIPT = textwrap.dedent("""
    import os, tempfile
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np, jax
    from jax.sharding import NamedSharding
    from repro.service import SDE
    from repro.launch.mesh import make_mesh

    rng = np.random.RandomState(0)
    sid_pop = np.unique(rng.randint(0, 2**63 - 1, 96, dtype=np.int64))
    eng = SDE()        # snapshot written WITHOUT a mesh (1-device layout)
    eng.handle({"type": "build", "request_id": "b", "synopsis_id": "cm",
                "kind": "countmin",
                "params": {"eps": 0.02, "delta": 0.1, "weighted": False},
                "per_stream_of_source": True,
                "stream_ids": [int(s) for s in sid_pop]})
    sids = sid_pop[rng.randint(0, len(sid_pop), 2048)]
    eng.ingest(sids, np.ones(len(sids), np.float32))
    d = tempfile.mkdtemp()
    eng.snapshot(d, 1)

    # restore onto an 8-device mesh: state rows shard over `synopsis`,
    # the routing-table mirror replicates
    mesh = make_mesh((8, 1), ("data", "model"))
    eng2 = SDE.restore(d, mesh=mesh)
    stack = next(iter(eng2.stacks.values()))
    for leaf in jax.tree.leaves(stack.state):
        assert isinstance(leaf.sharding, NamedSharding)
        assert leaf.sharding.spec and leaf.sharding.spec[0] == "data"
    for arr in stack.device_table():
        assert not arr.sharding.spec, arr.sharding   # replicated
    # ingest/query equivalence after the elastic repartition
    sid = int(sid_pop[5])
    q = eng2.handle({"type": "adhoc", "request_id": "q",
                     "synopsis_id": f"cm/{sid}", "query": {"items": [sid]}})
    assert float(q.value[0]) == float((sids == sid).sum()), q.value
    eng2.ingest(sids, np.ones(len(sids), np.float32))
    q = eng2.handle({"type": "adhoc", "request_id": "q2",
                     "synopsis_id": f"cm/{sid}", "query": {"items": [sid]}})
    assert float(q.value[0]) == 2 * float((sids == sid).sum()), q.value
    print("OK")
""")


def test_restore_onto_different_device_count():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", _RESTORE_MESH_SCRIPT],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "OK" in out.stdout


# ---------------------------------------------------------------------------
# satellite: JSON/service path hands ingest plain Python lists
# ---------------------------------------------------------------------------
def test_ingest_accepts_python_lists():
    eng = SDE()
    sid = 2**33 + 5
    eng.handle({"type": "build", "request_id": "b", "synopsis_id": "cm",
                "kind": "countmin",
                "params": {"eps": 0.02, "delta": 0.1, "weighted": False},
                "stream_id": sid})
    eng.ingest([sid, sid, sid], [1.0, 1.0, 1.0])          # plain lists
    eng.ingest([sid], [2.5], mask=[True])                 # list mask too
    q = eng.handle({"type": "adhoc", "request_id": "q", "synopsis_id":
                    "cm", "query": {"items": [sid]}})
    assert float(q.value[0]) == 4.0
    assert eng.tuples_ingested == 4


# ---------------------------------------------------------------------------
# satellite: continuous-query request ids never collide
# ---------------------------------------------------------------------------
def test_continuous_request_ids_unique_across_masked_batches():
    eng = SDE()
    eng.handle({"type": "build", "request_id": "c", "synopsis_id": "h",
                "kind": "hyperloglog", "params": {"rse": 0.05},
                "continuous": True})
    # two consecutive batches whose tuples are ALL masked out (negative
    # ids): tuples_ingested stays flat, so the old tuple-count key
    # collided; the batch counter must not
    for _ in range(2):
        eng.ingest(np.asarray([-1, -2], np.int64),
                   np.ones(2, np.float32))
    eng.ingest(np.arange(50, dtype=np.int64), np.ones(50, np.float32))
    rids = [r.request_id for r in eng.continuous_out]
    assert len(rids) == 3
    assert len(set(rids)) == len(rids), rids


# ---------------------------------------------------------------------------
# satellite: stopping a data-source synopsis must not leave a stale
# source-row index absorbing every tuple
# ---------------------------------------------------------------------------
def test_stopped_source_row_stops_absorbing():
    eng = SDE()
    eng.handle({"type": "build", "request_id": "b1", "synopsis_id":
                "all", "kind": "countmin",
                "params": {"eps": 0.02, "delta": 0.1, "weighted": False}})
    eng.handle({"type": "build", "request_id": "b2", "synopsis_id":
                "one", "kind": "countmin",
                "params": {"eps": 0.02, "delta": 0.1, "weighted": False},
                "stream_id": 7})
    eng.ingest(np.asarray([7, 8, 9], np.int64), np.ones(3, np.float32))
    assert eng.handle({"type": "stop", "request_id": "s",
                       "synopsis_id": "all"}).ok
    # the freed source row is reused by a ROUTED synopsis; if the cached
    # source index were stale it would keep absorbing every tuple
    eng.handle({"type": "build", "request_id": "b3", "synopsis_id":
                "two", "kind": "countmin",
                "params": {"eps": 0.02, "delta": 0.1, "weighted": False},
                "stream_id": 2**40})
    eng.ingest(np.asarray([7, 7, 2**40], np.int64),
               np.ones(3, np.float32))
    q = eng.handle({"type": "adhoc", "request_id": "q", "synopsis_id":
                    "two", "query": {"items": [2**40, 7]}})
    assert float(q.value[0]) == 1.0     # its own stream only
    assert float(q.value[1]) == 0.0     # nothing absorbed from stream 7
    q = eng.handle({"type": "adhoc", "request_id": "q2", "synopsis_id":
                    "one", "query": {"items": [7]}})
    assert float(q.value[0]) == 3.0     # routed synopsis unaffected
