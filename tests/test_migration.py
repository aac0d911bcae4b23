"""Row-granular migration plane tests (PR 8 tentpole).

The contract under test:

  * ``extract_rows`` -> ``implant_rows`` round-trips BYTE-identically for
    every registered kind — state leaves, routing keys (uint32 halves)
    and source flags all travel inside the payload, so a synopsis moved
    to another engine answers queries exactly as it did at home and
    keeps accumulating there.
  * ``move_rows`` relocates rows entirely on device — arbitrary
    permutations and chains are safe, routing follows atomically
    (``RouteTable.remap_rows`` — slot layout and max_probe untouched),
    and misuse (colliding or occupied targets, free sources) is refused
    before any state is touched.
  * ``SDE.resize_stack`` / ``SDE.compact`` are the capacity half of
    elasticity: grow pads with the init prototype, shrink demands the
    live rows sit below the cut, compact packs-then-shrinks in one
    ``move_rows`` batch.
  * snapshot/restore ride the same wire helpers
    (``export_route``/``import_route``) — probe layout is preserved
    exactly, never rebuilt by re-insertion.
  * all of it holds on an 8-device mesh (subprocess), where a row's
    position picks its device shard — eager and ``SDE_PIPELINED=1``
    (the CI matrix flips the env toggle).
"""
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro import core
from repro.service import SDE, migration, routing

_PARAMS = {
    "countmin": {"eps": 0.05, "delta": 0.1, "weighted": False},
    "hyperloglog": {"rse": 0.05},
    "ams": {"eps": 0.2, "delta": 0.2},
    "bloom": {"n_elements": 256, "fpr": 0.02},
    "fm": {"nmaps": 16},
    "dft": {"window": 16, "n_coeffs": 4},
    "rhp": {"n_bits": 32},
    "lossy_counting": {"eps": 0.05},
    "sticky_sampling": {},
    "chain_sampler": {"sample_size": 16},
    "gk_quantiles": {"eps": 0.05},
    "coreset_tree": {"bucket_size": 32, "dim": 1},
}

# per-kind ad-hoc query args (kinds not listed take no args)
_QUERY = {
    "countmin": {"items": [3, 7, 11]},
    "bloom": {"items": [3, 7, 11]},
    "lossy_counting": {"items": [3, 7, 11]},
    "sticky_sampling": {"items": [3, 7, 11]},
    "gk_quantiles": {"qs": [0.25, 0.5, 0.75]},
}

_STREAMS = [3, 7, 11, 900, 2**40 + 5]

# coreset ingest batches are capped at bucket_size points
_BATCH = {"coreset_tree": 32}


def _tree_equal(a, b):
    """BYTE-level tree equality (assert_array_equal alone treats
    -0.0 == +0.0)."""
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        x, y = np.asarray(x), np.asarray(y)
        np.testing.assert_array_equal(x, y)
        assert x.tobytes() == y.tobytes()


def _build_per_stream(eng, kind_name, prefix="pt", streams=_STREAMS):
    r = eng.handle({"type": "build", "request_id": "b",
                    "synopsis_id": prefix, "kind": kind_name,
                    "params": _PARAMS[kind_name],
                    "per_stream_of_source": True,
                    "stream_ids": list(streams)})
    assert r.ok, r.error
    return [f"{prefix}/{s}" for s in streams]


def _traffic(eng, streams=_STREAMS, seed=0, n=256):
    """Integer-valued routed traffic over ``streams`` (exact float32
    sums — the byte comparisons rely on it)."""
    rng = np.random.RandomState(seed)
    sids = np.asarray(rng.choice(streams, n), np.int64)
    vals = rng.randint(1, 5, n).astype(np.float32)
    eng.ingest(sids, vals)
    eng.flush()
    return sids, vals


def _ask(eng, sid, kind_name):
    r = eng.handle({"type": "adhoc", "request_id": "q", "synopsis_id":
                    sid, "query": _QUERY.get(kind_name, {})})
    assert r.ok, r.error
    return r.value               # scalar, array or dict-of-arrays by kind


# ---------------------------------------------------------------------------
# the matrix: extract -> implant round-trips byte-identically, per kind
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("kind_name", sorted(core.known_kinds()))
def test_extract_implant_round_trip_bytes(kind_name):
    n = _BATCH.get(kind_name, 256)
    src = SDE()
    ids = _build_per_stream(src, kind_name)
    _traffic(src, n=n)
    answers = {s: _ask(src, s, kind_name) for s in ids}

    package = src.extract_synopses(ids, remove=False)
    dst = SDE()
    assert dst.implant_synopses(package) == len(ids)

    # the payload the destination would ship onward is byte-identical to
    # the one it received: state leaves, keys and source flags all
    # survived the hop
    again = dst.extract_synopses(ids, remove=False)
    assert len(again) == len(package) == 1
    (_, metas_a, pay_a), (_, metas_b, pay_b) = package[0], again[0]
    assert [m["synopsis_id"] for m in metas_a] \
        == [m["synopsis_id"] for m in metas_b]
    _tree_equal(pay_a.state, pay_b.state)
    np.testing.assert_array_equal(pay_a.stream_ids(), pay_b.stream_ids())
    np.testing.assert_array_equal(pay_a.source, pay_b.source)

    # and it ANSWERS identically — then keeps accumulating: the carried
    # routing keys are live at the new home, not just decorative
    for s in ids:
        _tree_equal(_ask(dst, s, kind_name), answers[s])
    _traffic(dst, seed=1, n=n)
    dst.flush()
    assert dst.tuples_ingested == n


@pytest.mark.smoke
def test_extract_implant_round_trip_smoke():
    test_extract_implant_round_trip_bytes("countmin")


def test_extract_remove_frees_rows_and_routes():
    eng = SDE()
    ids = _build_per_stream(eng, "countmin")
    _traffic(eng)
    kind = eng.entries[ids[0]].kind_key
    package = eng.extract_synopses(ids[:2], remove=True)
    assert ids[0] not in eng.entries and ids[1] not in eng.entries
    stack = eng.stacks[kind]
    assert stack.table.count == len(ids) - 2
    # traffic for the extracted streams no longer lands anywhere
    before = np.asarray(jax.tree.leaves(stack.state)[0]).copy()
    eng.ingest(np.full(32, _STREAMS[0], np.int64), np.ones(32, np.float32))
    eng.flush()
    np.testing.assert_array_equal(
        before, np.asarray(jax.tree.leaves(stack.state)[0]))
    # the package is still implantable (the move half-completed is the
    # caller's problem, not the payload's)
    other = SDE()
    assert other.implant_synopses(package) == 2


def test_implant_refuses_duplicates_atomically():
    a, b = SDE(), SDE()
    ids = _build_per_stream(a, "countmin")
    _traffic(a)
    package = a.extract_synopses(ids, remove=False)
    _build_per_stream(b, "countmin", streams=_STREAMS[-1:])  # one clash
    with pytest.raises(ValueError, match="already"):
        b.implant_synopses(package)
    # nothing committed: no phantom entries, no stolen routes
    assert sorted(b.entries) == [f"pt/{_STREAMS[-1]}"]


# ---------------------------------------------------------------------------
# move_rows: on-device relocation, routing follows atomically
# ---------------------------------------------------------------------------
def test_move_rows_permutation_chain():
    eng = SDE()
    ids = _build_per_stream(eng, "countmin")
    _traffic(eng)
    kind = eng.entries[ids[0]].kind_key
    stack = eng.stacks[kind]
    rows = [eng.entries[s].row for s in ids[:3]]
    before = {s: _ask(eng, s, "countmin") for s in ids}
    probe_before = stack.table.max_probe
    # a 3-cycle: every target is occupied, but by another mover
    mapping = {rows[0]: rows[1], rows[1]: rows[2], rows[2]: rows[0]}
    assert eng.migrate_rows(kind, mapping) == 3
    assert stack.table.max_probe == probe_before      # no retrace hazard
    for s in ids:
        np.testing.assert_array_equal(np.asarray(_ask(eng, s, "countmin")),
                                      np.asarray(before[s]))
    # ingest routes to the NEW rows
    eng.ingest(np.full(8, _STREAMS[0], np.int64), np.ones(8, np.float32))
    eng.flush()
    after = np.asarray(_ask(eng, ids[0], "countmin"))
    b0 = np.asarray(before[ids[0]])
    assert (after >= b0).all() and after.sum() > b0.sum()


def test_move_rows_refuses_bad_mappings():
    eng = SDE()
    ids = _build_per_stream(eng, "countmin")
    kind = eng.entries[ids[0]].kind_key
    stack = eng.stacks[kind]
    r0, r1 = eng.entries[ids[0]].row, eng.entries[ids[1]].row
    free = next(i for i, u in enumerate(stack.used) if not u)
    with pytest.raises(ValueError, match="collide"):
        migration.move_rows(stack, {r0: free, r1: free})
    with pytest.raises(ValueError, match="occupied"):
        migration.move_rows(stack, {r0: r1})
    with pytest.raises(ValueError, match="free"):
        eng.migrate_rows(kind, {free: free + 1})
    # nothing above committed
    assert eng.entries[ids[0]].row == r0
    assert stack.used[r0] and not stack.used[free]


def test_migrate_rows_filters_identity_and_fences():
    eng = SDE(pipelined=True)
    ids = _build_per_stream(eng, "countmin")
    eng.ingest(np.full(16, _STREAMS[0], np.int64), np.ones(16, np.float32))
    kind = eng.entries[ids[0]].kind_key
    r0 = eng.entries[ids[0]].row
    # identity mapping is a no-op (and must not count as migrated rows)
    assert eng.migrate_rows(kind, {r0: r0}) == 0
    # the in-flight pipelined batch was fenced in before the (no-op) move
    assert float(_ask(eng, ids[0], "countmin")[0]) == 16.0


# ---------------------------------------------------------------------------
# resize + compact: the capacity half of elasticity
# ---------------------------------------------------------------------------
def test_resize_grow_then_compact_shrinks_back():
    eng = SDE()
    streams = list(range(20))
    ids = _build_per_stream(eng, "hyperloglog", streams=streams)
    rng = np.random.RandomState(2)
    eng.ingest(np.asarray(rng.choice(streams, 400), np.int64),
               np.ones(400, np.float32))
    eng.flush()
    kind = eng.entries[ids[0]].kind_key
    cap0 = eng.stacks[kind].capacity
    answers = {s: _ask(eng, s, "hyperloglog") for s in ids}

    assert eng.resize_stack(kind, cap0 * 4) == cap0 * 4
    # spread some rows into the grown tail, so shrink has work to refuse
    tail = {eng.entries[ids[i]].row: cap0 * 4 - 1 - i for i in range(4)}
    eng.migrate_rows(kind, tail)
    with pytest.raises(ValueError, match="compact"):
        eng.resize_stack(kind, cap0)
    # compact packs live rows low and shrinks to the smallest pow2 hold
    assert eng.compact(kind) == cap0
    assert sorted(eng.entries[s].row for s in ids) == list(range(len(ids)))
    for s in ids:
        np.testing.assert_array_equal(_ask(eng, s, "hyperloglog"),
                                      answers[s])


def test_resize_rejects_nonsense():
    eng = SDE()
    ids = _build_per_stream(eng, "countmin")
    kind = eng.entries[ids[0]].kind_key
    with pytest.raises(ValueError, match="< 1"):
        eng.resize_stack(kind, 0)


# ---------------------------------------------------------------------------
# routing-table primitives the plane rides
# ---------------------------------------------------------------------------
def test_remap_rows_preserves_probe_layout():
    t = routing.RouteTable(16)
    keys = [5, 21, 37, 2**40 + 1]            # 5, 21, 37 collide mod 16
    for i, k in enumerate(keys):
        t.insert(k, i * 10)
    probe, version = t.max_probe, t.version
    slots = t.keys.copy()
    t.remap_rows(np.asarray([10, 30], np.int32),
                 np.asarray([99, 10], np.int32))
    assert t.lookup(21) == 99 and t.lookup(2**40 + 1) == 10
    assert t.lookup(5) == 0 and t.lookup(37) == 20
    np.testing.assert_array_equal(slots, t.keys)   # keys never move slots
    assert t.max_probe == probe
    assert t.version == version + 1                # one atomic republish


def test_export_import_route_round_trip():
    t = routing.RouteTable(8)
    for k in (3, 11, 19, 2**50 + 7):         # wrap-around probe chains
        t.insert(k, k % 97)
    arrays = migration.export_route(t)
    t2 = migration.import_route(
        arrays, dict(size=t.size, count=t.count, max_probe=t.max_probe))
    np.testing.assert_array_equal(t.keys, t2.keys)
    np.testing.assert_array_equal(t.rows, t2.rows)
    assert (t.count, t.max_probe) == (t2.count, t2.max_probe)


# ---------------------------------------------------------------------------
# hypothesis property (skipped when hypothesis is not installed — the
# rest of this module must still run, so no module-level importorskip)
# ---------------------------------------------------------------------------
try:
    from hypothesis import given, settings, strategies as st, HealthCheck
    _HAVE_HYPOTHESIS = True
except ImportError:
    _HAVE_HYPOTHESIS = False

if _HAVE_HYPOTHESIS:
    @given(data=st.data())
    @settings(deadline=None, max_examples=10,
              suppress_health_check=[HealthCheck.too_slow,
                                     HealthCheck.data_too_large])
    def test_round_trip_property(data):
        kind_name = data.draw(st.sampled_from(
            ["countmin", "hyperloglog", "bloom", "chain_sampler"]))
        streams = data.draw(st.lists(st.integers(0, 2**62),
                                     min_size=1, max_size=8,
                                     unique=True))
        n = data.draw(st.integers(1, 64))
        src = SDE()
        ids = _build_per_stream(src, kind_name, streams=streams)
        rng = np.random.RandomState(data.draw(st.integers(0, 2**31)))
        src.ingest(np.asarray(rng.choice(streams, n), np.int64),
                   rng.randint(1, 4, n).astype(np.float32))
        src.flush()
        subset = data.draw(st.lists(st.sampled_from(ids), min_size=1,
                                    max_size=len(ids), unique=True))
        package = src.extract_synopses(subset, remove=False)
        dst = SDE()
        dst.implant_synopses(package)
        again = dst.extract_synopses(subset, remove=False)
        _tree_equal(package[0][2].state, again[0][2].state)
        np.testing.assert_array_equal(package[0][2].stream_ids(),
                                      again[0][2].stream_ids())
else:
    @pytest.mark.skip(reason="hypothesis not installed")
    def test_round_trip_property():
        pass


# ---------------------------------------------------------------------------
# 8-device sharded subprocess: the row axis is the device axis, so a
# migration IS a cross-device move. Inherits SDE_PIPELINED from the CI
# matrix env, so both execution modes are exercised.
# ---------------------------------------------------------------------------
_MESH_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np, jax
    from repro.service import SDE
    from repro.launch.mesh import make_mesh

    def mk():
        mesh = make_mesh((4, 2), ("data", "model"))
        eng = SDE(mesh=mesh)
        r = eng.handle({"type": "build", "request_id": "b",
                        "synopsis_id": "pt", "kind": "countmin",
                        "params": {"eps": 0.05, "delta": 0.1,
                                   "weighted": False},
                        "per_stream_of_source": True, "n_streams": 16})
        assert r.ok, r.error
        return eng

    def leaf(eng, kind):
        return jax.tree.leaves(eng.stacks[kind].state)[0]

    src = mk()
    rng = np.random.RandomState(0)
    sids = rng.randint(0, 16, 512).astype(np.int64)
    src.ingest(sids, np.ones(512, np.float32))
    src.flush()
    kind = src.entries["pt/0"].kind_key

    # cross-slice relocation on the sharded stack stays pinned + correct
    cap = src.stacks[kind].capacity
    r1 = src.entries["pt/1"].row
    assert src.migrate_rows(kind, {r1: cap - 1}) == 1
    assert leaf(src, kind).sharding.spec[0] == "data"
    q = src.handle({"type": "adhoc", "request_id": "q",
                    "synopsis_id": "pt/1", "query": {"items": [1]}})
    true_count = float(np.count_nonzero(sids == 1))
    assert q.ok and float(np.asarray(q.value)[0]) == true_count

    # extract -> implant onto a SECOND mesh engine, byte-identical state
    ids = [f"pt/{s}" for s in range(16)]
    package = src.extract_synopses(ids, remove=False)
    dst = mk()
    dst.extract_synopses(ids, remove=True)      # vacate the fresh builds
    dst.implant_synopses(package)
    dst.flush()
    kd = dst.entries["pt/0"].kind_key
    assert leaf(dst, kd).sharding.spec[0] == "data"
    for s in range(16):
        a = src.handle({"type": "adhoc", "request_id": "a",
                        "synopsis_id": f"pt/{s}", "query": {"items": [s]}})
        b = dst.handle({"type": "adhoc", "request_id": "b",
                        "synopsis_id": f"pt/{s}", "query": {"items": [s]}})
        assert a.ok and b.ok
        np.testing.assert_array_equal(np.asarray(a.value),
                                      np.asarray(b.value))
    # further ingest accumulates at the new home on the new mesh
    dst.ingest(np.full(8, 3, np.int64), np.ones(8, np.float32))
    dst.flush()
    print("OK")
""")


@pytest.mark.slow
def test_migration_plane_on_8_device_mesh():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", _MESH_SCRIPT],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "OK" in out.stdout
