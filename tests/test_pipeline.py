"""Pipelined blue path: bounded async ingest queue semantics (PR 4).

Covers the tentpole contract:
  * eager-vs-pipelined EXACT equivalence per registered kind —
    byte-identical synopsis state and identical continuous responses
    (ids and values, in the same order);
  * fencing — stop/grow/snapshot/merge mid-flight retire every pending
    batch before mutating stacks or routing tables, and ``query_many``
    drains before reading state;
  * flush — the explicit barrier drains ALL pending batches; monotonic
    batch-counter request ids are preserved under overlap;
  * satellites — bounded ``continuous_out`` with a dropped-count stat,
    in-flight depth probes in ``repro.tracing``, ingest length-mismatch
    guard, JSON ``ingest``/``flush`` requests with batch-counter acks,
    and the launch-layer JSON-lines server.
"""
import io
import json
import tempfile

import numpy as np
import jax
import pytest

from repro import core, tracing
from repro.service import SDE

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

_N_STREAMS = 6


def _build_continuous(eng: SDE, kind_name: str):
    r = eng.handle({"type": "build", "request_id": f"b-{kind_name}",
                    "synopsis_id": kind_name, "kind": kind_name,
                    "params": _PARAMS[kind_name],
                    "per_stream_of_source": True,
                    "n_streams": _N_STREAMS, "continuous": True})
    assert r.ok, r.error


def _batches(n_batches=5, tuples=24, seed=0):
    # tuples <= 32: the coreset kind ingests at most bucket_size points
    # per batch, and every other kind is size-agnostic
    rng = np.random.RandomState(seed)
    return [(rng.randint(0, _N_STREAMS, tuples).astype(np.uint32),
             rng.uniform(0.5, 2.0, tuples).astype(np.float32))
            for _ in range(n_batches)]


def _assert_engines_equal(eager: SDE, piped: SDE):
    """Byte-identical stack state + identical continuous responses."""
    assert list(eager.stacks) == list(piped.stacks)
    for kind in eager.stacks:
        for a, b in zip(jax.tree.leaves(eager.stacks[kind].state),
                        jax.tree.leaves(piped.stacks[kind].state)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert len(eager.continuous_out) == len(piped.continuous_out)
    for ra, rb in zip(eager.continuous_out, piped.continuous_out):
        assert ra.request_id == rb.request_id
        assert ra.synopsis_id == rb.synopsis_id
        jax.tree.map(
            lambda x, y: np.testing.assert_array_equal(
                np.asarray(x), np.asarray(y)), ra.value, rb.value)


# ---------------------------------------------------------------------------
# tentpole: exact equivalence per kind
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("kind_name", sorted(core.known_kinds()))
def test_eager_vs_pipelined_equivalence(kind_name):
    eager = SDE(pipelined=False)
    piped = SDE(pipelined=True)
    for eng in (eager, piped):
        _build_continuous(eng, kind_name)
    for sids, vals in _batches():
        eager.ingest(sids, vals)
        piped.ingest(sids, vals)
    assert piped.pending_batches > 0     # emission actually deferred
    piped.flush()
    _assert_engines_equal(eager, piped)


def test_equivalence_multi_kind_single_engine():
    """Several kinds (incl. the time-series path) in ONE engine, many
    batches: interleaved per-kind dispatches retire in ingest order."""
    names = ["countmin", "hyperloglog", "dft"]
    eager = SDE(pipelined=False)
    piped = SDE(pipelined=True)
    for eng in (eager, piped):
        for name in names:
            _build_continuous(eng, name)
    for sids, vals in _batches(n_batches=7, seed=3):
        eager.ingest(sids, vals)
        piped.ingest(sids, vals)
    piped.flush()
    _assert_engines_equal(eager, piped)


# ---------------------------------------------------------------------------
# tentpole: no host sync inside pipelined ingest
# ---------------------------------------------------------------------------
def test_pipelined_ingest_defers_materialization(monkeypatch):
    """A pipelined ingest must NOT materialize estimate outputs to host
    (the eager path's ``jax.tree.map(np.asarray, out)`` sync)."""
    piped = SDE(pipelined=True)
    _build_continuous(piped, "hyperloglog")
    sids, vals = _batches(1)[0]
    piped.ingest(sids, vals)             # warm up: plan + compile
    piped.flush()

    synced = []
    orig = np.asarray

    def spying_asarray(x, *a, **k):
        if isinstance(x, jax.Array):
            synced.append(type(x).__name__)
        return orig(x, *a, **k)

    monkeypatch.setattr(np, "asarray", spying_asarray)
    piped.ingest(sids, vals)
    assert synced == []                  # ingest returned with zero syncs
    monkeypatch.undo()
    assert piped.flush() == 1            # the sync happens at the barrier
    assert len(piped.continuous_out) == 2 * _N_STREAMS


# ---------------------------------------------------------------------------
# bounded queue: depth, retirement on overflow, flush drains all
# ---------------------------------------------------------------------------
@pytest.mark.smoke
def test_bounded_depth_and_flush_drains_all():
    eng = SDE(pipelined=True, pipeline_depth=2)
    _build_continuous(eng, "hyperloglog")
    batches = _batches(n_batches=3)
    eng.ingest(*batches[0])
    assert eng.pending_batches == 1 and len(eng.continuous_out) == 0
    eng.ingest(*batches[1])
    assert eng.pending_batches == 2 and len(eng.continuous_out) == 0
    # the 3rd submission exceeds depth 2: batch 1 retires, 2+3 in flight
    eng.ingest(*batches[2])
    assert eng.pending_batches == 2
    assert len(eng.continuous_out) == _N_STREAMS
    assert all(r.request_id.endswith("/1") for r in eng.continuous_out)
    # explicit barrier drains everything, oldest first; idempotent
    assert eng.flush() == 2
    assert eng.pending_batches == 0
    assert len(eng.continuous_out) == 3 * _N_STREAMS
    assert eng.flush() == 0


def test_monotonic_batch_ids_under_overlap():
    eng = SDE(pipelined=True, pipeline_depth=2)
    _build_continuous(eng, "hyperloglog")
    n = 5
    got_ids = [eng.ingest(*b) for b in _batches(n_batches=n)]
    assert got_ids == list(range(1, n + 1))
    eng.flush()
    rids = [r.request_id for r in eng.continuous_out]
    assert len(set(rids)) == len(rids)
    # responses surface in ingest order with the batch counter intact
    batch_of = [int(r.rsplit("/", 1)[1]) for r in rids]
    assert batch_of == sorted(batch_of)
    assert set(batch_of) == set(range(1, n + 1))


def test_in_flight_depth_probes():
    tag = "probe-site"
    tracing.PIPELINE_IN_FLIGHT.pop(tag, None)
    tracing.PIPELINE_MAX_IN_FLIGHT.pop(tag, None)
    eng = SDE(site=tag, pipelined=True, pipeline_depth=2)
    _build_continuous(eng, "hyperloglog")
    for b in _batches(n_batches=4):
        eng.ingest(*b)
    # the bounded queue really double-buffers: depth reached, never beyond
    assert tracing.PIPELINE_MAX_IN_FLIGHT[tag] == 2
    assert tracing.PIPELINE_IN_FLIGHT[tag] == 2
    eng.flush()
    assert tracing.PIPELINE_IN_FLIGHT[tag] == 0
    assert tracing.PIPELINE_MAX_IN_FLIGHT[tag] == 2


def test_bad_pipeline_depth_rejected():
    with pytest.raises(ValueError, match="depth"):
        SDE(pipelined=True, pipeline_depth=0)


# ---------------------------------------------------------------------------
# fencing: lifecycle events drain the pipeline before mutating state
# ---------------------------------------------------------------------------
def test_stop_fences_mid_flight():
    eng = SDE(pipelined=True)
    _build_continuous(eng, "hyperloglog")
    eng.ingest(*_batches(1)[0])
    assert eng.pending_batches == 1
    r = eng.handle({"type": "stop", "request_id": "s",
                    "synopsis_id": "hyperloglog"})
    assert r.ok, r.error
    # the stopped synopses' final responses landed BEFORE the rows freed
    assert eng.pending_batches == 0
    assert len(eng.continuous_out) == _N_STREAMS


def test_build_grow_fences_mid_flight():
    """A build that doubles stack capacity mid-flight must retire the
    pending batches first — and stay exactly equivalent to eager."""
    eager = SDE(pipelined=False)
    piped = SDE(pipelined=True)
    for eng in (eager, piped):
        _build_continuous(eng, "countmin")
    batches = _batches(n_batches=2, seed=5)
    for b in batches:
        eager.ingest(*b)
        piped.ingest(*b)
    assert piped.pending_batches == 2
    # 100 more routed synopses of the same kind (fresh stream ids —
    # each id routes to one row per kind): forces 64 -> 128 growth
    grow = {"type": "build", "request_id": "g", "synopsis_id": "more",
            "kind": "countmin", "params": _PARAMS["countmin"],
            "per_stream_of_source": True,
            "stream_ids": list(range(100, 200))}
    assert eager.handle(grow).ok
    assert piped.handle(grow).ok
    assert piped.pending_batches == 0
    assert piped.stacks[next(iter(piped.stacks))].capacity == 128
    for b in _batches(n_batches=2, seed=6):
        eager.ingest(*b)
        piped.ingest(*b)
    piped.flush()
    _assert_engines_equal(eager, piped)


def test_snapshot_fences_mid_flight():
    eager = SDE(pipelined=False)
    piped = SDE(pipelined=True)
    for eng in (eager, piped):
        _build_continuous(eng, "countmin")
    for b in _batches(n_batches=3, seed=7):
        eager.ingest(*b)
        piped.ingest(*b)
    assert piped.pending_batches > 0
    with tempfile.TemporaryDirectory() as d:
        piped.snapshot(d, 1)
        # snapshot is itself a fence ...
        assert piped.pending_batches == 0
        restored = SDE.restore(d)
    # ... and the checkpointed state equals the eager engine's
    piped.flush()
    _assert_engines_equal(eager, piped)
    for kind in eager.stacks:
        for a, b in zip(jax.tree.leaves(eager.stacks[kind].state),
                        jax.tree.leaves(restored.stacks[kind].state)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_query_many_fences_mid_flight():
    eng = SDE(pipelined=True)
    _build_continuous(eng, "countmin")
    sids, vals = _batches(1, seed=9)[0]
    eng.ingest(sids, np.ones_like(vals))
    assert eng.pending_batches == 1
    q = eng.handle({"type": "adhoc", "request_id": "q",
                    "synopsis_id": "countmin/2", "query": {"items": [2]}})
    assert q.ok, q.error
    # the read fenced first: continuous responses precede the answer ...
    assert eng.pending_batches == 0
    assert len(eng.continuous_out) == _N_STREAMS
    # ... and the answer observes the in-flight batch (exact CM count)
    assert float(q.value[0]) == float((sids == 2).sum())


def test_merge_from_fences_both_engines():
    a = SDE(pipelined=True)
    b = SDE(site="site-b", pipelined=True)
    for eng in (a, b):
        _build_continuous(eng, "hyperloglog")
    a.ingest(np.arange(0, 40, dtype=np.uint32) % _N_STREAMS,
             np.ones(40, np.float32))
    b.ingest(np.arange(0, 40, dtype=np.uint32) % _N_STREAMS,
             np.ones(40, np.float32))
    assert a.pending_batches == 1 and b.pending_batches == 1
    a.merge_from(b)
    assert a.pending_batches == 0 and b.pending_batches == 0
    assert len(a.continuous_out) == _N_STREAMS
    assert len(b.continuous_out) == _N_STREAMS


# ---------------------------------------------------------------------------
# satellite: bounded continuous_out
# ---------------------------------------------------------------------------
def test_continuous_out_bounded_with_dropped_stat():
    eng = SDE(pipelined=False, continuous_out_cap=3)
    r = eng.handle({"type": "build", "request_id": "b", "synopsis_id":
                    "h", "kind": "hyperloglog", "params": {"rse": 0.05},
                    "continuous": True})
    assert r.ok, r.error
    for b in _batches(n_batches=5):
        eng.ingest(*b)
    # newest 3 kept, oldest 2 dropped (and counted)
    assert len(eng.continuous_out) == 3
    assert eng.continuous_out.dropped == 2
    assert [r.request_id for r in eng.continuous_out] == \
        ["cq/h/3", "cq/h/4", "cq/h/5"]
    # cap=None / 0 means unbounded
    assert SDE(continuous_out_cap=None).continuous_out.maxlen is None
    assert SDE(continuous_out_cap=0).continuous_out.maxlen is None


# ---------------------------------------------------------------------------
# satellite: ingest input hygiene
# ---------------------------------------------------------------------------
def test_ingest_length_mismatch_is_clear_error():
    eng = SDE()
    with pytest.raises(ValueError, match="2 stream_ids vs 3 values"):
        eng.ingest([1, 2], [1.0, 2.0, 3.0])
    # a wrong-length mask is rejected too (never silently broadcast)
    with pytest.raises(ValueError, match="3 stream_ids vs 1 mask"):
        eng.ingest([1, 2, 3], [1.0, 2.0, 3.0], mask=[False])
    # nothing committed: the counters never moved
    assert eng.tuples_ingested == 0 and eng.batches_ingested == 0
    # the JSON path surfaces the same error as a response, not a crash
    r = eng.handle({"type": "ingest", "request_id": "i",
                    "stream_ids": [1, 2], "values": [1.0]})
    assert not r.ok and "stream_ids" in r.error


def test_ingest_no_copy_for_float32_values():
    """float32 input must flow through np.asarray un-copied."""
    vals = np.ones(8, np.float32)
    assert np.asarray(vals, np.float32) is vals          # the invariant
    eng = SDE()
    _build_continuous(eng, "countmin")
    eng.ingest(np.arange(8, dtype=np.uint32) % _N_STREAMS, vals)
    assert eng.tuples_ingested == 8


# ---------------------------------------------------------------------------
# satellite: JSON ingest/flush requests + the launch JSON-lines server
# ---------------------------------------------------------------------------
@pytest.mark.smoke
def test_json_ingest_ack_carries_batch_counter():
    eng = SDE(pipelined=True)
    _build_continuous(eng, "hyperloglog")
    a1 = eng.handle({"type": "ingest", "request_id": "i1",
                     "stream_ids": [0, 1, 2], "values": [1.0, 1.0, 1.0]})
    a2 = eng.handle({"type": "ingest", "request_id": "i2",
                     "stream_ids": [3, 4], "values": [1.0, 1.0],
                     "mask": [True, False]})
    assert a1.ok and a1.value["batch"] == 1
    assert a2.ok and a2.value["batch"] == 2
    assert a2.value["tuples_ingested"] == 4      # one tuple masked out
    assert a2.value["in_flight"] == 2
    fl = eng.handle({"type": "flush", "request_id": "f"})
    assert fl.ok and fl.value["drained"] == 2
    assert fl.value["batches_ingested"] == 2
    assert len(eng.continuous_out) == 2 * _N_STREAMS
    # flush on an idle pipeline (and on eager engines) is a cheap no-op
    assert eng.handle({"type": "flush", "request_id": "f2"}
                      ).value["drained"] == 0
    assert SDE().flush() == 0


def test_sde_server_json_lines_roundtrip():
    from repro.launch.sde_server import serve_lines
    requests = [
        {"type": "build", "request_id": "b", "synopsis_id": "h",
         "kind": "hyperloglog", "params": {"rse": 0.05},
         "continuous": True},
        {"type": "ingest", "request_id": "i1",
         "stream_ids": [1, 2, 3], "values": [1.0, 1.0, 1.0]},
        {"type": "ingest", "request_id": "i2",
         "stream_ids": [4, 5], "values": [1.0, 1.0]},
        {"type": "adhoc", "request_id": "q", "synopsis_id": "h"},
    ]
    out = io.StringIO()
    n = serve_lines((json.dumps(r) for r in requests),
                    SDE(pipelined=True), out=out)
    assert n == len(requests)
    resp = [json.loads(line) for line in out.getvalue().splitlines()]
    by_id = {r["request_id"]: r for r in resp}
    assert by_id["i1"]["value"]["batch"] == 1
    assert by_id["i2"]["value"]["batch"] == 2
    # both batches' continuous responses surfaced (the ad-hoc query
    # fences), keyed by the acked batch counters, in ingest order
    cq = [r["request_id"] for r in resp if r["request_id"].startswith("cq/")]
    assert cq == ["cq/h/1", "cq/h/2"]
    assert by_id["q"]["ok"]
    # EOF flushes: a trailing un-fenced ingest still emits
    out2 = io.StringIO()
    serve_lines((json.dumps(r) for r in requests[:2]),
                SDE(pipelined=True), out=out2)
    assert any(line.startswith('{"request_id": "cq/h/1"')
               or '"cq/h/1"' in line for line in out2.getvalue().splitlines())
