"""Sharded red path: jitted batched stacked-estimate programs.

Covers the PR 2 contract:
  * per-kind correctness — ``stacked_estimate`` over a row batch equals the
    scalar ``estimate`` per row for EVERY registered kind;
  * scale — ``query_many`` answers N queries against a kind with exactly
    ONE jitted dispatch per kind per query batch, and repeated same-shape
    batches reuse ONE compiled program (trace-count probe);
  * continuous queries — emission is one stacked-estimate program per kind
    per ingest batch, never a per-entry ``stacked_row`` gather, including
    on a multi-device ``synopsis``-sharded mesh;
  * satellites — actual per-row status bytes, stream-id routing guard.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import core, tracing
from repro.core import batched
from repro.service import SDE, Federation, api
from repro.service import engine as engine_mod


# ---------------------------------------------------------------------------
# per-kind equivalence: stacked_estimate == per-row scalar estimate
# ---------------------------------------------------------------------------
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


def _query_args(kind_name, n, rng):
    """Per-query args with a leading [n] axis (each query distinct)."""
    if kind_name in ("countmin", "bloom", "lossy_counting",
                     "sticky_sampling"):
        return (jnp.asarray(rng.randint(0, 50, (n, 3)).astype(np.uint32)),)
    if kind_name == "gk_quantiles":
        return (jnp.asarray(rng.uniform(0.0, 1.0, (n, 4)).astype(
            np.float32)),)
    return ()


@pytest.mark.parametrize("kind_name", sorted(core.known_kinds()))
def test_stacked_estimate_matches_per_row(kind_name):
    kind = core.make_kind(kind_name, **_PARAMS[kind_name])
    cap = 8
    state = batched.stacked_init(kind, cap)
    rng = np.random.RandomState(0)
    t = 32
    syn = jnp.asarray(rng.randint(0, cap, t).astype(np.int32))
    items = jnp.asarray(rng.randint(0, 50, t).astype(np.uint32))
    vals = jnp.asarray(rng.uniform(0.5, 2.0, t).astype(np.float32))
    mask = jnp.ones(t, bool)
    state = batched.stacked_update(kind, state, syn, items, vals, mask)

    row_list = [5, 0, 3, 5]        # duplicates allowed: N queries, one row
    rows = jnp.asarray(row_list, jnp.int32)
    args = _query_args(kind_name, len(row_list), rng)
    out = batched.stacked_estimate(kind, state, rows, *args)
    out = jax.tree.map(np.asarray, out)
    for i, r in enumerate(row_list):
        single = kind.estimate(batched.stacked_row(state, r),
                               *[a[i] for a in args])
        jax.tree.map(
            lambda g, s: np.testing.assert_allclose(
                np.asarray(g), np.asarray(s), rtol=1e-5, atol=1e-5),
            jax.tree.map(lambda x: x[i], out),
            jax.tree.map(np.asarray, single))


def test_pane_window_stacked_estimate_matches_per_row():
    """The window wrapper (not in the registry) batches too: pane merge +
    inner estimate vmapped over the gathered rows."""
    kind = core.PaneWindow(core.CountMin(eps=0.05, delta=0.1,
                                         weighted=False),
                           n_panes=2, pane_span=64)
    cap = 4
    state = batched.stacked_init(kind, cap)
    rng = np.random.RandomState(0)
    syn = jnp.asarray(rng.randint(0, cap, 32).astype(np.int32))
    items = jnp.asarray(rng.randint(0, 20, 32).astype(np.uint32))
    ones = jnp.ones(32, jnp.float32)
    state = batched.stacked_update(kind, state, syn, items, ones,
                                   jnp.ones(32, bool))
    rows = jnp.asarray([2, 0], jnp.int32)
    q_items = jnp.asarray(rng.randint(0, 20, (2, 3)).astype(np.uint32))
    out = np.asarray(batched.stacked_estimate(kind, state, rows, q_items))
    for i, r in enumerate([2, 0]):
        single = kind.estimate(batched.stacked_row(state, r), q_items[i])
        np.testing.assert_allclose(out[i], np.asarray(single))


def test_batched_estimate_is_one_program():
    """jax.make_jaxpr probe: ONE program answers N queries with their own
    per-query items — the batched output aval carries the [N, I] axes."""
    kind = core.CountMin(eps=0.031, delta=0.1, weighted=False)
    state = batched.stacked_init(kind, 16)
    rows = jnp.arange(8, dtype=jnp.int32)
    items = jnp.zeros((8, 4), jnp.uint32)
    jaxpr = jax.make_jaxpr(
        lambda s, r, it: batched.stacked_estimate(kind, s, r, it))(
            state, rows, items)
    assert jaxpr.out_avals[0].shape == (8, 4)


# ---------------------------------------------------------------------------
# query_many: one dispatch per kind per batch, one compiled program
# ---------------------------------------------------------------------------
@pytest.mark.smoke
def test_query_many_single_dispatch_per_kind():
    eng = SDE()
    # unique params => unique jit-cache key, so the trace count below is
    # not satisfied by a program compiled in another test
    r = eng.handle({"type": "build", "request_id": "b1",
                    "synopsis_id": "cm", "kind": "countmin",
                    "params": {"eps": 0.017, "delta": 0.1,
                               "weighted": False},
                    "per_stream_of_source": True, "n_streams": 50})
    assert r.ok, r.error
    r = eng.handle({"type": "build", "request_id": "b2",
                    "synopsis_id": "hll", "kind": "hyperloglog",
                    "params": {"rse": 0.0417}})
    assert r.ok, r.error
    rng = np.random.RandomState(0)
    sids = rng.randint(0, 50, 512).astype(np.uint32)
    eng.ingest(sids, np.ones(512, np.float32))

    reqs = [api.AdHocQuery(request_id=f"q{s}", synopsis_id=f"cm/{s}",
                           query={"items": [int(s)]})
            for s in range(20)]
    reqs.append(api.AdHocQuery(request_id="qh", synopsis_id="hll"))
    tracing.DISPATCH_COUNT.clear()
    tracing.TRACE_COUNT.clear()
    n_batches = 3
    for _ in range(n_batches):
        rs = eng.query_many(reqs)
    # N queries against a kind = ONE dispatch for that kind per batch
    assert tracing.DISPATCH_COUNT["CountMin"] == n_batches
    assert tracing.DISPATCH_COUNT["HyperLogLog"] == n_batches
    # ... and every same-shape batch reuses ONE compiled program
    assert tracing.TRACE_COUNT["CountMin"] == 1
    assert tracing.TRACE_COUNT["HyperLogLog"] == 1
    # correctness: unweighted per-stream CM counts are exact
    for s in range(20):
        assert float(rs[s].value[0]) == float((sids == s).sum()), s
    assert abs(float(rs[20].value) - 50) < 10


def test_query_many_mixed_arg_lengths_and_errors():
    eng = SDE()
    eng.handle({"type": "build", "request_id": "b", "synopsis_id": "cm",
                "kind": "countmin",
                "params": {"eps": 0.02, "delta": 0.1, "weighted": False},
                "per_stream_of_source": True, "n_streams": 8})
    sids = np.arange(8, dtype=np.uint32).repeat(16)
    eng.ingest(sids, np.ones(len(sids), np.float32))
    rs = eng.query_many([
        api.AdHocQuery(request_id="a", synopsis_id="cm/1",
                       query={"items": [1]}),
        api.AdHocQuery(request_id="b", synopsis_id="nope"),
        api.AdHocQuery(request_id="c", synopsis_id="cm/2",
                       query={"items": [2, 3, 4]}),
    ])
    assert rs[0].ok and len(rs[0].value) == 1
    assert float(rs[0].value[0]) == 16.0
    assert not rs[1].ok and "unknown synopsis" in rs[1].error
    # padded arg width is sliced back to the query's own length
    assert rs[2].ok and len(rs[2].value) == 3
    assert float(rs[2].value[0]) == 16.0
    # one query with uncoercible args fails alone, not the whole batch
    rs = eng.query_many([
        api.AdHocQuery(request_id="good", synopsis_id="cm/1",
                       query={"items": [1]}),
        api.AdHocQuery(request_id="bad", synopsis_id="cm/2",
                       query={"items": ["oops"]}),
    ])
    assert rs[0].ok and float(rs[0].value[0]) == 16.0
    assert not rs[1].ok and "items" in rs[1].error
    # ... and so does one whose query field is not an object at all
    rs = eng.query_many([
        api.AdHocQuery(request_id="bad2", synopsis_id="cm/2", query=5),
        api.AdHocQuery(request_id="good2", synopsis_id="cm/3",
                       query={"items": [3]}),
    ])
    assert not rs[0].ok and "must be an object" in rs[0].error
    assert rs[1].ok and float(rs[1].value[0]) == 16.0


def test_query_many_json_request():
    eng = SDE()
    eng.handle({"type": "build", "request_id": "b", "synopsis_id": "h",
                "kind": "hyperloglog", "params": {"rse": 0.05}})
    eng.ingest(np.arange(200, dtype=np.uint32), np.ones(200, np.float32))
    resp = eng.handle({"type": "query_many", "request_id": "m",
                       "queries": [{"synopsis_id": "h"},
                                   {"synopsis_id": "h"}]})
    assert resp.ok
    assert len(resp.value) == 2
    for sub in resp.value:
        assert sub["ok"] and abs(float(sub["value"]) - 200) < 40
    # a non-dict entry fails alone; the rest of the batch still answers
    resp = eng.handle({"type": "query_many", "request_id": "m2",
                       "queries": [{"synopsis_id": "h"}, "oops"]})
    assert not resp.ok and len(resp.value) == 2
    assert resp.error == "1/2 queries failed"
    assert resp.value[0]["ok"]
    assert abs(float(resp.value[0]["value"]) - 200) < 40
    assert not resp.value[1]["ok"]
    assert "must be an object" in resp.value[1]["error"]
    # falsy non-dict query fields are rejected too, not coerced to {}
    resp = eng.handle({"type": "query_many", "request_id": "m3",
                       "queries": [{"synopsis_id": "h", "query": 0}]})
    assert not resp.ok
    assert "must be an object" in resp.value[0]["error"]


def test_federated_query_single_fused_dispatch():
    fed = Federation(["eu", "us"])
    fed.broadcast({"type": "build", "request_id": "f", "synopsis_id": "h",
                   "kind": "hyperloglog", "params": {"rse": 0.03},
                   "federated": True, "responsible_site": "eu"})
    fed.sdes["eu"].ingest(np.arange(0, 2000, dtype=np.uint32),
                          np.ones(2000, np.float32))
    fed.sdes["us"].ingest(np.arange(1000, 3000, dtype=np.uint32),
                          np.ones(2000, np.float32))
    tracing.DISPATCH_COUNT.clear()
    est = float(fed.query_federated("h", {}, "eu"))
    # merge-over-sites + estimate fused into one program
    assert tracing.DISPATCH_COUNT["HyperLogLog"] == 1
    assert abs(est - 3000) / 3000 < 0.1


# ---------------------------------------------------------------------------
# continuous queries: one program per kind per ingest, never stacked_row
# ---------------------------------------------------------------------------
def test_continuous_emission_batched_no_stacked_row(monkeypatch):
    eng = SDE()
    r = eng.handle({"type": "build", "request_id": "b1",
                    "synopsis_id": "cm", "kind": "countmin",
                    "params": {"eps": 0.02, "delta": 0.1,
                               "weighted": False},
                    "per_stream_of_source": True, "n_streams": 10,
                    "continuous": True})
    assert r.ok, r.error
    r = eng.handle({"type": "build", "request_id": "b2",
                    "synopsis_id": "h", "kind": "hyperloglog",
                    "params": {"rse": 0.05}, "continuous": True})
    assert r.ok, r.error

    def boom(*a, **k):
        raise AssertionError("red path gathered a row to the host")

    monkeypatch.setattr(batched, "stacked_row", boom)
    plans = []
    orig_plan = engine_mod._plan_queries
    monkeypatch.setattr(engine_mod, "_plan_queries",
                        lambda *a: plans.append(1) or orig_plan(*a))
    tracing.DISPATCH_COUNT.clear()
    sids = np.arange(10, dtype=np.uint32).repeat(20)
    eng.ingest(sids, np.ones(len(sids), np.float32))
    # 10 per-stream CMs + 1 HLL, all continuous
    assert len(eng.continuous_out) == 11
    assert tracing.DISPATCH_COUNT["CountMin"] == 1
    assert tracing.DISPATCH_COUNT["HyperLogLog"] == 1
    # the grouping + arg planning is cached: further ingests re-dispatch
    # without re-planning on the host
    eng.ingest(sids, np.ones(len(sids), np.float32))
    assert len(eng.continuous_out) == 22
    assert len(plans) == 2          # one plan per kind, first ingest only
    hll_out = [o for o in eng.continuous_out if o.synopsis_id == "h"]
    assert abs(float(hll_out[0].value) - 10) < 5
    # lifecycle changes rebuild the grouping
    eng.handle({"type": "stop", "request_id": "s", "synopsis_id": "h"})
    eng.ingest(sids, np.ones(len(sids), np.float32))
    assert len(eng.continuous_out) == 22 + 10
    assert len(plans) == 3          # replanned once after the stop


_MESH_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np, jax
    from jax.sharding import NamedSharding
    from repro import tracing
    from repro.service import SDE, api
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((4, 2), ("data", "model"))
    eng = SDE(mesh=mesh)
    eng.handle({"type": "build", "request_id": "b", "synopsis_id": "cm",
                "kind": "countmin",
                "params": {"eps": 0.01, "delta": 0.05, "weighted": False},
                "per_stream_of_source": True, "n_streams": 12,
                "continuous": True})
    eng.handle({"type": "build", "request_id": "b2", "synopsis_id": "h",
                "kind": "hyperloglog", "params": {"rse": 0.03},
                "continuous": True})
    rng = np.random.RandomState(0)
    sids = rng.randint(0, 12, 512).astype(np.uint32)
    tracing.DISPATCH_COUNT.clear()
    n_batches = 3
    for _ in range(n_batches):
        eng.ingest(sids, np.ones(512, np.float32))
    # all continuous queries of a kind = ONE estimate dispatch per ingest
    assert tracing.DISPATCH_COUNT["CountMin"] == n_batches
    assert tracing.DISPATCH_COUNT["HyperLogLog"] == n_batches
    assert len(eng.continuous_out) == n_batches * 13
    # state stays row-sharded over the synopsis axis after queries
    for stack in eng.stacks.values():
        for leaf in jax.tree.leaves(stack.state):
            assert isinstance(leaf.sharding, NamedSharding)
            assert leaf.sharding.spec and leaf.sharding.spec[0] == "data"
    # batched ad-hoc values against the sharded stack are exact
    reqs = [api.AdHocQuery(request_id=f"q{s}", synopsis_id=f"cm/{s}",
                           query={"items": [int(s)]}) for s in range(12)]
    rs = eng.query_many(reqs)
    for s, r in enumerate(rs):
        got = float(r.value[0])
        want = float(n_batches) * float((sids == s).sum())
        assert got == want, (s, got, want)
    last = [o for o in eng.continuous_out if o.synopsis_id == "h"][-1]
    assert abs(float(last.value) - 12) < 6
    print("OK")
""")


def test_continuous_queries_on_multidevice_mesh():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", _MESH_SCRIPT],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "OK" in out.stdout


# ---------------------------------------------------------------------------
# satellites: actual per-row bytes in status, stream-id routing guard
# ---------------------------------------------------------------------------
def test_status_reports_actual_row_bytes():
    eng = SDE()
    r = eng.handle({"type": "build", "request_id": "b", "synopsis_id":
                    "bf", "kind": "bloom",
                    "params": {"n_elements": 100, "fpr": 0.01},
                    "stream_id": 1})
    assert r.ok, r.error
    st = eng.handle({"type": "status", "request_id": "s"})
    kind = eng.entries["bf"].kind_key
    # bits are int32 lanes in the stacked state: 4 bytes per bit, not the
    # packed n_bits/8 the abstract kind declares
    assert st.value["bf"]["memory_bytes"] == kind.n_bits * 4
    assert st.value["bf"]["memory_bytes"] != kind.memory_bytes()
    # and the row slice accounts for the whole engine state
    stack = eng.stacks[kind]
    assert stack.row_bytes() * stack.capacity == eng.memory_bytes()


def test_register_stream_id_guard():
    """Hashed routing: ids past the old 2**16 dense-table cap build and
    route fine; only unrepresentable ids (negative / >= 2**63) are
    rejected — and rejected BEFORE committing anything."""
    eng = SDE()
    for bad in (-1, 1 << 63, (1 << 63) + 5):
        r = eng.handle({"type": "build", "request_id": "b",
                        "synopsis_id": f"x{bad}", "kind": "hyperloglog",
                        "params": {"rse": 0.05}, "stream_id": bad})
        assert not r.ok and "2**63" in r.error, bad
    # a per-stream build with one unrepresentable id fails atomically
    r = eng.handle({"type": "build", "request_id": "b", "synopsis_id":
                    "big", "kind": "hyperloglog", "params": {"rse": 0.05},
                    "per_stream_of_source": True,
                    "stream_ids": [7, -3]})
    assert not r.ok and "2**63" in r.error
    assert not eng.entries and not eng.stacks   # nothing committed
    # ids far past the old 65536-slot table are accepted and routable
    sid = (1 << 16) + 12345
    r = eng.handle({"type": "build", "request_id": "b", "synopsis_id":
                    "ok", "kind": "hyperloglog", "params": {"rse": 0.05},
                    "stream_id": sid})
    assert r.ok, r.error
    eng.ingest(np.full(64, sid, np.int64), np.ones(64, np.float32))
    q = eng.handle({"type": "adhoc", "request_id": "q", "synopsis_id":
                    "ok"})
    assert float(q.value) > 0
    # tuples of OTHER high ids update nothing here (no clamping onto
    # this synopsis) but still count as ingested — they are valid data
    before = float(q.value)
    seen = eng.tuples_ingested
    eng.ingest(np.full(8, sid + 1, np.int64), np.ones(8, np.float32))
    assert eng.tuples_ingested == seen + 8
    # negative ids are unrepresentable: dropped, not counted
    eng.ingest(np.full(8, -5, np.int64), np.ones(8, np.float32))
    assert eng.tuples_ingested == seen + 8
    q = eng.handle({"type": "adhoc", "request_id": "q2", "synopsis_id":
                    "ok"})
    assert float(q.value) == before


# ---------------------------------------------------------------------------
# balancer satellite: workload estimation rides the batched path
# ---------------------------------------------------------------------------
def test_balancer_uses_batched_query_path():
    from repro.service.balancer import estimate_workload
    eng = SDE()
    eng.handle({"type": "build", "request_id": "b1", "synopsis_id":
                "card", "kind": "hyperloglog", "params": {"rse": 0.03}})
    eng.handle({"type": "build", "request_id": "b2", "synopsis_id":
                "freq", "kind": "countmin",
                "params": {"eps": 0.005, "delta": 0.01,
                           "weighted": False}})
    sids = np.arange(32, dtype=np.uint32).repeat(8)
    eng.ingest(sids, np.ones(len(sids), np.float32))
    tracing.DISPATCH_COUNT.clear()
    n_active, loads = estimate_workload(eng, "card", "freq",
                                        list(range(32)))
    # the 32 per-stream loads are ONE CM dispatch, not 32
    assert tracing.DISPATCH_COUNT["CountMin"] == 1
    assert tracing.DISPATCH_COUNT["HyperLogLog"] == 1
    assert abs(n_active - 32) < 6
    np.testing.assert_allclose(loads, 8.0)
    with pytest.raises(KeyError):
        estimate_workload(eng, "missing", "freq", [0])
