"""Real-collective federation: per-kind merge-equivalence test matrix.

PR 5 contract:
  * ``federated.merge_over_axis`` over an N-way axis equals the host-side
    responsible-site fold (``merge_reduce`` — the legacy oracle) bit-for-
    bit for EVERY registered kind, with sum/max/gather/fresh all
    exercised. Validated in-process with vmap(axis_name=...) collectives
    (psum/pmax/all_gather/axis_index work on one device under vmap) and
    on a real 8-device mesh in a subprocess.
  * the ``merge_mode == "fresh"`` branch performs the documented
    keep-max-count replica selection (DFT), ties to the lowest site.
  * ``Federation(mesh=...)`` answers ``query_federated`` as ONE compiled
    collective program (TRACE/DISPATCH probes on
    ``kernels.ops.estimate_collective``) byte-identical to the legacy
    host-merge Federation oracle, with collective operand bytes <=
    host-merge shipped bytes (fig 5d).
  * hypothesis properties: site merging is order-insensitive for sum/max
    kinds, and the mesh path equals the host oracle on random
    builds/ingests.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import jax
import pytest

from repro import core, tracing
from repro.core import federated
from repro.service import Federation

N_SITES = 4

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

# per-kind federated query args (kinds not listed take no args)
_QUERY = {
    "countmin": {"items": [3, 7, 11]},
    "bloom": {"items": [3, 7, 11]},
    "lossy_counting": {"items": [3, 7, 11]},
    "sticky_sampling": {"items": [3, 7, 11]},
    "gk_quantiles": {"qs": [0.25, 0.5, 0.75]},
}


def _feed(kind, items, values):
    """One site's partial state. Values are INTEGER-valued floats so sum
    merges are exact in float32 regardless of reduction order — the
    bit-for-bit comparisons below rely on it."""
    items = np.asarray(items, np.uint32)
    values = np.asarray(values, np.float32)
    return jax.jit(kind.add_batch)(kind.init(None), items, values,
                                   np.ones(len(items), bool))


def _site_states(kind_name, kind, n_sites=N_SITES, seed=7):
    rng = np.random.RandomState(seed)
    states = []
    for s in range(n_sites):
        if kind_name == "dft":
            # different tick counts per site => fresh-mode selection real
            n = 5 + 3 * s
            states.append(_feed(kind, np.zeros(n), rng.randint(-5, 6, n)))
        else:
            states.append(_feed(kind, rng.randint(0, 300, 32),
                                rng.randint(1, 5, 32)))
    return states


def _tree_equal(a, b):
    """BYTE-level tree equality: assert_array_equal alone treats
    -0.0 == +0.0, which would hide a merge path flipping zero signs."""
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        x, y = np.asarray(x), np.asarray(y)
        np.testing.assert_array_equal(x, y)
        assert x.tobytes() == y.tobytes(), (x, y)


def _vmap_merge(kind, states):
    """merge_over_axis under vmap-with-axis-name: the collective
    semantics (psum/pmax/all_gather/axis_index over the mapped axis) on
    one device — every output row is one shard's view of the merge."""
    return jax.jit(jax.vmap(
        lambda s: federated.merge_over_axis(kind, s, "site"),
        axis_name="site"))(federated.stack_states(states))


# ---------------------------------------------------------------------------
# the matrix: merge_over_axis == host responsible-site fold, per kind
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("kind_name", sorted(core.known_kinds()))
def test_merge_over_axis_matches_host_fold(kind_name):
    kind = core.make_kind(kind_name, **_PARAMS[kind_name])
    states = _site_states(kind_name, kind)
    oracle = federated.merge_reduce(kind, federated.stack_states(states))
    merged = _vmap_merge(kind, states)
    merged = jax.tree.map(np.asarray, merged)
    oracle = jax.tree.map(np.asarray, oracle)
    # every shard of the axis holds the SAME merged state, and it is
    # byte-identical to the host fold the legacy Federation runs
    for r in range(N_SITES):
        _tree_equal(jax.tree.map(lambda x: x[r], merged), oracle)
    mode = getattr(kind, "merge_mode", "gather")
    if mode != "gather":
        # psum/pmax/fresh additionally match the plain sequential fold
        # (gather kinds legitimately depend on fold shape; both paths use
        # the same pairwise tree, asserted above)
        seq = states[0]
        for s in states[1:]:
            seq = kind.merge(seq, s)
        _tree_equal(oracle, jax.tree.map(np.asarray, seq))


@pytest.mark.smoke
def test_merge_over_axis_smoke_sum_and_max():
    for kind_name in ("countmin", "hyperloglog"):
        test_merge_over_axis_matches_host_fold(kind_name)


# ---------------------------------------------------------------------------
# fresh mode: keep-max-count replica selection (DFT)
# ---------------------------------------------------------------------------
def test_fresh_merge_keeps_max_count_replica():
    kind = core.DFT(window=8, n_coeffs=2)
    rng = np.random.RandomState(3)
    ticks = [3, 9, 5, 7]                   # site 1 is freshest
    states = [_feed(kind, np.zeros(n), rng.randint(-4, 5, n))
              for n in ticks]
    merged = jax.tree.map(lambda x: x[0], _vmap_merge(kind, states))
    # the selected replica IS site 1's state, bit for bit — exchanged,
    # not reduced
    _tree_equal(merged, states[1])
    assert int(np.asarray(merged["count"])) == 9


def test_fresh_merge_tie_keeps_lowest_site():
    kind = core.DFT(window=8, n_coeffs=2)
    rng = np.random.RandomState(4)
    states = [_feed(kind, np.zeros(n), rng.randint(-4, 5, n))
              for n in (6, 6, 2)]
    merged = jax.tree.map(lambda x: x[0], _vmap_merge(kind, states))
    _tree_equal(merged, states[0])         # first max wins, like the fold
    seq = states[0]
    for s in states[1:]:
        seq = kind.merge(seq, s)
    _tree_equal(merged, seq)


def test_fresh_merge_tie_across_tree_bracket_boundary():
    """Regression: counts [5, 9, 9, 5] tie the max ACROSS the pairwise
    tree's halving boundary. A tournament of the keep-strictly-fresher
    ``merge`` would crown site 2 (bracket position), while the
    sequential fold and the collective argmax crown site 1 — so fresh
    stacks must be SELECTED, keeping collective, ``merge_reduce`` and
    the sequential fold byte-identical."""
    kind = core.DFT(window=8, n_coeffs=2)
    rng = np.random.RandomState(5)
    states = [_feed(kind, np.zeros(n), rng.randint(-4, 5, n))
              for n in (5, 9, 9, 5)]
    seq = states[0]
    for s in states[1:]:
        seq = kind.merge(seq, s)
    tree_fold = federated.merge_reduce(kind,
                                       federated.stack_states(states))
    merged = jax.tree.map(lambda x: x[0], _vmap_merge(kind, states))
    _tree_equal(merged, states[1])
    _tree_equal(tree_fold, states[1])
    _tree_equal(seq, states[1])


def test_fresh_merge_preserves_negative_zero_bytes():
    """Regression: the winner broadcast is a masked psum; losers must
    contribute -0.0 (not +0.0) for float leaves, or a -0.0 slot in the
    winning replica's ring would come back as +0.0 — a byte-level
    divergence from the host fold."""
    kind = core.DFT(window=4, n_coeffs=2)
    states = [
        _feed(kind, np.zeros(2), np.array([1.0, 2.0])),
        _feed(kind, np.zeros(3), np.array([-0.0, 3.0, -0.0])),  # winner
    ]
    assert np.signbit(np.asarray(states[1]["ring"])).any()
    merged = jax.tree.map(lambda x: x[0], _vmap_merge(kind, states))
    _tree_equal(merged, states[1])
    np.testing.assert_array_equal(np.signbit(np.asarray(merged["ring"])),
                                  np.signbit(np.asarray(
                                      states[1]["ring"])))


def test_estimate_over_axis_matches_merged_estimate():
    kind = core.HyperLogLog(rse=0.05)
    states = _site_states("hyperloglog", kind)
    out = jax.jit(jax.vmap(
        lambda s: federated.estimate_over_axis(kind, s, "site"),
        axis_name="site"))(federated.stack_states(states))
    oracle = kind.estimate(
        federated.merge_reduce(kind, federated.stack_states(states)))
    for r in range(N_SITES):
        assert float(np.asarray(out)[r]) == float(np.asarray(oracle))


# ---------------------------------------------------------------------------
# fig 5d byte accounting: collective operands never exceed host shipping
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("kind_name", sorted(core.known_kinds()))
def test_collective_operand_bytes_bounded_by_host(kind_name):
    kind = core.make_kind(kind_name, **_PARAMS[kind_name])
    state = kind.init(None)
    per_site = federated.communication_bytes(kind, state)
    for n in (1, 2, 4, 16):
        coll = federated.collective_operand_bytes(kind, state, n)
        assert coll <= n * per_site, (kind_name, n)
    mode = getattr(kind, "merge_mode", "gather")
    if mode in ("sum", "max"):
        # in-network reduction: independent of the site count
        assert federated.collective_operand_bytes(kind, state, 16) \
            == per_site


# ---------------------------------------------------------------------------
# hypothesis properties (skipped when hypothesis is not installed — the
# rest of this module must still run, so no module-level importorskip)
# ---------------------------------------------------------------------------
try:
    from hypothesis import given, settings, strategies as st, HealthCheck
    _HAVE_HYPOTHESIS = True
except ImportError:
    _HAVE_HYPOTHESIS = False

_MULTIDEV = len(jax.devices()) >= 2

_SUM_MAX_KINDS = ("countmin", "ams", "rhp", "hyperloglog", "bloom", "fm")

if _HAVE_HYPOTHESIS:
    _settings = dict(deadline=None, max_examples=15,
                     suppress_health_check=[HealthCheck.too_slow,
                                            HealthCheck.data_too_large])

    _site_batches = st.lists(
        st.lists(st.integers(0, 200), min_size=1, max_size=40),
        min_size=2, max_size=5)

    @pytest.mark.parametrize("kind_name", _SUM_MAX_KINDS)
    @given(data=st.data())
    @settings(**_settings)
    def test_site_merge_order_insensitive(kind_name, data):
        """Commutative/associative site merging: any arrival order of
        the sites' partials folds to the identical state for sum/max
        kinds (integer-valued updates keep float sums exact)."""
        kind = core.make_kind(kind_name, **_PARAMS[kind_name])
        batches = data.draw(_site_batches)
        perm = data.draw(st.permutations(range(len(batches))))
        states = [_feed(kind, b, np.ones(len(b))) for b in batches]

        def fold(ss):
            acc = ss[0]
            for s in ss[1:]:
                acc = kind.merge(acc, s)
            return jax.tree.map(np.asarray, acc)

        _tree_equal(fold(states), fold([states[i] for i in perm]))

    @pytest.mark.skipif(not _MULTIDEV, reason="needs >= 2 devices "
                        "(CI federated job forces 8 host devices)")
    @given(data=st.data())
    @settings(deadline=None, max_examples=10,
              suppress_health_check=[HealthCheck.too_slow,
                                     HealthCheck.data_too_large])
    def test_mesh_query_matches_host_oracle_property(data):
        """query_federated over the mesh path == the legacy host-merge
        Federation oracle, byte-identical, on random builds/ingests.
        Batches are padded to a fixed length (masked) so every example
        reuses the same compiled programs."""
        from repro.launch.mesh import make_federation_mesh
        kind_name = data.draw(st.sampled_from(
            ["countmin", "hyperloglog", "fm", "chain_sampler"]))
        per_site = [data.draw(st.lists(st.integers(0, 10**6),
                                       min_size=1, max_size=64))
                    for _ in range(2)]
        sites = ["eu", "us"]
        fed = Federation(sites, mesh=make_federation_mesh(2))
        oracle = Federation(sites)
        build = {"type": "build", "request_id": "b", "synopsis_id": "g",
                 "kind": kind_name, "params": _PARAMS[kind_name],
                 "federated": True, "responsible_site": "eu"}
        for f in (fed, oracle):
            assert all(r.ok for r in f.broadcast(build).values())
        for name, ids in zip(sites, per_site):
            sids = np.zeros(64, np.int64)
            sids[:len(ids)] = ids
            mask = np.zeros(64, bool)
            mask[:len(ids)] = True
            vals = np.ones(64, np.float32)
            fed.sdes[name].ingest(sids, vals, mask)
            oracle.sdes[name].ingest(sids, vals, mask)
        query = _QUERY.get(kind_name, {})
        got = fed.query_federated("g", query, "eu")
        want = oracle.query_federated("g", query, "eu")
        _tree_equal(got, want)
else:
    @pytest.mark.skip(reason="hypothesis not installed")
    def test_site_merge_order_insensitive():
        pass

    @pytest.mark.skip(reason="hypothesis not installed")
    def test_mesh_query_matches_host_oracle_property():
        pass


# ---------------------------------------------------------------------------
# Federation engine path: metrics, fallbacks, JSON errors
# ---------------------------------------------------------------------------
@pytest.mark.smoke
def test_federated_query_reports_byte_metrics_host_path():
    fed = Federation(["eu", "us"])
    fed.broadcast({"type": "build", "request_id": "b", "synopsis_id": "h",
                   "kind": "hyperloglog", "params": {"rse": 0.03},
                   "federated": True, "responsible_site": "eu"})
    fed.sdes["eu"].ingest(np.arange(500, dtype=np.uint32),
                          np.ones(500, np.float32))
    fed.sdes["us"].ingest(np.arange(300, 800, dtype=np.uint32),
                          np.ones(500, np.float32))
    r = fed.handle({"type": "federated_query", "request_id": "q",
                    "synopsis_id": "h", "responsible_site": "eu"})
    assert r.ok, r.error
    assert r.params["path"] == "host"
    assert r.params["sites"] == 2
    assert r.params["host_merge_bytes"] == fed.query_bytes("h")
    assert r.params["collective_operand_bytes"] \
        == r.params["host_merge_bytes"]
    assert abs(float(r.value) - 800) / 800 < 0.15
    # collective accounting is still quotable off-mesh (pmax: one state)
    assert fed.collective_query_bytes("h") == fed.query_bytes("h") // 2
    # unknown synopsis fails as a Response, not an exception
    r = fed.handle({"type": "federated_query", "request_id": "q2",
                    "synopsis_id": "nope", "responsible_site": "eu"})
    assert not r.ok and "nope" in r.error
    # non-federated requests broadcast as before
    rs = fed.handle({"type": "status", "request_id": "s"})
    assert set(rs) == {"eu", "us"}
    # malformed snippets keep the broadcast {site: Response} shape —
    # per-site error responses, never a bare Response the caller's
    # dict-iteration would trip over
    rs = fed.handle({"type": "status", "request_id": "s", "bogus": 1})
    assert set(rs) == {"eu", "us"}
    assert all(not r.ok and "bogus" in r.error for r in rs.values())


@pytest.mark.skipif(not _MULTIDEV, reason="needs >= 2 devices")
def test_mesh_partial_coverage_falls_back_to_host():
    from repro.launch.mesh import make_federation_mesh
    fed = Federation(["eu", "us"], mesh=make_federation_mesh(2))
    # build on ONE site only: the collective spans the whole axis, so a
    # partial synopsis must take the host-merge fallback
    fed.sdes["eu"].handle({"type": "build", "request_id": "b",
                           "synopsis_id": "h", "kind": "hyperloglog",
                           "params": {"rse": 0.03}})
    fed.sdes["eu"].ingest(np.arange(400, dtype=np.uint32),
                          np.ones(400, np.float32))
    r = fed.handle({"type": "federated_query", "request_id": "q",
                    "synopsis_id": "h", "responsible_site": "eu"})
    assert r.ok, r.error
    assert r.params["path"] == "host" and r.params["sites"] == 1
    assert abs(float(r.value) - 400) / 400 < 0.15


@pytest.mark.skipif(not _MULTIDEV, reason="needs >= 2 devices")
def test_mesh_collective_one_dispatch_and_metrics():
    from repro.launch.mesh import make_federation_mesh
    fed = Federation(["eu", "us"], mesh=make_federation_mesh(2))
    oracle = Federation(["eu", "us"])
    build = {"type": "build", "request_id": "b", "synopsis_id": "cm",
             "kind": "countmin",
             "params": {"eps": 0.0213, "delta": 0.1, "weighted": False},
             "federated": True, "responsible_site": "eu"}
    for f in (fed, oracle):
        assert all(r.ok for r in f.broadcast(build).values())
    rng = np.random.RandomState(0)
    for name in ("eu", "us"):
        sids = rng.randint(0, 50, 256).astype(np.uint32)
        for f in (fed, oracle):
            f.sdes[name].ingest(sids.copy(), np.ones(256, np.float32))
    want = oracle.query_federated("cm", {"items": [1, 2, 3]}, "eu")
    tracing.DISPATCH_COUNT.clear()
    tracing.TRACE_COUNT.clear()
    for _ in range(3):
        r = fed.handle({"type": "federated_query", "request_id": "q",
                        "synopsis_id": "cm", "query": {"items": [1, 2, 3]},
                        "responsible_site": "eu"})
        assert r.ok, r.error
        np.testing.assert_array_equal(np.asarray(r.value),
                                      np.asarray(want))
    # merge + estimate fused into ONE collective program per query...
    assert tracing.DISPATCH_COUNT["CountMin"] == 3
    # ... and repeated queries reuse ONE compiled program
    assert tracing.TRACE_COUNT["CountMin"] == 1
    assert r.params["path"] == "collective"
    # CM is a linear sketch: the psum combines in-network, so the
    # collective ships ONE state regardless of the site count
    assert r.params["collective_operand_bytes"] \
        == r.params["host_merge_bytes"] // 2
    assert fed.collective_query_bytes("cm") \
        == r.params["collective_operand_bytes"]


# ---------------------------------------------------------------------------
# the full per-kind matrix on a REAL 8-device mesh (4-site federation,
# every registered kind, byte-identical vs the host oracle + probes)
# ---------------------------------------------------------------------------
_MESH_MATRIX_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import numpy as np, jax
    from repro import core, tracing
    from repro.core import federated
    from repro.launch.mesh import make_federation_mesh
    from repro.service import Federation

    PARAMS = %s
    QUERY = %s
    N_SITES = 4
    sites = [f"s{i}" for i in range(N_SITES)]
    rng = np.random.RandomState(11)
    for kind_name in sorted(core.known_kinds()):
        fed = Federation(sites, mesh=make_federation_mesh(N_SITES))
        oracle = Federation(sites)
        build = {"type": "build", "request_id": "b", "synopsis_id": "g",
                 "kind": kind_name, "params": PARAMS[kind_name],
                 "federated": True, "responsible_site": sites[0]}
        if kind_name == "dft":
            build["stream_id"] = 0       # time-series kinds are routed
        for f in (fed, oracle):
            assert all(r.ok for r in f.broadcast(build).values()), kind_name
        for i, name in enumerate(sites):
            if kind_name == "dft":
                # one tick per ingest batch; different counts per site
                for v in rng.randint(-5, 6, 4 + 2 * i):
                    for f in (fed, oracle):
                        f.sdes[name].ingest(np.zeros(1, np.int64),
                                            np.full(1, v, np.float32))
            else:
                sids = rng.randint(i * 100, i * 100 + 90, 32)
                vals = rng.randint(1, 5, 32).astype(np.float32)
                for f in (fed, oracle):
                    f.sdes[name].ingest(sids.astype(np.int64).copy(),
                                        vals.copy())
        q = QUERY.get(kind_name, {})
        want = oracle.query_federated("g", q, sites[0])
        tracing.DISPATCH_COUNT.clear()
        tracing.TRACE_COUNT.clear()
        pname = type(core.make_kind(kind_name,
                                    **PARAMS[kind_name])).__name__
        for rep in range(2):
            r = fed.handle({"type": "federated_query", "request_id": "q",
                            "synopsis_id": "g", "query": q,
                            "responsible_site": sites[0]})
            assert r.ok, (kind_name, r.error)
            for a, b in zip(jax.tree.leaves(r.value),
                            jax.tree.leaves(want)):
                np.testing.assert_array_equal(np.asarray(a),
                                              np.asarray(b),
                                              err_msg=kind_name)
        assert r.params["path"] == "collective", kind_name
        assert tracing.DISPATCH_COUNT[pname] == 2, (kind_name,
                                                 tracing.DISPATCH_COUNT)
        assert tracing.TRACE_COUNT[pname] == 1, (kind_name,
                                              tracing.TRACE_COUNT)
        assert r.params["collective_operand_bytes"] \\
            <= r.params["host_merge_bytes"], kind_name
        print(kind_name, "OK")
    print("ALL_OK")
""") % (repr(_PARAMS), repr(_QUERY))


def test_mesh_matrix_all_kinds_byte_identical():
    """Every registered kind, federated over a real 8-device mesh: one
    compiled collective program per query, byte-identical to the legacy
    host-merge oracle, collective bytes <= host bytes (the PR 5
    acceptance criterion end to end)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..",
                                     "src")
    out = subprocess.run([sys.executable, "-c", _MESH_MATRIX_SCRIPT],
                         capture_output=True, text=True, env=env,
                         timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "ALL_OK" in out.stdout, out.stdout[-2000:]
