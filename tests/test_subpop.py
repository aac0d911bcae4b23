"""Multidim subpopulation analytics + the continuous outlier workflow.

The acceptance matrix of the tentpole:

  * per-kind oracle — ``subpop_query`` over a 2-d family matches the
    brute-force host-side group-by within each sketch's own error
    budget, in BOTH blue-path modes (eager and pipelined),
  * one fused dispatch answers a predicate however many covering keys
    it expands to (``DISPATCH_COUNT``),
  * multidim key encoding properties (hypothesis when available):
    determinism, 63-bit range, injectivity over a family's groups,
    insertion-order independence,
  * the outlier workflow flags a planted hot group, is deterministic
    across runs AND across execution modes, and costs ZERO additional
    builds (entry count + stack capacities pinned).
"""
import numpy as np
import pytest

from repro import tracing
from repro.core import MultidimSpec
from repro.service import SDE

_DIMS = {"region": ["EU", "US", "APAC", "LATAM"],
         "platform": ["web", "mobile"]}
_N = 1600


def _workload(n=_N, seed=0):
    rng = np.random.RandomState(seed)
    regions = rng.choice(_DIMS["region"], n, p=[0.4, 0.3, 0.2, 0.1])
    platforms = rng.choice(_DIMS["platform"], n, p=[0.65, 0.35])
    records = [{"region": str(r), "platform": str(p)}
               for r, p in zip(regions, platforms)]
    values = rng.uniform(0.0, 100.0, n)
    return records, values


def _family(kind, params, pipelined, records, values, items=None):
    sde = SDE(pipelined=pipelined)
    r = sde.handle({"type": "build_multidim", "request_id": "b",
                    "synopsis_id": "md", "kind": kind, "params": params,
                    "dims": _DIMS})
    assert r.ok, r.error
    req = {"type": "ingest_multidim", "request_id": "i",
           "synopsis_id": "md", "records": records,
           "values": [float(v) for v in values]}
    if items is not None:
        req["items"] = [int(x) for x in items]
    r = sde.handle(req)
    assert r.ok, r.error
    return sde


def _mask(records, where):
    def hit(rec):
        return all(rec[d] in (v if isinstance(v, list) else [v])
                   for d, v in where.items())
    return np.asarray([hit(rec) for rec in records])


def _subpop(sde, where, query=None):
    r = sde.handle({"type": "subpop_query", "request_id": "q",
                    "synopsis_id": "md", "where": where,
                    "query": query or {}})
    assert r.ok, r.error
    return np.asarray(r.value, np.float64).ravel()


_WHERES = [{"region": "EU"},
           {"region": ["EU", "US"], "platform": "web"},
           {"platform": "mobile"}]


# ---------------------------------------------------------------------------
# tentpole: per-kind oracle matrix, eager + pipelined
# ---------------------------------------------------------------------------
@pytest.mark.smoke
@pytest.mark.parametrize("pipelined", [False, True],
                         ids=["eager", "pipelined"])
def test_subpop_countmin_oracle(pipelined):
    records, values = _workload()
    sde = _family("countmin",
                  {"eps": 0.002, "delta": 0.01, "weighted": False},
                  pipelined, records, values)
    spec = sde.multidim["md"]
    leaf = spec.leaf_key({"region": "EU", "platform": "web"})
    for where in _WHERES:
        sub = _mask(records, where)
        true = sum(1 for rec, s in zip(records, sub)
                   if s and rec["region"] == "EU"
                   and rec["platform"] == "web")
        est = _subpop(sde, where, {"items": [leaf]})[0]
        tol = 0.002 * sub.sum() + 1.0       # eps * covering mass
        assert abs(est - true) <= tol, (where, est, true)
    sde.close()


@pytest.mark.parametrize("pipelined", [False, True],
                         ids=["eager", "pipelined"])
@pytest.mark.parametrize("kind,params,rel_tol", [
    ("hyperloglog", {"rse": 0.02}, 0.12),
    ("fm", {"nmaps": 256}, 0.35),
], ids=["hll", "fm"])
def test_subpop_distinct_oracle(kind, params, rel_tol, pipelined):
    records, values = _workload()
    # one distinct item per record: the subpop distinct count IS the
    # subpopulation size
    sde = _family(kind, params, pipelined, records, values,
                  items=np.arange(len(records)))
    for where in _WHERES:
        true = int(_mask(records, where).sum())
        est = _subpop(sde, where)[0]
        assert abs(est - true) <= rel_tol * true + 5, (where, est, true)
    sde.close()


@pytest.mark.parametrize("pipelined", [False, True],
                         ids=["eager", "pipelined"])
def test_subpop_bloom_membership(pipelined):
    records, values = _workload()
    sde = _family("bloom", {"n_elements": 4096, "fpr": 0.001},
                  pipelined, records, values)
    spec = sde.multidim["md"]
    present = spec.leaf_key(records[0])
    absent = 123456789                    # never ingested anywhere
    for where in _WHERES:
        est = _subpop(sde, where, {"items": [present, absent]})
        in_sub = bool(_mask([records[0]], where)[0])
        if in_sub:                        # Bloom: no false negatives
            assert est[0] == 1.0, where
        assert est[1] == 0.0, where       # fpr 1e-3: a hit is a bug
    sde.close()


@pytest.mark.parametrize("pipelined", [False, True],
                         ids=["eager", "pipelined"])
def test_subpop_ams_f2_oracle(pipelined):
    records, values = _workload()
    sde = _family("ams", {"eps": 0.02, "delta": 0.05},
                  pipelined, records, values)
    spec = sde.multidim["md"]
    for where in _WHERES:
        sub = _mask(records, where)
        leaf_mass = {}                    # AMS is value-weighted
        for rec, v, s in zip(records, values, sub):
            if s:
                k = spec.leaf_key(rec)
                leaf_mass[k] = leaf_mass.get(k, 0.0) + float(v)
        true = float(sum(m * m for m in leaf_mass.values()))
        est = _subpop(sde, where)[0]
        assert abs(est - true) <= 0.3 * true, (where, est, true)
    sde.close()


@pytest.mark.parametrize("pipelined", [False, True],
                         ids=["eager", "pipelined"])
def test_subpop_gk_median_oracle(pipelined):
    records, values = _workload()
    sde = _family("gk_quantiles", {"eps": 0.01}, pipelined,
                  records, values)
    for where in _WHERES:
        sub = _mask(records, where)
        sub_vals = np.sort(values[sub])
        est = _subpop(sde, where, {"qs": [0.5]})[0]
        # rank accuracy: the estimated median's rank inside the true
        # subpop values stays near n/2 (merging covering summaries
        # compounds eps; 8% of n is a generous envelope over eps=1%)
        rank = np.searchsorted(sub_vals, est)
        assert abs(rank - len(sub_vals) / 2) <= 0.08 * len(sub_vals) + 2, \
            (where, est, rank, len(sub_vals))
    sde.close()


# ---------------------------------------------------------------------------
# tentpole: one fused dispatch per predicate + the cover-keys probe
# ---------------------------------------------------------------------------
@pytest.mark.smoke
def test_subpop_single_fused_dispatch():
    records, values = _workload(400)
    sde = _family("countmin", {"eps": 0.01, "delta": 0.05,
                               "weighted": False},
                  False, records, values)
    sde.flush()                           # fence outside the window
    for where, n_cover in [({"region": "EU"}, 1),
                           ({"region": ["EU", "US", "APAC"]}, 3),
                           ({"region": ["EU", "US"],
                             "platform": ["web", "mobile"]}, 4)]:
        d0 = int(tracing.DISPATCH_COUNT["CountMin"])
        c0 = int(tracing.SUBPOP_COVER_KEYS[sde.site])
        r = sde.handle({"type": "subpop_query", "request_id": "q",
                        "synopsis_id": "md", "where": where,
                        "query": {"items": [7]}})
        assert r.ok, r.error
        assert r.params["cover_keys"] == n_cover
        assert int(tracing.DISPATCH_COUNT["CountMin"]) - d0 == 1, \
            "a covering set must merge+estimate in ONE fused dispatch"
        assert int(tracing.SUBPOP_COVER_KEYS[sde.site]) - c0 == n_cover
    sde.close()


def test_subpop_validation_errors():
    records, values = _workload(200)
    sde = _family("countmin", {"eps": 0.01, "delta": 0.05,
                               "weighted": False},
                  False, records, values)
    # unknown dimension
    r = sde.handle({"type": "subpop_query", "request_id": "q1",
                    "synopsis_id": "md", "where": {"planet": "earth"}})
    assert not r.ok and "unknown dimension" in r.error
    # unknown family
    r = sde.handle({"type": "subpop_query", "request_id": "q2",
                    "synopsis_id": "nope", "where": {"region": "EU"}})
    assert not r.ok
    # duplicate family id refused
    r = sde.handle({"type": "build_multidim", "request_id": "b2",
                    "synopsis_id": "md", "kind": "countmin",
                    "params": {}, "dims": _DIMS})
    assert not r.ok and "already exists" in r.error
    sde.close()


def test_subpop_rejects_non_mergeable_kind():
    # DFT replicas are exchanged, never merged — a covering-set merge
    # would fabricate coefficients
    sde = SDE()
    r = sde.handle({"type": "build_multidim", "request_id": "b",
                    "synopsis_id": "md", "kind": "dft",
                    "params": {"window": 16, "n_coeffs": 4},
                    "dims": {"a": ["x", "y"]}})
    assert r.ok, r.error
    r = sde.handle({"type": "subpop_query", "request_id": "q",
                    "synopsis_id": "md", "where": {"a": "x"}})
    assert not r.ok and "mergeable" in r.error
    r = sde.handle({"type": "track_outliers", "request_id": "t",
                    "workflow_id": "w", "synopsis_id": "md",
                    "level": ["a"]})
    assert not r.ok
    sde.close()


def test_explicit_levels_gate_queries():
    records, values = _workload(200)
    sde = SDE()
    r = sde.handle({"type": "build_multidim", "request_id": "b",
                    "synopsis_id": "md", "kind": "countmin",
                    "params": {"eps": 0.01, "delta": 0.05},
                    "dims": _DIMS, "levels": [["region"]]})
    assert r.ok, r.error
    # population + region only: 1 + 4 groups
    assert r.params["n_groups"] == 5 and r.params["n_levels"] == 2
    r = sde.handle({"type": "ingest_multidim", "request_id": "i",
                    "synopsis_id": "md", "records": records,
                    "values": [1.0] * len(records)})
    assert r.ok, r.error
    assert _subpop(sde, {"region": "EU"}, {"items": [3]}).size == 1
    r = sde.handle({"type": "subpop_query", "request_id": "q",
                    "synopsis_id": "md", "where": {"platform": "web"}})
    assert not r.ok and "not materialized" in r.error
    sde.close()


# ---------------------------------------------------------------------------
# multidim key encoding properties
# ---------------------------------------------------------------------------
def _spec_roundtrip_and_keys(spec):
    keys = spec.all_keys()
    assert all(0 <= k < (1 << 63) for k in keys)
    assert len(set(keys)) == len(keys)    # injective across the family
    again = MultidimSpec.from_json_dict(spec.to_json_dict())
    assert again == spec and again.all_keys() == keys


@pytest.mark.smoke
def test_multidim_keys_basics():
    spec = MultidimSpec(_DIMS)
    _spec_roundtrip_and_keys(spec)
    # insertion order of the ASSIGNMENT dict is irrelevant
    assert (spec.group_key({"region": "EU", "platform": "web"})
            == spec.group_key({"platform": "web", "region": "EU"}))
    # declaration order of the DIMENSIONS is load-bearing
    other = MultidimSpec({"platform": _DIMS["platform"],
                          "region": _DIMS["region"]})
    assert (spec.group_key({"region": "EU"})
            != other.group_key({"region": "EU"}))
    # expand covers every level exactly once, leaf included
    rec = {"region": "US", "platform": "mobile"}
    ks = spec.expand(rec)
    assert len(ks) == len(spec.levels) == 4
    assert spec.population_key() in ks and spec.leaf_key(rec) in ks
    # bools never alias their int twins
    bspec = MultidimSpec({"flag": [True, False, 1, 0]})
    _spec_roundtrip_and_keys(bspec)
    with pytest.raises(ValueError):
        spec.group_key({"region": "MOON"})
    with pytest.raises(ValueError):
        spec.expand({"region": "EU"})     # platform missing


try:
    from hypothesis import given, settings, HealthCheck
    from hypothesis import strategies as st
    _HAVE_HYPOTHESIS = True
except ImportError:                       # pragma: no cover
    _HAVE_HYPOTHESIS = False

if _HAVE_HYPOTHESIS:
    _names = st.text("abcdefgh", min_size=1, max_size=4)
    _atoms = st.one_of(st.integers(-2**40, 2**40),
                       st.text(max_size=6), st.booleans())

    @settings(max_examples=40, deadline=None,
              suppress_health_check=[HealthCheck.too_slow])
    @given(st.dictionaries(_names, st.lists(_atoms, min_size=1,
                                            max_size=5, unique=True),
                           min_size=1, max_size=3))
    def test_multidim_keys_property(dims):
        spec = MultidimSpec(dims)
        _spec_roundtrip_and_keys(spec)
        # every leaf expansion lands on maintained groups, population
        # always included
        leaf = {n: vs[0] for n, vs in spec.domains.items()}
        ks = spec.expand(leaf)
        maintained = set(spec.all_keys())
        assert set(ks) <= maintained
        assert spec.population_key() in ks
        # covering keys of a full assignment = that leaf alone
        lvl, cover = spec.covering_keys(leaf)
        assert cover == [spec.leaf_key(leaf)]
        assert lvl == tuple(spec.dim_names)


# ---------------------------------------------------------------------------
# the continuous outlier workflow
# ---------------------------------------------------------------------------
def _hot_workload(n=900, seed=3):
    """Uniform across the grid except region EU, which runs ~6x hot —
    the planted outlier every configuration must flag."""
    rng = np.random.RandomState(seed)
    regions = rng.choice(_DIMS["region"], n, p=[0.7, 0.1, 0.1, 0.1])
    platforms = rng.choice(_DIMS["platform"], n)
    return ([{"region": str(r), "platform": str(p)}
             for r, p in zip(regions, platforms)],
            np.ones(n))


def _drive_outliers(pipelined, n_ticks=3):
    records, values = _hot_workload()
    sde = SDE(pipelined=pipelined)
    r = sde.handle({"type": "build_multidim", "request_id": "b",
                    "synopsis_id": "md", "kind": "countmin",
                    "params": {"eps": 0.005, "delta": 0.01,
                               "weighted": False},
                    "dims": _DIMS, "continuous": False})
    assert r.ok, r.error
    # every record carries the same item id, so a CM point query of
    # item 42 reads each group's total tuple count — the stat the
    # workflow scores across the region level
    r = sde.handle({"type": "track_outliers", "request_id": "t",
                    "workflow_id": "hot-regions", "synopsis_id": "md",
                    "level": ["region"], "query": {"items": [42]},
                    "threshold": 2.0, "min_dev": 1.0})
    assert r.ok, r.error
    step = len(records) // n_ticks
    for i in range(n_ticks):
        chunk = records[i * step:(i + 1) * step]
        r = sde.handle({"type": "ingest_multidim", "request_id": f"i{i}",
                        "synopsis_id": "md", "records": chunk,
                        "values": [1.0] * len(chunk),
                        "items": [42] * len(chunk)})
        assert r.ok, r.error
    sde.flush()
    out = [resp for resp in sde.continuous_out.drain()
           if resp.synopsis_id == "hot-regions"]
    payloads = [resp.value for resp in out]
    ids = [resp.request_id for resp in out]
    sde.close()
    return ids, payloads


@pytest.mark.smoke
@pytest.mark.parametrize("pipelined", [False, True],
                         ids=["eager", "pipelined"])
def test_outlier_workflow_flags_planted_hot_group(pipelined):
    ids, payloads = _drive_outliers(pipelined)
    assert len(payloads) == 3             # one response per ingest tick
    assert all(i.startswith("ow/hot-regions/") for i in ids)
    final = payloads[-1]
    assert final["n_groups"] == 4
    flagged = [o["group"] for o in final["outliers"]]
    assert {"region": "EU"} in flagged, final
    eu = next(o for o in final["outliers"]
              if o["group"] == {"region": "EU"})
    assert eu["z"] > 0 and eu["stat"] > final["center"]


def test_outlier_workflow_deterministic_across_modes():
    a = _drive_outliers(False)
    b = _drive_outliers(False)
    c = _drive_outliers(True)
    assert a == b                         # bit-for-bit rerun stability
    assert a == c                         # eager == pipelined


def test_outlier_workflow_zero_additional_builds():
    records, values = _hot_workload(300)
    sde = SDE()
    r = sde.handle({"type": "build_multidim", "request_id": "b",
                    "synopsis_id": "md", "kind": "countmin",
                    "params": {"eps": 0.01, "delta": 0.05,
                               "weighted": False}, "dims": _DIMS})
    assert r.ok, r.error
    r = sde.handle({"type": "ingest_multidim", "request_id": "i0",
                    "synopsis_id": "md", "records": records,
                    "values": [1.0] * len(records)})
    assert r.ok, r.error
    sde.flush()
    n_entries = len(sde.entries)
    caps = {k: s.capacity for k, s in sde.stacks.items()}
    e0 = int(tracing.OUTLIER_EMITS[sde.site])
    r = sde.handle({"type": "track_outliers", "request_id": "t",
                    "workflow_id": "w", "synopsis_id": "md",
                    "level": ["region"], "query": {"items": [1]},
                    "threshold": 0.0})   # threshold 0: every tick flags
    assert r.ok, r.error
    for i in range(2):
        r = sde.handle({"type": "ingest_multidim", "request_id": f"i{i}",
                        "synopsis_id": "md", "records": records[:50],
                        "values": [1.0] * 50, "items": [1] * 50})
        assert r.ok, r.error
    sde.flush()
    # the workflow rode the maintained synopses: no entry appeared, no
    # stack grew, yet emissions flowed
    assert len(sde.entries) == n_entries
    assert {k: s.capacity for k, s in sde.stacks.items()} == caps
    assert int(tracing.OUTLIER_EMITS[sde.site]) > e0
    assert any(resp.synopsis_id == "w"
               for resp in sde.continuous_out.drain())
    # untrack silences the stream
    r = sde.handle({"type": "untrack_outliers", "request_id": "u",
                    "workflow_id": "w"})
    assert r.ok and not sde.outliers
    sde.handle({"type": "ingest_multidim", "request_id": "ix",
                "synopsis_id": "md", "records": records[:10],
                "values": [1.0] * 10})
    sde.flush()
    assert not [resp for resp in sde.continuous_out.drain()
                if resp.synopsis_id == "w"]
    sde.close()


def test_multidim_snapshot_roundtrip(tmp_path):
    records, values = _workload(300)
    sde = _family("countmin", {"eps": 0.01, "delta": 0.05,
                               "weighted": False},
                  False, records, values)
    r = sde.handle({"type": "track_outliers", "request_id": "t",
                    "workflow_id": "w", "synopsis_id": "md",
                    "level": ["region"], "query": {"items": [5]}})
    assert r.ok, r.error
    sde.flush()
    before = _subpop(sde, {"region": "EU"}, {"items": [5]})
    sde.snapshot(str(tmp_path))
    sde.close()
    back = SDE.restore(str(tmp_path))
    assert back.multidim["md"] == MultidimSpec(_DIMS)
    assert "w" in back.outliers and back.outliers["w"].level == ("region",)
    after = _subpop(back, {"region": "EU"}, {"items": [5]})
    np.testing.assert_allclose(after, before)
    back.close()
