"""Kind-stack lifecycle regressions + the fused single-dispatch blue path.

Covers the three state-corruption bugs (freed-row reuse, grow padding,
plugged-kind snapshot naming) and the scale contract: `ingest` issues
exactly ONE jitted update dispatch per kind per batch, and kind stacks
carry a NamedSharding over the `synopsis` axis on multi-device meshes.
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

from repro import core
from repro.core import batched, federated
from repro.service import SDE
from repro.service import engine as engine_mod


def _build_cm(eng, syn_id, *, stream_id=None, per_stream=False, n=None):
    req = {"type": "build", "request_id": "b", "synopsis_id": syn_id,
           "kind": "countmin",
           "params": {"eps": 0.02, "delta": 0.1, "weighted": False}}
    if per_stream:
        req.update(per_stream_of_source=True, n_streams=n)
    elif stream_id is not None:
        req["stream_id"] = stream_id
    r = eng.handle(req)
    assert r.ok, r.error
    return r


# ---------------------------------------------------------------------------
# tentpole contract: one jitted update dispatch per kind per batch
# ---------------------------------------------------------------------------
@pytest.mark.smoke
def test_single_update_dispatch_per_kind_per_batch(monkeypatch):
    calls = []
    orig = engine_mod._update

    def counting(kind, *a, **k):
        calls.append(kind)
        return orig(kind, *a, **k)

    monkeypatch.setattr(engine_mod, "_update", counting)
    eng = SDE()
    # routed synopses + a data-source synopsis of the SAME kind (the old
    # path paid one extra dispatch per source row per batch) + a second
    # kind that is source-only
    _build_cm(eng, "cm", per_stream=True, n=50)
    _build_cm(eng, "cm_all")
    r = eng.handle({"type": "build", "request_id": "b2",
                    "synopsis_id": "hll", "kind": "hyperloglog",
                    "params": {"rse": 0.03}})
    assert r.ok, r.error
    rng = np.random.RandomState(0)
    n_batches = 4
    for _ in range(n_batches):
        sids = rng.randint(0, 50, 256).astype(np.uint32)
        eng.ingest(sids, np.ones(256, np.float32))
    assert len(calls) == n_batches * len(eng.stacks)


def test_fused_source_and_routed_are_exact():
    """Routed rows and data-source rows agree with ground truth after the
    single fused dispatch (CM unweighted counts are exact per-stream)."""
    for backend in ("xla", "pallas"):
        eng = SDE(backend=backend)
        _build_cm(eng, "cm", per_stream=True, n=32)
        _build_cm(eng, "cm_all")
        rng = np.random.RandomState(1)
        sids = rng.randint(0, 32, 512).astype(np.uint32)
        eng.ingest(sids, np.ones(512, np.float32))
        q = eng.handle({"type": "adhoc", "request_id": "q",
                        "synopsis_id": "cm/5", "query": {"items": [5]}})
        assert float(q.value[0]) == float((sids == 5).sum())
        q = eng.handle({"type": "adhoc", "request_id": "q2",
                        "synopsis_id": "cm_all", "query": {"items": [5]}})
        assert float(q.value[0]) == float((sids == 5).sum())


def test_scan_kind_source_row_sees_every_tuple():
    """The vmap-fallback (scan) kinds fold source rows into the same
    single dispatch; a source LossyCounting must track the heavy item."""
    eng = SDE()
    r = eng.handle({"type": "build", "request_id": "b", "synopsis_id":
                    "lc", "kind": "lossy_counting",
                    "params": {"eps": 0.01}})
    assert r.ok, r.error
    items = np.concatenate([np.full(300, 7), np.arange(50)])
    np.random.RandomState(0).shuffle(items)
    eng.ingest(items.astype(np.uint32), np.ones(len(items), np.float32))
    q = eng.handle({"type": "adhoc", "request_id": "q", "synopsis_id":
                    "lc", "query": {"items": [7]}})
    assert float(q.value[0]) >= 300


# ---------------------------------------------------------------------------
# bug 1: freed rows must hand fresh state to the next synopsis
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("kind_name,params", [
    ("countmin", {"eps": 0.02, "delta": 0.1, "weighted": False}),
    ("hyperloglog", {"rse": 0.03}),
    ("lossy_counting", {"eps": 0.02}),
])
def test_freed_row_reuse_starts_fresh(kind_name, params):
    eng = SDE()
    build = {"type": "build", "request_id": "b", "synopsis_id": "x",
             "kind": kind_name, "params": params, "stream_id": 1}
    assert eng.handle(build).ok
    eng.ingest(np.ones(200, np.uint32), np.ones(200, np.float32))
    q = eng.handle({"type": "adhoc", "request_id": "q", "synopsis_id":
                    "x", "query": {"items": [1]}})
    assert float(np.asarray(q.value).ravel()[0]) > 0
    assert eng.handle({"type": "stop", "request_id": "s",
                       "synopsis_id": "x"}).ok
    # rebuild the SAME id: alloc hands back the same row — it must not
    # carry the dead synopsis's counts
    assert eng.handle(dict(build, request_id="b2")).ok
    q = eng.handle({"type": "adhoc", "request_id": "q2", "synopsis_id":
                    "x", "query": {"items": [1]}})
    assert float(np.asarray(q.value).ravel()[0]) == 0.0


# ---------------------------------------------------------------------------
# bug 2: grow must pad with the kind's init prototype, not zeros
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("kind_name", sorted(core.known_kinds()))
def test_grow_pads_with_init_prototype(kind_name):
    kind = core.make_kind(kind_name)
    stacked = batched.stacked_init(kind, 4)
    grown = batched.grow(kind, stacked, 8)
    proto = batched.stacked_init(kind, 4)
    for g, p in zip(jax.tree.leaves(grown), jax.tree.leaves(proto)):
        assert g.shape[0] == 8
        np.testing.assert_array_equal(np.asarray(g[4:]), np.asarray(p))


def test_grown_lossy_rows_are_not_occupied_by_item_zero():
    """The observable corruption: after doubling, a fresh LossyCounting
    row must report 0 for item 0 (zero-padded keys claimed otherwise)."""
    eng = SDE()
    for i in range(65):     # 65th alloc doubles the 64-row stack
        r = eng.handle({"type": "build", "request_id": "b",
                        "synopsis_id": f"lc{i}", "kind": "lossy_counting",
                        "params": {"eps": 0.05}, "stream_id": i})
        assert r.ok, r.error
    eng.ingest(np.full(10, 3, np.uint32), np.ones(10, np.float32))
    q = eng.handle({"type": "adhoc", "request_id": "q", "synopsis_id":
                    "lc64", "query": {"items": [0]}})
    assert float(q.value[0]) == 0.0


# ---------------------------------------------------------------------------
# bug 3: snapshot/restore of kinds plugged in with non-class factories
# ---------------------------------------------------------------------------
def _narrow_cm(**params):
    """A function (NOT a class) factory, as Load Synopsis allows."""
    return core.CountMin(**params)


def test_plugged_kind_snapshot_roundtrip():
    core.register_kind("plugged_cm", _narrow_cm, overwrite=True)
    eng = SDE()
    r = eng.handle({"type": "build", "request_id": "b", "synopsis_id":
                    "p", "kind": "plugged_cm",
                    "params": {"eps": 0.02, "delta": 0.1,
                               "weighted": False}, "stream_id": 4})
    assert r.ok, r.error
    eng.ingest(np.full(64, 4, np.uint32), np.ones(64, np.float32))
    with tempfile.TemporaryDirectory() as d:
        eng.snapshot(d, 1)
        eng2 = SDE.restore(d)
    for e in (eng, eng2):
        q = e.handle({"type": "adhoc", "request_id": "q", "synopsis_id":
                      "p", "query": {"items": [4]}})
        assert q.ok, q.error
        assert float(q.value[0]) == 64.0


# ---------------------------------------------------------------------------
# elastic merge: vectorized row-wise merge per kind
# ---------------------------------------------------------------------------
def test_merge_rows_matches_scalar_merge():
    kind = core.CountMin(eps=0.02, delta=0.1, weighted=False)
    a = batched.stacked_init(kind, 8)
    b = batched.stacked_init(kind, 8)
    rng = np.random.RandomState(0)
    items = jnp.asarray(rng.randint(0, 100, 256).astype(np.uint32))
    ones = jnp.ones(256, jnp.float32)
    mask = jnp.ones(256, bool)
    a = batched.stacked_add_batch(kind, a, items % 8, items, ones, mask)
    b = batched.stacked_add_batch(kind, b, (items + 3) % 8, items, ones,
                                  mask)
    rows_a = jnp.asarray([1, 4, 6], jnp.int32)
    rows_b = jnp.asarray([0, 2, 5], jnp.int32)
    out = federated.merge_rows(kind, a, rows_a, b, rows_b)
    for ra, rb in zip([1, 4, 6], [0, 2, 5]):
        expect = kind.merge(batched.stacked_row(a, ra),
                            batched.stacked_row(b, rb))
        np.testing.assert_allclose(np.asarray(batched.stacked_row(out, ra)),
                                   np.asarray(expect))
    # untouched rows unchanged
    np.testing.assert_array_equal(np.asarray(batched.stacked_row(out, 0)),
                                  np.asarray(batched.stacked_row(a, 0)))


# ---------------------------------------------------------------------------
# sharding: stacks carry a NamedSharding over the `synopsis` axis
# ---------------------------------------------------------------------------
_SHARDED_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np, jax
    from jax.sharding import NamedSharding
    from repro.service import SDE
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((4, 2), ("data", "model"))
    eng = SDE(mesh=mesh)
    eng.handle({"type": "build", "request_id": "b", "synopsis_id": "cm",
                "kind": "countmin",
                "params": {"eps": 0.01, "delta": 0.05, "weighted": False},
                "per_stream_of_source": True, "n_streams": 50})
    eng.handle({"type": "build", "request_id": "b2", "synopsis_id": "h",
                "kind": "hyperloglog", "params": {"rse": 0.03}})
    rng = np.random.RandomState(0)
    sids = rng.randint(0, 50, 512).astype(np.uint32)
    for _ in range(3):
        eng.ingest(sids, np.ones(512, np.float32))
    for stack in eng.stacks.values():
        for leaf in jax.tree.leaves(stack.state):
            sh = leaf.sharding
            assert isinstance(sh, NamedSharding), sh
            assert sh.spec and sh.spec[0] == "data", sh.spec
    q = eng.handle({"type": "adhoc", "request_id": "q", "synopsis_id":
                    "cm/7", "query": {"items": [7]}})
    assert float(q.value[0]) == 3.0 * float((sids == 7).sum()), q.value
    # capacity doubling keeps the placement
    for i in range(70):
        eng.handle({"type": "build", "request_id": "g",
                    "synopsis_id": f"g{i}", "kind": "hyperloglog",
                    "params": {"rse": 0.03}, "stream_id": 60 + i})
    eng.ingest(sids, np.ones(512, np.float32))
    hstack = [s for s in eng.stacks.values() if s.capacity == 128][0]
    assert hstack.state.sharding.spec[0] == "data"
    print("OK")
""")


def test_stacks_sharded_over_synopsis_axis_multidevice():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", _SHARDED_SCRIPT],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "OK" in out.stdout


def test_explicit_axis_mesh_refused_at_construction():
    """``jax.make_mesh`` defaults to Explicit axes, under which the
    row-sharded scatter cannot resolve its output sharding: the engine
    refuses such a mesh up front; ``launch.mesh.make_mesh`` serves."""
    from repro.launch.mesh import make_mesh
    explicit = jax.make_mesh((1, 1), ("data", "model"))
    with pytest.raises(ValueError, match="Auto axes"):
        SDE(mesh=explicit)
    eng = SDE(mesh=make_mesh((1, 1), ("data", "model")))
    assert eng.handle({"type": "build", "request_id": "b",
                       "synopsis_id": "c", "kind": "countmin",
                       "per_stream_of_source": True, "n_streams": 4}).ok
    eng.ingest(np.arange(4, dtype=np.int64), np.ones(4, np.float32))
