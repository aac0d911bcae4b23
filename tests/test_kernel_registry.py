"""The update-kernel registry contract (PR 6 tentpole).

Every scatter kind resolves its Pallas kernel by NAME (no isinstance
dispatch in the engine); the probe-then-scatter kernels are
byte-identical to the XLA reference path across all twelve kinds,
including unrouted ids and data-source rows; the compiled-program caches
are bounded and release per-kind entries on stop/close; the env
overrides (SDE_PALLAS_INTERPRET, SDE_BACKEND) follow their contract.
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
from repro.kernels import ops
from repro.service import SDE
from repro.service import routing


# small-footprint params per kind: the matrix builds 24 routed rows per
# kind and runs Pallas in interpret mode, so sketch widths stay tiny
_PARAMS = {
    "countmin": {"eps": 0.1, "delta": 0.1, "weighted": False},
    "ams": {"eps": 0.1, "delta": 0.1},
    "hyperloglog": {"rse": 0.1},
    "bloom": {"n_elements": 64, "fpr": 0.05},
    "fm": {"nmaps": 8, "bitmap_size": 16},
    "rhp": {"n_bits": 64},
    "dft": {"window": 16, "n_coeffs": 4, "threshold": 0.9},
    "lossy_counting": {"eps": 0.05},
    "sticky_sampling": {},
    "chain_sampler": {},
    "gk_quantiles": {},
    "coreset_tree": {"bucket_size": 256, "dim": 1},
}

# engine-level skips: kinds whose blue path never reaches the update
# registry, with the reason stated
_SKIP = {
    "dft": "timeseries kind: ingest runs the stacked step path "
           "(route probe fused into stacked_step), not the update "
           "registry",
}


def _hashed_pop(rng, n):
    """n distinct 62-bit stream ids (exercises hashed routing, not the
    dense 0..n-1 id space)."""
    pop = np.unique(rng.randint(0, 2**62, size=4 * n, dtype=np.int64))
    return pop[:n]


def _tree_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(la, lb))


# ---------------------------------------------------------------------------
# registry resolution
# ---------------------------------------------------------------------------
def test_every_scatter_kind_declares_a_registered_kernel():
    for name in core.known_kinds():
        if name not in _PARAMS:
            # kinds plugged in by other test modules (register_kind has
            # process-global effect); the contract covers the stock set
            continue
        kind = core.make_kind(name, **_PARAMS[name])
        kname = getattr(kind, "update_kernel", None)
        if hasattr(kind, "stacked_add_batch") and not hasattr(kind, "step"):
            assert kname in ops.UPDATE_KERNELS, (
                f"{name} has a scatter path but no registered kernel")
            assert callable(ops.resolve_update_kernel(kind))
        elif kname is None:
            assert ops.resolve_update_kernel(kind) is None


def test_unregistered_kernel_name_raises_with_guidance():
    class Odd:
        update_kernel = "no_such_kernel"

    with pytest.raises(KeyError, match="no_such_kernel"):
        ops.resolve_update_kernel(Odd())


def test_register_duplicate_kernel_requires_overwrite():
    builder = lambda kind: None
    ops.register_update_kernel("_test_dup", builder)
    try:
        with pytest.raises(ValueError, match="already registered"):
            ops.register_update_kernel("_test_dup", builder)
        ops.register_update_kernel("_test_dup", builder, overwrite=True)
    finally:
        ops.UPDATE_KERNELS.pop("_test_dup", None)


# ---------------------------------------------------------------------------
# registry-level equivalence: probe-then-scatter kernel == XLA
# ---------------------------------------------------------------------------
_SCATTER_KINDS = [
    core.CountMin(eps=0.1, delta=0.1, weighted=False),
    core.AMS(eps=0.1, delta=0.1),
    core.HyperLogLog(rse=0.1),
    core.BloomFilter(n_elements=64, fpr=0.05),
    core.FMSketch(nmaps=8, bitmap_size=16),
    core.RHP(n_bits=64),
]


@pytest.mark.parametrize("kind", _SCATTER_KINDS,
                         ids=lambda k: type(k).__name__)
def test_registry_kernel_matches_xla_reference(kind):
    n, t = 24, 300
    rng = np.random.RandomState(3)
    pop = _hashed_pop(rng, n)
    table = routing.RouteTable()
    table.insert_many(pop, np.arange(n, dtype=np.int32))
    klo, khi = (jnp.asarray(h) for h in routing.split64(table.keys))
    trows = jnp.asarray(table.rows)
    n_probe = routing.next_pow2(table.max_probe)

    sids = pop[rng.randint(0, n, t)]
    sids[::13] = int(pop.max()) + 7          # unrouted: must be dropped
    slo, shi = (jnp.asarray(h) for h in routing.split64(sids))
    items = jnp.asarray(routing.fold64(sids))
    vals = jnp.asarray(rng.randint(1, 4, t).astype(np.float32))
    msk = jnp.asarray(rng.rand(t) > 0.2)
    src = jnp.asarray([1, 5], jnp.int32)     # data-source rows

    state = batched.stacked_init(kind, n)
    rows = ops.route_probe(klo, khi, trows, slo, shi, n_probe=n_probe)
    got = np.asarray(ops.resolve_update_kernel(kind)(
        state, rows, items, vals, msk, src))
    want = np.asarray(batched.stacked_update(kind, state, rows, items,
                                             vals, msk, src))
    assert np.array_equal(got, want), "pallas kernel diverged"


# ---------------------------------------------------------------------------
# engine-level matrix: pallas backend == xla backend for ALL twelve kinds
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("kind_name", sorted(core.known_kinds()))
def test_engine_backend_matrix(kind_name):
    if kind_name in _SKIP:
        pytest.skip(_SKIP[kind_name])
    n = 24
    rng = np.random.RandomState(7)
    pop = _hashed_pop(rng, n)
    batches = []
    for _ in range(2):
        sids = pop[rng.randint(0, n, 256)]
        sids[::17] = int(pop.max()) + 3      # unrouted ids in every batch
        vals = rng.randint(1, 5, 256).astype(np.float32)
        batches.append((sids, vals))
    states = {}
    for backend in ("xla", "pallas"):
        eng = SDE(backend=backend)
        r = eng.handle({"type": "build", "request_id": "b",
                        "synopsis_id": "s", "kind": kind_name,
                        "params": _PARAMS[kind_name],
                        "per_stream_of_source": True,
                        "stream_ids": [int(s) for s in pop]})
        assert r.ok, r.error
        r = eng.handle({"type": "build", "request_id": "b2",
                        "synopsis_id": "src", "kind": kind_name,
                        "params": _PARAMS[kind_name]})
        assert r.ok, r.error
        for sids, vals in batches:
            eng.ingest(sids, vals)
        states[backend] = next(iter(eng.stacks.values())).state
        eng.close()
    assert _tree_equal(states["xla"], states["pallas"]), (
        f"{kind_name}: pallas state != xla state")


# ---------------------------------------------------------------------------
# dispatch discipline: one trace, one dispatch per batch on the fused path
# ---------------------------------------------------------------------------
def test_fused_pallas_update_one_trace_one_dispatch_per_batch():
    # unique eps => fresh cache entry => trace count starts at zero here
    eng = SDE(backend="pallas")
    r = eng.handle({"type": "build", "request_id": "b", "synopsis_id": "c",
                    "kind": "countmin",
                    "params": {"eps": 0.0421, "delta": 0.1,
                               "weighted": False},
                    "per_stream_of_source": True, "n_streams": 16})
    assert r.ok, r.error
    d0 = tracing.DISPATCH_COUNT["update:CountMin"]
    t0 = tracing.TRACE_COUNT["update:CountMin"]
    rng = np.random.RandomState(0)
    for _ in range(3):
        eng.ingest(rng.randint(0, 16, 128).astype(np.uint32),
                   np.ones(128, np.float32))
    assert tracing.DISPATCH_COUNT["update:CountMin"] - d0 == 3
    assert tracing.TRACE_COUNT["update:CountMin"] - t0 == 1
    eng.close()


# ---------------------------------------------------------------------------
# bounded caches: stop/close release the kind's compiled programs
# ---------------------------------------------------------------------------
def test_update_cache_entries_released_on_stop():
    g0 = tracing.KERNEL_CACHE_SIZE["update"]
    eng = SDE(backend="xla")
    r = eng.handle({"type": "build", "request_id": "b", "synopsis_id": "c",
                    "kind": "countmin",
                    "params": {"eps": 0.0517, "delta": 0.1,
                               "weighted": False},
                    "per_stream_of_source": True, "n_streams": 8})
    assert r.ok, r.error
    eng.ingest(np.arange(8, dtype=np.uint32), np.ones(8, np.float32))
    assert tracing.KERNEL_CACHE_SIZE["update"] > g0
    r = eng.handle({"type": "stop", "request_id": "s", "synopsis_id": "c"})
    assert r.ok, r.error
    assert tracing.KERNEL_CACHE_SIZE["update"] == g0
    assert not eng.stacks


def test_close_releases_every_kind_cache_entry():
    g0 = {c: tracing.KERNEL_CACHE_SIZE[c] for c in ("update", "step")}
    eng = SDE(backend="pallas")
    for i, (kname, params) in enumerate([
            ("hyperloglog", {"rse": 0.0987}),
            ("rhp", {"n_bits": 56}),
            ("dft", {"window": 24, "n_coeffs": 4, "threshold": 0.9})]):
        r = eng.handle({"type": "build", "request_id": f"b{i}",
                        "synopsis_id": f"s{i}", "kind": kname,
                        "params": params, "per_stream_of_source": True,
                        "n_streams": 8})
        assert r.ok, r.error
    eng.ingest(np.arange(8, dtype=np.uint32), np.ones(8, np.float32))
    assert tracing.KERNEL_CACHE_SIZE["update"] > g0["update"]
    assert tracing.KERNEL_CACHE_SIZE["step"] > g0["step"]
    eng.close()
    for c in ("update", "step"):
        assert tracing.KERNEL_CACHE_SIZE[c] == g0[c]
    assert not eng.stacks and not eng.entries


# ---------------------------------------------------------------------------
# env overrides
# ---------------------------------------------------------------------------
def test_pallas_interpret_env_override(monkeypatch):
    monkeypatch.setenv("SDE_PALLAS_INTERPRET", "1")
    assert ops._interpret() is True
    monkeypatch.setenv("SDE_PALLAS_INTERPRET", "off")
    assert ops._interpret() is False
    monkeypatch.setenv("SDE_PALLAS_INTERPRET", "bogus")
    with pytest.raises(ValueError, match="SDE_PALLAS_INTERPRET"):
        ops._interpret()
    monkeypatch.delenv("SDE_PALLAS_INTERPRET")
    assert ops._interpret() is (jax.default_backend() != "tpu")


def test_pallas_interpret_refused_on_tpu_backend(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.delenv("SDE_PALLAS_INTERPRET", raising=False)
    assert ops._interpret() is False          # never interpreted on TPU
    monkeypatch.setenv("SDE_PALLAS_INTERPRET", "0")
    assert ops._interpret() is False
    monkeypatch.setenv("SDE_PALLAS_INTERPRET", "1")
    with pytest.raises(ValueError, match="TPU backend"):
        ops._interpret()


def test_backend_env_default(monkeypatch):
    monkeypatch.setenv("SDE_BACKEND", "pallas")
    assert SDE().backend == "pallas"
    monkeypatch.delenv("SDE_BACKEND")
    assert SDE().backend == "xla"
    assert SDE(backend="xla").backend == "xla"


# ---------------------------------------------------------------------------
# multi-device: pallas backend on a synopsis-sharded 8-device mesh
# ---------------------------------------------------------------------------
_PALLAS_SHARDED_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np, jax
    from repro.service import SDE
    from repro.launch.mesh import make_mesh

    states = {}
    for backend in ("xla", "pallas"):
        mesh = make_mesh((8, 1), ("data", "model"))
        eng = SDE(backend=backend, mesh=mesh)
        r = eng.handle({"type": "build", "request_id": "b",
                        "synopsis_id": "cm", "kind": "countmin",
                        "params": {"eps": 0.1, "delta": 0.1,
                                   "weighted": False},
                        "per_stream_of_source": True, "n_streams": 64})
        assert r.ok, r.error
        r = eng.handle({"type": "build", "request_id": "b2",
                        "synopsis_id": "all", "kind": "countmin",
                        "params": {"eps": 0.1, "delta": 0.1,
                                   "weighted": False}})
        assert r.ok, r.error
        rng = np.random.RandomState(0)
        for _ in range(2):
            sids = rng.randint(0, 64, 512).astype(np.uint32)
            eng.ingest(sids, np.ones(512, np.float32))
        stack = next(iter(eng.stacks.values()))
        assert stack.state.sharding.spec[0] == "data", stack.state.sharding
        states[backend] = np.asarray(stack.state)
    assert np.array_equal(states["xla"], states["pallas"])
    print("OK")
""")


def test_pallas_backend_sharded_over_synopsis_axis():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..",
                                     "src")
    out = subprocess.run([sys.executable, "-c", _PALLAS_SHARDED_SCRIPT],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "OK" in out.stdout
