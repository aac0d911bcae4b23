"""Durability: incremental dirty-row snapshots + write-ahead ingest log.

The contract under test (paper Section 4's always-on SDEaaS): an acked
request is recoverable — kill the serving process ANYWHERE and
``recover`` (latest snapshot + WAL tail replay) rebuilds the engine
byte-identically to one that applied the acked stream once, in order.
Incremental (delta) snapshots must restore byte-identical to full ones,
survive migration/compaction in the chain, and land on a different
device mesh; the checkpoint layer must round-trip bf16 NaN payloads,
sweep crashed saves' tmp dirs, serialize concurrent async saves, and
never GC a delta chain's base.
"""
import io
import json
import os
import subprocess
import sys
import textwrap
import threading
import time

import jax
import numpy as np
import pytest

from repro.service import (SDE, Checkpointer, WriteAheadLog, recover,
                           replay)
from repro.service.wal import read_records
from repro.training import checkpoint as ckpt

_CM = {"eps": 0.02, "delta": 0.1, "weighted": False}
_DFT = {"window": 16, "n_coeffs": 4}
_N_STREAMS = 20


def _build(eng):
    for req in (
        {"type": "build", "request_id": "b1", "synopsis_id": "cm",
         "kind": "countmin", "params": _CM,
         "per_stream_of_source": True, "n_streams": _N_STREAMS},
        {"type": "build", "request_id": "b2", "synopsis_id": "src",
         "kind": "countmin", "params": _CM},
        {"type": "build", "request_id": "b3", "synopsis_id": "dft",
         "kind": "dft", "params": _DFT,
         "per_stream_of_source": True, "n_streams": 4},
    ):
        r = eng.handle(req)
        assert r.ok, r.error


def _batch(rng, n=64):
    """Integer-valued routed traffic (exact float32 sums — the byte
    comparisons rely on it)."""
    return (rng.randint(0, _N_STREAMS, n).astype(np.int64),
            rng.randint(1, 5, n).astype(np.float32))


def _assert_engines_equal(a: SDE, b: SDE):
    """FULL byte equality: stack state, allocation, routing layout,
    registry and counters."""
    assert list(a.stacks) == list(b.stacks)
    for kind in a.stacks:
        sa, sb = a.stacks[kind], b.stacks[kind]
        assert sa.capacity == sb.capacity
        assert list(sa.used) == list(sb.used)
        assert sorted(sa.source_rows) == sorted(sb.source_rows)
        for x, y in zip(jax.tree.leaves(sa.state),
                        jax.tree.leaves(sb.state)):
            x, y = np.asarray(x), np.asarray(y)
            assert x.tobytes() == y.tobytes()
        np.testing.assert_array_equal(sa.table.keys, sb.table.keys)
        np.testing.assert_array_equal(sa.table.rows, sb.table.rows)
        assert sa.table.count == sb.table.count
        assert sa.table.max_probe == sb.table.max_probe
    assert set(a.entries) == set(b.entries)
    for sid in a.entries:
        ea, eb = a.entries[sid], b.entries[sid]
        for f in ("kind_key", "row", "stream_id", "federated",
                  "responsible_site", "continuous", "source_id"):
            assert getattr(ea, f) == getattr(eb, f), (sid, f)
    assert a.batches_ingested == b.batches_ingested
    assert a.tuples_ingested == b.tuples_ingested
    assert a.wal_seq == b.wal_seq


# ---------------------------------------------------------------------------
# tentpole: incremental restore == full restore, with lifecycle +
# migration + compaction inside the delta chain
# ---------------------------------------------------------------------------
@pytest.mark.smoke
def test_incremental_restore_equals_full(tmp_path):
    rng = np.random.RandomState(0)
    eng = SDE()
    _build(eng)
    d_inc, d_full = str(tmp_path / "inc"), str(tmp_path / "full")

    eng.ingest(*_batch(rng))
    assert eng.snapshot(d_inc, 0, incremental=True) == "full"  # no base
    eng.ingest(*_batch(rng))
    # lifecycle + structural churn INSIDE the chain: stop a synopsis,
    # compact its stack, migrate a row — the deltas must carry all of it
    r = eng.handle({"type": "stop", "request_id": "s",
                    "synopsis_id": "dft/1"})
    assert r.ok, r.error
    dft_kind = eng.entries["dft/0"].kind_key
    eng.compact(dft_kind, min_capacity=2)
    assert eng.snapshot(d_inc, 1, incremental=True) == "delta"
    cm_kind = eng.entries["cm/0"].kind_key
    stack = eng.stacks[cm_kind]
    free = [i for i in range(stack.capacity) if not stack.used[i]]
    if free:
        eng.migrate_rows(cm_kind, {eng.entries["cm/0"].row: free[0]})
    eng.ingest(*_batch(rng))
    assert eng.snapshot(d_inc, 2, incremental=True) == "delta"

    from_chain = SDE.restore(d_inc)          # base 0 + deltas 1, 2
    _assert_engines_equal(from_chain, eng)
    eng.snapshot(d_full, 7)                  # full of the same moment
    from_full = SDE.restore(d_full)
    _assert_engines_equal(from_chain, from_full)
    # a restored engine EXTENDS the chain it was restored from
    from_chain.ingest(*_batch(rng))
    assert from_chain.snapshot(d_inc, 3, incremental=True) == "delta"
    eng.close(), from_chain.close(), from_full.close()


def test_delta_chain_pipelined_and_rebase(tmp_path):
    """Deltas under the pipelined engine (no fence) restore identically,
    and the chain rebases to a fresh full after ``rebase_every``."""
    rng = np.random.RandomState(1)
    eng = SDE(pipelined=True)
    _build(eng)
    d = str(tmp_path / "ck")
    eng.snapshot(d, 0)
    modes = []
    for step in range(1, 5):
        for _ in range(3):
            eng.ingest(*_batch(rng))
        modes.append(eng.snapshot(d, step, incremental=True,
                                  async_=True, rebase_every=3))
    eng.wait_for_snapshot()
    # steps 1..3 extend the chain; the 4th hits rebase_every and folds
    assert modes == ["delta", "delta", "delta", "full"]
    eng.flush()
    back = SDE.restore(d, pipelined=True)
    _assert_engines_equal(back, eng)
    eng.close(), back.close()


# ---------------------------------------------------------------------------
# tentpole: kill -9 anywhere, recover byte-identically (exactly-once)
# ---------------------------------------------------------------------------
_SERVER_SCRIPT = textwrap.dedent("""
    import json, os, sys, time
    import numpy as np
    from repro.service import SDE, WriteAheadLog, Checkpointer
    from repro.launch import sde_server

    wal_path, ck_dir, pipelined, kill_after = (
        sys.argv[1], sys.argv[2], sys.argv[3] == "1", int(sys.argv[4]))
    sde = SDE(pipelined=pipelined)
    wal = WriteAheadLog(wal_path, tag=sde.site)
    ckp = Checkpointer(sde, ck_dir, interval=3, keep=2, rebase_every=4)
    rng = np.random.RandomState(7)
    reqs = [
        {"type": "build", "request_id": "b1", "synopsis_id": "cm",
         "kind": "countmin",
         "params": {"eps": 0.02, "delta": 0.1, "weighted": False},
         "per_stream_of_source": True, "n_streams": 20},
        {"type": "build", "request_id": "b2", "synopsis_id": "src",
         "kind": "countmin",
         "params": {"eps": 0.02, "delta": 0.1, "weighted": False}},
    ]
    for i in range(40):
        sids = rng.randint(0, 20, 48)
        vals = rng.randint(1, 5, 48)
        reqs.append({"type": "ingest", "request_id": f"i{i}",
                     "stream_ids": [int(s) for s in sids],
                     "values": [float(v) for v in vals]})
    devnull = open(os.devnull, "w")
    for i, req in enumerate(reqs):
        sde_server.serve_lines([json.dumps(req)], sde, out=devnull,
                               wal=wal, checkpointer=ckp)
        print(f"ACK {i}", flush=True)      # durable: wal.sync() ran
        if i == kill_after:                # no request races the kill
            break
    print("DONE", flush=True)
    while True:                            # hold state until SIGKILL
        time.sleep(0.1)
""")


@pytest.mark.parametrize("pipelined", [False, True],
                         ids=["eager", "pipelined"])
def test_sigkill_recovery_byte_identical(tmp_path, pipelined):
    wal_path = str(tmp_path / "ingest.wal")
    ck_dir = str(tmp_path / "ck")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..",
                                     "src")
    env.pop("SDE_PIPELINED", None)
    proc = subprocess.Popen(
        [sys.executable, "-c", _SERVER_SCRIPT, wal_path, ck_dir,
         "1" if pipelined else "0", "17"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        env=env)
    try:
        # kill mid-stream, between checkpoints (interval=3, acks 2..41
        # are ingest batches): after ACK 17 the engine holds batches the
        # latest snapshot does NOT — recovery must stitch snapshot + tail
        for line in proc.stdout:
            if line.strip() == "ACK 17":
                break
        else:
            pytest.fail(f"server died early: {proc.stderr.read()[-2000:]}")
    finally:
        proc.kill()
        proc.wait(timeout=60)

    assert ckpt.latest_step(ck_dir) is not None   # it did checkpoint
    recovered = recover(ck_dir, wal_path, pipelined=pipelined)
    assert recovered.batches_ingested == 16       # acked ingests exactly
    # the oracle applies the acked stream ONCE, in order, eagerly
    oracle = SDE(pipelined=False)
    replay(oracle, wal_path)
    recovered.flush()
    oracle.flush()
    _assert_engines_equal(recovered, oracle)

    # the recovered server keeps serving: WAL seq resumes, checkpoints
    # extend the existing lineage, and a second recovery still matches
    wal2 = WriteAheadLog(wal_path, tag=recovered.site)
    assert wal2.seq == recovered.wal_seq
    ckp2 = Checkpointer(recovered, ck_dir, interval=3, keep=2,
                        rebase_every=4)
    rng = np.random.RandomState(99)
    for i in range(4):
        sids, vals = _batch(rng, 48)
        wal2.append_ingest(recovered.batches_ingested + 1, sids, vals)
        wal2.sync()
        recovered.ingest(sids, vals)
        recovered.wal_seq = wal2.seq
        ckp2.maybe_snapshot()
        oracle.ingest(sids, vals)
        oracle.wal_seq = wal2.seq
    wal2.close()
    recovered.wait_for_snapshot()
    recovered.flush()
    again = recover(ck_dir, wal_path, pipelined=False)
    oracle.flush()
    _assert_engines_equal(again, oracle)
    _assert_engines_equal(recovered, oracle)
    recovered.close(), oracle.close(), again.close()


# ---------------------------------------------------------------------------
# delta chain restores onto a DIFFERENT device mesh (elastic restart)
# ---------------------------------------------------------------------------
_MESH_SCRIPT = textwrap.dedent("""
    import os, tempfile
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np, jax
    from jax.sharding import NamedSharding
    from repro.service import SDE
    from repro.launch.mesh import make_mesh

    rng = np.random.RandomState(0)
    eng = SDE()          # chain written WITHOUT a mesh (1-device layout)
    eng.handle({"type": "build", "request_id": "b", "synopsis_id": "cm",
                "kind": "countmin",
                "params": {"eps": 0.02, "delta": 0.1, "weighted": False},
                "per_stream_of_source": True, "n_streams": 24})
    sids = rng.randint(0, 24, 512).astype(np.int64)
    eng.ingest(sids, np.ones(512, np.float32))
    d = tempfile.mkdtemp()
    eng.snapshot(d, 0)
    sids2 = rng.randint(0, 24, 512).astype(np.int64)
    eng.ingest(sids2, np.ones(512, np.float32))
    assert eng.snapshot(d, 1, incremental=True) == "delta"

    mesh = make_mesh((8, 1), ("data", "model"))
    eng2 = SDE.restore(d, mesh=mesh)     # base + delta, repartitioned
    stack = next(iter(eng2.stacks.values()))
    for leaf in jax.tree.leaves(stack.state):
        assert isinstance(leaf.sharding, NamedSharding)
        assert leaf.sharding.spec and leaf.sharding.spec[0] == "data"
    q = eng2.handle({"type": "adhoc", "request_id": "q",
                     "synopsis_id": "cm/5", "query": {"items": [5]}})
    want = float((sids == 5).sum() + (sids2 == 5).sum())
    assert float(q.value[0]) == want, (q.value, want)
    print("OK")
""")


def test_delta_restore_onto_8_devices():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..",
                                     "src")
    out = subprocess.run([sys.executable, "-c", _MESH_SCRIPT],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "OK" in out.stdout


# ---------------------------------------------------------------------------
# WAL semantics: idempotent replay, torn tails, interior corruption
# ---------------------------------------------------------------------------
def test_wal_replay_idempotent(tmp_path):
    path = str(tmp_path / "w.wal")
    rng = np.random.RandomState(3)
    live = SDE()
    wal = WriteAheadLog(path)
    for req in ({"type": "build", "request_id": "b", "synopsis_id": "cm",
                 "kind": "countmin", "params": _CM,
                 "per_stream_of_source": True, "n_streams": _N_STREAMS},):
        wal.append_request(req)
        assert live.handle(req).ok
        live.wal_seq = wal.seq
    batches = [_batch(rng, 32) for _ in range(5)]
    for sids, vals in batches:
        wal.append_ingest(live.batches_ingested + 1, sids, vals)
        live.ingest(sids, vals)
        live.wal_seq = wal.seq
    wal.close()

    fresh = SDE()
    assert replay(fresh, path) == 6
    _assert_engines_equal(fresh, live)
    assert replay(fresh, path) == 0          # idempotent: second pass
    _assert_engines_equal(fresh, live)

    # overlapping tail: the file grows a duplicate of its last 3 records
    # (same seqs — two writers raced into one log); still exactly-once
    with open(path) as f:
        lines = [ln for ln in f.read().split("\n") if ln]
    with open(path, "a") as f:
        f.write("\n".join(lines[-3:]) + "\n")
    assert replay(fresh, path) == 0
    _assert_engines_equal(fresh, live)
    live.close(), fresh.close()


@pytest.mark.smoke
def test_wal_torn_tail_tolerated_interior_raises(tmp_path):
    path = str(tmp_path / "w.wal")
    wal = WriteAheadLog(path)
    wal.append_ingest(1, [3, 3], [1.0, 2.0])
    wal.append_ingest(2, [4], [1.0], mask=[True])
    wal.close()
    with open(path, "a") as f:
        f.write('{"seq": 3, "kind": "ing')    # crash mid-append
    recs = list(read_records(path))
    assert [r["seq"] for r in recs] == [1, 2]  # torn tail dropped
    eng = SDE()
    eng.handle({"type": "build", "request_id": "b", "synopsis_id": "cm",
                "kind": "countmin", "params": _CM,
                "per_stream_of_source": True, "n_streams": 5})
    assert replay(eng, path) == 2
    assert eng.batches_ingested == 2
    # a reopened WAL resumes numbering past everything readable
    wal2 = WriteAheadLog(path)
    assert wal2.seq == 2
    wal2.close()
    # interior corruption is NOT a torn append: it must raise
    with open(path) as f:
        lines = f.read().split("\n")
    lines.insert(1, '{"seq": broken')
    with open(path, "w") as f:
        f.write("\n".join(lines))
    with pytest.raises(ValueError, match="corrupt WAL record"):
        list(read_records(path))         # generator: consume to detect
    eng.close()


def test_wal_read_records_streams(tmp_path):
    """``read_records`` is a lazy generator: records ahead of an
    interior corruption still stream out one at a time, and the raise
    fires exactly when iteration crosses the corrupt line — never at
    open. A replay over a huge log holds one record, not the list."""
    import types
    path = str(tmp_path / "w.wal")
    wal = WriteAheadLog(path)
    for i in range(4):
        wal.append_ingest(i + 1, [i], [1.0])
    wal.close()
    it = read_records(path)
    assert isinstance(it, types.GeneratorType)
    assert next(it)["seq"] == 1          # lazy: nothing else parsed yet
    # corrupt record 3 of 4 — the good prefix must still stream
    with open(path) as f:
        lines = [ln for ln in f.read().split("\n") if ln]
    lines[2] = '{"seq": broken'
    with open(path, "w") as f:
        f.write("\n".join(lines) + "\n")
    it = read_records(path)
    assert [next(it)["seq"], next(it)["seq"]] == [1, 2]
    with pytest.raises(ValueError, match="corrupt WAL record"):
        next(it)
    # the SAME bad line as the final line is a torn append: dropped
    with open(path, "w") as f:
        f.write("\n".join(lines[:2] + [lines[2]]) + "\n")
    assert [r["seq"] for r in read_records(path)] == [1, 2]


def test_wal_recovers_multidim_ingest_and_workflows(tmp_path):
    """Crash-recovery of the multidim plane: ``build_multidim`` /
    ``track_outliers`` replay as lifecycle requests (pre-apply records)
    and ``ingest_multidim`` as post-apply data records keyed by batch
    id — the recovered engine answers the same subpop query and keeps
    the workflow tracked."""
    import io

    from repro.launch import sde_server
    path = str(tmp_path / "w.wal")
    wal = WriteAheadLog(path)
    dims = {"region": ["EU", "US"], "platform": ["web", "mobile"]}
    recs = [{"region": "EU", "platform": "web"},
            {"region": "US", "platform": "mobile"},
            {"region": "EU", "platform": "mobile"}]
    reqs = [
        {"type": "build_multidim", "request_id": "b", "synopsis_id": "md",
         "kind": "countmin", "params": _CM, "dims": dims},
        {"type": "track_outliers", "request_id": "t", "workflow_id": "w",
         "synopsis_id": "md", "level": ["region"],
         "query": {"items": [5]}},
        {"type": "ingest_multidim", "request_id": "i", "synopsis_id":
         "md", "records": recs, "values": [1.0, 2.0, 3.0],
         "items": [5, 5, 5]},
    ]
    sde = SDE()
    out = io.StringIO()
    sde_server.serve_lines([json.dumps(r) for r in reqs], sde,
                           out=out, wal=wal)
    assert all(json.loads(ln)["ok"]
               for ln in out.getvalue().splitlines()
               if json.loads(ln).get("request_id"))
    wal.close()
    kinds = [r.get("kind") for r in read_records(path)]
    assert kinds == ["req", "req", "ingest_md"]
    recovered = recover(None, path)
    sde.flush()
    _assert_engines_equal(recovered, sde)
    assert recovered.multidim["md"] == sde.multidim["md"]
    assert "w" in recovered.outliers
    q = {"type": "subpop_query", "request_id": "q", "synopsis_id": "md",
         "where": {"region": "EU"}, "query": {"items": [5]}}
    np.testing.assert_allclose(np.asarray(recovered.handle(q).value),
                               np.asarray(sde.handle(q).value))
    # replay is idempotent: a second pass applies nothing
    assert replay(recovered, path) == 0
    sde.close(), recovered.close()


try:
    from hypothesis import HealthCheck, given, settings
    from hypothesis import strategies as st
    _HAVE_HYPOTHESIS = True
except ImportError:                          # pragma: no cover
    _HAVE_HYPOTHESIS = False

if _HAVE_HYPOTHESIS:
    _batch_st = st.lists(
        st.tuples(st.lists(st.integers(0, 7), min_size=1, max_size=12),
                  st.integers(1, 4)),
        min_size=1, max_size=6)

    @given(batches=_batch_st, dup_tail=st.integers(0, 6),
           extra_passes=st.integers(1, 3))
    @settings(deadline=None, max_examples=15,
              suppress_health_check=[HealthCheck.too_slow])
    def test_wal_replay_idempotence_property(tmp_path_factory, batches,
                                             dup_tail, extra_passes):
        """Replaying a WAL any number of times, with any duplicated
        tail appended, equals applying the acked stream exactly once."""
        tmp = tmp_path_factory.mktemp("wal")
        path = str(tmp / "w.wal")
        build = {"type": "build", "request_id": "b", "synopsis_id":
                 "cm", "kind": "countmin", "params": _CM,
                 "per_stream_of_source": True, "n_streams": 8}
        live = SDE()
        wal = WriteAheadLog(path)
        wal.append_request(build)
        assert live.handle(build).ok
        live.wal_seq = wal.seq
        for sids, val in batches:
            a = np.asarray(sids, np.int64)
            v = np.full(a.size, val, np.float32)
            wal.append_ingest(live.batches_ingested + 1, a, v)
            live.ingest(a, v)
            live.wal_seq = wal.seq
        wal.close()
        with open(path) as f:
            lines = [ln for ln in f.read().split("\n") if ln]
        if dup_tail:
            with open(path, "a") as f:
                f.write("\n".join(lines[-dup_tail:]) + "\n")
        fresh = SDE()
        replay(fresh, path)
        for _ in range(extra_passes - 1):
            assert replay(fresh, path) == 0
        _assert_engines_equal(fresh, live)
        live.close()
        fresh.close()


# ---------------------------------------------------------------------------
# checkpoint layer: bf16 bit-exactness, tmp sweep, async serialization,
# lineage-aware GC, keep= plumbing
# ---------------------------------------------------------------------------
@pytest.mark.smoke
def test_bf16_checkpoint_byte_identical(tmp_path):
    """bf16 leaves round-trip as bit patterns — including NaN payloads
    a float32 widening round trip would canonicalize."""
    bits = np.array([0x7FC1, 0x7F81, 0xFFC0, 0x8000, 0x0001, 0x3F80],
                    np.uint16)
    arr = jax.numpy.asarray(bits.view(jax.numpy.bfloat16.dtype))
    state = {"w": arr, "f": jax.numpy.arange(4, dtype=jax.numpy.float32)}
    d = str(tmp_path / "ck")
    ckpt.save(state, d, 0)
    back, man = ckpt.restore(state, d)
    assert man["leaf_dtypes"] == {"w": "bfloat16"}
    got = np.asarray(jax.device_get(back["w"])).view(np.uint16)
    np.testing.assert_array_equal(got, bits)     # BIT equality
    np.testing.assert_array_equal(np.asarray(back["f"]),
                                  np.asarray(state["f"]))
    # and the stored file really holds uint16, not widened f32
    blob = np.load(os.path.join(d, "step-00000000", "leaves.npz"))
    assert blob["w"].dtype == np.uint16


def test_stale_tmp_dirs_swept(tmp_path):
    d = str(tmp_path / "ck")
    os.makedirs(d)
    stale = os.path.join(d, "tmp-5-99999999")   # pid past pid_max: dead
    mine = os.path.join(d, f"tmp-6-{os.getpid()}")
    os.makedirs(stale)
    os.makedirs(mine)
    ckpt.save({"x": np.arange(3)}, d, 7)
    assert not os.path.exists(stale)      # dead pid: swept
    assert os.path.exists(mine)           # live pid: left alone
    assert ckpt.latest_step(d) == 7


def test_concurrent_async_saves_serialize(tmp_path):
    d = str(tmp_path / "ck")
    for step in range(4):
        ckpt.save({"x": np.full(1 << 16, step, np.int32)}, d, step,
                  keep=2, async_=True)
    ckpt.wait(d)
    assert ckpt.latest_step(d) == 3
    back, man = ckpt.restore({"x": np.zeros(1 << 16, np.int32)}, d)
    assert man["step"] == 3
    assert int(np.asarray(back["x"])[0]) == 3
    steps = sorted(p for p in os.listdir(d) if p.startswith("step-"))
    assert len(steps) == 2                # keep= plumbed through
    assert not [p for p in os.listdir(d) if p.startswith("tmp-")]


def test_gc_never_collects_delta_lineage(tmp_path):
    """keep=2 with a 4-delta chain: the base and interior deltas are
    outside the keep window but referenced by kept manifests — GC must
    leave the whole chain restorable."""
    rng = np.random.RandomState(5)
    eng = SDE()
    _build(eng)
    d = str(tmp_path / "ck")
    eng.snapshot(d, 0, keep=2)
    for step in range(1, 5):
        eng.ingest(*_batch(rng))
        assert eng.snapshot(d, step, incremental=True, keep=2,
                            rebase_every=10) == "delta"
    names = sorted(p for p in os.listdir(d) if p.startswith("step-"))
    assert names == [f"step-{s:08d}" for s in range(5)]  # all protected
    back = SDE.restore(d)                 # latest delta needs ALL of them
    _assert_engines_equal(back, eng)
    eng.close(), back.close()


# ---------------------------------------------------------------------------
# acked => recoverable, even against malformed requests: a refused
# ingest never reaches the WAL (logged post-apply), replay tolerates
# pre-fix poisoned records, and the log is truncated behind snapshots
# ---------------------------------------------------------------------------
def test_malformed_ingest_never_poisons_wal(tmp_path):
    """An ingest the engine refuses (length mismatch, non-numeric
    values) is acked with an error, serving continues, NOTHING lands in
    the WAL, and recovery replays exactly the acked batches — the batch
    id the bad request would have stolen goes to the next good one."""
    from repro.launch import sde_server
    path = str(tmp_path / "w.wal")
    sde = SDE()
    wal = WriteAheadLog(path)
    out = io.StringIO()
    reqs = [
        {"type": "build", "request_id": "b", "synopsis_id": "cm",
         "kind": "countmin", "params": _CM,
         "per_stream_of_source": True, "n_streams": _N_STREAMS},
        {"type": "ingest", "request_id": "good1",
         "stream_ids": [1, 2], "values": [1.0, 2.0]},
        {"type": "ingest", "request_id": "bad-mismatch",
         "stream_ids": [1, 2, 3], "values": [1.0]},
        {"type": "ingest", "request_id": "bad-values",
         "stream_ids": [1], "values": ["not-a-number"]},
        {"type": "ingest", "request_id": "good2",
         "stream_ids": [3, 4], "values": [3.0, 4.0]},
    ]
    n = sde_server.serve_lines([json.dumps(r) for r in reqs], sde,
                               out=out, wal=wal)
    assert n == len(reqs)            # serving survived the bad batches
    by_id = {r["request_id"]: r
             for r in map(json.loads, out.getvalue().splitlines())
             if r.get("request_id")}
    assert by_id["good1"]["ok"] and by_id["good2"]["ok"]
    assert not by_id["bad-mismatch"]["ok"]
    assert not by_id["bad-values"]["ok"]
    assert sde.batches_ingested == 2
    wal.close()
    ingests = [r for r in read_records(path) if r["kind"] == "ingest"]
    assert [r["batch"] for r in ingests] == [1, 2]   # acked ids only
    recovered = recover(None, path)
    sde.flush()
    _assert_engines_equal(recovered, sde)
    sde.close(), recovered.close()


def test_gateway_malformed_ingest_never_poisons_wal(tmp_path):
    """Same contract through the micro-batching gateway: the coalesced
    tick logs post-apply, so a tick whose every part is malformed adds
    nothing to the WAL."""
    import asyncio
    from repro.service.gateway import SynopsisGateway
    path = str(tmp_path / "w.wal")
    wal = WriteAheadLog(path)
    gw = SynopsisGateway(SDE(), wal=wal)

    async def drive():
        await gw.start()
        c = gw.connect("c")
        ok = await gw.submit(c, {
            "type": "build", "request_id": "b", "synopsis_id": "cm",
            "kind": "countmin", "params": _CM,
            "per_stream_of_source": True, "n_streams": _N_STREAMS})
        assert ok.ok, ok.error
        bad = await gw.submit(c, {"type": "ingest", "request_id": "x",
                                  "stream_ids": [1, 2], "values": [1.0]})
        assert not bad.ok
        good = await gw.submit(c, {"type": "ingest", "request_id": "g",
                                   "stream_ids": [1], "values": [2.0]})
        assert good.ok and good.value["batch"] == 1
        await gw.stop()

    asyncio.run(drive())
    wal.close()
    ingests = [r for r in read_records(path) if r["kind"] == "ingest"]
    assert [r["batch"] for r in ingests] == [1]
    recovered = recover(None, path)
    assert recovered.batches_ingested == 1
    recovered.close()


def test_replay_tolerates_poisoned_prefix_record(tmp_path):
    """A pre-fix WAL could hold a record for an ingest that FAILED live
    (it was logged before validation): replay must neither crash on it
    nor let it consume the batch id the next acked batch owns."""
    path = str(tmp_path / "w.wal")
    wal = WriteAheadLog(path)
    wal.append_request(
        {"type": "build", "request_id": "b", "synopsis_id": "cm",
         "kind": "countmin", "params": _CM,
         "per_stream_of_source": True, "n_streams": _N_STREAMS})
    wal.append_ingest(1, [1, 2, 3], [1.0])       # poisoned: mismatch
    wal.append_ingest(1, [5, 5], [1.0, 1.0])     # the REAL acked batch 1
    wal.close()
    eng = SDE()
    assert replay(eng, path) == 2                # build + real batch
    assert eng.batches_ingested == 1
    assert eng.wal_seq == 3                      # cursor passed the poison
    r = eng.handle({"type": "adhoc", "request_id": "q",
                    "synopsis_id": "cm/5", "query": {"items": [5]}})
    assert float(r.value[0]) == 2.0              # acked data not skipped
    eng.close()


def test_wal_truncated_after_durable_snapshot(tmp_path):
    """The Checkpointer drops WAL records folded into a snapshot that
    durably landed, so the log stops growing without bound — and a
    reopened WAL resumes its numbering past the dropped records instead
    of reusing seqs replay would then skip."""
    from repro.launch import sde_server
    path = str(tmp_path / "w.wal")
    d = str(tmp_path / "ck")
    sde = SDE()
    wal = WriteAheadLog(path)
    ckp = Checkpointer(sde, d, interval=2, keep=2, rebase_every=3,
                       wal=wal)
    rng = np.random.RandomState(21)
    reqs = [{"type": "build", "request_id": "b", "synopsis_id": "cm",
             "kind": "countmin", "params": _CM,
             "per_stream_of_source": True, "n_streams": _N_STREAMS}]
    for i in range(12):
        sids, vals = _batch(rng, 24)
        reqs.append({"type": "ingest", "request_id": f"i{i}",
                     "stream_ids": [int(s) for s in sids],
                     "values": [float(v) for v in vals]})
    sde_server.serve_lines([json.dumps(r) for r in reqs], sde,
                           out=io.StringIO(), wal=wal, checkpointer=ckp)
    recs = list(read_records(path))
    assert any(r.get("kind") == "trunc" for r in recs)
    assert len([r for r in recs if r.get("kind") == "ingest"]) < 12
    sde.wait_for_snapshot()
    recovered = recover(d, path)
    sde.flush()
    _assert_engines_equal(recovered, sde)
    wal.close()
    wal2 = WriteAheadLog(path)               # numbering survives rotation
    assert wal2.seq == sde.wal_seq == 13
    wal2.close()
    sde.close(), recovered.close()


# ---------------------------------------------------------------------------
# checkpoint layer: failed background saves surface and force a fresh
# full base; concurrent saves hold the per-directory lock; tmp age-out
# ---------------------------------------------------------------------------
def test_failed_async_save_forces_full_rebase(tmp_path, monkeypatch):
    """A background delta write that dies (disk full) must not chain:
    the next snapshot detects it, drops the broken lineage and takes a
    FULL base that re-ships the rows the failed delta cleared."""
    rng = np.random.RandomState(11)
    eng = SDE()
    _build(eng)
    d = str(tmp_path / "ck")
    assert eng.snapshot(d, 0, incremental=True, async_=True) == "full"
    eng.wait_for_snapshot()
    eng.ingest(*_batch(rng))
    real_savez, fail = np.savez, {"on": True}

    def maybe_boom(*a, **k):
        if fail["on"]:
            raise OSError("disk full")
        return real_savez(*a, **k)

    monkeypatch.setattr(np, "savez", maybe_boom)
    assert eng.snapshot(d, 1, incremental=True, async_=True) == "delta"
    eng.wait_for_snapshot()              # failure captured, not raised
    fail["on"] = False
    eng.ingest(*_batch(rng))
    assert eng.snapshot(d, 2, incremental=True, async_=True) == "full"
    assert eng.ckpt_failures == 1
    eng.wait_for_snapshot()
    eng.flush()
    back = SDE.restore(d)                # latest = the recovery full
    _assert_engines_equal(back, eng)
    eng.close(), back.close()


def test_failed_async_save_raises_on_next_save(tmp_path, monkeypatch):
    d = str(tmp_path / "ck")
    real_savez, fail = np.savez, {"on": True}

    def maybe_boom(*a, **k):
        if fail["on"]:
            raise OSError("disk full")
        return real_savez(*a, **k)

    monkeypatch.setattr(np, "savez", maybe_boom)
    t = ckpt.save({"x": np.arange(3)}, d, 0, async_=True)
    t.join()
    fail["on"] = False
    with pytest.raises(RuntimeError, match="never landed"):
        ckpt.save({"x": np.arange(3)}, d, 1, async_=True)
    ckpt.save({"x": np.arange(3)}, d, 2, async_=True)  # error drained
    ckpt.wait(d)
    assert ckpt.latest_step(d) == 2


def test_threaded_saves_serialize(tmp_path):
    """save() from many threads at once: the per-directory lock keeps
    the join-previous/register sequence atomic, so every step lands and
    no tmp dir is orphaned by an overlapping rename/GC."""
    d = str(tmp_path / "ck")
    threads = [threading.Thread(
        target=ckpt.save,
        args=({"x": np.full(1 << 14, i, np.int32)}, d, i),
        kwargs=dict(keep=10, async_=True)) for i in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    ckpt.wait(d)
    steps = sorted(p for p in os.listdir(d) if p.startswith("step-"))
    assert len(steps) == 6
    assert not [p for p in os.listdir(d) if p.startswith("tmp-")]


def test_stale_tmp_aged_out_despite_live_pid(tmp_path):
    """pid reuse fallback: a tmp dir owned by a live pid that is not
    ours is swept once it is older than the age cap — a recycled pid
    must not pin a crashed save's tmp dir forever."""
    d = str(tmp_path / "ck")
    os.makedirs(d)
    reused = os.path.join(d, "tmp-5-1")      # pid 1: always alive
    os.makedirs(reused)
    past = time.time() - 2 * 3600
    os.utime(reused, (past, past))
    fresh = os.path.join(d, "tmp-6-1")       # young: could be live
    os.makedirs(fresh)
    ckpt.save({"x": np.arange(3)}, d, 7)
    assert not os.path.exists(reused)        # aged out
    assert os.path.exists(fresh)             # too young to condemn


def test_checkpointer_paces_and_recovers_empty(tmp_path):
    """Checkpointer fires every ``interval`` ingested batches; recover
    with nothing on disk hands back a fresh engine."""
    rng = np.random.RandomState(8)
    eng = SDE()
    _build(eng)
    d = str(tmp_path / "ck")
    ckp = Checkpointer(eng, d, interval=2, async_=False)
    assert ckp.maybe_snapshot() is None          # nothing ingested yet
    eng.ingest(*_batch(rng))
    assert ckp.maybe_snapshot() is None          # 1 < interval
    eng.ingest(*_batch(rng))
    assert ckp.maybe_snapshot() == "full"        # first = base
    eng.ingest(*_batch(rng))
    eng.ingest(*_batch(rng))
    assert ckp.maybe_snapshot() == "delta"
    assert ckp.snapshots == 2
    eng.close()
    empty = recover(str(tmp_path / "nothing"), str(tmp_path / "no.wal"))
    assert empty.batches_ingested == 0 and not empty.stacks
    empty.close()
