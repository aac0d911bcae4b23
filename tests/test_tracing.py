"""``repro.tracing``: counters in one module, spans that record only
under a profiler session, and jitted programs named by their kind."""
import collections
import time

import jax
import numpy as np
import pytest

from repro import tracing
from repro.kernels import ops
from repro.service import SDE, SynopsisGateway
from repro.service import engine as engine_mod

N = 64                                   # events per ingest request


def _engine() -> SDE:
    sde = SDE()
    sde.handle({"type": "build", "request_id": "b", "synopsis_id": "cm",
                "kind": "countmin", "params": {"eps": 0.05, "delta": 0.1},
                "per_stream_of_source": True, "n_streams": 16})
    sde.handle({"type": "build", "request_id": "b2", "synopsis_id": "hll",
                "kind": "hyperloglog", "params": {"rse": 0.05}})
    return sde


def _ingest(rid: str) -> dict:
    return {"type": "ingest", "request_id": rid,
            "stream_ids": [i % 16 for i in range(N)], "values": [1.0] * N}


def _one_tick(gw: SynopsisGateway) -> None:
    client = gw.connect(f"c{len(gw.clients)}")
    futs = [gw.submit_nowait(client, _ingest(f"r{i}")) for i in range(2)]
    gw.tick()
    assert [f.result().ok for f in futs] == [True, True]


@pytest.mark.smoke
def test_a_span_records_nothing_with_the_profiler_off():
    assert not tracing.enabled()
    t0 = time.monotonic()
    with tracing.span("off.test", n=1) as sp:
        sp.note(m=2)
    tracing.record("off.test", 0, time.monotonic_ns())
    _one_tick(SynopsisGateway(_engine()))
    assert tracing.spans(t0, time.monotonic() + 1) == []


def test_one_tick_of_two_ingests_records_its_phases_nested(tmp_path):
    gw = SynopsisGateway(_engine())
    _one_tick(gw)                        # compile outside the session
    t0 = time.monotonic()
    jax.profiler.start_trace(str(tmp_path))
    try:
        _one_tick(gw)
    finally:
        jax.profiler.stop_trace()
    got = collections.defaultdict(list)
    for name, s, e, attrs in tracing.spans(t0, time.monotonic()):
        assert s <= e
        got[name].append((s, e, attrs))
    assert sorted(got) == ["gateway.coalesce", "gateway.queue_wait",
                           "gateway.tick", "ingest.h2d", "ingest.update",
                           "sde.ingest"]
    (tick,), (coal,), (ing,), (h2d,) = (got[k] for k in (
        "gateway.tick", "gateway.coalesce", "sde.ingest", "ingest.h2d"))
    waits, updates = got["gateway.queue_wait"], got["ingest.update"]
    assert [w[2] for w in waits] == [{"type": "ingest"}] * 2
    assert all(w[1] <= tick[0] for w in waits)
    assert tick[2] == {"requests": 2}
    assert coal[2] == {"requests": 2, "events": 2 * N}
    assert ing[2] == {"events": 2 * N, "multidim": False}
    # lo, hi, item (uint32 each), value (f32), mask (bool): 17 B an event
    assert h2d[2] == {"bytes": 17 * 2 * N}
    assert sorted(u[2]["kind"] for u in updates) == ["CountMin",
                                                     "HyperLogLog"]
    assert tick[0] <= coal[0] <= coal[1] <= ing[0] <= ing[1] <= tick[1]
    assert ing[0] <= h2d[0] <= h2d[1] <= min(u[0] for u in updates)
    assert max(u[1] for u in updates) <= ing[1]


def test_the_ring_is_bounded(monkeypatch):
    assert tracing._RING.maxlen == tracing.RING_SIZE == 1 << 20
    monkeypatch.setattr(tracing, "_RING", collections.deque(maxlen=4))
    monkeypatch.setattr(tracing, "enabled", lambda: True)
    t0 = time.monotonic_ns()
    for i in range(6):
        tracing.record("ring.test", t0 + i, t0 + i, i=i)
    got = tracing.spans(t0 / 1e9 - 1, time.monotonic() + 1, "ring.test")
    assert [s[3]["i"] for s in got] == [2, 3, 4, 5]


@pytest.mark.parametrize("kind", ["CountMin", "HyperLogLog"])
def test_the_update_program_is_named_by_its_kind(kind):
    sde = _engine()
    (key,) = [k for k in sde.stacks if type(k).__name__ == kind]
    stack = sde.stacks[key]
    src = stack.source_rows_idx()
    fn = engine_mod._update_fn(key, sde.backend, stack.sharding,
                               src is not None, stack.n_probe)
    ids = np.arange(N, dtype=np.uint32)
    args = (stack.state, *stack.device_table(), ids, np.zeros_like(ids),
            ids, np.ones(N, np.float32), np.ones(N, bool))
    text = fn.lower(*args, *(() if src is None else (src,))).as_text()
    assert f"jit_fused_update_{kind}" in text


def test_counters_live_in_tracing_only():
    assert ops.GATEWAY_TICKS is tracing.GATEWAY_TICKS
    assert ops.TRACE_COUNT is tracing.TRACE_COUNT
    for name, value in vars(ops).items():
        if isinstance(value, collections.Counter):
            assert value is getattr(tracing, name), name
