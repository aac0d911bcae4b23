"""``program_spans.py`` and the readers of the program's own spans: the
clock mapping and the idle attribution on a hand-made trace, every reader
on a program without ``repro.tracing``, and a tiny traced CPU run in which
the span metrics read."""
from __future__ import annotations

import collections
import sys

import pytest

from bench import program_spans, roofline, run as harness, testing
from bench import traffic as tr

SEED = 2**31 + 202
OFF = -98_765_432_100_123        # profiler clock minus monotonic, in ns
SPAN_METRICS = ("frontend.decode_pct.closed", "gateway.queue_wait_ms.closed",
                "wal.encode_pct.closed", "wal.sync_pct.closed")
DEVICE_METRICS = ("update_roofline.countmin",
                  "device.idle_unspanned_pct.closed")


def _read(name, ctx):
    return harness.load_reader(testing.REAL / "metrics" / f"{name}.py")(ctx)


def _ns(s: float) -> int:
    return int(round(s * 1e9))


@pytest.fixture
def ctx(monkeypatch):
    """A window of [100.0, 100.6) s on ``time.monotonic`` with three
    ticks, and its trace on the profiler's clock (``OFF`` away): device
    gaps under ``ingest.h2d`` (4 ms), ``wal.sync`` (6 ms) and no span
    (10 ms, covered only by a request's queue wait)."""
    from repro import tracing
    ring = collections.deque([
        ("gateway.tick", _ns(100.0), _ns(100.19), {"requests": 2}),
        ("sde.ingest", _ns(100.01), _ns(100.15),
         {"events": 1000, "multidim": False}),
        ("ingest.h2d", _ns(100.02), _ns(100.03), {"bytes": 17000}),
        ("wal.encode", _ns(100.15), _ns(100.16), {"events": 1000}),
        ("wal.sync", _ns(100.16), _ns(100.18), {}),
        ("frontend.decode", _ns(100.25), _ns(100.26), {"bytes": 10}),
        ("gateway.queue_wait", _ns(100.3), _ns(100.4), {"type": "ingest"}),
        ("gateway.queue_wait", _ns(100.38), _ns(100.4), {"type": "ingest"}),
    ], maxlen=tracing.RING_SIZE)
    monkeypatch.setattr(tracing, "_RING", ring)
    ticks = [100.0, 100.2, 100.4]
    # the harness's own tick spans, and the same calls in the trace
    # (one more tick traced before the window, and 2 us of jitter on one)
    hspans = [("gateway.tick", t, t + 0.19, None) for t in ticks]
    host = [("bench/gateway.tick", _ns(t) + OFF + d, _ns(t + 0.19) + OFF)
            for t, d in zip([99.8] + ticks, [0, 0, 2000, 0])]
    p = lambda t: float(_ns(t) + OFF)    # noqa: E731
    ops = [("u", p(100.0), p(100.021)), ("u", p(100.025), p(100.165)),
           ("u", p(100.171), p(100.34)), ("u", p(100.35), p(100.6))]
    # the window's annotation opens a sleep's overshoot after its start
    trace = dict(window=[p(100.0) + 60_000, p(100.6)], host=host, device={
        "/device:TPU:0": dict(ops=ops, modules=[
            ("jit_fused_update_CountMin(1)", p(100.025), p(100.125)),
            ("jit_fused_update_HyperLogLog(2)", p(100.13), p(100.135))])})
    cfg = tr.load("configs", "stocks5k_paper", testing.REAL)
    return harness.Context(cfg, (100.0, 100.6), hspans, {}, trace,
                           {"hbm_bytes_per_s": 819e9})


def test_clock_mapping_and_idle_by_span_of_a_hand_made_trace(ctx):
    assert program_spans.offset_ns(ctx) == OFF
    got = dict(program_spans.idle_by_span(ctx))
    assert got == pytest.approx({"ingest.h2d": 0.004, "wal.sync": 0.006,
                                 "no span": 0.010})
    assert _read("device.idle_unspanned_pct.closed", ctx) == \
        pytest.approx(50.0)


def test_the_span_readers_on_a_hand_made_window(ctx):
    assert _read("frontend.decode_pct.closed", ctx) == \
        pytest.approx(100 * 0.01 / 0.6)
    assert _read("wal.encode_pct.closed", ctx) == \
        pytest.approx(100 * 0.01 / 0.6)
    assert _read("wal.sync_pct.closed", ctx) == \
        pytest.approx(100 * 0.02 / 0.6)
    assert _read("gateway.queue_wait_ms.closed", ctx) == pytest.approx(60.0)
    cm_only = dict(ctx.cfg, synopses=ctx.cfg["synopses"][:1])
    assert _read("update_roofline.countmin", ctx) == pytest.approx(
        100 * roofline.update_bytes(cm_only, 1000, 0) / 819e9 / 0.1)


def test_every_reader_is_silent_on_a_program_without_tracing(
        ctx, monkeypatch):
    import repro
    monkeypatch.delattr(repro, "tracing")
    monkeypatch.setitem(sys.modules, "repro.tracing", None)
    assert program_spans.recorded(0.0, 1e9) == []
    for name in SPAN_METRICS + DEVICE_METRICS:
        assert _read(name, ctx) is None, name


def test_the_span_metrics_read_in_a_tiny_traced_run(tmp_path):
    root = testing.make_root(tmp_path)
    out = harness.run("tiny.closed", SEED, 1.5, True, root=root,
                      check=testing.cpu_as_chip)
    assert out["correct"], out["compared"]
    for name in SPAN_METRICS:
        assert out["metrics"][name]["value"] > 0, name
    # no device in a CPU trace: the device metrics find nothing to read
    assert not set(DEVICE_METRICS) & set(out["metrics"])
