"""The reader of ``route.probe_passes.closed`` on a hand-made window: the
mean ``n_probe`` of the window's CountMin updates, and nothing where the
program's spans carry no ``n_probe``."""
from __future__ import annotations

import collections

import pytest

from bench import run as harness, testing

NAME = "route.probe_passes.closed"


def _ns(s: float) -> int:
    return int(round(s * 1e9))


def _ctx(monkeypatch, updates):
    """A window of [100.0, 101.0) s whose ring holds ``updates``, each
    (start in s, attrs) of an ``ingest.update`` span."""
    from repro import tracing
    ring = collections.deque(
        [("ingest.update", _ns(t), _ns(t + 0.01), a) for t, a in updates],
        maxlen=tracing.RING_SIZE)
    monkeypatch.setattr(tracing, "_RING", ring)
    return harness.Context({}, (100.0, 101.0), [], {}, {}, {})


def _read(ctx):
    return harness.load_reader(testing.REAL / "metrics" / f"{NAME}.py")(ctx)


def test_the_mean_of_the_window_s_countmin_updates(monkeypatch):
    ctx = _ctx(monkeypatch, [
        (99.9, {"kind": "CountMin", "n_probe": 32}),      # before the window
        (100.1, {"kind": "CountMin", "n_probe": 8}),
        (100.2, {"kind": "HyperLogLog", "n_probe": 1}),
        (100.5, {"kind": "CountMin", "n_probe": 16}),
        (101.0, {"kind": "CountMin", "n_probe": 32})])    # after it
    assert _read(ctx) == pytest.approx(12.0)


@pytest.mark.parametrize("updates", [
    [(100.1, {"kind": "CountMin"}), (100.2, {"kind": "HyperLogLog"})],
    [(100.1, {"kind": "HyperLogLog", "n_probe": 1})],
    []], ids=["no_n_probe", "no_countmin", "no_update"])
def test_silent_where_nothing_carries_n_probe(monkeypatch, updates):
    assert _read(_ctx(monkeypatch, updates)) is None
