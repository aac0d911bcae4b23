"""The harness at a tiny size on the CPU with the timed path broken
underneath: each fault a one-chip cell can have must come out as not
correct, in the price-walk cell through the number of its own module's
template. (The fault of an exchange between chips does not apply: every
cell runs on one chip.)"""
from __future__ import annotations

import numpy as np
import pytest

from bench import run as harness, testing

SEED = 2**31 + 202


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return testing.make_root(tmp_path_factory.mktemp("tiny"))


def _unchanged(monkeypatch):
    """The update step returns its state unchanged."""
    from repro.service import engine
    monkeypatch.setattr(engine, "_update",
                        lambda kind, backend, sharding, n_probe, state,
                        *a, **kw: state)


def _half_batch(monkeypatch):
    """Half of every batch left out, the rest weighted double so the
    mean is kept."""
    from repro.service.engine import SDE
    orig = SDE.ingest

    def ingest(self, stream_ids, values, mask=None, items=None):
        keep = np.arange(len(stream_ids)) % 2 == 0
        sub = (lambda x: None if x is None else np.asarray(x)[keep])
        return orig(self, sub(stream_ids), 2 * np.asarray(values,
                                                          np.float32)[keep],
                    sub(mask), sub(items))
    monkeypatch.setattr(SDE, "ingest", ingest)


def _altered_answer(monkeypatch):
    """One answer altered where it is produced: the first estimate of
    every query batch is one less."""
    from repro.service.engine import SDE
    orig = SDE.query_many

    def query_many(self, requests):
        out = orig(self, requests)
        if out and out[0].ok:
            v = out[0].value
            out[0].value = ([v[0] - 1, *v[1:]] if isinstance(v, list)
                            else v - 1)
        return out
    monkeypatch.setattr(SDE, "query_many", query_many)


@pytest.mark.parametrize("cell", ["tinystocks.closed", "tiny.closed",
                                  "tinyprice.closed"])
@pytest.mark.parametrize("fault", [_unchanged, _half_batch, _altered_answer])
def test_a_broken_timed_path_is_not_correct(tiny, monkeypatch, fault, cell):
    fault(monkeypatch)
    out = harness.run(cell, SEED, 1.0, False, root=tiny,
                      check=testing.cpu_as_chip)
    assert out["correct"] is False, out["compared"]
    if cell == "tinyprice.closed":
        gap = out["compared"]["turnover_gap"]
        assert gap["value"] > gap["limit"], out["compared"]
