"""The built-in generator and reference, frozen: for configurations that
bring no module of their own, the build requests, the first request lines
of the window's pool, the warm-up lines and the post-window queries, and
the numbers judged of a recorded run, hash as they did before
configuration modules existed."""
from __future__ import annotations

import gzip
import hashlib
import json
import pathlib

import pytest

from bench import loadgen, testing, traffic as tr
from bench.reference import exact

BENCH = pathlib.Path(__file__).resolve().parent
# a run of ``tiny.closed`` on the CPU (seed 2**31 + 303, 1 s window)
RECORDED = BENCH / "testdata" / "tiny_results.json.gz"
RECORDED_SEED = 2**31 + 303

LINES = {
    ("stocks5k_paper", 2**31 + 17):
        "c98ff92b34a7f2f6b04156bae1ea408d5c089ac0f21eed1c6e124dd6a3a93833",
    ("stocks5k_paper", 3 * 2**40 + 5):
        "7d90882c2153ded21fd672c7ecd185930e5377562f016ea81b6c7ce56d22c5ff",
    ("tiny", 2**31 + 17):
        "93e0d69132c62521a5e3db8cca099361c7a229f5382ea728ba5b1fcc63a7438e",
    ("tiny", 3 * 2**40 + 5):
        "9ca6fb471013c512743d5dec610435e321a27847e99e39946d60841fa8a2dd3a",
}
MIX = {"stocks5k_paper": "ingest_closed", "tiny": "tiny_closed"}
JUDGED = "1a06dc5ba42a365e3a60e9bdfb8e443a2f96400505f1d9f6a812f7291f8dd5cf"


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return testing.make_root(tmp_path_factory.mktemp("tiny"))


@pytest.mark.parametrize("config,seed", list(LINES))
def test_the_built_in_requests_are_unchanged(tiny, config, seed):
    plan = tr.make_plan(config, MIX[config], seed, 30.0, tiny)
    assert plan.module is None
    gen = loadgen.Gen(plan)
    gen.encode()
    h = hashlib.sha256()
    h.update(json.dumps(plan.build_requests(), sort_keys=True).encode())
    for line in gen.pool[:8] + gen.warm_lines:
        h.update(line)
    for q in plan.post_queries():
        h.update(json.dumps(plan.query(*q), sort_keys=True).encode())
    assert h.hexdigest() == LINES[config, seed]


def test_the_built_in_judge_is_unchanged(tiny):
    results = json.loads(gzip.decompress(RECORDED.read_bytes()))
    plan = tr.make_plan("tiny", "tiny_closed", RECORDED_SEED, 1.0, tiny)
    numbers = exact.judge_run(plan, results)
    assert hashlib.sha256(json.dumps(numbers, sort_keys=True).encode()
                          ).hexdigest() == JUDGED, numbers
