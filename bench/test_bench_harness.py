"""The whole harness on the CPU at a tiny size: a sound run is correct, a
new configuration, traffic mix and metric are found by name, and the
harness refuses to run without a chip or without the program."""
from __future__ import annotations

import filecmp
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

from bench import run as harness, testing

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
SEED = 2**31 + 101


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return testing.make_root(tmp_path_factory.mktemp("tiny"))


def test_new_files_are_found_by_name_and_nothing_existing_is_edited(tiny):
    out = harness.run("tiny.closed", SEED, 1.5, True, root=tiny,
                      check=testing.cpu_as_chip)
    assert out["correct"], out["compared"]
    assert out["metrics"][testing.DUMMY_METRIC]["value"] > 0
    assert out["metrics"]["gateway.events_per_tick.closed"]["value"] > 0
    assert out["device"]["window_s"] > 0
    # every file the copy shares with the benchmark is unchanged
    cmp = filecmp.dircmp(BENCH, tiny, ignore=["__pycache__"])

    def same(c):
        assert not c.diff_files, c.diff_files
        for sub in c.subdirs.values():
            same(sub)
    same(cmp)


@pytest.mark.parametrize("cell,trace,want", [
    ("tinystocks.closed", False, {"setup_s", "events_per_s"}),
    ("tinystocks.closed", True, {"gateway.events_per_tick.closed",
                                 "wal.busy_pct.closed",
                                 "blue.compiles.closed"}),
    ("tiny.closed", False, {"setup_s", "events_per_s"}),
])
def test_a_sound_run_is_correct_and_reports_its_metrics(tiny, cell, trace,
                                                        want):
    out = harness.run(cell, SEED + 1, 1.5, trace, root=tiny,
                      check=testing.cpu_as_chip)
    assert out["correct"], out["compared"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == want
    assert list(out)[-1] == "compared"


def _bench(args, cwd, env=None):
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd,
                          capture_output=True, text=True, timeout=600,
                          env=dict(os.environ, JAX_PLATFORMS="cpu",
                                   **(env or {})))


def test_refuses_a_device_that_is_not_a_tpu():
    out = _bench(["--workload", "stocks5k.ingest_closed", "--seed", "1",
                  "--seconds", "1", "--trace", "0"], ROOT)
    assert out.returncode != 0
    assert out.stdout == ""
    assert "needs a TPU" in out.stderr


def test_fails_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _bench(["--workload", "stocks5k.ingest_closed", "--seed", "1",
                  "--seconds", "1", "--trace", "0"], tmp_path)
    assert out.returncode != 0
    assert out.stdout == ""
