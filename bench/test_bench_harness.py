"""The whole harness on the CPU at a tiny size: a sound run is correct, a
new configuration, traffic mix, configuration module and metric are found
by name, a configuration's mesh shards the engine over four devices, and
the harness refuses to run without a chip or without the program."""
from __future__ import annotations

import filecmp
import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

from bench import run as harness, testing, traffic as tr

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
    # the configuration's own module, found by its name: its events are
    # sent and its template is asked and judged by its own number
    module = tiny / "modules" / "tinyprice.py"
    assert not (BENCH / "modules" / "tinyprice.py").exists()
    plan = tr.make_plan("tinyprice", "tiny_closed", SEED, 1.0, tiny)
    assert plan.module.__file__ == str(module)
    line = json.loads(plan.ingest_line(tr.TAG_WINDOW, 0, 8))
    assert min(line["values"]) > 64         # prices, not built-in weights
    out = harness.run("tinyprice.closed", SEED, 1.0, False, root=tiny,
                      check=testing.cpu_as_chip)
    assert out["correct"], out["compared"]
    assert out["compared"]["turnover_gap"] == dict(value=0.0, limit=0)
    # every file the copy shares with the benchmark is unchanged
    cmp = filecmp.dircmp(BENCH, tiny, ignore=["__pycache__"])

    def same(c):
        assert not c.diff_files, c.diff_files
        for sub in c.subdirs.values():
            same(sub)
    same(cmp)


@pytest.mark.parametrize("cell,trace,want", [
    ("tinystocks.closed", False, {"setup_s", "events_per_s"}),
    # every per-layer metric whose reader finds something on the CPU:
    # the device-trace ones find no device there
    ("tinystocks.closed", True, {"gateway.events_per_tick.closed",
                                 "wal.busy_pct.closed",
                                 "blue.compiles.closed",
                                 "frontend.decode_pct.closed",
                                 "gateway.queue_wait_ms.closed",
                                 "wal.encode_pct.closed",
                                 "wal.sync_pct.closed",
                                 "route.probe_passes.closed"}),
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


MESHED = """
import json, pathlib, sys
sys.path.insert(0, {root!r})
sys.path.insert(0, {src!r})
from bench import run as harness, testing
from repro.service import engine

specs = []
place = engine._KindStack._place


def record(self):
    place(self)
    specs.append(None if self.sharding is None else str(self.sharding.spec))


engine._KindStack._place = record
tiny = testing.make_root(pathlib.Path({tmp!r}))
out = harness.run("tinymesh.closed", {seed}, 1.0, False, root=tiny,
                  check=testing.cpu_as_chip)
print(json.dumps(dict(out=out, specs=specs)))
"""


def test_a_configured_mesh_row_shards_the_engine_and_its_recovery(
        tmp_path):
    code = MESHED.format(root=str(ROOT), src=str(ROOT / "src"),
                         tmp=str(tmp_path), seed=SEED + 2)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=600, env=env, cwd=str(ROOT))
    assert proc.returncode == 0, proc.stderr[-4000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    out = got["out"]
    assert out["correct"], out["compared"]
    assert out["compared"]["recovered_diff"] == dict(value=0, limit=0)
    assert out["device"]["count"] == 4
    # every stack of the live engine and of the recovered one has its
    # rows over the mesh's data axis
    assert got["specs"] and all("data" in (s or "") for s in got["specs"])


def test_a_mesh_of_another_size_than_the_cell_is_refused():
    mesh = {"shape": [4, 1], "axes": ["data", "model"]}
    assert harness.engine_mesh({"backend": "xla"}, 1) is None
    with pytest.raises(ValueError, match="asks for 1 chips"):
        harness.engine_mesh({"mesh": mesh}, 1)


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
