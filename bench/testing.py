"""A tiny copy of the benchmark for the CPU tests: the real files, plus
new configuration, traffic and metric files and a ``BENCHMARK.json`` that
names them. Nothing that exists is edited."""
from __future__ import annotations

import copy
import json
import pathlib
import shutil

from bench import traffic as tr

REAL = tr.ROOT
TINY_CELLS = {"tiny.closed": ("tiny", "tiny_closed"),
              "tinystocks.closed": ("tinystocks", "tiny_closed")}
DUMMY_METRIC = "dummy.ticks"

# Every kind and query template the generator and the reference know, at
# a size the CPU runs in seconds: Zipf-popular hashed stream ids,
# per-stream CountMin and HyperLogLog, data-source CountMin and
# HyperLogLog, continuous F2 on the hottest streams, a multidim family.
# (No data-source GK in the served runs: the program's GK drifts past its
# bound under many small batches, a known fault that PERF.md records.)
TINY_CONFIG = {
    "streams": {"count": 512, "ids": "hashed63", "population_seed": 0,
                "popularity": "zipf", "zipf_s": 0.99},
    "values": {"dist": "uniform_int", "low": 1, "high": 64},
    "synopses": [
        {"id": "cm", "kind": "countmin", "params": {"eps": 0.01,
                                                    "delta": 0.01},
         "per_stream": "all"},
        {"id": "hll", "kind": "hyperloglog", "params": {"rse": 0.0325},
         "per_stream": "all"},
        {"id": "src_cm", "kind": "countmin", "params": {"eps": 0.001,
                                                        "delta": 0.01}},
        {"id": "src_hll", "kind": "hyperloglog", "params": {"rse": 0.01}},
        {"id": "f2", "kind": "ams", "params": {"eps": 0.05, "delta": 0.05},
         "per_stream": "hottest", "hottest": 8, "continuous": True}],
    "multidim": {"id": "md", "kind": "hyperloglog", "params": {"rse": 0.02},
                 "dims": {"region": [f"r{i}" for i in range(8)],
                          "device": [f"d{i}" for i in range(4)]},
                 "users_zipf": 1.3, "user_base": 10**12},
    "queries": {"cm_rows": {"synopsis": "cm", "rows": 64},
                "cm_items": {"synopsis": "src_cm", "items": 16},
                "hll_total": {"synopsis": "src_hll"},
                "subpop_hll": {"synopsis": "md", "where": {"region": "r3"}},
                "hll_rows": {"synopsis": "hll", "rows": 4}},
    "engine": {"pipelined": False, "backend": "xla"},
    "durability": {"wal": "fsync before every ack",
                   "snapshot_after_setup": True,
                   "checkpoint_interval": 4,        # deltas in the window
                   "checkpoint_keep": 3, "rebase_every": 8,
                   "incremental": True, "async": True},
    "server": {"tick_s": 0.001, "max_in_flight": 8, "client_log_cap": 65536},
    "limits": {"cm_under": 0.001, "cm_over": 1.0, "hll_err": 4.0,
               "gk_err": 1.0, "cq_missing": 0, "cq_err": 1.0, "lost": 0,
               "recovered_diff": 0},
}


def with_gk(cfg: dict) -> dict:
    """``cfg`` with a data-source GK and its query template, for the
    reference's own tests (no server)."""
    cfg = copy.deepcopy(cfg)
    cfg["synopses"].append({"id": "src_gk", "kind": "gk_quantiles",
                            "params": {"eps": 0.01}})
    cfg["queries"]["gk"] = {"synopsis": "src_gk",
                            "qs": [0.01, 0.05, 0.25, 0.5, 0.75, 0.95, 0.99]}
    return cfg


TINY_MIX = {"ingest": {"connections": 2, "outstanding": 2, "events": 256,
                       "pool": 16},
            "multidim": {"events_per_record": 64, "records": 64},
            "warm": {"coalesced_max": 4, "seconds": 0.5}}


def make_root(tmp: pathlib.Path) -> pathlib.Path:
    """``tmp/bench`` (a copy of the benchmark with the tiny cells added)
    and ``tmp/BENCHMARK.json``; returns ``tmp/bench``."""
    root = pathlib.Path(tmp) / "bench"
    shutil.copytree(REAL, root, ignore=shutil.ignore_patterns(
        "__pycache__", "test_*"))

    def add(kind, name, data):
        path = root / kind / f"{name}.json"
        assert not path.exists()
        path.write_text(json.dumps(data))

    stocks = tr.load("configs", "stocks5k_paper", REAL)
    stocks["streams"]["count"] = 200
    stocks["queries"]["cm_rows"]["rows"] = 64
    add("configs", "tiny", TINY_CONFIG)
    add("configs", "tinystocks", stocks)
    add("traffic", "tiny_closed", TINY_MIX)
    (root / "metrics" / f"{DUMMY_METRIC}.py").write_text(
        "def read(ctx):\n    return float(ctx.counters['ticks'])\n")

    bench = json.loads((REAL.parent / "BENCHMARK.json").read_text())
    bench["workloads"] = [dict(name=n, config=c, traffic=t, chips=1,
                               why="tiny CPU test cell")
                          for n, (c, t) in TINY_CELLS.items()]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = list(TINY_CELLS)
    bench["per_layer"].append(dict(
        name=DUMMY_METRIC, unit="count", better="lower",
        source="program_counter", layer="gateway micro-batcher",
        moves="events_per_s", workloads=["tiny.closed"]))
    (root.parent / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def cpu_as_chip(chips: int) -> dict:
    """Stands in for the harness's look for a chip in CPU tests."""
    import jax
    d = jax.devices()
    return dict(platform=d[0].platform, kind="TPU v5 lite", count=len(d))
