"""Aggregate dry-run records into the roofline table (EXPERIMENTS.md
§Roofline reads this output), and gate the Pallas update kernels.

  PYTHONPATH=src python -m benchmarks.roofline --dir benchmarks/out
  PYTHONPATH=src python -m benchmarks.roofline --check [--shape full]

``--check`` runs every registered update kernel (kernels.ops registry)
through ``SDE(backend="pallas")`` and proves its state byte-identical to
the XLA reference path (``backend="xla"``) on the same batch. Records
land next to the dry-run records with ``mesh_name`` set to the backend
the kernels ran on, so ``table()`` can filter them the same way.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import time
from typing import Dict, List

# kernel-gate cases: (registry kernel, api kind, params)
_GATE = [
    ("countmin_scatter", "countmin",
     {"eps": 0.1, "delta": 0.1, "weighted": False}),
    ("ams_scatter", "ams", {"eps": 0.1, "delta": 0.1}),
    ("hll_max", "hyperloglog", {"rse": 0.1}),
    ("bloom_bitset", "bloom", {"n_elements": 64, "fpr": 0.05}),
    ("fm_bitmap", "fm", {}),
    ("rhp_project", "rhp", {"n_bits": 64}),
]


def load(dir_: str) -> List[Dict]:
    out = []
    for p in sorted(glob.glob(os.path.join(dir_, "*.json"))):
        with open(p) as f:
            r = json.load(f)
        # legacy skip/error records stuffed the mesh NAME into ``mesh``
        # (ok records hold a dict there) — normalize so every record
        # carries ``mesh_name`` and table() can filter on one field
        if "mesh_name" not in r and isinstance(r.get("mesh"), str):
            r["mesh_name"] = r["mesh"]
        out.append(r)
    return out


def _fmt_bytes(b):
    if b is None:
        return "-"
    return f"{b/2**30:.2f}"


def table(records: List[Dict], mesh: str = "pod16x16") -> str:
    recs = [r for r in records
            if "arch" in r and r.get("mesh_name") == mesh]
    lines = [
        "| arch | shape | dom | compute_s | memory_s | coll_s | "
        "useful/HLO | roofline frac | HBM GiB/dev | note |",
        "|---|---|---|---|---|---|---|---|---|---|",
    ]
    for r in sorted(recs, key=lambda x: (x["arch"], x["shape"])):
        if "skipped" in r:
            lines.append(f"| {r['arch']} | {r['shape']} | — | — | — | — |"
                         f" — | — | — | {r['skipped'][:40]} |")
            continue
        if "error" in r:
            lines.append(f"| {r['arch']} | {r['shape']} | ERR | | | | | |"
                         f" | {r['error'].splitlines()[-1][:60]} |")
            continue
        ro = r["roofline"]
        pd = r["per_device"]
        lines.append(
            f"| {r['arch']} | {r['shape']} | {ro['dominant'][:-2]} "
            f"| {ro['compute_s']:.3f} | {ro['memory_s']:.3f} "
            f"| {ro['collective_s']:.3f} | {ro['hlo_useful_ratio']:.2f} "
            f"| {ro['roofline_fraction']:.3f} "
            f"| {_fmt_bytes(pd.get('peak_bytes'))} | |")
    lines.append(f"\n{len(recs)} dry-run record(s) on mesh `{mesh}`")
    return "\n".join(lines)


def summary(records: List[Dict]) -> Dict:
    ok = [r for r in records if "roofline" in r]
    skip = [r for r in records if "skipped" in r]
    err = [r for r in records if "error" in r]
    worst = sorted(ok, key=lambda r: r["roofline"]["roofline_fraction"])
    coll = sorted(ok, key=lambda r: -r["roofline"]["collective_s"])
    return dict(
        n_ok=len(ok), n_skip=len(skip), n_err=len(err),
        worst_fraction=[(r["arch"], r["shape"], r.get("mesh_name"),
                         round(r["roofline"]["roofline_fraction"], 4))
                        for r in worst[:5]],
        most_collective=[(r["arch"], r["shape"], r.get("mesh_name"),
                          round(r["roofline"]["collective_s"], 3))
                         for r in coll[:5]],
    )


# ---------------------------------------------------------------------------
# update-kernel acceptance gate (--check)
# ---------------------------------------------------------------------------
def kernel_records(shape: str = "gate") -> List[Dict]:
    """One record per registry kernel: byte equality of the Pallas state
    against the XLA reference engine on the same batch."""
    import numpy as np
    import jax
    from repro.service import SDE

    # gate shapes keep the interpret-mode grids tiny (CI runs this on
    # CPU); --shape full scales to the 1024-synopsis point
    full = shape == "full"
    n_syn = 1024 if full else 16
    t_tuples = 4096 if full else 512

    rng = np.random.RandomState(11)
    pop = np.unique(rng.randint(0, 2**62, size=4 * n_syn,
                                dtype=np.int64))[:n_syn]
    sids = pop[rng.randint(0, n_syn, t_tuples)]
    # sprinkle ids outside the routed population — the probe's miss
    # (-1) path must round-trip through every kernel too
    sids[::max(t_tuples // 16, 1)] = int(pop.max()) + 1
    vals = rng.randint(1, 5, t_tuples).astype(np.float32)

    records = []
    for kernel, kind_name, params in _GATE:
        states, wall = {}, None
        for backend in ("xla", "pallas"):
            eng = SDE(backend=backend)
            r = eng.handle({
                "type": "build", "request_id": "b",
                "synopsis_id": "g", "kind": kind_name,
                "params": params, "per_stream_of_source": True,
                "stream_ids": [int(s) for s in pop]})
            assert r.ok, r.error
            t0 = time.perf_counter()
            eng.ingest(sids, vals)
            jax.block_until_ready([s.state for s in eng.stacks.values()])
            wall = time.perf_counter() - t0
            states[backend] = np.asarray(next(iter(eng.stacks.values()))
                                         .state)
            eng.close()
        records.append(dict(
            kernel=kernel, kind=kind_name, shape=shape,
            n_synopses=n_syn, batch_tuples=t_tuples,
            state_bytes=states["pallas"].nbytes,
            byte_equal=bool(np.array_equal(states["xla"],
                                           states["pallas"])),
            wall_seconds_pallas_incl_compile=round(wall, 4),
            backend="pallas", mesh_name=jax.default_backend()))
    return records


def check(records: List[Dict], out_dir: str = None) -> List[str]:
    """Print the gate table, persist the records, return failures."""
    failures = []
    lines = [
        "| kernel | n_syn | batch | bytes | ok |",
        "|---|---|---|---|---|",
    ]
    for r in records:
        if not r["byte_equal"]:
            failures.append(f"{r['kernel']}: pallas != xla state bytes")
        lines.append(
            f"| {r['kernel']} | {r['n_synopses']} | {r['batch_tuples']} "
            f"| {'equal' if r['byte_equal'] else 'DIFFER'} "
            f"| {'PASS' if r['byte_equal'] else 'FAIL'} |")
    print("\n".join(lines))
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        for r in records:
            path = os.path.join(
                out_dir, f"kernel__{r['kernel']}__{r['shape']}.json")
            with open(path, "w") as f:
                json.dump(r, f, indent=1)
        print(f"\n{len(records)} kernel record(s) -> {out_dir}")
    return failures


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dir", default="benchmarks/out")
    ap.add_argument("--mesh", default="pod16x16")
    ap.add_argument("--check", action="store_true",
                    help="run the update-kernel acceptance gate")
    ap.add_argument("--shape", default="gate", choices=["gate", "full"],
                    help="--check problem size (full = 1024 synopses)")
    args = ap.parse_args()

    if args.check:
        failures = check(kernel_records(args.shape), out_dir=args.dir)
        if failures:
            print("\nFAIL:\n  " + "\n  ".join(failures))
            sys.exit(1)
        print("\nall update kernels pass the roofline gate")
        return

    records = load(args.dir)
    print(table(records, args.mesh))
    print()
    print(json.dumps(summary(records), indent=1))


if __name__ == "__main__":
    main()
