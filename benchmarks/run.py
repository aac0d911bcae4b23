"""Benchmark runner — one harness per paper figure.

Prints ``name,us_per_call,derived`` CSV rows and writes the same rows
as machine-readable ``BENCH_summary.json`` (``--summary`` to relocate
it) so CI and regression tooling diff runs without scraping stdout.
``--full`` uses the paper's exact sizes (5000 streams etc.); default
sizes finish in ~2 minutes on one CPU core. Dry-run/roofline cells are
produced separately by ``python -m repro.launch.dryrun --all`` (they
need 512 fake devices).
"""
from __future__ import annotations

import argparse
import json
import sys
import traceback


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale sizes (5000 streams)")
    ap.add_argument("--only", default=None,
                    help="comma list: fig5,fig6,fig7,fig8,fig9,fig10,"
                         "fig11,fig12,fig13")
    ap.add_argument("--summary", default="BENCH_summary.json",
                    help="machine-readable results file "
                         "(empty string to skip)")
    args = ap.parse_args(argv)

    from . import fig5_scalability, fig6_dft_workflow, fig7_coreset, \
        fig8_sdeaas, fig9_routing, fig10_gateway, fig11_elasticity, \
        fig12_durability, fig13_subpop

    figs = dict(fig5=fig5_scalability, fig6=fig6_dft_workflow,
                fig7=fig7_coreset, fig8=fig8_sdeaas,
                fig9=fig9_routing, fig10=fig10_gateway,
                fig11=fig11_elasticity, fig12=fig12_durability,
                fig13=fig13_subpop)
    only = set(args.only.split(",")) if args.only else set(figs)

    from repro.launch.compile_cache import enable_compilation_cache
    enable_compilation_cache()
    results, failed = [], []
    print("name,us_per_call,derived")
    for name, mod in figs.items():
        if name not in only:
            continue
        try:
            for row in mod.run(full=args.full):
                print(row, flush=True)
                cells = row.split(",", 2)
                results.append(dict(
                    fig=name, name=cells[0],
                    us_per_call=float(cells[1]),
                    derived=cells[2] if len(cells) > 2 else ""))
        except Exception:  # run the other figures, then fail
            traceback.print_exc()
            failed.append(name)
    if args.summary:
        with open(args.summary, "w", encoding="utf-8") as f:
            json.dump(dict(full=args.full, rows=results, failed=failed),
                      f, indent=2)
            f.write("\n")
    if failed:
        sys.exit(f"figures failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()
