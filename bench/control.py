"""The control: the exact reference put in the program's place, computed
one precision lower than the configuration states (bfloat16 for its f32
counters), on the same inputs as a run.

    python bench/control.py --workload <cell> --seed <n>

reads the results a run of that cell and seed left in ``.bench_run/``
(``bench/run.py`` first), builds the post-window answers with bf16
arithmetic and judges them as the run's own answers are judged. Its
numbers set the upper readings the configuration's ``limits`` lie under.

* CountMin: a bf16 counter per queried row and item; each acked request
  adds its exact weight, in the order the engine applied the batches.
* Any other template: the answer of the configuration module's
  ``control(req, qtype, k, order)`` (``order`` is that order of the acked
  requests), where the module has one and it gives one, not None.
* Everything else: the exact answer rounded to bf16. (HyperLogLog
  registers are small integers, exact in bf16, and an estimate rounded
  to bf16 moves by under 0.4 %, inside its bound: the control separates
  the CountMin numbers only.)
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

import ml_dtypes  # noqa: E402

from bench import traffic as tr  # noqa: E402
from bench.reference import exact  # noqa: E402
from bench.reference.hashing import fold64  # noqa: E402

BF16 = ml_dtypes.bfloat16


def bf16_running(contribs: np.ndarray) -> np.ndarray:
    """Counters kept in bf16, each request's (exact) weight added in
    turn: ``[n_requests, n]`` -> ``[n]``."""
    c = np.zeros(contribs.shape[1], BF16)
    for row in contribs:
        c = (c.astype(np.float32) + row.astype(BF16).astype(np.float32)
             ).astype(BF16)
    return c.astype(np.float64)


def control_answers(plan, results: dict) -> dict:
    """The post-window answers, computed by the bf16 control."""
    req = exact.Requests(plan, results["records"])
    order = np.argsort(req.batch, kind="stable")
    out = {}
    for rid in results["answers"]:
        r = results["records"][rid]
        if r["phase"] != "post":
            continue
        _, qtype, k = r["kind"].split(":")
        if qtype == "cm_rows":
            est = bf16_running(req.row_w[order])
            other = np.roll(plan.ids[req.qrows], 1)
            same = fold64(other) == req.ids_fold[req.qrows]
            out[rid] = [dict(value=[float(e), float(e) if s else 0.0])
                        for e, s in zip(est, same)]
        elif qtype == "cm_items":
            out[rid] = bf16_running(req.item_w[order]).tolist()
        else:
            own = plan.hook("control")
            ans = own(req, qtype, int(k), order) if own else None
            out[rid] = ans if ans is not None else \
                _bf16_round(exact.exact_answer(req, qtype, int(k)))
    return out


def _bf16_round(ans):
    if isinstance(ans, list):
        return [_bf16_round(a) for a in ans]
    if isinstance(ans, dict):
        return {k: _bf16_round(v) if k == "value" else v
                for k, v in ans.items()}
    return float(np.asarray(ans, np.float32).astype(BF16).astype(np.float64))


def control_numbers(plan, results: dict) -> dict:
    post = {rid: a for rid, a in results["answers"].items()
            if results["records"][rid]["phase"] == "post"}
    return exact.judge_run(plan, dict(results, answers=post),
                           control_answers(plan, results))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    root = tr.ROOT
    cell = tr.cell(args.workload, root)
    results = json.loads((root.parent / ".bench_run" / args.workload /
                          "results.json").read_text())
    t0, t1 = results["window"]
    plan = tr.make_plan(cell["config"], cell["traffic"], args.seed, t1 - t0,
                        root)
    limits = tr.load("configs", cell["config"], root)["limits"]
    numbers = control_numbers(plan, results)
    for k, v in numbers.items():
        print(f"control {k} {v!r} limit {limits[k]!r}", file=sys.stderr)
    print(json.dumps(dict(workload=args.workload, seed=args.seed,
                          correct=all(v <= limits[k]
                                      for k, v in numbers.items()),
                          numbers=numbers)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
