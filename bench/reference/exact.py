"""The exact reference a run is judged by, and the numbers compared.

Written for the benchmark from the semantics of each sketch (the checks
follow the repository's first chip smoke test): it imports nothing of the
program and reads nothing the program made. It rebuilds the events of
every acked request from the seed (``bench.traffic``), computes exact
counts, distinct counts and quantiles with numpy, and measures each
answer of the run against them:

* ``cm_under`` - how far a CountMin answer lies below the exact weight,
  relative to it. CountMin never undercounts; f32 sums of integers are
  exact below 2**24.
* ``cm_over`` - how far it lies above, as a share of ``eps * n``.
* ``hll_err`` - distance of a HyperLogLog answer from the exact distinct
  count, in relative standard errors (``rse * exact``).
* ``gk_err`` - rank error of a GK quantile, as a share of ``eps``.
* ``cq_missing`` - continuous responses missing or extra.
* ``cq_err`` - error of a continuous AMS F2 answer, as a share of
  ``eps * exact``.
* ``lost`` - requests due in the window that never got an answer.

The answers judged are those of the queries sent once the window has
closed and every acked batch is flushed, so each is held to the exact
answer over all acked requests. A template this module does not know
goes to the configuration's own module (``plan.module``, from
``bench/modules/``: its ``exact`` and ``judge``, see ``bench/traffic.py``).
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional

import numpy as np

from bench.reference.hashing import fold64

N_VALUES = 65                # integer weights 1..64 (and 0)


def rank_error(hist: np.ndarray, x: float, q: float) -> float:
    """Distance from ``q`` to the rank interval of ``x`` in the multiset
    whose histogram over integer values is ``hist``."""
    n = hist.sum()
    if n == 0:
        return 1.0
    xi = int(math.floor(x))
    below = hist[:max(0, min(xi + (0 if x == xi else 1), len(hist)))].sum()
    at = hist[xi] if x == xi and 0 <= xi < len(hist) else 0
    lo, hi = below / n, (below + at) / n
    return 0.0 if lo <= q <= hi else min(abs(q - lo), abs(q - hi))


class Requests:
    """The acked data requests of a run, with their events rebuilt."""

    def __init__(self, plan, records: Dict[str, dict]):
        self.plan = plan
        self.n_levels = 2 ** len(plan.md["dims"]) if plan.md else 0
        rows = []
        for rid, r in records.items():
            if r["ok"] and r["kind"] in ("ingest", "ingest_multidim"):
                rows.append((r["batch"], rid, r))
        rows.sort(key=lambda x: (x[0], x[2]["sent"]))
        self.rids = [rid for _, rid, _ in rows]
        self.recs = [r for _, _, r in rows]
        self.batch = np.asarray([r["batch"] for r in self.recs], np.int64)
        self.is_md = np.asarray([r["kind"] == "ingest_multidim"
                                 for r in self.recs], bool)
        self.ranks: List[np.ndarray] = []
        self.vals: List[np.ndarray] = []
        self.extra: List[Dict[str, np.ndarray]] = []
        self.users: List[np.ndarray] = []
        self.region: List[np.ndarray] = []
        for r in self.recs:
            if r["kind"] == "ingest":
                rk, v, extra = plan.ingest_events(r["tag"], r["index"],
                                                  r["size"])
                self.ranks.append(rk.astype(np.int32))
                self.vals.append(v)             # in the dtype it was sent
                self.extra.append(extra)
                self.users.append(np.zeros(0, np.int64))
                self.region.append(np.zeros(0, np.int64))
            else:
                reg, _, users, v = plan.md_records(r["index"], r["size"])
                self.ranks.append(np.zeros(0, np.int32))
                self.vals.append(v.astype(np.int64))
                self.extra.append({})
                self.users.append(users)
                self.region.append(reg)
        n = len(self.recs)
        # weights each request adds to the queried per-stream rows, and
        # to the queried data-source items; its value histogram, where a
        # GK template reads it (only integer weights under N_VALUES have
        # one)
        q = plan.cfg.get("queries", {})
        with_hist = "gk" in q
        self.qrows = plan.query_rows() if "cm_rows" in q else \
            np.zeros(0, np.int64)
        col = np.full(plan.n_streams, -1, np.int64)
        col[self.qrows] = np.arange(len(self.qrows))
        self.row_w = np.zeros((n, len(self.qrows)))
        n_items = int(q["cm_items"]["items"]) if "cm_items" in q else 0
        self.items = fold64(plan.ids[self.qrows[:n_items]])
        item_col = {int(f): i for i, f in enumerate(self.items)}
        self.item_w = np.zeros((n, len(self.items)))
        ids_fold = fold64(plan.ids)
        self.hist = np.zeros((n, N_VALUES))
        self.total = np.zeros(n)
        self.n_hot = plan.n_hot
        self.hot_w = np.zeros((n, self.n_hot))
        for i in range(n):
            rk, v = self.ranks[i], self.vals[i]
            if not self.is_md[i]:
                c = col[rk]
                m = c >= 0
                self.row_w[i] = np.bincount(c[m], v[m],
                                            minlength=len(self.qrows))
                h = rk < self.n_hot
                self.hot_w[i] = np.bincount(rk[h], v[h],
                                            minlength=self.n_hot)
                folds = ids_fold[rk]
                if with_hist:
                    self.hist[i] = np.bincount(v, minlength=N_VALUES)
                self.total[i] = v.sum()
            else:
                folds = fold64(self.users[i])
                v = v * self.n_levels
                if with_hist:
                    self.hist[i] = np.bincount(
                        self.vals[i], minlength=N_VALUES) * self.n_levels
                self.total[i] = v.sum()
            if item_col:
                uf, inv = np.unique(folds, return_inverse=True)
                wsum = np.bincount(inv, v)
                for f, w in zip(uf.tolist(), wsum.tolist()):
                    j = item_col.get(f)
                    if j is not None:
                        self.item_w[i, j] += w
        self.ids_fold = ids_fold

    # -- exact answers over a set of requests --------------------------------
    def distinct(self, mask: np.ndarray) -> int:
        idx = np.flatnonzero(mask)
        plain = [self.ranks[i] for i in idx if not self.is_md[i]]
        users = [self.users[i] for i in idx if self.is_md[i]]
        folds = []
        if plain:
            seen = np.zeros(self.plan.n_streams, bool)
            for rk in plain:
                seen[rk] = True
            folds.append(self.ids_fold[seen])
        if users:
            folds.append(fold64(np.concatenate(users)))
        return int(np.unique(np.concatenate(folds)).size) if folds else 0

    def subpop_distinct(self, mask: np.ndarray, region: int) -> int:
        us = [self.users[i][self.region[i] == region]
              for i in np.flatnonzero(mask) if self.is_md[i]]
        return int(np.unique(fold64(np.concatenate(us))).size) if us else 0


def _hook(plan, name: str, qtype: str):
    """The configuration module's ``name`` for a template this module
    does not know."""
    hook = plan.hook(name)
    if hook is None:
        raise ValueError(f"no {name} for query template {qtype!r}")
    return hook


def _params(plan, t: dict) -> dict:
    """The parameters of the synopsis a built-in template reads."""
    sid = t["synopsis"]
    return plan.syn[sid]["params"] if sid in plan.syn else plan.md["params"]


def _hll_err(est: float, exact: float, rse: float) -> float:
    return abs(est - exact) / (rse * max(exact, 1.0))


class Judge:
    """Accumulates the worst reading of each compared number."""

    def __init__(self):
        self.numbers: Dict[str, float] = {}

    def put(self, name: str, value: float) -> None:
        self.numbers[name] = max(self.numbers.get(name, 0.0), float(value))


def judge_query(j: Judge, req: Requests, qtype: str, k: int,
                answer) -> None:
    """Measure one post-window answer against the exact answer over
    every acked request."""
    plan = req.plan
    t = plan.cfg["queries"][qtype]
    if qtype == "cm_rows":
        eps = _params(plan, t)["eps"]
        w = req.row_w.sum(0)
        other = np.roll(plan.ids[req.qrows], 1)
        same = fold64(other) == req.ids_fold[req.qrows]
        for i, r in enumerate(answer):
            est_self, est_other = r["value"]
            for est, e in ((est_self, w[i]), (est_other, w[i] * same[i])):
                j.put("cm_under", max(0.0, e - est) / max(e, 1.0))
                j.put("cm_over", max(0.0, est - e) / max(eps * w[i], 1.0))
    elif qtype == "cm_items":
        eps = _params(plan, t)["eps"]
        n = req.total.sum()
        for est, e in zip(answer, req.item_w.sum(0)):
            j.put("cm_under", max(0.0, e - est) / max(e, 1.0))
            j.put("cm_over", max(0.0, est - e) / max(eps * n, 1.0))
    elif qtype in ("hll_total", "subpop_hll", "hll_rows"):
        j.put("hll_err", _hll_err(float(answer),
                                  exact_answer(req, qtype, k),
                                  _params(plan, t)["rse"]))
    elif qtype == "gk":
        h = req.hist.sum(0)
        for q, x in zip(t["qs"], answer):
            j.put("gk_err", rank_error(h, x, q) / _params(plan, t)["eps"])
    else:
        _hook(plan, "judge", qtype)(j, req, qtype, k, answer)


def exact_answer(req: Requests, qtype: str, k: int):
    """The exact answer of a post-window query, in the run's format."""
    plan = req.plan
    t = plan.cfg["queries"][qtype]
    everything = np.ones(len(req.recs), bool)
    if qtype == "cm_rows":
        w = req.row_w.sum(0)
        other = np.roll(plan.ids[req.qrows], 1)
        same = fold64(other) == req.ids_fold[req.qrows]
        return [dict(value=[float(e), float(e) if s else 0.0])
                for e, s in zip(w, same)]
    if qtype == "cm_items":
        return req.item_w.sum(0).tolist()
    if qtype == "hll_total":
        return float(req.distinct(everything))
    if qtype == "subpop_hll":
        (dim, value), = t["where"].items()
        return float(req.subpop_distinct(
            everything, list(plan.md["dims"][dim]).index(value)))
    if qtype == "gk":
        cum = np.cumsum(req.hist.sum(0))
        return [float(np.searchsorted(cum, q * cum[-1])) for q in t["qs"]]
    if qtype == "hll_rows":
        rows = plan.hll_rows()
        col = int(np.flatnonzero(req.qrows == rows[k % len(rows)])[0])
        return float(req.row_w[:, col].sum() > 0)
    return _hook(plan, "exact", qtype)(req, qtype, k)


def judge_continuous(j: Judge, req: Requests, cq: dict) -> None:
    """Every batch's continuous responses: count, and F2 against the
    exact weight of each hot stream through that batch."""
    plan = req.plan
    if not plan.continuous:
        return
    eps = plan.continuous[0]["params"]["eps"]
    count = {int(b): n for b, n in cq["count"].items()}
    last = int(req.batch.max()) if len(req.batch) else 0
    missing = sum(abs(plan.n_hot - count.get(b, 0))
                  for b in range(1, last + 1))
    missing += sum(n for b, n in count.items() if b > last or b < 1)
    j.put("cq_missing", missing)
    rank_of = {str(int(s)): i for i, s in enumerate(plan.ids[:plan.n_hot])}
    order = np.argsort(req.batch, kind="stable")
    batches, cum = req.batch[order], np.cumsum(req.hot_w[order], axis=0)
    for b, vals in cq["values"].items():
        pos = np.searchsorted(batches, int(b), side="right") - 1
        w = cum[pos] if pos >= 0 else np.zeros(plan.n_hot)
        for sid, est in vals:
            exact = w[rank_of[sid]] ** 2
            j.put("cq_err", abs(est - exact) / (eps * max(exact, 1.0)))


def judge_run(plan, results: dict, answers: Optional[dict] = None
              ) -> Dict[str, float]:
    """All compared numbers of one run. ``answers`` replaces the run's
    own answers where given (the control and the planted faults)."""
    recs = results["records"]
    answers = results["answers"] if answers is None else answers
    req = Requests(plan, recs)
    j = Judge()
    j.put("lost", results["lost"])
    for rid, ans in answers.items():
        _, qtype, k = recs[rid]["kind"].split(":")
        judge_query(j, req, qtype, int(k), ans)
    judge_continuous(j, req, results["cq"])
    return j.numbers
