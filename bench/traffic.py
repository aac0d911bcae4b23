"""One cell's traffic, made from the seed: stream ids, the events of every
request, multidim records, the arrival schedule and the query set.

Numpy only: the load generator imports this module in a process that must
never touch JAX, and the harness imports it again to rebuild the events of
the requests that were acked. Every request's contents are a pure function
of ``(seed, tag, index)``, so both sides agree without passing events
between processes.

A configuration (``configs/<name>.json``) says what the deployment keeps:
streams, synopses, the multidim family and its query templates. A traffic
mix (``traffic/<name>.json``) says how its closed-loop clients drive
it: request sizes, connections, requests in flight and the warm-up. Both
are data; this module is the one generator that reads them.

A configuration may also bring a module of its own,
``modules/<name>.py`` (numpy only: the load generator imports it too),
for what the built-in vocabulary cannot say. Each of these is optional;
where the module leaves one out, the built-in path runs unchanged:

* ``ingest_events(plan, tag, index, size)`` - the ranks and values of one
  ingest request, in any dtype, and optionally a third item: a dict of
  further request fields, each an array of one entry per event;
* ``query(plan, qtype, j)`` - the ``j``-th request of a query template
  the built-in ``Plan.query`` does not know;
* ``exact(req, qtype, k)`` and ``judge(j, req, qtype, k, answer)`` - the
  exact answer of such a template and the numbers it is judged by
  (``reference/exact.py`` calls them; their limits live in the
  configuration's ``limits``);
* ``control(req, qtype, k, order)`` - a template's answer computed one
  precision lower, the acked requests taken in the engine's ``order``
  (``control.py`` calls it for every template but the CountMin ones;
  where it is missing or gives None, the control rounds the exact
  answer once).
"""
from __future__ import annotations

import importlib.util
import json
import pathlib
from types import ModuleType
from typing import Dict, List, Optional, Tuple

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent

# request tags: each names an independent random stream of the seed
TAG_IDS, TAG_WINDOW, TAG_WARM, TAG_MD, TAG_ORDER, TAG_QUERIES = range(6)


def load(kind: str, name: str, root: pathlib.Path = ROOT) -> dict:
    """``configs/<name>.json`` or ``traffic/<name>.json``, by name."""
    path = root / kind / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} file {path}")
    return json.loads(path.read_text())


def load_module(name: str, root: pathlib.Path = ROOT
                ) -> Optional[ModuleType]:
    """The configuration's own module, ``modules/<name>.py``, or None
    where it brings none."""
    path = root / "modules" / f"{name}.py"
    if not path.is_file():
        return None
    spec = importlib.util.spec_from_file_location(
        f"bench_module_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _rng(seed: int, *key: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % (1 << 64), *key])


class Plan:
    """Everything a run sends, as a function of (config, mix, seed)."""

    def __init__(self, cfg: dict, mix: dict, seed: int, seconds: float,
                 module: Optional[ModuleType] = None):
        self.cfg, self.mix, self.seed = cfg, mix, int(seed)
        self.module = module
        self.seconds = float(seconds)
        st = cfg["streams"]
        self.n_streams = n = int(st["count"])
        if st["ids"] == "hashed63":
            # the deployment's streams and their popularity are fixed by
            # the configuration; the seed draws the events. (A new id set
            # per seed would change the routing table's probe length, a
            # static argument of every update program, and recompile.)
            rng = _rng(int(st["population_seed"]), TAG_IDS)
            ids = np.unique(rng.integers(1, 2**63 - 1, n + n // 64 + 16,
                                         dtype=np.int64))
            rng.shuffle(ids)
            self.ids = ids[:n]                   # in popularity order
        else:                                    # "index": 0 .. n-1
            self.ids = np.arange(n, dtype=np.int64)
        self.popularity = st["popularity"]
        if self.popularity == "zipf":
            p = 1.0 / np.arange(1, n + 1) ** float(st["zipf_s"])
            self.cdf = np.cumsum(p / p.sum())
        else:                                    # "round_robin"
            self.order = _rng(seed, TAG_ORDER).permutation(n)
        self.values = cfg["values"]
        self.md = cfg.get("multidim")
        self.syn = {s["id"]: s for s in cfg["synopses"]}
        self.continuous = [s for s in cfg["synopses"] if s.get("continuous")]
        self.n_hot = max([int(s["hottest"]) for s in self.continuous],
                         default=0)
        self.ingest_size = int(mix["ingest"]["events"])

    # -- events ------------------------------------------------------------
    def ranks(self, tag: int, index: int, size: int) -> np.ndarray:
        """Popularity ranks of one request's events (``ids[rank]`` is the
        stream id)."""
        rng = _rng(self.seed, tag, index)
        if self.popularity == "zipf":
            r = np.searchsorted(self.cdf, rng.random(size), side="right")
            return np.minimum(r, self.n_streams - 1)
        start = int(rng.integers(self.n_streams))
        return self.order[(start + np.arange(size)) % self.n_streams]

    def hook(self, name: str):
        """The configuration module's ``name``, or None."""
        return getattr(self.module, name, None)

    def ingest_events(self, tag: int, index: int, size: int
                      ) -> Tuple[np.ndarray, np.ndarray, Dict[str,
                                                            np.ndarray]]:
        """(ranks, weights, further request fields) of one ingest
        request; the built-in path gives integer weights and no further
        field."""
        hook = self.hook("ingest_events")
        if hook is not None:
            ranks, vals, *extra = hook(self, tag, index, size)
            return ranks, vals, (extra[0] if extra else {})
        ranks = self.ranks(tag, index, size)
        v = self.values
        if v["dist"] == "unit":
            vals = np.ones(size, np.int64)
        else:                                    # "uniform_int"
            vals = _rng(self.seed, tag, index, 1).integers(
                int(v["low"]), int(v["high"]) + 1, size)
        return ranks, vals, {}

    def md_records(self, index: int, size: int):
        """(region index, device index, user id, weight) per record."""
        md = self.md
        rng = _rng(self.seed, TAG_MD, index)
        dims = list(md["dims"].values())
        region = rng.integers(0, len(dims[0]), size)
        device = rng.integers(0, len(dims[1]), size)
        users = rng.zipf(float(md["users_zipf"]), size).astype(np.int64) \
            + int(md["user_base"])
        vals = rng.integers(int(self.values.get("low", 1)),
                            int(self.values.get("high", 1)) + 1, size)
        return region, device, users, vals

    # -- request lines -----------------------------------------------------
    def ingest_line(self, tag: int, index: int, size: int) -> bytes:
        """One ingest request, encoded, without its request id."""
        ranks, vals, extra = self.ingest_events(tag, index, size)
        sids = ",".join(map(str, self.ids[ranks].tolist()))
        more = "".join(f',"{k}":[{",".join(map(str, a.tolist()))}]'
                       for k, a in extra.items())
        return (f'{{"type":"ingest","stream_ids":[{sids}],"values":'
                f'[{",".join(map(str, vals.tolist()))}]{more}}}\n').encode()

    def md_line(self, index: int, size: int) -> bytes:
        region, device, users, vals = self.md_records(index, size)
        (dn0, d0), (dn1, d1) = list(self.md["dims"].items())
        records = [{dn0: d0[r], dn1: d1[d]} for r, d in zip(region, device)]
        return (json.dumps(dict(
            type="ingest_multidim", synopsis_id=self.md["id"], records=records,
            values=vals.tolist(), items=users.tolist())) + "\n").encode()

    def build_requests(self) -> List[dict]:
        ids = [int(s) for s in self.ids]
        out = []
        for s in self.cfg["synopses"]:
            req = dict(type="build", synopsis_id=s["id"], kind=s["kind"],
                       params=s["params"])
            scope = s.get("per_stream")
            if scope == "all":
                req.update(per_stream_of_source=True, stream_ids=ids)
            elif scope == "hottest":
                req.update(per_stream_of_source=True,
                           stream_ids=ids[:int(s["hottest"])])
            if s.get("continuous"):
                req["continuous"] = True
            out.append(req)
        if self.md:
            out.append(dict(type="build_multidim", synopsis_id=self.md["id"],
                            kind=self.md["kind"], params=self.md["params"],
                            dims=self.md["dims"]))
        return out

    # -- queries -----------------------------------------------------------
    def query_rows(self) -> np.ndarray:
        """Ranks of the per-stream rows the ``cm_rows`` template reads:
        half the hottest, half drawn from the rest."""
        t = self.cfg["queries"]["cm_rows"]
        n = min(int(t["rows"]), self.n_streams)
        hot = np.arange(n // 2)
        cold = _rng(self.seed, TAG_QUERIES).choice(
            np.arange(n // 2, self.n_streams), n - n // 2, replace=False)
        return np.concatenate([hot, cold])

    def query(self, qtype: str, j: int = 0) -> dict:
        """The ``j``-th request of one query template."""
        t = self.cfg["queries"][qtype]
        sid = t.get("synopsis")
        if qtype == "cm_rows":
            rows = self.ids[self.query_rows()]
            other = np.roll(rows, 1)
            return dict(type="query_many", queries=[
                dict(synopsis_id=f"{sid}/{int(s)}",
                     query=dict(items=[int(s), int(o)]))
                for s, o in zip(rows, other)])
        if qtype == "cm_items":
            items = self.ids[self.query_rows()[:int(t["items"])]]
            return dict(type="adhoc", synopsis_id=sid,
                        query=dict(items=[int(x) for x in items]))
        if qtype == "hll_total":
            return dict(type="adhoc", synopsis_id=sid)
        if qtype == "gk":
            return dict(type="adhoc", synopsis_id=sid,
                        query=dict(qs=list(t["qs"])))
        if qtype == "subpop_hll":
            return dict(type="subpop_query", synopsis_id=sid,
                        where=dict(t["where"]))
        if qtype == "hll_rows":
            rows = self.hll_rows()
            return dict(type="adhoc",
                        synopsis_id=f"{sid}/{int(self.ids[rows[j % len(rows)]])}")
        hook = self.hook("query")
        if hook is not None:
            return hook(self, qtype, j)
        raise ValueError(f"unknown query template {qtype!r}")

    def hll_rows(self) -> np.ndarray:
        n = int(self.cfg["queries"]["hll_rows"]["rows"])
        rows = self.query_rows()
        return np.concatenate([rows[:n // 2], rows[-(n - n // 2):]])

    def post_queries(self) -> List[Tuple[str, int]]:
        """The red-path requests sent once the window has closed: every
        template, and every row of ``hll_rows``."""
        out = []
        for qtype in self.cfg["queries"]:
            n = (int(self.cfg["queries"][qtype]["rows"])
                 if qtype == "hll_rows" else 1)
            out += [(qtype, j) for j in range(n)]
        return out


def make_plan(config: str, traffic: str, seed: int, seconds: float,
              root: Optional[pathlib.Path] = None) -> Plan:
    root = ROOT if root is None else root
    return Plan(load("configs", config, root), load("traffic", traffic, root),
                seed, seconds, load_module(config, root))


def cell(name: str, root: Optional[pathlib.Path] = None) -> Dict[str, str]:
    """A cell of ``BENCHMARK.json`` by name: its config and traffic."""
    root = ROOT if root is None else root
    bench = json.loads((root.parent / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")
