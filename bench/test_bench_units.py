"""CPU tests of the benchmark's parts that need no server: the traffic
generator, the window arithmetic, the trace reduction, the roofline
model, the reference checks and the control."""
from __future__ import annotations

import json
import pathlib
import subprocess
import sys

import numpy as np
import pytest

from bench import control, devtrace, roofline, stats, testing, traffic as tr
from bench.reference import exact

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


# -- traffic ----------------------------------------------------------------
@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return testing.make_root(tmp_path_factory.mktemp("tiny"))


@pytest.mark.parametrize("config,mix", [("stocks5k_paper", "ingest_closed"),
                                        ("tiny", "tiny_closed")])
def test_traffic_is_a_function_of_the_seed_and_never_imports_jax(
        tiny, config, mix):
    code = f"""
import sys, hashlib, pathlib
sys.path.insert(0, {str(ROOT)!r})
from bench import loadgen, traffic as tr
root = pathlib.Path({str(tiny)!r})
def digest(seed):
    p = tr.make_plan({config!r}, {mix!r}, seed, 2.0, root)
    h = hashlib.sha256()
    h.update(p.ingest_line(tr.TAG_WINDOW, 3, 4096))
    h.update(p.ingest_line(tr.TAG_WARM, 0, 512))
    if p.md:
        h.update(p.md_line(5, 64))
    for q in p.post_queries():
        h.update(repr(p.query(*q)).encode())
    return h.hexdigest()
a, b, c = digest(2**31 + 11), digest(2**31 + 11), digest(2**31 + 12)
assert a == b and a != c, (a, b, c)
assert "jax" not in sys.modules, "the traffic generator imported JAX"
print("ok")
"""
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=300)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "ok"


# -- window arithmetic ---------------------------------------------------------
def _closed_loop(stall: float, window=(0.0, 10.0)):
    """One closed-loop slot: 1,000-event requests, each acked 10 ms after
    it was sent and the next sent at once; request 300 stalls ``stall``
    seconds longer."""
    recs, t, i = {}, 0.0, 0
    while t < window[1]:
        done = t + 0.010 + (stall if i == 300 else 0.0)
        recs[f"i{i}"] = dict(kind="ingest", phase="window", due=t, sent=t,
                             done=done, ok=True, batch=i + 1, size=1000)
        t, i = round(done, 9), i + 1
    return dict(window=list(window), records=recs)


def test_a_stall_inside_the_window_moves_the_rate():
    steady = stats.end_to_end(_closed_loop(0.0))["events_per_s"]
    assert steady == pytest.approx(1000 / 0.010, rel=1e-9)
    stalled = stats.end_to_end(_closed_loop(2.0))["events_per_s"]
    assert stalled == pytest.approx(steady * 8.0 / 10.0, rel=0.01)
    # the rate ends at the last ack: a window that ends mid-request
    # reads the same rate, not one short by that request's share
    assert stats.end_to_end(_closed_loop(0.0, (0.0, 10.005)))[
        "events_per_s"] == pytest.approx(steady, rel=1e-9)


def test_failed_and_unanswered_requests_count_as_failed():
    res = _closed_loop(0.0)
    for i in range(0, 1000, 10):         # 10 % failed
        res["records"][f"i{i}"]["ok"] = False
    res["records"]["i5"].update(ok=None, done=None)      # never answered
    assert stats.failed(res) == 101 and stats.attempted(res) == 1000
    # i999 is acked at 10.00 s, after the window: 898 acks, the last at
    # 9.99 s
    assert stats.end_to_end(res)["events_per_s"] == pytest.approx(
        898 * 1000 / 9.99, rel=1e-9)


# -- trace reduction -----------------------------------------------------------
def _brute(trace):
    """Busy and module time by a 1 us bitmap, independent of ``union``."""
    t0, t1 = trace["window"]
    n = int((t1 - t0) / 1e3) + 1
    busy = np.zeros(n, bool)
    for d in trace["device"].values():
        for _, s, e in d["ops"]:
            a, b = int((max(s, t0) - t0) / 1e3), int((min(e, t1) - t0) / 1e3)
            if b > a:
                busy[a:b] = True
    return busy.sum() / 1e6


def test_trace_reduction_on_a_recorded_chip_trace():
    trace = json.loads((BENCH / "testdata" / "v5e_trace_slice.json")
                       .read_text())
    busy = devtrace.busy_seconds(trace)
    assert busy == pytest.approx(_brute(trace), rel=0.01)
    assert 0 < busy <= devtrace.window_seconds(trace)
    fused = devtrace.module_seconds(trace, "jit_fused")
    t0, t1 = trace["window"]
    by_hand = sum(min(e, t1) - max(s, t0)
                  for d in trace["device"].values()
                  for n, s, e in d["modules"]
                  if n.startswith("jit_fused") and e > t0 and s < t1) / 1e9
    assert fused == pytest.approx(by_hand)
    # a program's span also holds the short gaps between its operations
    assert busy * 0.99 < fused <= devtrace.window_seconds(trace)
    gaps = devtrace.idle_gaps(trace)
    assert sum(v for _, v in gaps) == pytest.approx(
        devtrace.window_seconds(trace) - busy, rel=0.01)
    assert devtrace.top_ops(trace)[0][1] >= devtrace.top_ops(trace)[-1][1]


def test_union_and_idle_gaps_of_hand_made_intervals():
    trace = dict(window=[0.0, 100.0], host=[("bench/wal.sync", 40.0, 70.0)],
                 device={"/device:TPU:0": dict(
                     ops=[("a", 10.0, 30.0), ("b", 20.0, 35.0),
                          ("c", 80.0, 120.0)],
                     modules=[("jit_fused(1)", 10.0, 35.0),
                              ("jit_program(2)", 80.0, 120.0)])})
    assert devtrace.union(trace["device"]["/device:TPU:0"]["ops"]) == \
        [(10.0, 35.0), (80.0, 120.0)]
    assert devtrace.busy_seconds(trace) == pytest.approx(45e-9)
    assert devtrace.module_seconds(trace, "jit_fused") == \
        pytest.approx(25e-9)
    assert dict(devtrace.idle_gaps(trace)) == pytest.approx(
        {"wal.sync": 45e-9, "no span": 10e-9})


def test_roofline_least_bytes_by_hand():
    cfg = tr.load("configs", "stocks5k_paper")
    t = 16384
    # CountMin eps=0.002 delta=0.01: d=5, w=ceil(e/0.002)=1360 per
    # stream; HyperLogLog rse=0.03: 1,202 registers, one source row
    cm = t * (17 + 12) + 2 * 4 * min(t * 5, 5000 * 5 * 1360)
    hll = t * 17 + 2 * 4 * min(t * 1, 1202)
    assert roofline.update_bytes(cfg, t, 0) == pytest.approx(cm + hll)


# -- reference checks and the control -----------------------------------------
def _synthetic(plan, n_req: int = 24):
    """A run's results without a server: ``n_req`` acked requests, one
    batch each, and the exact answers of every post-window query."""
    size = plan.ingest_size
    recs = {}
    for i in range(n_req):
        recs[f"i{i}"] = dict(kind="ingest", tag=tr.TAG_WINDOW, index=i,
                             size=size, phase="window", due=i, sent=i,
                             done=i + 0.5, ok=True, batch=i + 1)
    req = exact.Requests(plan, recs)
    answers = {}
    for j, (qtype, k) in enumerate(plan.post_queries()):
        recs[f"p{j}"] = dict(kind=f"q:{qtype}:{k}", phase="post", due=99,
                             sent=99, done=99.5, ok=True, batch=None)
        answers[f"p{j}"] = exact.exact_answer(req, qtype, k)
    cq = dict(count={}, last={}, values={})
    if plan.continuous:
        cum = np.cumsum(req.hot_w, axis=0)
        for b in range(1, n_req + 1):
            cq["count"][str(b)] = plan.n_hot
            cq["values"][str(b)] = [[str(int(plan.ids[r])),
                                     float(cum[b - 1, r] ** 2)]
                                    for r in range(plan.n_hot)]
    return dict(window=[0.0, 2.0], lost=0, records=recs, answers=answers,
                cq=cq)


def _stocks(seed):
    c = tr.cell("stocks5k.ingest_closed")
    return tr.make_plan(c["config"], c["traffic"], seed, 2.0)


def _tiny(seed):
    return tr.Plan(testing.with_gk(testing.TINY_CONFIG), testing.TINY_MIX,
                   seed, 2.0)


def _correct(plan, numbers):
    limits = plan.cfg["limits"]
    return all(v <= limits[k] for k, v in numbers.items())


@pytest.mark.parametrize("make,n_req", [(_stocks, 400), (_tiny, 40)])
def test_exact_answers_pass_and_the_bf16_control_fails(make, n_req):
    # stocks: a 16,384-tick request adds 3 or 4 to each of 5,000
    # counters; from 1,024 on, bf16's step is 8 and such adds are lost.
    # tiny: weights 1..64 on Zipf-hot streams pass 256 within 40 requests
    plan = make(2**31 + 5)
    res = _synthetic(plan, n_req)
    assert _correct(plan, exact.judge_run(plan, res))
    numbers = control.control_numbers(plan, res)
    limits = plan.cfg["limits"]
    assert (numbers["cm_under"] > limits["cm_under"]
            or numbers["cm_over"] > limits["cm_over"])
    assert not _correct(plan, numbers)


@pytest.mark.parametrize("qtype,alter", [
    ("cm_rows", lambda a: [dict(x, value=[x["value"][0] - 1,
                                          x["value"][1]]) for x in a]),
    ("cm_rows", lambda a: [dict(x, value=[x["value"][0] * 1.5 + 1,
                                          x["value"][1]]) for x in a]),
    ("cm_items", lambda a: [v * 0.9 for v in a]),
    ("hll_total", lambda a: a * 1.5),
    ("gk", lambda a: [v + 8 for v in a]),
    ("subpop_hll", lambda a: a * 2 + 10),
    ("hll_rows", lambda a: a + 1),
])
def test_the_reference_checks_fail_on_a_wrong_answer(qtype, alter):
    plan = _tiny(2**31 + 7)
    res = _synthetic(plan)
    rid = next(r for r, rec in res["records"].items()
               if rec["kind"] == f"q:{qtype}:0")
    res["answers"][rid] = alter(res["answers"][rid])
    assert not _correct(plan, exact.judge_run(plan, res))


def test_the_continuous_checks_fail_on_a_missing_or_wrong_response():
    plan = _tiny(2**31 + 9)
    res = _synthetic(plan)
    missing = json.loads(json.dumps(res))
    missing["cq"]["count"]["3"] -= 1
    assert exact.judge_run(plan, missing)["cq_missing"] == 1
    wrong = json.loads(json.dumps(res))
    wrong["cq"]["values"]["5"][0][1] *= 1.2
    assert exact.judge_run(plan, wrong)["cq_err"] > plan.cfg["limits"][
        "cq_err"]
