"""Load generator: the clients of one run, in a process of their own.

    python bench/loadgen.py --config C --traffic T --seed S --seconds N \
        --port P --out results.json

It never imports JAX. It connects to the server, sends the configuration's
builds, warms every shape the mix uses, prints ``READY`` and waits for
``GO <t0>`` on standard input (``t0`` on ``time.monotonic``, which every
process of the machine shares). From ``t0`` it drives the window for
``--seconds`` as a closed loop: each ingest connection keeps a fixed
number of requests in flight. Every request carries the time it was due
(when its slot freed), when it was sent and when its answer came, so
latency counts from the due time and lateness (sent - due) is reported
apart.
After the window it waits up to a minute for every answer, flushes, sends
the post-window query set, prints ``POSTED`` and waits for ``SHUTDOWN``
(the harness copies the engine's state meanwhile), sends ``shutdown``,
writes ``--out`` and exits.
"""
from __future__ import annotations

import argparse
import asyncio
import faulthandler
import itertools
import json
import pathlib
import signal
import sys
import time
from typing import Dict, List

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

import numpy as np  # noqa: E402

from bench import traffic as tr  # noqa: E402

DRAIN_S = 60.0          # how long answers due in the window may lag


def _with_rid(body: bytes, rid: str) -> bytes:
    """Splice a request id into a pre-encoded request object."""
    return b'{"request_id":"' + rid.encode() + b'",' + body[1:]


def _encode(req: dict) -> bytes:
    return (json.dumps(req) + "\n").encode()


class Conn:
    def __init__(self, gen: "Gen", name: str):
        self.gen, self.name = gen, name

    async def open(self, port: int) -> None:
        self.reader, self.writer = await asyncio.open_connection(
            "127.0.0.1", port, limit=1 << 30)
        self.task = asyncio.create_task(self._read())

    async def send(self, rid: str, line: bytes, *, kind: str, due: float,
                   phase: str, tag: int = -1, index: int = -1,
                   size: int = 0) -> asyncio.Future:
        fut = asyncio.get_running_loop().create_future()
        self.gen.waiting[rid] = fut
        rec = dict(kind=kind, tag=tag, index=index, size=size,
                   conn=self.name, phase=phase, due=due,
                   sent=time.monotonic(), done=None, ok=None, batch=None)
        self.gen.records[rid] = rec
        self.writer.write(_with_rid(line, rid))
        await self.writer.drain()
        return fut

    async def call(self, rid: str, req: dict, phase: str) -> dict:
        fut = await self.send(rid, _encode(req), kind=req["type"],
                              due=time.monotonic(), phase=phase)
        resp = await fut
        if not resp.get("ok"):
            raise RuntimeError(f"{req['type']} {rid} failed: "
                               f"{resp.get('error')}")
        return resp

    async def _read(self) -> None:
        while True:
            line = await self.reader.readline()
            if not line:
                return
            now = time.monotonic()
            resp = json.loads(line)
            rid = resp.get("request_id", "")
            if rid.startswith("cq/"):
                self.gen.on_cq(resp, now)
            else:
                self.gen.on_answer(rid, resp, now)


class Gen:
    def __init__(self, plan: tr.Plan):
        self.plan = plan
        self.mix = plan.mix
        self.records: Dict[str, dict] = {}
        self.waiting: Dict[str, asyncio.Future] = {}
        self.answers: Dict[str, object] = {}
        self.keep_answer = set()
        self.cq_count: Dict[int, int] = {}
        self.cq_last: Dict[int, float] = {}
        self.cq_values: Dict[int, List] = {}
        self.cq_errors = 0
        self.acked_events = 0
        self.acked = asyncio.Event()

    # -- answers -----------------------------------------------------------
    def on_answer(self, rid: str, resp: dict, now: float) -> None:
        rec = self.records.get(rid)
        fut = self.waiting.pop(rid, None)
        if rec is None or fut is None:
            return
        rec["done"], rec["ok"] = now, bool(resp.get("ok"))
        value = resp.get("value")
        if rec["ok"] and isinstance(value, dict) and "batch" in value:
            rec["batch"] = int(value["batch"])
        if not rec["ok"]:
            rec["error"] = str(resp.get("error"))[:500]
        if rid in self.keep_answer:
            self.answers[rid] = value
        if rec["kind"] == "ingest" and rec["ok"]:
            self.acked_events += rec["size"]
            self.acked.set()
        fut.set_result(resp)

    def on_cq(self, resp: dict, now: float) -> None:
        if not resp.get("ok"):
            self.cq_errors += 1
            return
        sid, batch = resp["request_id"][3:].rsplit("/", 1)
        b = int(batch)
        self.cq_count[b] = self.cq_count.get(b, 0) + 1
        self.cq_last[b] = now
        self.cq_values.setdefault(b, []).append(
            [sid.rsplit("/", 1)[1], resp["value"]])

    # -- phases ------------------------------------------------------------
    async def connect(self, port: int) -> None:
        ing = self.mix["ingest"]
        self.sub = Conn(self, "sub")
        self.ingest = [Conn(self, f"i{c}") for c in range(int(
            ing["connections"]))]
        self.mdc = Conn(self, "md") if (self.plan.md and
                                        self.mix.get("multidim")) else None
        for c in [self.sub, *self.ingest] + ([self.mdc] if self.mdc
                                              else []):
            await c.open(port)

    async def build(self) -> None:
        for i, req in enumerate(self.plan.build_requests()):
            await self.sub.call(f"b{i}", req, "build")

    def encode(self) -> None:
        """Pre-encode every request line the run can send."""
        p, mix = self.plan, self.mix
        ing = mix["ingest"]
        size = p.ingest_size
        self.warm_sizes = [k * size for k in range(
            1, int(mix["warm"]["coalesced_max"]) + 1)]
        self.warm_lines = [p.ingest_line(tr.TAG_WARM, k, n)
                           for k, n in enumerate(self.warm_sizes)]
        md = mix.get("multidim")
        self.md_size = int(md["records"]) if (md and p.md) else 0
        self.pool = [p.ingest_line(tr.TAG_WINDOW, i, size)
                     for i in range(int(ing["pool"]))]

    async def warm(self) -> None:
        """Every coalesced ingest length the mix can produce (one request
        of k requests' events, alone, so the tick sees exactly that
        length), the multidim length, then the mix itself for a few
        seconds."""
        c = self.ingest[0]
        for k, (n, line) in enumerate(zip(self.warm_sizes, self.warm_lines)):
            t0 = time.monotonic()
            await (await c.send(f"s{k}", line, kind="ingest",
                                due=t0, phase="warm",
                                tag=tr.TAG_WARM, index=k, size=n))
            log(f"warm ingest of {n} events", t0)
        if self.md_size:
            await self._md_send(self.mdc, "sm0", 10**6, time.monotonic(),
                                "warm", wait=True)
        t0 = time.monotonic() + 0.05
        await self.drive(t0, t0 + float(self.mix["warm"]["seconds"]), "warm")
        await self.drain(DRAIN_S)

    async def _md_send(self, conn, rid, index, due, phase, wait=False):
        fut = await conn.send(rid, self.plan.md_line(index, self.md_size),
                              kind="ingest_multidim", due=due, phase=phase,
                              tag=tr.TAG_MD, index=index, size=self.md_size)
        if wait:
            await fut
        return fut

    # -- the window --------------------------------------------------------
    async def drive(self, t0: float, t_end: float, phase: str) -> None:
        ing = self.mix["ingest"]
        counter = itertools.count()
        pool = len(self.pool)
        size = self.plan.ingest_size
        prefix = "i" if phase == "window" else "w"

        async def slot(conn: Conn) -> None:
            await _sleep_until(t0)
            due = t0
            while time.monotonic() < t_end:
                n = next(counter)
                fut = await conn.send(
                    f"{prefix}{n}", self.pool[n % pool], kind="ingest",
                    due=due, phase=phase, tag=tr.TAG_WINDOW,
                    index=n % pool, size=size)
                await fut
                due = time.monotonic()

        async def md_loop() -> None:
            per = float(self.mix["multidim"]["events_per_record"])
            sent = 0
            base = self.acked_events
            j = itertools.count(0 if phase == "window" else 10**6 + 1)
            while time.monotonic() < t_end:
                if (self.acked_events - base) >= (sent + self.md_size) * per:
                    i = next(j)
                    await self._md_send(self.mdc, f"{prefix}m{i}", i,
                                        time.monotonic(), phase, wait=True)
                    sent += self.md_size
                    continue
                self.acked.clear()
                try:
                    await asyncio.wait_for(self.acked.wait(),
                                           max(0.0, t_end - time.monotonic()))
                except asyncio.TimeoutError:
                    return

        tasks = [slot(c) for c in self.ingest
                 for _ in range(int(ing["outstanding"]))]
        if self.md_size:
            tasks.append(md_loop())
        await asyncio.gather(*tasks)

    async def drain(self, timeout: float) -> int:
        """Wait for every answer still due; returns how many never came."""
        pending = list(self.waiting.values())
        if pending:
            await asyncio.wait(pending, timeout=timeout)
        return sum(not f.done() for f in pending)

    async def post(self) -> None:
        await self.sub.call("flush0", dict(type="flush"), "post")
        for j, (qtype, k) in enumerate(self.plan.post_queries()):
            rid = f"p{j}"
            self.keep_answer.add(rid)
            fut = await self.sub.send(rid, _encode(self.plan.query(qtype, k)),
                                      kind=f"q:{qtype}:{k}",
                                      due=time.monotonic(), phase="post")
            await fut
        # the harness copies the engine's state before it shuts down
        await _handshake("POSTED", "SHUTDOWN")
        await self.sub.call("shutdown0", dict(type="shutdown"), "post")
        conns = [self.sub, *self.ingest] + ([self.mdc] if self.mdc else [])
        await asyncio.wait([c.task for c in conns], timeout=DRAIN_S)
        for c in conns:
            c.writer.close()

    def results(self, t0: float, t_end: float, lost: int) -> dict:
        late = np.asarray([r["sent"] - r["due"] for r in self.records.values()
                           if r["phase"] == "window"]) * 1e3
        return dict(
            window=[t0, t_end], lost=lost, cq_errors=self.cq_errors,
            lateness_ms=dict(
                n=int(late.size),
                p50=float(np.median(late)) if late.size else 0.0,
                p95=float(np.percentile(late, 95)) if late.size else 0.0,
                max=float(late.max()) if late.size else 0.0),
            records=self.records, answers=self.answers,
            cq=dict(count=self.cq_count, last=self.cq_last,
                    values=self.cq_values))


async def _handshake(say: str, expect: str) -> str:
    """Tell the harness ``say``; wait for its line starting ``expect``."""
    print(say, flush=True)
    line = await asyncio.get_running_loop().run_in_executor(
        None, sys.stdin.readline)
    if not line.startswith(expect):
        raise RuntimeError(f"expected {expect}, got {line!r}")
    return line


async def _sleep_until(t: float) -> None:
    dt = t - time.monotonic()
    if dt > 0:
        await asyncio.sleep(dt)


def log(phase: str, t0: float) -> None:
    print(f"[loadgen] {phase} at {time.monotonic() - t0:.3f} s",
          file=sys.stderr, flush=True)


async def run(plan: tr.Plan, port: int, out: pathlib.Path) -> dict:
    t0 = time.monotonic()
    gen = Gen(plan)
    gen.encode()
    log("encoded", t0)
    await gen.connect(port)
    await gen.build()
    log("built", t0)
    await gen.warm()
    log("warm", t0)
    go = float((await _handshake("READY", "GO")).split()[1])
    t_end = go + plan.seconds
    await gen.drive(go, t_end, "window")
    lost = await gen.drain(max(DRAIN_S - (time.monotonic() - t_end), 1.0))
    await gen.post()
    res = gen.results(go, t_end, lost)
    out.write_text(json.dumps(res))
    print(json.dumps(dict(lateness_ms=res["lateness_ms"], lost=lost)),
          flush=True)
    return res


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--root", default=None,
                    help="benchmark directory holding configs/ and traffic/")
    args = ap.parse_args(argv)
    # the harness asks for a stack dump when the generator is stuck
    faulthandler.register(signal.SIGUSR1, all_threads=True)
    root = pathlib.Path(args.root) if args.root else tr.ROOT
    plan = tr.make_plan(args.config, args.traffic, args.seed, args.seconds,
                        root)
    asyncio.run(run(plan, args.port, pathlib.Path(args.out)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
