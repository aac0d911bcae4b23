"""Cross-tenant micro-batching gateway tests.

The contract under test (the PR's acceptance invariants):

  * N concurrent clients interleaving ingest/query/build traffic leave
    the engine byte-identical to a serialized single-client replay of
    the gateway's commit log (coalescing is state-invisible).
  * Probe-verified amortization: one blue-path dispatch per kind per
    tick regardless of client count (``DISPATCH_COUNT`` vs
    ``GATEWAY_COALESCED``), and one stacked-estimate dispatch for a
    tick's worth of concurrent ad-hoc queries.
  * Tenant namespaces isolate synopsis keys; stream ids stay shared.
  * Continuous responses route to the building client's bounded log.
  * Admission control caps per-client in-flight requests.
  * The socket server round-trips all of it over TCP, eager and
    pipelined, and ``shutdown`` stops it cleanly.
"""
import asyncio
import builtins
import io
import json

import jax
import numpy as np
import pytest

from repro import tracing
from repro.launch import sde_server
from repro.service import SDE, SynopsisGateway, replay_log

CM = {"eps": 0.05, "delta": 0.1, "weighted": False}


def _build(synopsis_id="cm", request_id="b", **kw):
    return dict({"type": "build", "request_id": request_id,
                 "synopsis_id": synopsis_id, "kind": "countmin",
                 "params": CM}, **kw)


def _ingest(request_id, sids, vals=None):
    return {"type": "ingest", "request_id": request_id,
            "stream_ids": list(map(int, sids)),
            "values": [1.0] * len(sids) if vals is None else list(vals)}


def _assert_states_equal(a: SDE, b: SDE):
    assert sorted(a.stacks) == sorted(b.stacks)
    assert sorted(a.entries) == sorted(b.entries)
    for kind in a.stacks:
        for x, y in zip(jax.tree.leaves(a.stacks[kind].state),
                        jax.tree.leaves(b.stacks[kind].state)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# tenant namespaces
# ---------------------------------------------------------------------------
@pytest.mark.smoke
def test_tenant_namespacing_and_isolation():
    gw = SynopsisGateway(SDE())
    acme = gw.connect("a0", tenant="acme")
    glob = gw.connect("g0", tenant="globex")
    admin = gw.connect("root")           # empty tenant = admin view
    fa = gw.submit_nowait(acme, _build())
    fg = gw.submit_nowait(glob, _build())
    gw.tick()
    assert fa.result().ok and fg.result().ok
    # same client-visible id, two engine entries — and responses carry
    # the client-visible (stripped) id back
    assert sorted(gw.sde.entries) == ["acme::cm", "globex::cm"]
    assert fa.result().synopsis_id == "cm"
    # a tenant cannot reach across: "globex::cm" namespaces to
    # "acme::globex::cm", which does not exist
    fx = gw.submit_nowait(acme, {"type": "adhoc", "request_id": "x",
                                 "synopsis_id": "globex::cm",
                                 "query": {"items": [1]}})
    fs = gw.submit_nowait(acme, {"type": "status", "request_id": "s"})
    fr = gw.submit_nowait(admin, {"type": "status", "request_id": "r"})
    gw.tick()
    assert not fx.result().ok
    assert list(fs.result().value) == ["cm"]          # own, stripped
    assert sorted(fr.result().value) == ["acme::cm", "globex::cm"]


@pytest.mark.smoke
def test_separator_bearing_tenant_names_rejected():
    # tenant "a" + synopsis "b::c" and tenant "a::b" + synopsis "c" both
    # namespace to "a::b::c" — a separator-bearing TENANT silently
    # merges two tenants' namespaces, so it is refused at the door
    gw = SynopsisGateway(SDE())
    with pytest.raises(ValueError, match="reserved namespace separator"):
        gw.connect("evil", tenant="a::b")
    assert "evil" not in gw.clients
    # the per-request tenant override is the other door in
    c = gw.connect("c0", tenant="a")
    f = gw.submit_nowait(c, dict(_build(), tenant="a::b"))
    gw.tick()
    assert not f.result().ok
    assert "reserved namespace separator" in f.result().error
    assert not gw.sde.entries             # nothing reached the engine
    # SYNOPSIS ids may carry "::" freely — the split stays unambiguous
    # because only the left side is separator-clean. Round-trip one:
    fb = gw.submit_nowait(c, _build(synopsis_id="b::c"))
    fi = gw.submit_nowait(c, _ingest("i", [1, 2, 3]))
    fq = gw.submit_nowait(c, {"type": "adhoc", "request_id": "q",
                              "synopsis_id": "b::c",
                              "query": {"items": [1]}})
    gw.tick()
    assert fb.result().ok and fi.result().ok and fq.result().ok
    assert list(gw.sde.entries) == ["a::b::c"]
    assert fq.result().synopsis_id == "b::c"          # stripped exactly
    assert float(np.asarray(fq.result().value).ravel()[0]) >= 1.0


def test_outlier_workflow_routes_to_tracking_client():
    gw = SynopsisGateway(SDE())
    acme = gw.connect("a0", tenant="acme")
    other = gw.connect("g0", tenant="globex")
    fb = gw.submit_nowait(acme, {
        "type": "build_multidim", "request_id": "b", "synopsis_id": "md",
        "kind": "countmin", "params": CM,
        "dims": {"region": ["EU", "US"]}})
    ft = gw.submit_nowait(acme, {
        "type": "track_outliers", "request_id": "t",
        "workflow_id": "w", "synopsis_id": "md",
        "level": ["region"], "query": {"items": [7]}, "threshold": 0.0})
    gw.tick()
    assert fb.result().ok, fb.result().error
    assert ft.result().ok, ft.result().error
    assert gw._subs["acme::w"] == ("a0", "acme")
    fi = gw.submit_nowait(acme, {
        "type": "ingest_multidim", "request_id": "i",
        "synopsis_id": "md",
        "records": [{"region": "EU"}, {"region": "US"}],
        "values": [1.0, 1.0], "items": [7, 7]})
    gw.tick()
    assert fi.result().ok, fi.result().error
    gw.sde.flush()
    gw.tick()                             # route the retired emissions
    out = acme.log.drain()
    assert out and all(r.synopsis_id == "w" for r in out)
    assert out[0].request_id.startswith("ow/w/")      # prefix stripped
    assert not other.log.drain()
    # commit-log replay reproduces the multidim state serially
    replayed = replay_log(gw.commit_log)
    _assert_states_equal(replayed, gw.sde)
    replayed.close(), gw.sde.close()


# ---------------------------------------------------------------------------
# the headline invariant: 64 clients, ONE dispatch per kind per tick
# ---------------------------------------------------------------------------
@pytest.mark.smoke
def test_64_clients_one_blue_dispatch_per_tick():
    gw = SynopsisGateway(SDE())
    clients = [gw.connect(f"c{i}") for i in range(64)]
    gw.submit_nowait(clients[0], _build())
    gw.tick()
    d0 = tracing.DISPATCH_COUNT.get("update:CountMin", 0)
    c0 = tracing.GATEWAY_COALESCED.get("ingest", 0)
    rng = np.random.RandomState(0)
    futs = [gw.submit_nowait(c, _ingest(f"i{i}", rng.randint(0, 100, 16)))
            for i, c in enumerate(clients)]
    n = gw.tick()
    assert n == 64
    assert tracing.DISPATCH_COUNT["update:CountMin"] - d0 == 1
    assert tracing.GATEWAY_COALESCED["ingest"] - c0 == 64
    acks = [f.result() for f in futs]
    assert all(a.ok for a in acks)
    # every client was folded into the SAME engine batch
    assert len({a.value["batch"] for a in acks}) == 1
    assert all(a.value["coalesced"] == 64 for a in acks)
    assert all(a.value["tuples"] == 16 for a in acks)


def test_query_run_coalesces_to_one_red_dispatch():
    gw = SynopsisGateway(SDE())
    acme = gw.connect("a0", tenant="acme")
    glob = gw.connect("g0", tenant="globex")
    gw.submit_nowait(acme, _build(stream_id=1))
    gw.submit_nowait(glob, _build(stream_id=2))
    gw.tick()
    sids = np.array([1] * 3 + [2] * 5)
    gw.submit_nowait(acme, _ingest("i", sids))
    gw.tick()
    d0 = tracing.DISPATCH_COUNT.get("CountMin", 0)
    q0 = gw.submit_nowait(acme, {"type": "adhoc", "request_id": "qa",
                                 "synopsis_id": "cm",
                                 "query": {"items": [1]}})
    q1 = gw.submit_nowait(glob, {"type": "adhoc", "request_id": "qg",
                                 "synopsis_id": "cm",
                                 "query": {"items": [2]}})
    q2 = gw.submit_nowait(glob, {"type": "query_many", "request_id": "qm",
                                 "queries": [
                                     {"synopsis_id": "cm",
                                      "query": {"items": [2]}},
                                     {"synopsis_id": "nope"}]})
    gw.tick()
    # one stacked-estimate dispatch answered all three requests
    assert tracing.DISPATCH_COUNT["CountMin"] - d0 == 1
    assert float(np.ravel(q0.result().value)[0]) == 3.0
    assert float(np.ravel(q1.result().value)[0]) == 5.0
    many = q2.result()
    assert not many.ok                   # one sub-query hit a missing key
    assert float(np.ravel(many.value[0]["value"])[0]) == 5.0
    assert many.value[0]["synopsis_id"] == "cm"      # ns stripped
    assert not many.value[1]["ok"]
    # per-part validation: a malformed ingest fails ALONE in its run
    good = gw.submit_nowait(acme, _ingest("ok", [1, 2]))
    bad = gw.submit_nowait(glob, {"type": "ingest", "request_id": "bad",
                                  "stream_ids": [1, 2], "values": [1.0]})
    gw.tick()
    assert good.result().ok
    assert not bad.result().ok and "mismatch" in bad.result().error


# ---------------------------------------------------------------------------
# concurrent clients == serialized oracle, byte for byte
# ---------------------------------------------------------------------------
def test_concurrent_clients_match_serialized_oracle():
    async def drive():
        gw = SynopsisGateway(SDE(), tick_interval=0.001)
        await gw.start()
        d0 = tracing.DISPATCH_COUNT.get("update:CountMin", 0)
        c0 = tracing.GATEWAY_COALESCED.get("ingest", 0)

        async def client_traffic(j):
            tenant = f"t{j % 4}"
            c = gw.connect(f"c{j}", tenant=tenant)
            r = await gw.submit(c, _build(f"cm{j}", request_id=f"b{j}"))
            assert r.ok, r.error
            rng = np.random.RandomState(j)
            for k in range(6):
                r = await gw.submit(
                    c, _ingest(f"i{j}/{k}", rng.randint(0, 50, 32),
                               rng.uniform(0.5, 2.0, 32)))
                assert r.ok, r.error
                if k % 2:
                    q = await gw.submit(
                        c, {"type": "adhoc", "request_id": f"q{j}/{k}",
                            "synopsis_id": f"cm{j}",
                            "query": {"items": [int(rng.randint(50))]}})
                    assert q.ok, q.error

        await asyncio.gather(*(client_traffic(j) for j in range(8)))
        await gw.stop()
        return gw, d0, c0

    gw, d0, c0 = asyncio.run(drive())
    n_ingest_calls = sum(1 for e in gw.commit_log if e[0] == "ingest")
    n_ingest_reqs = 8 * 6
    # every coalesced call was ONE dispatch; concurrency actually
    # amortized (strictly fewer engine calls than client requests)
    assert tracing.DISPATCH_COUNT["update:CountMin"] - d0 == n_ingest_calls
    assert tracing.GATEWAY_COALESCED["ingest"] - c0 == n_ingest_reqs
    assert n_ingest_calls < n_ingest_reqs
    gw.sde.flush()
    _assert_states_equal(gw.sde, replay_log(gw.commit_log))


def test_commit_log_replays_on_pipelined_oracle():
    """The oracle is execution-mode-agnostic: replaying the commit log
    on a PIPELINED engine matches the gateway's eager engine bytewise."""
    gw = SynopsisGateway(SDE())
    c = gw.connect("c0", tenant="acme")
    gw.submit_nowait(c, _build())
    gw.tick()
    rng = np.random.RandomState(7)
    for k in range(4):
        for j in range(3):
            gw.submit_nowait(c, _ingest(f"i{k}/{j}",
                                        rng.randint(0, 40, 16),
                                        rng.uniform(0.5, 2.0, 16)))
        gw.tick()
    gw.sde.flush()
    _assert_states_equal(gw.sde, replay_log(gw.commit_log,
                                            SDE(pipelined=True)))


# ---------------------------------------------------------------------------
# continuous-query routing
# ---------------------------------------------------------------------------
def test_continuous_responses_route_to_subscriber():
    gw = SynopsisGateway(SDE())
    sub = gw.connect("sub", tenant="acme")
    other = gw.connect("other", tenant="acme")
    gw.submit_nowait(sub, _build(continuous=True))
    gw.tick()
    gw.submit_nowait(other, _ingest("i0", [1, 2, 3]))
    gw.submit_nowait(other, _ingest("i1", [1, 1, 4]))
    gw.tick()
    gw.sde.flush()                       # pipelined engine: retire, then
    gw.tick()                            # an empty tick still routes
    assert len(sub.log) == 1             # one coalesced batch => one cq
    assert len(other.log) == 0 and len(gw.unrouted) == 0
    r = sub.log.popleft()
    assert r.synopsis_id == "cm"         # ns stripped on the way out
    assert r.request_id == "cq/cm/1"
    oracle = replay_log(gw.commit_log)
    ro = oracle.continuous_out.popleft()
    jax.tree.map(lambda x, y: np.testing.assert_array_equal(
        np.asarray(x), np.asarray(y)), r.value, ro.value)
    # stop drops the subscription; a disconnected subscriber's responses
    # fall into the bounded unrouted log
    gw.submit_nowait(sub, {"type": "stop", "request_id": "s",
                           "synopsis_id": "cm"})
    gw.tick()
    assert gw._subs == {}
    gw.submit_nowait(sub, _build("cm2", continuous=True))
    gw.tick()
    gw.disconnect(sub)
    gw.submit_nowait(other, _ingest("i2", [5, 6]))
    gw.tick()
    gw.sde.flush()
    gw.tick()
    assert len(gw.unrouted) == 1


# ---------------------------------------------------------------------------
# admission control
# ---------------------------------------------------------------------------
def test_admission_control_caps_in_flight():
    async def drive():
        gw = SynopsisGateway(SDE(), max_in_flight=2)
        c = gw.connect("c0")
        gw.submit_nowait(c, _build())
        gw.tick()
        subs = [asyncio.ensure_future(
            gw.submit(c, _ingest(f"i{k}", [1, 2]))) for k in range(3)]
        for _ in range(10):
            await asyncio.sleep(0)
        assert gw.queued == 2            # third submission NOT admitted
        gw.tick()                        # acks 1+2 -> slots free
        for _ in range(10):
            await asyncio.sleep(0)
        assert gw.queued == 1            # third got in only after acks
        gw.tick()
        acks = await asyncio.gather(*subs)
        assert all(a.ok for a in acks)
        # the delayed request rode a LATER batch than the admitted pair
        assert acks[2].value["batch"] > acks[0].value["batch"]

    asyncio.run(drive())


# ---------------------------------------------------------------------------
# socket server round-trip
# ---------------------------------------------------------------------------
@pytest.mark.smoke
@pytest.mark.parametrize("pipelined", [False, True])
def test_socket_server_roundtrip(pipelined):
    async def drive():
        ready = asyncio.get_running_loop().create_future()
        server = asyncio.ensure_future(sde_server.serve_socket(
            SDE(pipelined=pipelined), port=0, tick_interval=0.001,
            ready=ready, err=io.StringIO()))
        port = await asyncio.wait_for(ready, 10)
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        reqs = [dict(_build(continuous=True), tenant="acme"),
                dict(_ingest("i0", [1, 2, 3]), tenant="acme"),
                {"type": "adhoc", "request_id": "q", "tenant": "acme",
                 "synopsis_id": "cm", "query": {"items": [1]}},
                {"type": "shutdown", "request_id": "bye"}]
        writer.write("".join(json.dumps(r) + "\n" for r in reqs).encode())
        await writer.drain()
        lines = []
        while True:                      # server EOFs after shutdown ack
            line = await asyncio.wait_for(reader.readline(), 10)
            if not line:
                break
            lines.append(json.loads(line))
        writer.close()
        gw = await asyncio.wait_for(server, 10)
        return gw, lines

    gw, lines = asyncio.run(drive())
    by_id = {r["request_id"]: r for r in lines}
    assert by_id["b"]["ok"] and by_id["i0"]["ok"] and by_id["q"]["ok"]
    assert by_id["q"]["synopsis_id"] == "cm"
    assert float(np.ravel(by_id["q"]["value"])[0]) == 1.0
    assert by_id["bye"]["ok"]
    assert by_id["bye"]["value"]["tuples_ingested"] == 3
    # the builder's connection received its continuous response
    cq = [r for r in lines if r["request_id"].startswith("cq/")]
    assert len(cq) == 1 and cq[0]["synopsis_id"] == "cm"
    # shutdown closed the engine and the gateway refuses new work
    assert gw.closed and gw.sde.stacks == {}
    fut = gw.submit_nowait(
        type("C", (), {"tenant": "", "client_id": "late"})(),
        {"type": "status", "request_id": "late"})
    assert not fut.result().ok


def test_socket_server_takes_megabyte_requests_and_idle_clients():
    """A per-stream build over 65,536 ids is a ~1.3 MB request line; and
    an idle second connection must not hold shutdown open."""
    sids = list(range(1, 1 << 16))

    async def drive():
        ready = asyncio.get_running_loop().create_future()
        server = asyncio.ensure_future(sde_server.serve_socket(
            SDE(), port=0, ready=ready, err=io.StringIO()))
        port = await asyncio.wait_for(ready, 10)
        idle = await asyncio.open_connection("127.0.0.1", port)
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        reqs = [{"type": "build", "request_id": "b", "synopsis_id": "cm",
                 "kind": "countmin", "per_stream_of_source": True,
                 "stream_ids": sids},
                _ingest("i0", sids[:4096]),
                {"type": "shutdown", "request_id": "bye"}]
        writer.write("".join(json.dumps(r) + "\n" for r in reqs).encode())
        await writer.drain()
        lines = []
        while True:
            line = await asyncio.wait_for(reader.readline(), 30)
            if not line:
                break
            lines.append(json.loads(line))
        assert await asyncio.wait_for(idle[0].read(), 10) == b""  # EOF
        idle[1].close()
        writer.close()
        await asyncio.wait_for(server, 10)
        return lines

    by_id = {r["request_id"]: r for r in asyncio.run(drive())}
    assert by_id["b"]["ok"], by_id["b"]["error"]
    assert by_id["i0"]["ok"] and by_id["bye"]["ok"]
    assert by_id["bye"]["value"]["synopses"] == len(sids)


# ---------------------------------------------------------------------------
# shutdown request — engine level and JSON-lines server
# ---------------------------------------------------------------------------
def test_shutdown_request_flushes_and_closes():
    eng = SDE(pipelined=True)
    assert eng.handle(_build(continuous=True)).ok
    eng.ingest(np.array([1, 2], np.uint32), np.ones(2, np.float32))
    assert eng.pending_batches > 0
    r = eng.handle({"type": "shutdown", "request_id": "bye"})
    assert r.ok
    assert r.value["drained"] >= 1
    assert r.value["tuples_ingested"] == 2
    assert r.value["continuous_unread"] == 1
    assert eng.stacks == {} and eng.entries == {}


def test_serve_lines_stops_after_shutdown():
    lines = [json.dumps(_build()),
             json.dumps(_ingest("i", [1, 2])),
             json.dumps({"type": "shutdown", "request_id": "bye"}),
             json.dumps(_ingest("never", [3]))]
    out = io.StringIO()
    n = sde_server.serve_lines(lines, out=out)
    assert n == 3                        # the post-shutdown line is dead
    ids = [json.loads(l)["request_id"] for l in out.getvalue().splitlines()]
    assert ids == ["b", "i", "bye"]


# ---------------------------------------------------------------------------
# satellite fixes: file-handle lifetime, batched continuous drain
# ---------------------------------------------------------------------------
def test_main_closes_input_file(tmp_path, monkeypatch, capsys):
    req = tmp_path / "reqs.jsonl"
    req.write_text(json.dumps(_build()) + "\n"
                   + json.dumps(_ingest("i", [1, 2, 3])) + "\n")
    opened = []
    real_open = builtins.open
    def spy(path, *a, **kw):
        fh = real_open(path, *a, **kw)
        if str(path) == str(req):
            opened.append(fh)
        return fh
    monkeypatch.setattr(builtins, "open", spy)
    n = sde_server.main(["--input", str(req)])
    assert n == 2
    assert opened and all(fh.closed for fh in opened)


def test_drain_continuous_writes_once():
    class CountingOut(io.StringIO):
        calls = 0
        def write(self, s):
            CountingOut.calls += 1
            return super().write(s)

    eng = SDE()
    assert eng.handle(_build(continuous=True)).ok
    for k in range(3):
        eng.ingest(np.array([1, 2], np.uint32), np.ones(2, np.float32))
    eng.flush()                          # retire under SDE_PIPELINED=1 too
    assert len(eng.continuous_out) == 3
    out = CountingOut()
    n = sde_server._drain_continuous(eng, out)
    assert n == 3 and CountingOut.calls == 1
    assert len(out.getvalue().splitlines()) == 3
    assert len(eng.continuous_out) == 0
