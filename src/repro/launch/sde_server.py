"""SDEaaS front end — JSON-lines driver and multi-client socket server.

Two serving modes over the same JSON request contract (the paper's Kafka
RequestTopic, Section 3):

  * **JSON-lines** (default; kept for tests and one-shot replay): one
    request per input line, one response per output line, continuous
    responses interleaved as their batches retire. EOF — or a
    ``{"type": "shutdown"}`` request — performs a final flush so no
    continuous response is ever lost.

      PYTHONPATH=src python -m repro.launch.sde_server --pipelined \
          < requests.jsonl > responses.jsonl

  * **Socket server** (``--port``): N concurrent newline-delimited-JSON
    TCP clients multiplexed onto ONE engine through the
    ``SynopsisGateway`` micro-batcher — concurrent ingest coalesces to
    one fused blue-path dispatch per kind per tick, concurrent queries
    to one stacked-estimate dispatch, and each connection receives only
    its own acks plus the continuous responses of the synopses it
    built. Admission control (``--max-in-flight``) delays reads — and
    therefore acks — when a client floods, pushing backpressure into
    its TCP window instead of the engine's queue.

      PYTHONPATH=src python -m repro.launch.sde_server --port 7077 \
          --pipelined
"""
from __future__ import annotations

import argparse
import asyncio
import contextlib
import itertools
import json
import sys
from typing import IO, Iterable, Optional

from repro import tracing
from repro.launch.compile_cache import enable_compilation_cache
from repro.service import SDE, api
from repro.service.gateway import SynopsisGateway


# longest request line the socket server reads: a per-stream build or an
# ingest batch of a few hundred thousand tuples is megabytes of JSON
# (asyncio's default stream limit, 64 KiB, would drop the connection)
MAX_REQUEST_BYTES = 1 << 28


def _drain_continuous(sde: SDE, out: IO[str]) -> int:
    """Write every retired continuous response (emission order) with ONE
    write call — a pipelined drain can retire thousands of responses at
    once, and one syscall per response dominated the drain cost."""
    rs = sde.continuous_out.drain()
    if rs:
        out.write("".join(r.to_json() + "\n" for r in rs))
    return len(rs)


def serve_lines(lines: Iterable[str], sde: Optional[SDE] = None, *,
                out: IO[str] = sys.stdout, reconciler=None,
                wal=None, checkpointer=None) -> int:
    """Drive ``sde`` (or a fresh eager/env-default engine) with
    JSON-lines requests; write one response line per request plus the
    continuous responses retired so far. Construct the SDE yourself to
    pick the execution mode (``SDE(pipelined=True, ...)``). Stops after
    acking a successful ``shutdown`` (the engine has already flushed and
    closed); plain EOF gets the same final flush. A ``reconciler``
    rides the request loop (``maybe_step`` after each request — its
    interval does the throttling); a ``wal`` (service/wal.py) records
    every state-mutating request durably before its ack line is written
    (lifecycle requests pre-apply, ingest post-apply keyed by the
    engine-assigned batch id — a refused ingest never reaches the log),
    and a ``checkpointer`` snapshots every N ingested batches. Returns
    the number of requests handled."""
    if sde is None:
        sde = SDE()
    n_requests = 0
    for line in lines:
        line = line.strip()
        if not line:
            continue
        try:
            req = json.loads(line)
        except json.JSONDecodeError:
            req = line               # engine's handler reports the error
        seq = None
        rtype = req.get("type") if isinstance(req, dict) else None
        if wal is not None and rtype in api.MUTATING_REQUESTS:
            # lifecycle: logged pre-apply (replay re-executes verbatim;
            # a request that fails live fails identically on replay). A
            # WAL write error must not kill serving — the request is
            # refused instead, keeping "acked => in the WAL" intact.
            try:
                seq = wal.append_request(req)
                wal.sync()           # durable before apply AND ack
            except Exception as e:  # noqa: BLE001 - serving must survive
                out.write(api.Response(
                    request_id=str(req.get("request_id", "")), ok=False,
                    error=f"WAL append failed: {e!r}").to_json() + "\n")
                n_requests += 1
                continue
        resp = sde.handle(req)
        if wal is not None and rtype == "ingest_multidim" and resp.ok:
            # multidim ingest is a data record too: logged post-apply,
            # keyed by the engine-assigned batch id, replayed through
            # ``sde.handle`` (the expansion is deterministic per spec)
            try:
                seq = wal.append_ingest_multidim(resp.value["batch"], req)
                wal.sync()           # durable before ack
            except Exception as e:  # noqa: BLE001 - serving must survive
                resp = api.Response(
                    request_id=resp.request_id, ok=False,
                    error=f"ingested but WAL append failed: {e!r}")
                seq = None
        if wal is not None and rtype == "ingest" and resp.ok:
            # ingest: logged POST-apply with the batch id the engine
            # actually assigned — a malformed batch the engine refused
            # (acked with an error, no batch id) never reaches the log,
            # so replay cannot be poisoned or consume an acked id
            try:
                seq = wal.append_ingest(
                    resp.value["batch"], req.get("stream_ids", []),
                    req.get("values", []), req.get("mask"))
                wal.sync()           # durable before ack
            except Exception as e:  # noqa: BLE001 - serving must survive
                # applied but not durable: ack an error so no client
                # counts on this batch surviving a crash
                resp = api.Response(
                    request_id=resp.request_id, ok=False,
                    error=f"ingested but WAL append failed: {e!r}")
                seq = None
        if seq is not None:
            sde.wal_seq = seq
        out.write(resp.to_json() + "\n")
        n_requests += 1
        _drain_continuous(sde, out)
        if resp.ok and isinstance(req, dict) \
                and req.get("type") == "shutdown":
            return n_requests        # shutdown already flushed + closed
        if checkpointer is not None:
            try:
                checkpointer.maybe_snapshot()
            except Exception as e:  # noqa: BLE001 - serving must survive
                print(f"[sde-server] checkpoint error: {e!r}",
                      file=sys.stderr)
        if reconciler is not None:
            try:
                reconciler.maybe_step()
            except Exception as e:  # noqa: BLE001 - serving must survive
                print(f"[sde-server] reconcile error: {e!r}",
                      file=sys.stderr)
    sde.flush()                      # final barrier: retire everything
    _drain_continuous(sde, out)
    return n_requests


async def serve_socket(sde: Optional[SDE] = None,
                       host: str = "127.0.0.1", port: int = 0, *,
                       tick_interval: float = 0.001,
                       max_in_flight: int = 8,
                       client_log_cap: Optional[int] = 1024,
                       ready: Optional[asyncio.Future] = None,
                       err: IO[str] = sys.stderr,
                       reconciler=None, wal=None,
                       checkpointer=None) -> SynopsisGateway:
    """Run the multi-client socket server until a client sends a
    successful ``{"type": "shutdown"}``. ``port=0`` binds an ephemeral
    port; the bound port is announced on ``err`` and resolved into
    ``ready`` (when given), so tests can connect without racing. A
    ``reconciler`` rides the gateway tick; so do the durability hooks —
    ``wal`` (fsynced once per tick, before its acks go out) and
    ``checkpointer`` (incremental snapshot every N ingested batches).
    Returns the gateway (engine closed, probes/commit log intact). With
    a profiler session running, each request line's JSON decode and each
    ack's encode and write are spans (``repro.tracing``)."""
    gw = SynopsisGateway(sde, tick_interval=tick_interval,
                         max_in_flight=max_in_flight,
                         client_log_cap=client_log_cap,
                         reconciler=reconciler, wal=wal,
                         checkpointer=checkpointer)
    await gw.start()
    conn_seq = itertools.count()
    writers = set()

    async def handle_conn(reader: asyncio.StreamReader,
                          writer: asyncio.StreamWriter) -> None:
        client = gw.connect(f"conn-{next(conn_seq)}")
        writers.add(writer)
        wlock = asyncio.Lock()       # acks and continuous pushes interleave
        pending = set()

        async def write_lines(text: str) -> None:
            async with wlock:
                writer.write(text.encode())
                await writer.drain()

        async def finish(fut) -> None:
            try:
                resp = await fut
                with tracing.span("frontend.ack"):
                    await write_lines(resp.to_json() + "\n")
            except (ConnectionError, RuntimeError):
                pass                 # client gone mid-ack
            finally:
                client.release()

        async def push_continuous() -> None:
            while True:
                await client.wakeup.wait()
                client.wakeup.clear()
                rs = client.log.drain()
                if rs:
                    await write_lines(
                        "".join(r.to_json() + "\n" for r in rs))

        pusher = asyncio.create_task(push_continuous())
        try:
            while True:
                # admission control: no read until a response slot frees,
                # so a flooding client sees delayed acks (TCP backpressure)
                await client.admit()
                try:
                    line = await reader.readline()
                except ValueError:   # one line over MAX_REQUEST_BYTES
                    await write_lines(api.Response(
                        request_id="", ok=False,
                        error=f"request line exceeds {MAX_REQUEST_BYTES} "
                              "bytes").to_json() + "\n")
                    client.release()
                    break
                if not line:
                    client.release()
                    break
                line = line.strip()
                if not line:
                    client.release()
                    continue
                try:
                    with tracing.span("frontend.decode", bytes=len(line)):
                        req = json.loads(line)
                    if not isinstance(req, dict):
                        raise ValueError("request must be a JSON object")
                except Exception as e:  # noqa: BLE001 - report, keep serving
                    await write_lines(api.Response(
                        request_id="", ok=False,
                        error=repr(e)).to_json() + "\n")
                    client.release()
                    continue
                task = asyncio.create_task(
                    finish(gw.submit_nowait(client, req)))
                pending.add(task)
                task.add_done_callback(pending.discard)
                if req.get("type") == "shutdown":
                    break            # ack (in flight) is this conn's last line
        finally:
            if pending:
                await asyncio.gather(*pending, return_exceptions=True)
            pusher.cancel()
            with contextlib.suppress(asyncio.CancelledError):
                await pusher
            rs = client.log.drain()
            if rs:                   # final push of routed continuous output
                with contextlib.suppress(ConnectionError, RuntimeError):
                    await write_lines(
                        "".join(r.to_json() + "\n" for r in rs))
            gw.disconnect(client)
            writers.discard(writer)
            with contextlib.suppress(ConnectionError):
                writer.close()

    server = await asyncio.start_server(handle_conn, host, port,
                                        limit=MAX_REQUEST_BYTES)
    bound = server.sockets[0].getsockname()[1]
    print(f"[sde-server] listening on {host}:{bound}", file=err, flush=True)
    if ready is not None and not ready.done():
        ready.set_result(bound)
    async with server:
        await gw.closed_event.wait()
        await asyncio.sleep(0.05)    # let shutdown acks reach their clients
        server.close()
        # EOF every idle connection BEFORE waiting on the server: since
        # Python 3.12 ``wait_closed`` also waits for open connections,
        # and an idle client would otherwise hold shutdown forever
        for w in list(writers):
            with contextlib.suppress(ConnectionError):
                w.close()
        while writers:               # their handlers finish promptly
            await asyncio.sleep(0.01)
        await server.wait_closed()
    await gw.stop()
    return gw


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--pipelined", action="store_true",
                    help="bounded async ingest queue (deferred emission)")
    ap.add_argument("--depth", type=int, default=2,
                    help="pipeline depth (in-flight ingest batches)")
    ap.add_argument("--input", default="-",
                    help="requests file, '-' for stdin (JSON-lines mode)")
    ap.add_argument("--port", type=int, default=None,
                    help="serve N concurrent TCP clients through the "
                         "micro-batching gateway (0 = ephemeral port)")
    ap.add_argument("--host", default="127.0.0.1",
                    help="bind address for --port mode")
    ap.add_argument("--tick", type=float, default=0.001,
                    help="gateway micro-batch tick interval, seconds")
    ap.add_argument("--max-in-flight", type=int, default=8,
                    help="per-client admission-control window")
    ap.add_argument("--reconcile-interval", type=float, default=None,
                    help="run the elasticity reconciler every S seconds "
                         "(off the gateway tick in --port mode, off the "
                         "request loop otherwise)")
    ap.add_argument("--reconcile-hll", default="reconcile-hll",
                    help="synopsis id of the estimator HLL "
                         "(#pieces of work)")
    ap.add_argument("--reconcile-cm", default="reconcile-cm",
                    help="synopsis id of the estimator CountMin "
                         "(per-piece load)")
    ap.add_argument("--reconcile-workers", type=int, default=None,
                    help="worker-slice count for placement (default: the "
                         "synopsis mesh axis size)")
    ap.add_argument("--wal", default=None, metavar="PATH",
                    help="write-ahead ingest log: every state-mutating "
                         "request is durable (fsynced) before its ack")
    ap.add_argument("--checkpoint-dir", default=None, metavar="DIR",
                    help="take periodic engine snapshots into DIR")
    ap.add_argument("--checkpoint-interval", type=int, default=8,
                    help="snapshot every N ingested batches (default 8)")
    ap.add_argument("--checkpoint-keep", type=int, default=3,
                    help="keep-k snapshot GC (delta bases are protected)")
    ap.add_argument("--rebase-every", type=int, default=8,
                    help="fold the delta chain into a fresh full base "
                         "every N deltas (default 8)")
    ap.add_argument("--full-snapshots", action="store_true",
                    help="synchronous full snapshots instead of "
                         "incremental async deltas (the pre-durability "
                         "baseline; fig12 measures the difference)")
    ap.add_argument("--recover", action="store_true",
                    help="restore the latest snapshot from "
                         "--checkpoint-dir and replay the --wal tail "
                         "before serving")
    args = ap.parse_args(argv)
    enable_compilation_cache()
    from repro.service import wal as wal_mod
    if args.recover:
        sde = wal_mod.recover(args.checkpoint_dir, args.wal,
                              pipelined=args.pipelined)
        print(f"[sde-server] recovered: {sde.batches_ingested} batches, "
              f"{len(sde.entries)} synopses, wal_seq={sde.wal_seq}",
              file=sys.stderr, flush=True)
    else:
        sde = SDE(pipelined=args.pipelined, pipeline_depth=args.depth)
    wal = (wal_mod.WriteAheadLog(args.wal, tag=sde.site)
           if args.wal else None)
    checkpointer = (wal_mod.Checkpointer(
        sde, args.checkpoint_dir, interval=args.checkpoint_interval,
        keep=args.checkpoint_keep, rebase_every=args.rebase_every,
        incremental=not args.full_snapshots,
        async_=not args.full_snapshots, wal=wal)
        if args.checkpoint_dir else None)
    reconciler = None
    if args.reconcile_interval is not None:
        from repro.service.reconciler import Reconciler
        # None when the flag is unset — the Reconciler then infers the
        # synopsis mesh axis size (the documented default) and raises a
        # clear ValueError when there is neither a mesh nor a flag
        reconciler = Reconciler(
            sde, args.reconcile_hll, args.reconcile_cm,
            n_workers=args.reconcile_workers,
            interval=args.reconcile_interval)
    try:
        if args.port is not None:
            gw = asyncio.run(serve_socket(
                sde, args.host, args.port, tick_interval=args.tick,
                max_in_flight=args.max_in_flight, reconciler=reconciler,
                wal=wal, checkpointer=checkpointer))
            n = gw.requests
        elif args.input == "-":
            n = serve_lines(sys.stdin, sde, reconciler=reconciler,
                            wal=wal, checkpointer=checkpointer)
        else:
            with open(args.input) as fh:
                n = serve_lines(fh, sde, reconciler=reconciler,
                                wal=wal, checkpointer=checkpointer)
        print(f"[sde-server] handled {n} requests; "
              f"{sde.tuples_ingested:,} tuples in {sde.batches_ingested} "
              f"batches; continuous dropped={sde.continuous_out.dropped}",
              file=sys.stderr)
        return n
    finally:
        if wal is not None:
            wal.close()
        sde.wait_for_snapshot()      # join the background save, if any
        sde.close()                  # idempotent after a shutdown request


if __name__ == "__main__":
    main()
