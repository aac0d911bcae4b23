#!/usr/bin/env python3
"""Run one cell of the benchmark once, on the chip this process finds.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell (``BENCHMARK.json``) names a configuration (``configs/``) and a
traffic mix (``traffic/``). This process owns the chip: it builds the
engine, its write-ahead log and checkpointer as ``sde_server.main`` does,
serves it with ``launch.sde_server.serve_socket`` on its own event loop
thread, and starts the load generator (``loadgen.py``, no JAX) as a
separate process. The generator sends the builds and warms every shape;
that is set-up. Then the window: ``--seconds`` of the mix, with the
profiler on when ``--trace 1``. After it the generator drains, flushes,
asks the post-window queries and shuts the server down; this process
reads the memory peak, frees the engine, recovers a second one from the
checkpoint and WAL, and judges every answer against the exact reference
(``reference/``), each number under the limit its configuration
states. The last line of standard output is the result as one
JSON object; the compared numbers and their limits are also the last
lines of standard error. Without a TPU, or with fewer chips than the
cell needs, it exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import asyncio
import faulthandler
import importlib.util
import json
import os
import pathlib
import shutil
import signal
import subprocess
import sys
import threading
import time
from typing import Callable, Dict, Optional

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

RUN_DIR = ".bench_run"          # beside bench/, listed in .gitignore
READY_TIMEOUT_S = 1100.0      # a first run compiles every shape
END_TIMEOUT_S = 240.0          # drain (<= 60 s) + post-window queries


class NoChip(RuntimeError):
    pass


def _process_age() -> float:
    """Seconds since this process started (set-up counts from there)."""
    with open("/proc/self/stat") as f:
        start = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        up = float(f.read().split()[0])
    return up - start / os.sysconf("SC_CLK_TCK")


def check_chips(chips: int) -> dict:
    """The device this run reports; raises ``NoChip`` unless JAX finds a
    TPU with at least ``chips`` devices."""
    import jax
    devs = jax.devices()
    dev = dict(platform=devs[0].platform, kind=devs[0].device_kind,
               count=len(devs))
    if dev["platform"] != "tpu":
        raise NoChip(f"needs a TPU, JAX found {dev}")
    if dev["count"] < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {dev}")
    return dev


class Compiles:
    """Backend compiles in this process (cache misses of the persistent
    compilation cache), from JAX's monitoring events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.count, self.seconds = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            self.count += 1
            self.seconds += duration


def counters(sde, compiles: Compiles) -> Dict[str, float]:
    from repro.kernels import ops as kops
    return dict(ticks=sum(kops.GATEWAY_TICKS.values()),
                tuples=sde.tuples_ingested, compiles=compiles.count,
                compile_s=compiles.seconds)


def _traces():
    """Programs traced so far, by name (``kops.TRACE_COUNT``)."""
    from collections import Counter
    from repro.kernels import ops as kops
    return Counter(kops.TRACE_COUNT)


class Server:
    """``serve_socket`` on its own event loop thread."""

    def __init__(self, sde, wal, checkpointer, cfg: dict):
        from repro.launch import sde_server
        self.gw = None
        self.error: Optional[BaseException] = None
        self._port: Dict[str, int] = {}
        self._ready = threading.Event()
        srv = cfg["server"]

        def serve() -> None:
            loop = asyncio.new_event_loop()
            asyncio.set_event_loop(loop)
            ready = loop.create_future()
            ready.add_done_callback(
                lambda f: (self._port.update(port=f.result()),
                           self._ready.set()))
            try:
                self.gw = loop.run_until_complete(sde_server.serve_socket(
                    sde, "127.0.0.1", 0, ready=ready, wal=wal,
                    checkpointer=checkpointer,
                    tick_interval=float(srv["tick_s"]),
                    max_in_flight=int(srv["max_in_flight"]),
                    client_log_cap=srv["client_log_cap"]))
            except BaseException as e:  # noqa: BLE001 - reported by join
                self.error = e
                self._ready.set()
            finally:
                loop.close()

        self.thread = threading.Thread(target=serve, name="sde-server",
                                       daemon=True)
        self.thread.start()

    def port(self) -> int:
        self._ready.wait(120)
        if "port" not in self._port:
            raise RuntimeError(f"server did not start: {self.error!r}")
        return self._port["port"]

    def join(self, timeout: float):
        self.thread.join(timeout)
        if self.thread.is_alive():
            raise RuntimeError("server did not shut down")
        if self.error is not None:
            raise self.error
        return self.gw


def load_reader(path: pathlib.Path) -> Callable:
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class Context:
    """What a per-layer metric reader may read of a traced run."""

    def __init__(self, cfg, window, spans, counters, trace, peaks):
        self.cfg, self.window, self.counters = cfg, window, counters
        self.trace, self.peaks = trace, peaks
        self._spans = spans

    @property
    def seconds(self) -> float:
        return self.window[1] - self.window[0]

    def spans(self, *names: str):
        return [s for s in self._spans if s[0] in names
                and self.window[0] <= s[1] < self.window[1]]


def cell_metrics(bench: dict, workload: str, section: str):
    out = []
    for m in bench[section]:
        cells = m.get("workloads")
        if cells is None or workload in cells:
            out.append(m)
    return out


def run(workload: str, seed: int, seconds: float, trace: bool,
        root: pathlib.Path = BENCH,
        check: Callable[[int], dict] = check_chips) -> dict:
    """One run; returns the result object (the last output line)."""
    from bench import traffic as tr
    bench = json.loads((root.parent / "BENCHMARK.json").read_text())
    cell = tr.cell(workload, root)
    cfg = tr.load("configs", cell["config"], root)
    limits = cfg["limits"]

    from repro.launch.compile_cache import enable_compilation_cache
    enable_compilation_cache()
    import jax
    # every program goes to the persistent cache, however quick to build
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    device = check(int(cell["chips"]))
    compiles = Compiles()

    from repro.service import SDE, wal as wal_mod
    from bench import spans as spans_mod
    from bench.reference import exact, recovery
    recorder = spans_mod.Recorder(annotate=trace).install() if trace \
        else None

    run_dir = root.parent / RUN_DIR / workload
    shutil.rmtree(run_dir, ignore_errors=True)
    run_dir.mkdir(parents=True)
    dur = cfg["durability"]
    sde = SDE(pipelined=bool(cfg["engine"]["pipelined"]), pipeline_depth=2,
              backend=cfg["engine"]["backend"])
    wal = wal_mod.WriteAheadLog(str(run_dir / "wal.jsonl"), tag=sde.site)
    checkpointer = wal_mod.Checkpointer(
        sde, str(run_dir / "ckpt"), interval=int(dur["checkpoint_interval"]),
        keep=int(dur["checkpoint_keep"]), rebase_every=int(dur["rebase_every"]),
        incremental=bool(dur["incremental"]), async_=bool(dur["async"]),
        wal=wal)
    server = Server(sde, wal, checkpointer, cfg)
    out_path = run_dir / "results.json"
    gen = subprocess.Popen(
        [sys.executable, str(BENCH / "loadgen.py"), "--config", cell["config"],
         "--traffic", cell["traffic"], "--seed", str(seed), "--seconds",
         str(seconds), "--port", str(server.port()), "--out", str(out_path),
         "--root", str(root)],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
        cwd=str(ROOT))
    try:
        line = _readline(gen, READY_TIMEOUT_S)
        if line.strip() != "READY":
            raise RuntimeError(f"load generator: {line!r}")
        if cfg["durability"].get("snapshot_after_setup"):
            # the base snapshot recovery starts from; the generator is
            # idle, so nothing races the engine here
            checkpointer.snapshot()
            sde.wait_for_snapshot()
        setup_s = _process_age()
        c0 = counters(sde, compiles)
        t_start = _traces()
        trace_dir = run_dir / "trace"
        if trace:
            jax.profiler.start_trace(str(trace_dir))
        t0 = time.monotonic() + 0.05
        gen.stdin.write(f"GO {t0!r}\n")
        gen.stdin.flush()
        _sleep_until(t0)
        if trace:
            with jax.profiler.TraceAnnotation("bench/window"):
                _sleep_until(t0 + seconds)
        else:
            _sleep_until(t0 + seconds)
        c1 = counters(sde, compiles)
        traced = _traces() - t_start
        if trace:
            jax.profiler.stop_trace()
        line = _readline(gen, END_TIMEOUT_S)
        if line.strip() != "POSTED":
            raise RuntimeError(f"load generator: {line!r}")
        # the generator is idle: copy the state that acked every request
        sde.wait_for_snapshot()
        live = recovery.engine_image(sde)
        gen.stdin.write("SHUTDOWN\n")
        gen.stdin.flush()
        report = _readline(gen, END_TIMEOUT_S)
        if gen.wait(END_TIMEOUT_S) != 0:
            raise RuntimeError(f"load generator exited {gen.returncode}")
        print(f"[bench] generator: {report.strip()}", file=sys.stderr)
        server.join(END_TIMEOUT_S)
    except BaseException:
        # where each side was stuck, for the record
        faulthandler.dump_traceback(all_threads=True)
        if gen.poll() is None:
            gen.send_signal(signal.SIGUSR1)
            time.sleep(1.0)
        gen.kill()
        gen.wait()
        if server.thread.is_alive():
            _shutdown(server.port())
        raise
    finally:
        if recorder is not None:
            recorder.uninstall()
    device["memory_peak_bytes"] = max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
        for d in jax.devices()[:int(cell["chips"])])
    results = json.loads(out_path.read_text())

    # durability: free the engine, recover from checkpoint + WAL
    wal.close()
    sde.wait_for_snapshot()
    sde.close()
    del sde, checkpointer, server
    rec = wal_mod.recover(str(run_dir / "ckpt"), str(run_dir / "wal.jsonl"))
    diff = recovery.image_diff(live, recovery.engine_image(rec))
    rec.close()
    del live, rec

    plan = tr.make_plan(cell["config"], cell["traffic"], seed, seconds, root)
    numbers = exact.judge_run(plan, results)
    numbers["recovered_diff"] = diff
    compared = {k: dict(value=v, limit=limits[k]) for k, v in numbers.items()}
    correct = all(v <= limits[k] for k, v in numbers.items())

    from bench import stats
    out = dict(correct=correct, attempted=stats.attempted(results),
               failed=stats.failed(results))
    run_info = dict(setup_s=setup_s, lateness_ms=results["lateness_ms"],
                    counters=dict(start=c0, end=c1),
                    traced_in_window=dict(traced))
    if not trace:
        e2e = stats.end_to_end(results)
        e2e["setup_s"] = setup_s
        metrics = {}
        for m in cell_metrics(bench, workload, "end_to_end"):
            if e2e.get(m["name"]) is not None:
                metrics[m["name"]] = dict(value=e2e[m["name"]], unit=m["unit"])
        out["metrics"] = metrics
        out["device"] = device
    else:
        from bench import devtrace
        tdata = devtrace.collect(trace_dir)
        tdata["window"] = tdata["window"] or [0.0, 0.0]
        (run_dir / "trace.json").write_text(json.dumps(tdata))
        device["busy_s"] = devtrace.busy_seconds(tdata)
        device["window_s"] = devtrace.window_seconds(tdata)
        peaks = json.loads((root / "peaks.json").read_text())
        if device["kind"] not in peaks:
            raise KeyError(f"no peaks for device kind {device['kind']!r} "
                           "in peaks.json")
        ctx = Context(cfg, (t0, t0 + seconds), recorder.spans,
                      {k: c1[k] - c0[k] for k in c0}, tdata,
                      peaks[device["kind"]])
        metrics = {}
        for m in cell_metrics(bench, workload, "per_layer"):
            value = load_reader(root / "metrics" / f"{m['name']}.py")(ctx)
            if value is not None:
                metrics[m["name"]] = dict(value=value, unit=m["unit"])
        out["metrics"] = metrics
        out["device"] = device
        out["breakdown"] = dict(device_ops=devtrace.top_ops(tdata),
                                idle_gaps=devtrace.idle_gaps(tdata))
        run_info["missing_spans"] = recorder.missing
    out["compared"] = compared
    (run_dir / "run.json").write_text(json.dumps(dict(result=out, **run_info)))
    shutil.rmtree(run_dir / "ckpt", ignore_errors=True)
    shutil.rmtree(trace_dir, ignore_errors=True)
    return out


def _readline(proc: subprocess.Popen, timeout: float) -> str:
    box = []
    t = threading.Thread(target=lambda: box.append(proc.stdout.readline()),
                         daemon=True)
    t.start()
    t.join(timeout)
    if not box:
        raise TimeoutError("load generator said nothing in time")
    if not box[0]:
        raise RuntimeError(f"load generator ended (exit {proc.wait()})")
    return box[0]


def _sleep_until(t: float) -> None:
    dt = t - time.monotonic()
    if dt > 0:
        time.sleep(dt)


def _shutdown(port: int) -> None:
    import socket
    try:
        with socket.create_connection(("127.0.0.1", port), timeout=30) as s:
            s.sendall(b'{"type":"shutdown","request_id":"bench-stop"}\n')
            s.recv(1 << 16)
    except OSError as e:
        print(f"[bench] server did not answer shutdown: {e!r}",
              file=sys.stderr)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        out = run(args.workload, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for name, c in out["compared"].items():
        print(f"{name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
