"""Spans around the program's layers, recorded from the benchmark's side.

The program has no spans of its own, so the harness wraps the methods at
its layer boundaries: each call is timed on ``time.monotonic`` and, in
a traced run, also opens a ``jax.profiler.TraceAnnotation`` so the device
trace shows what the host was doing. A method that no longer exists is
skipped: its metric then finds nothing to read and is left out.
"""
from __future__ import annotations

import functools
import importlib
import time
from typing import Callable, List, Optional, Tuple

# (span name, module, class, method, what to record of the call)
WRAPPED = [
    ("gateway.tick", "repro.service.gateway", "SynopsisGateway", "tick",
     None),
    ("sde.ingest", "repro.service.engine", "SDE", "ingest",
     lambda args, kw: (len(args[1]), kw.get("items") is not None)),
    ("sde.query_many", "repro.service.engine", "SDE", "query_many",
     lambda args, kw: len(args[1])),
    ("sde.subpop", "repro.service.engine", "SDE", "handle", "subpop"),
    ("sde.retire_batch", "repro.service.engine", "SDE", "_retire_batch",
     None),
    ("wal.append_ingest", "repro.service.wal", "WriteAheadLog",
     "append_ingest", None),
    ("wal.sync", "repro.service.wal", "WriteAheadLog", "sync",
     lambda args, kw: bool(getattr(args[0], "_dirty", True))),
    ("checkpoint.maybe_snapshot", "repro.service.wal", "Checkpointer",
     "maybe_snapshot", None),
]


def _is_subpop(args, kw) -> bool:
    req = args[1] if len(args) > 1 else kw.get("snippet")
    return isinstance(req, dict) and req.get("type") == "subpop_query"


class Recorder:
    """Installs the wrappers and keeps the spans in memory:
    ``(name, t0, t1, note)`` on ``time.monotonic``; ``note`` is what
    the call is about (batch length, query count), taken before it runs."""

    def __init__(self, annotate: bool):
        self.annotate = annotate
        self.spans: List[Tuple[str, float, float, object]] = []
        self.missing: List[str] = []
        self._undo: List[Tuple[type, str, Callable]] = []

    def install(self) -> "Recorder":
        from jax.profiler import TraceAnnotation
        for name, mod, cls, meth, info in WRAPPED:
            klass = getattr(importlib.import_module(mod), cls, None)
            orig = getattr(klass, meth, None) if klass is not None else None
            if orig is None:
                self.missing.append(name)
                continue
            setattr(klass, meth, self._wrap(name, orig, info,
                                            TraceAnnotation))
            self._undo.append((klass, meth, orig))
        return self

    def uninstall(self) -> None:
        for klass, meth, orig in reversed(self._undo):
            setattr(klass, meth, orig)
        self._undo.clear()

    def _wrap(self, name: str, orig: Callable, info, annotation):
        spans, annotate = self.spans, self.annotate
        only_subpop = info == "subpop"
        what: Optional[Callable] = None if only_subpop else info
        label = f"bench/{name}"

        @functools.wraps(orig)
        def wrapper(*args, **kw):
            if only_subpop and not _is_subpop(args, kw):
                return orig(*args, **kw)
            note = what(args, kw) if what else None
            t0 = time.monotonic()
            try:
                if annotate:
                    with annotation(label):
                        return orig(*args, **kw)
                return orig(*args, **kw)
            finally:
                spans.append((name, t0, time.monotonic(), note))
        return wrapper
