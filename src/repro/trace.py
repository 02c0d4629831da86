"""Spans and counters of the served path: one process-wide tally
(DESIGN.md §12).

``span(name)`` times one phase.  It enters a
``jax.profiler.TraceAnnotation`` of the same name, so a profiler trace
shows the phase on the host's line, on the clock of the device ops, and
on leaving it adds the phase's seconds and one call to the tally; the
span object keeps its own ``seconds`` for callers that report the phase
themselves (``core.batch.BatchTiming``).  ``count(name, n)`` adds to a
counter.  ``snapshot()`` copies the tally out as plain values and
``delta(after, before)`` subtracts two copies.

The tally is always on and takes a lock per update, so the async
server's worker threads add to it safely; the annotation costs a flag
check while no profiler trace is being collected.  Every name starts
with ``pathenum.``: a reader of a profiler trace tells the program's
spans from the runtime's by that prefix, and ``serving.metrics`` exports
them as the ``pathenum_span_*`` and ``pathenum_<counter>_total``
families.
"""
from __future__ import annotations

import threading
import time
from types import TracebackType
from typing import Any, Dict, List, Optional, Type

from jax.profiler import TraceAnnotation

PREFIX = "pathenum."

_lock = threading.Lock()
_spans: Dict[str, List[float]] = {}      # name -> [seconds, calls]
_counters: Dict[str, int] = {}

# what ``snapshot`` returns: {"spans": {...}, "counters": {...}}
Snapshot = Dict[str, Dict[str, Any]]


class Span:
    """One timed phase; use through ``span(name)`` as a context manager."""
    __slots__ = ("name", "seconds", "_t0", "_annotation")

    def __init__(self, name: str) -> None:
        self.name = name
        self.seconds = 0.0
        self._t0 = 0.0
        self._annotation = TraceAnnotation(name)

    def __enter__(self) -> "Span":
        self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type: Optional[Type[BaseException]],
                 exc: Optional[BaseException],
                 tb: Optional[TracebackType]) -> None:
        self.seconds = time.perf_counter() - self._t0
        self._annotation.__exit__(exc_type, exc, tb)
        with _lock:
            rec = _spans.get(self.name)
            if rec is None:
                _spans[self.name] = [self.seconds, 1]
            else:
                rec[0] += self.seconds
                rec[1] += 1


def span(name: str) -> Span:
    """A span named ``name`` (``pathenum.`` first), to enter with
    ``with``; its ``seconds`` hold the phase's duration once left."""
    return Span(name)


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to the counter ``name``."""
    with _lock:
        _counters[name] = _counters.get(name, 0) + n


def counter(name: str) -> int:
    """The value of one counter (0 before its first ``count``)."""
    with _lock:
        return _counters.get(name, 0)


def counters(prefix: str) -> Dict[str, int]:
    """A copy of the counters whose names start with ``prefix``, in the
    order they were first counted."""
    with _lock:
        return {k: v for k, v in _counters.items() if k.startswith(prefix)}


def snapshot() -> Snapshot:
    """A value copy of the tally: ``{"spans": {name: [seconds, calls]},
    "counters": {name: value}}``."""
    with _lock:
        return {"spans": {k: [v[0], int(v[1])] for k, v in _spans.items()},
                "counters": dict(_counters)}


def delta(after: Snapshot, before: Snapshot) -> Snapshot:
    """``after - before`` of two snapshots; names that did not move are
    left out."""
    spans: Dict[str, Any] = {}
    for k, (s, c) in after["spans"].items():
        s0, c0 = before["spans"].get(k, (0.0, 0))
        if c != c0:
            spans[k] = [s - s0, c - c0]
    ctrs: Dict[str, Any] = {}
    for k, v in after["counters"].items():
        if v != before["counters"].get(k, 0):
            ctrs[k] = v - before["counters"].get(k, 0)
    return {"spans": spans, "counters": ctrs}
