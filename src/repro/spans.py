"""The program's spans and counters: one recorder, on the profiler's clock.

``span(name, **meta)`` opens a ``jax.profiler.TraceAnnotation`` -- in a
profiler trace the span lands on the calling thread's host line, on the
clock of the device's ``XLA Ops`` -- and, when it closes, appends a
:class:`Record` to a bounded in-memory record for ``name``.  ``count``
appends one value of a per-call counter.  ``recent`` and ``last`` read
either back.

JAX's own compile events (tracing, lowering to MLIR, the backend compile
and the persistent-cache fetch inside it) are charged to every span open
on the thread that compiles, so a span says how much of it was JAX
building executables, and how many it built.

Always on, with no switch: with the profiler off a span costs a
``TraceAnnotation`` and a deque append, a few microseconds against a
schedule call of hundreds of milliseconds.
"""
from __future__ import annotations

import threading
import time
from collections import deque
from contextlib import ContextDecorator
from typing import Any, NamedTuple, Optional

import jax

#: records held per name; older ones drop off
MAXLEN = 4096

#: ``jax.monitoring`` duration events of JAX's compile path
COMPILE_EVENTS = frozenset({
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
    "/jax/compilation_cache/cache_retrieval_time_sec",
})
#: fires once per executable, compiled or fetched from the persistent
#: cache (the fetch is timed inside it)
EXECUTABLE_EVENT = "/jax/core/compile/backend_compile_duration"


class Record(NamedTuple):
    start_ns: int             #: ``time.perf_counter_ns()`` at open
    dur_ns: int
    parent: Optional[str]     #: innermost span open on the thread then
    compiles: int             #: executables JAX built inside the span
    #: wall time inside JAX's compile events; nested events (a jitted
    #: kernel traced inside its caller) count once
    compile_ns: int


class _Open:
    """A span open on this thread."""
    __slots__ = ("name", "parent", "annotation", "start", "compiles",
                 "intervals")

    def __init__(self, name: str) -> None:
        self.name = name
        self.compiles = 0
        self.intervals: list[tuple[int, int]] = []


_records: dict[str, deque] = {}
_local = threading.local()


def _stack() -> list[_Open]:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


def _append(name: str, value: Any) -> None:
    q = _records.get(name)
    if q is None:
        q = _records.setdefault(name, deque(maxlen=MAXLEN))
    q.append(value)


def _union_ns(intervals: list[tuple[int, int]]) -> int:
    total, end = 0, None
    for s, t in sorted(intervals):
        if end is not None and s < end:
            s = end
        if t > s:
            total += t - s
            end = t
    return total


class span(ContextDecorator):
    """Time a ``with`` body (or each call of a decorated function) as span
    ``name``; ``meta`` are the trace annotation's arguments."""

    def __init__(self, name: str, **meta) -> None:
        self.name = name
        self.meta = meta

    def __enter__(self) -> None:
        stack = _stack()
        frame = _Open(self.name)
        frame.parent = stack[-1].name if stack else None
        frame.annotation = jax.profiler.TraceAnnotation(self.name,
                                                        **self.meta)
        stack.append(frame)
        frame.annotation.__enter__()
        frame.start = time.perf_counter_ns()

    def __exit__(self, *exc) -> None:
        end = time.perf_counter_ns()
        frame = _stack().pop()
        frame.annotation.__exit__(*exc)
        _append(self.name, Record(
            frame.start, end - frame.start, frame.parent, frame.compiles,
            _union_ns(frame.intervals) if frame.intervals else 0))


def count(name: str, value) -> None:
    """Append one value of the per-call counter ``name``."""
    _append(name, value)


def recent(name: str, n: int) -> list:
    """The last ``n`` records of ``name``, oldest first."""
    q = _records.get(name, ())
    if n < 1 or len(q) < n:
        raise LookupError(f"span/counter {name!r}: asked for the last {n} "
                          f"records, {len(q)} held")
    return list(q)[-n:]


def last(name: str):
    """The newest record of ``name``."""
    q = _records.get(name)
    if not q:
        raise LookupError(f"span/counter {name!r}: nothing recorded")
    return q[-1]


def _on_duration(event: str, secs: float, **_meta) -> None:
    if event not in COMPILE_EVENTS:
        return
    stack = getattr(_local, "stack", None)
    if not stack:
        return
    end = time.perf_counter_ns()
    interval = (end - int(secs * 1e9), end)
    for frame in stack:
        frame.intervals.append(interval)
        frame.compiles += event == EXECUTABLE_EVENT


jax.monitoring.register_event_duration_secs_listener(_on_duration)
