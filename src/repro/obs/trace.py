"""Zero-dependency span tracer for the staged pipeline.

Spans are context managers::

    with trace.span("plan", method="isd"):
        ...

Disabled by default: ``span()`` then returns a shared no-op context manager
whose enter/exit are empty slots-class methods, so instrumented call sites
cost one function call when tracing is off.  Hot loops (the wavefront
per-level loop) must not even pay that — they hoist ``tracing_enabled()``
once and call :func:`emit` with raw ``perf_counter_ns`` stamps only when it
was true.

Enabled spans record Chrome-trace *complete* events (``"ph": "X"``): wall
timestamps in microseconds, duration, pid/tid, plus the span's keyword args.
Nesting is tracked per thread through a ``threading.local`` stack — two
planner threads tracing concurrently interleave in the buffer but each
thread's own spans keep strict stack discipline (pinned by a test).  The
buffer is a bounded deque guarded by one lock; exceeding the bound drops the
*oldest* events, so a long serving run keeps its most recent waves.

Every event also records the thread's CPU time over it (``cpu_us``, from
``time.thread_time_ns``; ``dur - cpu_us`` is time the thread waited: for the
interpreter lock, a lock or the device) and, inside :class:`request`, the id
of the request its thread is serving (``req``).  When a profiler annotation
factory is installed (:func:`set_annotation`; the xla backend installs
``jax.profiler.TraceAnnotation``), each span also opens one, so a profiler
capture holds the program's spans on the device trace's clock.  Events
recorded after the fact (:func:`emit`) cannot be annotated and are not.
The ``trace.dropped`` counter counts the events the bounded buffer let go.

Everything here is stdlib-only on purpose: this module sits below
``repro.core.policy`` in the dependency stack and must never pull in
numpy/jax.
"""

from __future__ import annotations

import json
import os
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional

from . import metrics as _metrics

MAX_EVENTS = 65536

_events: deque = deque(maxlen=MAX_EVENTS)
_events_lock = threading.Lock()
_tls = threading.local()
_enabled = False
# the profiler annotation each span also opens: factory(name, **metadata)
# returning a context manager, or None
_annotate: Optional[Callable[..., Any]] = None
_DROPPED = _metrics.counter("trace.dropped")

# perf_counter_ns is monotonic but epoch-less; anchor ts=0 at import so
# exported traces start near zero instead of at machine uptime
_T0_NS = time.perf_counter_ns()


def enable() -> None:
    """Turn span recording on (global, all threads)."""

    global _enabled
    _enabled = True


def disable() -> None:
    global _enabled
    _enabled = False


def tracing_enabled() -> bool:
    return _enabled


def set_annotation(
    factory: Optional[Callable[..., Any]],
) -> Optional[Callable[..., Any]]:
    """Install the profiler annotation every span also opens while tracing
    is on (``None`` removes it); returns the factory it replaces."""

    global _annotate
    prev, _annotate = _annotate, factory
    return prev


class tracing:
    """``with trace.tracing():`` — enable within a block, restore on exit."""

    __slots__ = ("_prev",)

    def __enter__(self) -> "tracing":
        self._prev = _enabled
        enable()
        return self

    def __exit__(self, *exc) -> None:
        global _enabled
        _enabled = self._prev


class request:
    """``with trace.request(n):`` — every event this thread records inside
    carries ``req=n``, the request it belongs to."""

    __slots__ = ("_req", "_prev")

    def __init__(self, req: int) -> None:
        self._req = req

    def __enter__(self) -> "request":
        self._prev = getattr(_tls, "req", None)
        _tls.req = self._req
        return self

    def __exit__(self, *exc) -> None:
        _tls.req = self._prev


def _stack() -> List[str]:
    s = getattr(_tls, "stack", None)
    if s is None:
        s = _tls.stack = []
    return s


def _record(
    name: str,
    cat: str,
    t0_ns: int,
    t1_ns: int,
    cpu_ns: int,
    args: Dict[str, Any],
    stack: List[str],
) -> None:
    """Buffer one complete event; ``stack`` is its thread's open spans, the
    event itself not among them."""

    args = dict(
        args,
        depth=len(stack) + 1,
        parent=stack[-1] if stack else None,
        cpu_us=cpu_ns / 1000.0,
    )
    req = getattr(_tls, "req", None)
    if req is not None:
        args["req"] = req
    ev = {
        "name": name,
        "cat": cat,
        "ph": "X",
        "ts": (t0_ns - _T0_NS) / 1000.0,
        "dur": (t1_ns - t0_ns) / 1000.0,
        "pid": os.getpid(),
        "tid": threading.get_ident(),
        "args": args,
    }
    with _events_lock:
        full = len(_events) == _events.maxlen
        _events.append(ev)
    if full:
        _DROPPED.inc()


def emit(
    name: str,
    t0_ns: int,
    t1_ns: Optional[int] = None,
    cat: str = "repro",
    cpu_ns: int = 0,
    **args: Any,
) -> None:
    """Record one complete event from raw ``perf_counter_ns`` stamps.

    The low-level hook for hot loops that hoist the enabled check: caller
    guarantees tracing was enabled when the stamps were taken.  ``cpu_ns``
    is the thread's CPU time over the event, where the caller took it; 0
    records a wait.
    """

    if t1_ns is None:
        t1_ns = time.perf_counter_ns()
    _record(name, cat, t0_ns, t1_ns, cpu_ns, args, _stack())


class _Span:
    __slots__ = ("name", "cat", "args", "t0", "c0", "ann")

    def __init__(self, name: str, cat: str, args: Dict[str, Any]):
        self.name = name
        self.cat = cat
        self.args = args

    def note(self, **args: Any) -> None:
        """Add args known only by the span's end (counts of the work it
        covered)."""

        self.args.update(args)

    def __enter__(self) -> "_Span":
        _stack().append(self.name)
        factory = _annotate
        self.ann = None
        if factory is not None:
            req = getattr(_tls, "req", None)
            self.ann = (
                factory(self.name) if req is None
                else factory(self.name, req=req)
            )
            self.ann.__enter__()
        # the wall interval holds the CPU interval, so cpu_us <= dur
        self.t0 = time.perf_counter_ns()
        self.c0 = time.thread_time_ns()
        return self

    def __exit__(self, *exc) -> None:
        c1 = time.thread_time_ns()
        t1 = time.perf_counter_ns()
        if self.ann is not None:
            self.ann.__exit__(None, None, None)
        stack = _stack()
        stack.pop()
        _record(self.name, self.cat, self.t0, t1, c1 - self.c0, self.args,
                stack)


class _NullSpan:
    """Shared do-nothing context manager for the disabled path."""

    __slots__ = ()

    def note(self, **args: Any) -> None:
        pass

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> None:
        pass


_NULL = _NullSpan()


def span(name: str, cat: str = "repro", **args: Any):
    """A timed span context manager (no-op while tracing is disabled)."""

    if not _enabled:
        return _NULL
    return _Span(name, cat, args)


def events() -> List[dict]:
    """Snapshot of the buffered events, oldest first."""

    with _events_lock:
        return list(_events)


def clear() -> None:
    with _events_lock:
        _events.clear()


def to_chrome_trace() -> Dict[str, Any]:
    """The buffered spans in Chrome trace-event format (load in
    ``chrome://tracing`` / Perfetto)."""

    return {"traceEvents": events(), "displayTimeUnit": "ms"}


def trace_json(indent: Optional[int] = None) -> str:
    return json.dumps(to_chrome_trace(), indent=indent)
