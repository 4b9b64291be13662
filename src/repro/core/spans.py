"""Program spans: where a query's time goes, on the profiler's clock.

A :class:`span` marks one layer boundary of the query path
(``grid.plan``, ``blockstore.fetch``, ``fold.dispatch``, ...).  Each span
does three things at once, with no switch:

1. it enters a :class:`jax.profiler.TraceAnnotation` (a TraceMe event)
   carrying the active query's ``qid``, so a traced run holds the program's
   host spans in the same ``.xplane.pb`` as the device planes — and with
   no trace active, records nothing there;
2. it adds its duration, and its self time (duration minus its child
   spans on the same thread), to the active query's :class:`QueryTrace`;
3. it adds its count and seconds to a process-wide total per span name
   (:func:`totals`).

The active record is thread-local: :class:`~repro.core.frontend
.GridFrontend` activates each query's record on the worker that executes
it, and :class:`~repro.core.grid.GridSession` creates one for a direct
call.  Timestamps are ``time.time_ns()``: the wall clock that the
profiler stamps host events with (its trace's ``profile_start_time`` is on
the same clock), so a record's span starts line up with the trace's.

One process-wide listener on JAX's backend-compile event adds each
executable build to the record and the innermost span open on the
compiling thread, so a compile is named by the step that paid for it.
"""

from __future__ import annotations

import dataclasses
import itertools
import threading
import time
from typing import Dict, List, Optional

import jax
from jax.profiler import TraceAnnotation

#: JAX's event around every executable build (``jax._src.dispatch``)
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_qids = itertools.count(1)
_tls = threading.local()
_totals: Dict[str, "SpanStat"] = {}
_totals_lock = threading.Lock()


@dataclasses.dataclass
class SpanStat:
    """Time under one span name: count, seconds, self seconds, compiles,
    and the first start and last end (ns, profiler clock)."""

    count: int = 0
    total_s: float = 0.0
    self_s: float = 0.0
    compiles: int = 0
    start_ns: int = 0
    end_ns: int = 0

    def add(self, start: int, end: int, child_ns: int, compiles: int) -> None:
        if not self.count:
            self.start_ns = start
        self.count += 1
        self.total_s += (end - start) / 1e9
        self.self_s += (end - start - child_ns) / 1e9
        self.compiles += compiles
        self.end_ns = end


@dataclasses.dataclass(eq=False)
class QueryTrace:
    """One execution's timing record (``RunReport.trace``).

    ``queue_s`` runs from admission to the moment the execution holds the
    frontend's read lock; ``device_s`` from the end of the last dispatch
    (the merge enqueued) to the results being ready on the device, stamped
    by the frontend's watcher.  Both stay ``None`` where nothing measured
    them (a direct session call has no queue and no watcher)."""

    qid: int = dataclasses.field(default_factory=lambda: next(_qids))
    submit_ns: int = dataclasses.field(default_factory=time.time_ns)
    queue_s: Optional[float] = None
    device_s: Optional[float] = None
    compiles: int = 0
    compile_s: float = 0.0
    spans: Dict[str, SpanStat] = dataclasses.field(default_factory=dict)

    def self_s(self, name: str) -> float:
        st = self.spans.get(name)
        return 0.0 if st is None else st.self_s

    def total_s(self, name: str) -> float:
        st = self.spans.get(name)
        return 0.0 if st is None else st.total_s

    def mark_running(self, now_ns: int) -> None:
        """The execution holds its lock: the queue wait ends (once)."""
        if self.queue_s is None:
            self.queue_s = (now_ns - self.submit_ns) / 1e9

    def mark_ready(self, now_ns: int) -> None:
        """The results are ready on the device."""
        last = self.spans.get("merge.dispatch") or self.spans.get(
            "grid.execute")
        if last is not None:
            self.device_s = max(0, now_ns - last.end_ns) / 1e9


def current() -> Optional[QueryTrace]:
    """The record active on this thread, if any."""
    return getattr(_tls, "trace", None)


class active:
    """Make ``trace`` this thread's active record for a ``with`` block."""

    __slots__ = ("trace", "_prev")

    def __init__(self, trace: QueryTrace):
        self.trace = trace

    def __enter__(self) -> QueryTrace:
        self._prev = getattr(_tls, "trace", None)
        _tls.trace = self.trace
        return self.trace

    def __exit__(self, *exc) -> None:
        _tls.trace = self._prev


def _stack() -> List["span"]:
    st = getattr(_tls, "stack", None)
    if st is None:
        st = _tls.stack = []
    return st


class span:
    """One timed layer boundary (see the module doc); ``meta`` goes into
    the TraceMe event beside the active record's ``qid``."""

    __slots__ = ("name", "meta", "_trace", "_tm", "_start", "_child",
                 "_compiles")

    def __init__(self, name: str, **meta):
        self.name = name
        self.meta = meta

    def __enter__(self) -> "span":
        trace = self._trace = getattr(_tls, "trace", None)
        if trace is not None:
            self._tm = TraceAnnotation(self.name, qid=trace.qid, **self.meta)
        else:
            self._tm = TraceAnnotation(self.name, **self.meta)
        self._tm.__enter__()
        _stack().append(self)
        self._child = 0
        self._compiles = 0
        self._start = time.time_ns()
        return self

    def __exit__(self, *exc) -> None:
        end = time.time_ns()
        stack = _tls.stack
        stack.pop()
        if stack:
            stack[-1]._child += end - self._start
        self._tm.__exit__(None, None, None)
        if self._trace is not None:
            st = self._trace.spans.get(self.name)
            if st is None:
                st = self._trace.spans[self.name] = SpanStat()
            st.add(self._start, end, self._child, self._compiles)
        with _totals_lock:
            st = _totals.get(self.name)
            if st is None:
                st = _totals[self.name] = SpanStat()
            st.add(self._start, end, self._child, self._compiles)


def totals() -> Dict[str, SpanStat]:
    """Process-wide time per span name (a detached copy)."""
    with _totals_lock:
        return {k: dataclasses.replace(v) for k, v in _totals.items()}


def _on_duration(event: str, duration: float, **_kw) -> None:
    if event != BACKEND_COMPILE_EVENT:
        return
    stack = getattr(_tls, "stack", None)
    if stack:
        stack[-1]._compiles += 1
    trace = getattr(_tls, "trace", None)
    if trace is not None:
        trace.compiles += 1
        trace.compile_s += duration


jax.monitoring.register_event_duration_secs_listener(_on_duration)
