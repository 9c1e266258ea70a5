"""Program spans of the serving path: a bounded in-memory log.

A span is one record ``(name, replica, rid, t_start_ns, t_end_ns)`` on the
``time.perf_counter_ns`` clock; ``replica`` is the worker node of the
replica that recorded it and ``rid`` the request it belongs to (-1 for
none).  Span names start with ``ham.`` (docs/serving.md, "Tracing", lists
them).  The log is a ring: past ``capacity`` records the oldest go, and
:attr:`SpanLog.dropped` counts them.

Spans are off by default.  Whoever records them (the worker decode loop,
the serving engine) then holds ``None`` instead of a log, and each span
site costs one ``is None`` test: no clock read and no annotation object.
When a log is present, every span opened with :meth:`SpanLog.span` also
opens a ``jax.profiler.TraceAnnotation`` of the same name, so that a
profiler running at the time records it beside the device's operations.
Spans recorded after the fact with :meth:`SpanLog.record` (a request's
wait, which crosses threads and loop iterations) are in the log only.

This module is jax-free at import time; jax is imported when a span opens.
"""

from __future__ import annotations

import contextlib
import threading
import time
from collections import deque

__all__ = ["SpanLog", "span"]

#: what a span site enters when spans are off (reusable, does nothing)
_NO_SPAN = contextlib.nullcontext()


def span(log: "SpanLog | None", name: str, replica: int, rid: int = -1):
    """The context a span site enters: a span of ``log``, or, when ``log``
    is None (spans off), a shared one that does nothing and yields None."""
    return _NO_SPAN if log is None else log.span(name, replica, rid)


class _Span:
    """One open span: times itself and holds a profiler annotation."""

    __slots__ = ("log", "name", "replica", "rid", "t0", "t1", "_ann")

    def __init__(self, log: "SpanLog", name: str, replica: int, rid: int):
        self.log, self.name, self.replica, self.rid = log, name, replica, rid

    def __enter__(self) -> "_Span":
        import jax

        self._ann = jax.profiler.TraceAnnotation(self.name)
        self._ann.__enter__()
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        self.t1 = time.perf_counter_ns()
        self._ann.__exit__(*exc)
        self.log.record(self.name, self.replica, self.rid, self.t0, self.t1)


class SpanLog:
    """A bounded ring of span records, shared by every thread that records
    into it."""

    def __init__(self, capacity: int = 1 << 16):
        if capacity < 1:
            raise ValueError(f"capacity must be at least 1, got {capacity}")
        self.capacity = int(capacity)
        self._ring: deque = deque(maxlen=self.capacity)
        self._lock = threading.Lock()
        #: records the ring let go of on overflow
        self.dropped = 0

    def span(self, name: str, replica: int, rid: int = -1) -> _Span:
        """A context manager that records ``name`` from entry to exit."""
        return _Span(self, name, replica, rid)

    def record(self, name: str, replica: int, rid: int, t_start_ns: int,
               t_end_ns: int) -> None:
        with self._lock:
            if len(self._ring) == self.capacity:
                self.dropped += 1
            self._ring.append((name, int(replica), int(rid), int(t_start_ns),
                               int(t_end_ns)))

    def records(self) -> list[tuple[str, int, int, int, int]]:
        """A copy of the records held, oldest first."""
        with self._lock:
            return list(self._ring)
