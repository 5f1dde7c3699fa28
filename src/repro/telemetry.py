"""Spans and counters inside the service, on the profiler's clock.

One tracing system for the whole process:

* :class:`span` -- a context manager around one piece of work.  It enters
  a ``jax.profiler.TraceAnnotation`` (so the span lands in the profiler's
  host plane, on the device trace's clock, whenever a trace is running)
  and, on exit, appends one :class:`Record` to an in-memory ring on
  ``time.perf_counter_ns()``.  Its parent is the innermost open span of
  the same thread.
* :func:`record` -- a span whose start and end were taken elsewhere: a
  wait that begins on one thread and ends on another (an admission
  ticket's wait ends on the queue leader's thread).
* :func:`event` -- a zero-length record carrying counts the host resolved,
  such as one step's repair statistics.
* :class:`request` -- marks the work of one client request: every record
  made on this thread inside the block carries its id (``req``).

There is no switch: with the profiler off a ``TraceAnnotation`` costs
about a microsecond, and so does the ring append.  The ring is bounded;
:func:`records` returns its contents with the total ever recorded, so a
reader can tell whether it dropped part of a window.  Records are
appended when they end (``record`` is called at its ``t1_ns``), so the
ring holds them in order of their end.
"""
from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import NamedTuple

from jax.profiler import TraceAnnotation

__all__ = ["Record", "RING_SIZE", "span", "record", "event", "request",
           "current_span", "current_request", "records"]

RING_SIZE = 1 << 18


class Record(NamedTuple):
    id: int          # > 0, unique in the process
    parent: int      # id of the enclosing span, 0 for none
    req: int         # id of the enclosing request, 0 for none
    name: str
    t0_ns: int       # time.perf_counter_ns()
    t1_ns: int
    thread: int      # threading.get_ident() of the recording thread
    attrs: dict


class _Local(threading.local):
    def __init__(self):
        self.stack = []
        self.req = 0


_local = _Local()
_ids = itertools.count(1)
_req_ids = itertools.count(1)
_ring: collections.deque = collections.deque(maxlen=RING_SIZE)
_ring_lock = threading.Lock()
_total = 0


def _append(rec: Record):
    global _total
    with _ring_lock:
        _ring.append(rec)
        _total += 1


def current_span() -> int:
    """Id of this thread's innermost open span (0 outside any span)."""
    st = _local.stack
    return st[-1] if st else 0


def current_request() -> int:
    """Id of this thread's current request (0 outside any request)."""
    return _local.req


class span:
    """``with span("layer.work", key=value):`` -- one record per use.

    ``attrs`` may still be filled in inside the block (``s.attrs[k] =
    v``); the profiler's copy carries only those given on entry."""

    __slots__ = ("name", "attrs", "id", "_ann", "_parent", "_t0")

    def __init__(self, name: str, **attrs):
        self.name = name
        self.attrs = attrs

    def __enter__(self) -> "span":
        self._ann = TraceAnnotation(self.name, **self.attrs)
        self._ann.__enter__()
        st = _local.stack
        self._parent = st[-1] if st else 0
        self.id = next(_ids)
        st.append(self.id)
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter_ns()
        _local.stack.pop()
        self._ann.__exit__(*exc)
        _append(Record(self.id, self._parent, _local.req, self.name,
                       self._t0, t1, threading.get_ident(), self.attrs))
        return False


def record(name: str, t0_ns: int, t1_ns: int, *, parent: int | None = None,
           req: int | None = None, **attrs) -> int:
    """Record a span timed elsewhere; ``parent`` and ``req`` default to
    the calling thread's.  Call it at ``t1_ns``.  Returns its id."""
    rid = next(_ids)
    _append(Record(rid, current_span() if parent is None else parent,
                   _local.req if req is None else req, name, int(t0_ns),
                   int(t1_ns), threading.get_ident(), attrs))
    return rid


def event(name: str, **attrs) -> int:
    """A zero-length record of counts known now.  Returns its id."""
    now = time.perf_counter_ns()
    return record(name, now, now, **attrs)


class request:
    """``with request():`` -- records made on this thread inside the
    block carry a fresh request id (restored on exit)."""

    __slots__ = ("id", "_prev")

    def __enter__(self) -> "request":
        self._prev = _local.req
        self.id = _local.req = next(_req_ids)
        return self

    def __exit__(self, *exc):
        _local.req = self._prev
        return False


def records() -> tuple:
    """``(list of Record, oldest first; total ever recorded)``.  When the
    total exceeds the list's length the ring dropped that many of the
    oldest records."""
    with _ring_lock:
        return list(_ring), _total
