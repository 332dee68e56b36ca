"""Spans of the served path, on the profiler's clock (DESIGN.md §13, §15).

A span is a named interval of host time with the ids that join it to
its cause: ``req`` for a request, ``batch`` for a micro-batch (-1 when
the span has none). Every name starts with ``geek.``.

Recording is on exactly while a profiler session is live: a local
``jax.profiler.start_trace`` / ``jax.profiler.trace``, or an XProf
capture through ``jax.profiler.start_server``. There is no other
switch. With no session a span site costs one :func:`enabled` call and
reads no clock. In a session:

- :func:`span` also enters a ``jax.profiler.TraceAnnotation`` of the same
  name (with its ids as stats), so the span lands on the profiler's host
  plane beside the device ops;
- every span, and every :func:`interval` (a wait that starts on one
  thread and ends on another, which no ``with`` can bound and which is
  therefore kept here only), goes into a fixed-capacity ring of
  preallocated numpy arrays. Past its capacity the ring overwrites its
  oldest spans and counts them in :func:`dropped`.

Timestamps are ``time.time_ns()``: epoch nanoseconds, the clock of the
profiler's trace, whose events are offsets from the ``profile_start_time``
of its ``Task Environment`` plane. A span's offset in the trace is its
start less that time.

Read the record with :func:`records`; :func:`clear` empties it. The ring
is process-wide, as the profiler session that switches it is.
"""
from __future__ import annotations

import itertools
import threading
import time

import jax
import numpy as np
from jax._src.lib import _profiler

#: True exactly while a profiler session is live (private JAX API; the
#: tests pin its behaviour)
enabled = _profiler.TraceMe.is_enabled

#: spans the ring holds before it overwrites the oldest
CAPACITY = 1 << 17

#: ``span(..., req=NEW)``: give the span a fresh request id
NEW = -2

_ids = itertools.count()
_local = threading.local()


def new_id() -> int:
    """A process-unique id for a request or a micro-batch."""
    return next(_ids)


def current_req() -> int:
    """The ``req`` of the innermost open span on this thread, or -1."""
    return getattr(_local, "req", -1)


class _Ring:
    """Spans in preallocated arrays; slot ``count % capacity`` is next."""

    def __init__(self, capacity: int):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = int(capacity)
        self.name = np.zeros(self.capacity, np.int32)
        self.start = np.zeros(self.capacity, np.int64)
        self.end = np.zeros(self.capacity, np.int64)
        self.req = np.zeros(self.capacity, np.int64)
        self.batch = np.zeros(self.capacity, np.int64)
        self.count = 0

    def add(self, code: int, t0: int, t1: int, req: int, batch: int):
        i = self.count % self.capacity
        self.count += 1
        self.name[i] = code
        self.start[i] = t0
        self.end[i] = t1
        self.req[i] = req
        self.batch[i] = batch


_lock = threading.Lock()
_codes: dict[str, int] = {}
_ring = _Ring(CAPACITY)


def _code(name: str) -> int:
    code = _codes.get(name)
    if code is None:
        with _lock:
            code = _codes.setdefault(name, len(_codes))
    return code


def _add(name: str, t0: int, t1: int, req: int, batch: int) -> None:
    code = _code(name)
    with _lock:
        _ring.add(code, t0, t1, req, batch)


class _Span:
    """One open span; ``req`` is also this thread's current request
    while it is open, so that nested spans and ``submit`` inherit it."""

    __slots__ = ("name", "req", "batch", "t0", "prev", "ann")

    def __init__(self, name: str, req: int, batch: int):
        self.name = name
        self.req = req
        self.batch = batch

    def __enter__(self):
        self.prev = current_req()
        if self.req is None:
            self.req = self.prev
        elif self.req == NEW:
            self.req = new_id()
        ids = {k: v for k, v in (("req", self.req), ("batch", self.batch))
               if v >= 0}
        self.ann = jax.profiler.TraceAnnotation(self.name, **ids)
        self.ann.__enter__()
        _local.req = self.req
        self.t0 = time.time_ns()
        return self

    def __exit__(self, *exc):
        t1 = time.time_ns()
        _local.req = self.prev
        self.ann.__exit__(*exc)
        _add(self.name, self.t0, t1, self.req, self.batch)
        return False


class _Off:
    """The span of a site while no profiler session is live."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_OFF = _Off()


def span(name: str, req: int | None = None, batch: int = -1):
    """A context manager recording the block as span ``name``.

    ``req``: ``None`` inherits the enclosing span's request (or none),
    :data:`NEW` draws a fresh id, an int is that request. ``batch``: the
    micro-batch, or -1. Records nothing, and reads no clock, while no
    profiler session is live.
    """
    if not enabled():
        return _OFF
    return _Span(name, req, batch)


def interval(name: str, t0_ns: int, t1_ns: int, req: int = -1,
             batch: int = -1) -> None:
    """Record ``[t0_ns, t1_ns]`` (``time.time_ns()`` stamps) as span
    ``name``. Callers stamp times only while :func:`enabled` holds."""
    _add(name, int(t0_ns), int(t1_ns), int(req), int(batch))


def records(name: str) -> dict:
    """The ring's spans of ``name``, oldest first: numpy arrays
    ``start`` and ``end`` (ns) and ``req`` and ``batch`` (-1: none)."""
    with _lock:
        count, cap = _ring.count, _ring.capacity
        slots = np.arange(max(0, count - cap), count) % cap
        code = _codes.get(name)
        if code is not None:
            slots = slots[_ring.name[slots] == code]
        else:
            slots = slots[:0]
        return {k: getattr(_ring, k)[slots]
                for k in ("start", "end", "req", "batch")}


def dropped() -> int:
    """Spans overwritten since the last :func:`clear`."""
    with _lock:
        return max(0, _ring.count - _ring.capacity)


def clear(capacity: int = CAPACITY) -> None:
    """Forget every recorded span; the ring then holds ``capacity``."""
    global _ring
    with _lock:
        _ring = _Ring(capacity)
