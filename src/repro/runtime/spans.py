"""Program spans: named host-time intervals of the serving path's phases,
recorded where the work happens.

``span(name, rid=None)`` is a context manager.  Each span it closes is a
``Span(id, name, t0, t1, parent, rid)`` on ``time.perf_counter``: the
parent is the span open on the same thread when it was entered, and a
span given no ``rid`` takes its parent's, so every span of one request's
admission carries that request's id.  The same block runs inside
``jax.profiler.TraceAnnotation(name)``: while a profiler trace is taken,
every span is also an event on its thread's line of the trace, on the
clock the device operations are stamped with.

A span times host time only and never synchronizes with the device: a
span around a pull includes the wait for the device work the pull needs.

The recorder is always on.  It keeps the last ``CAPACITY`` closed spans
in a ring and, for every name, the count and summed seconds since the
process started.  ``recorded(t0, t1)`` returns the spans that start in
``[t0, t1)`` and whether the ring still holds all of them; ``totals()``
returns the per-name sums.
"""
from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import NamedTuple

from jax import profiler as _profiler

CAPACITY = 1 << 16


class Span(NamedTuple):
    id: int
    name: str
    t0: float                          # perf_counter at entry
    t1: float                          # perf_counter at exit
    parent: int | None                 # id of the enclosing span
    rid: int | None                    # request id, inherited from the parent


class Window(NamedTuple):
    spans: list[Span]                  # by start time
    complete: bool                     # False: the ring dropped some of them


class _Open:
    """One span while it is open; ``Recorder.span`` makes it."""
    __slots__ = ("_rec", "_name", "_rid", "_id", "_parent", "_ann", "_t0")

    def __init__(self, rec: "Recorder", name: str, rid: int | None):
        self._rec = rec
        self._name = name
        self._rid = rid

    def __enter__(self) -> "_Open":
        rec = self._rec
        try:
            stack = rec._local.stack
        except AttributeError:
            stack = rec._local.stack = []
        if stack:
            top = stack[-1]
            self._parent = top._id
            if self._rid is None:
                self._rid = top._rid
        else:
            self._parent = None
        self._id = next(rec._ids)
        stack.append(self)
        self._ann = _profiler.TraceAnnotation(self._name)
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        t1 = time.perf_counter()
        self._ann.__exit__(*exc)
        rec = self._rec
        rec._local.stack.pop()
        rec._close((self._id, self._name, self._t0, t1, self._parent,
                    self._rid))


class Recorder:
    """A bounded ring of closed spans plus cumulative per-name totals."""

    def __init__(self, capacity: int = CAPACITY):
        # plain tuples in ``Span``'s field order: a tuple is cheaper to make
        # than the named one, which ``recorded`` builds
        self._ring: collections.deque[tuple] = collections.deque(
            maxlen=capacity)
        self._totals: dict[str, list] = {}      # name -> [count, seconds]
        self._ids = itertools.count(1)
        self._local = threading.local()         # each thread's open spans
        self._lock = threading.Lock()           # threads close into one ring
        self._dropped_t0: float | None = None   # latest start ever dropped

    def span(self, name: str, rid: int | None = None) -> _Open:
        return _Open(self, name, rid)

    def _close(self, s: tuple) -> None:
        dt = s[3] - s[2]
        with self._lock:
            ring = self._ring
            if len(ring) == ring.maxlen:
                t = ring[0][2]
                if self._dropped_t0 is None or t > self._dropped_t0:
                    self._dropped_t0 = t
            ring.append(s)
            tot = self._totals.get(s[1])
            if tot is None:
                self._totals[s[1]] = [1, dt]
            else:
                tot[0] += 1
                tot[1] += dt

    def recorded(self, t0: float, t1: float) -> Window:
        """The closed spans that start in ``[t0, t1)``; ``complete`` is
        False when the ring has dropped a span that started at or after
        ``t0``."""
        with self._lock:
            ring, dropped_t0 = list(self._ring), self._dropped_t0
        spans = [Span._make(s) for s in ring if t0 <= s[2] < t1]
        spans.sort(key=lambda s: (s.t0, s.id))
        return Window(spans, dropped_t0 is None or dropped_t0 < t0)

    def totals(self) -> dict[str, tuple[int, float]]:
        """``{name: (count, seconds)}`` of every span closed so far."""
        with self._lock:
            return {k: (n, secs) for k, (n, secs) in self._totals.items()}


RECORDER = Recorder()


def span(name: str, rid: int | None = None) -> _Open:
    """Time the block as a span of ``name`` (see the module docstring)."""
    return RECORDER.span(name, rid)


def recorded(t0: float, t1: float) -> Window:
    return RECORDER.recorded(t0, t1)


def totals() -> dict[str, tuple[int, float]]:
    return RECORDER.totals()
