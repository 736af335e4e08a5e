"""Spans and counters of the query service, on the profiler's clock.

Every span opens a ``jax.profiler.TraceAnnotation`` of its own name, so a
profiler trace shows it on a host thread beside the device ops it waits
for.  On the host clock (``time.perf_counter``) it also leaves a
``Record``: name, start, end, thread, the id of its parent span and the
ids of the work it belongs to (the coalesced batch, where there is one).
Per-name aggregates are kept as records arrive: count, total seconds, and
self seconds (the duration less the part of it that child spans cover).
Every name starts with ``repro.`` so that a trace reduction can pick the
service's spans out of everything else on the host::

    with trace.tag(batch=trace.new_id()):   # ids of every span inside
        with trace.span("repro.fleet.knn"):
            ...
    trace.add("repro.fleet.partition_calls")          # a counter
    trace.add_time("repro.queue.wait", seconds)       # a duration, no span
    trace.snapshot()                                  # aggregates, counters

The open span and the tagged ids live in ``contextvars``: work handed to
another thread keeps them when the hand-off runs it in a copy of the
caller's context, as ``runtime/straggler.ShardPool`` does.  Recording is
always on; a span costs a few microseconds of host time.  Records go to a
ring of ``CAPACITY``; ``dropped`` counts those pushed out of it.
"""
from __future__ import annotations

import collections
import contextlib
import contextvars
import functools
import itertools
import threading
import time
from typing import Any, Dict, List, Mapping, NamedTuple, Optional

import jax

CAPACITY = 65536

_IDS: contextvars.ContextVar = contextvars.ContextVar("repro_trace_ids",
                                                      default={})


class Record(NamedTuple):
    name: str
    start: float                # time.perf_counter() seconds
    end: float
    thread: str
    span: int                   # this span's id
    parent: Optional[int]       # the enclosing span's id, or None
    ids: Mapping[str, Any]      # e.g. {"batch": 12}


class _Open:
    """A span while it runs: its id, start, and the intervals of the child
    spans that closed inside it (appended from any thread)."""
    __slots__ = ("id", "start", "children")

    def __init__(self, sid: int, start: float):
        self.id, self.start, self.children = sid, start, []


def _covered(intervals, start: float, end: float) -> float:
    """Seconds of [start, end] covered by the union of ``intervals``."""
    total, reach = 0.0, start
    for s, e in sorted(intervals):
        s, e = max(s, reach), min(e, end)
        if e > s:
            total += e - s
            reach = e
    return total


class _Span:
    __slots__ = ("tracer", "name", "ids", "open", "parent", "token",
                 "annotation")

    def __init__(self, tracer: "Tracer", name: str, ids: Mapping):
        self.tracer, self.name, self.ids = tracer, name, ids

    def __enter__(self):
        t = self.tracer
        self.parent = t._current.get()
        self.annotation = jax.profiler.TraceAnnotation(self.name)
        self.annotation.__enter__()
        self.open = _Open(next(t._ids), time.perf_counter())
        self.token = t._current.set(self.open)
        return self

    def __exit__(self, *exc):
        end = time.perf_counter()
        t, me = self.tracer, self.open
        t._current.reset(self.token)
        self.annotation.__exit__(*exc)
        parent = self.parent
        if parent is not None:
            parent.children.append((me.start, end))
        ids = _IDS.get()
        if self.ids:
            ids = {**ids, **self.ids}
        child_s = _covered(me.children, me.start, end) if me.children \
            else 0.0
        t._record(Record(self.name, me.start, end,
                         threading.current_thread().name, me.id,
                         None if parent is None else parent.id, ids),
                  end - me.start - child_s)
        return False


class Tracer:
    """The store behind the module's functions; a test may make its own."""

    def __init__(self, capacity: int = CAPACITY):
        self._lock = threading.Lock()
        self._records: collections.deque = collections.deque(
            maxlen=capacity)
        self._dropped = 0
        self._spans: Dict[str, List[float]] = {}    # count, total, self
        self._times: Dict[str, List[float]] = {}    # count, total
        self._counters: Dict[str, int] = collections.Counter()
        self._ids = itertools.count(1)
        self._current: contextvars.ContextVar = contextvars.ContextVar(
            f"repro_trace_span_{id(self)}", default=None)

    def new_id(self) -> int:
        """A process-unique id, for a batch or a request."""
        return next(self._ids)

    def span(self, name: str, **ids) -> _Span:
        """A context manager timing ``name``; ``ids`` add to the tagged
        ids for this span alone."""
        return _Span(self, name, ids)

    def spanned(self, name: str):
        """Decorator: run each call of the function inside ``span(name)``."""
        def deco(fn):
            @functools.wraps(fn)
            def call(*args, **kw):
                with self.span(name):
                    return fn(*args, **kw)
            return call
        return deco

    def add(self, name: str, n: int = 1) -> None:
        with self._lock:
            self._counters[name] += n

    def add_time(self, name: str, seconds: float) -> None:
        with self._lock:
            agg = self._times.setdefault(name, [0, 0.0])
            agg[0] += 1
            agg[1] += seconds

    def _record(self, rec: Record, self_s: float) -> None:
        with self._lock:
            if len(self._records) == self._records.maxlen:
                self._dropped += 1
            self._records.append(rec)
            agg = self._spans.setdefault(rec.name, [0, 0.0, 0.0])
            agg[0] += 1
            agg[1] += rec.end - rec.start
            agg[2] += self_s

    def snapshot(self) -> Dict[str, Any]:
        """A consistent copy of the aggregates and counters."""
        with self._lock:
            return {
                "spans": {n: {"count": a[0], "total_s": a[1],
                              "self_s": a[2]}
                          for n, a in self._spans.items()},
                "times": {n: {"count": a[0], "total_s": a[1]}
                          for n, a in self._times.items()},
                "counters": dict(self._counters),
                "dropped": self._dropped,
            }

    def records(self) -> List[Record]:
        """The records still in the ring, oldest first."""
        with self._lock:
            return list(self._records)


@contextlib.contextmanager
def tag(**ids):
    """Tag every span opened inside (in this context, and in contexts copied
    from it) with ``ids``, e.g. ``batch=7``."""
    token = _IDS.set({**_IDS.get(), **ids})
    try:
        yield
    finally:
        _IDS.reset(token)


TRACER = Tracer()
new_id = TRACER.new_id
span = TRACER.span
spanned = TRACER.spanned
add = TRACER.add
add_time = TRACER.add_time
snapshot = TRACER.snapshot
records = TRACER.records
