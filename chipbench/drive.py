"""Drive the system under test: open and closed loops of requests into a
``ServeQueue``, and the benchmark's spans around the calls into the fleet.

Every request is one ``Sent`` record: its rows, when it was due (open loop)
or sent (closed loop), when it was sent and resolved on the host clock
(``time.perf_counter``), and its answer or error.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from typing import Any, Callable, List, Optional

import numpy as np

WAIT_AFTER_S = 60.0     # how long past the window's close answers may come


@dataclasses.dataclass(eq=False)
class Sent:
    rows: np.ndarray
    due: float                      # when it was due to be sent
    sent: float = float("nan")
    done: float = float("nan")      # when its future resolved
    answer: Any = None
    error: Optional[BaseException] = None

    @property
    def ok(self) -> bool:
        return self.done == self.done and self.error is None


def _annotate(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


class Spans:
    """The benchmark's span around each call into the fleet: wraps a bound
    method, opens a ``chipbench.dispatch`` trace annotation around it and
    keeps (start, end) on the host clock."""

    def __init__(self):
        self.records: List[tuple] = []

    def wrap(self, fn: Callable) -> Callable:
        def call(batch, *args, **kw):
            with _annotate("chipbench.dispatch"):
                t0 = time.perf_counter()
                out = fn(batch, *args, **kw)
                t1 = time.perf_counter()
            self.records.append((t0, t1))               # atomic under GIL
            return out
        return call

    def durations(self, t0: float, t1: float) -> List[float]:
        """Seconds of each call that started in [t0, t1]."""
        return [e - s for s, e in list(self.records) if t0 <= s <= t1]


class Overflows:
    """``Counters.overflow`` of every engine call the fleet makes: wraps the
    fleet's ``engine_for`` so that each engine it hands out keeps, with the
    call's end on the host clock, the flag as the device returned it.  The
    flags are read after the window, so the window pays no extra sync."""

    def __init__(self):
        self.records: List[tuple] = []
        self.engines: dict = {}         # partition -> the engine handed out

    def wrap(self, engine_for: Callable) -> Callable:
        def get(op, pi, *args, **kw):
            fn = engine_for(op, pi, *args, **kw)
            self.engines[pi] = fn

            def call(*a, **k):
                out = fn(*a, **k)
                self.records.append((time.perf_counter(), out[-1].overflow))
                return out
            return call
        return get

    def tiers(self) -> str:
        """For the log: each partition engine's escalations so far, and ``S``
        where it has pinned itself to its full tier (an escalating engine
        exposes ``escalation_count`` and ``stuck``; others show nothing)."""
        out = [f"p{pi}:{fn.escalation_count()}{'S' if fn.stuck() else ''}"
               for pi, fn in sorted(self.engines.items())
               if hasattr(fn, "stuck") and hasattr(fn, "escalation_count")]
        return " ".join(out) or "no escalating engine"

    def count(self, t0: float, t1: float) -> int:
        """Engine calls that ended in [t0, t1] with the flag set."""
        return sum(bool(np.asarray(f)) for t, f in list(self.records)
                   if t0 <= t <= t1)


def _submit(queue, req: Sent, then: Optional[Callable] = None) -> None:
    """Send ``req``; when its future resolves, record the answer and call
    ``then()`` (the closed loop's next request)."""
    def resolved(fut):
        req.done = time.perf_counter()
        try:
            req.answer = fut.result()
        except Exception as exc:            # an answer that failed
            req.error = exc
        if then is not None:
            then()

    with _annotate("chipbench.submit"):
        req.sent = time.perf_counter()
        try:
            fut = queue.submit(req.rows)
        except Exception as exc:            # refused at the door
            req.done, req.error = req.sent, exc
            return
    fut.add_done_callback(resolved)


def open_loop(queue, due: np.ndarray, rows: np.ndarray, t0: float
              ) -> List[Sent]:
    """Send request i at ``t0 + due[i]`` whatever the system's state, from
    the calling thread.  Returns the records, filled in as answers come."""
    reqs = [Sent(rows=r, due=t0 + d) for d, r in zip(due, rows)]
    for req in reqs:
        wait = req.due - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        _submit(queue, req)
    return reqs


class ClosedLoop:
    """``clients`` callers, each sending its next request when its last one
    resolves, until ``stop()``.  The next request is sent from the future's
    callback, so the loop needs no thread of its own."""

    def __init__(self, queue, make: Callable[[int], np.ndarray],
                 clients: int, limit: Optional[int] = None):
        self.queue, self.make, self.clients = queue, make, clients
        self.limit = limit              # requests to send at most
        self.sent: List[Sent] = []
        self._lock = threading.Lock()
        self._stopped = False

    def start(self) -> None:
        for _ in range(self.clients):
            self._next()

    def stop(self) -> None:
        with self._lock:
            self._stopped = True

    def _next(self) -> None:
        with self._lock:
            if self._stopped or len(self.sent) == self.limit:
                return
            i = len(self.sent)
            req = Sent(rows=self.make(i), due=float("nan"))
            self.sent.append(req)
        req.due = time.perf_counter()
        _submit(self.queue, req, then=self._next)


def wait(reqs: List[Sent], until: float) -> None:
    """Wait until every request has resolved or ``until`` has passed."""
    for req in reqs:
        while req.done != req.done and time.perf_counter() < until:
            time.sleep(0.005)
