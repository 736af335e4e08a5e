"""Attribute the device's idle time to the service's own spans.

The program opens a ``jax.profiler.TraceAnnotation`` for each of its spans
(``repro.runtime.trace``), so a profiler trace holds them on the host
threads that ran them, on the device ops' clock.  Each idle instant of the
traced window (``trace_reduce.WINDOW``) is labelled by the innermost
``repro.*`` span open on each host thread that has one; the labels, less
their ``repro.`` prefix, are sorted and joined with ``|`` (for example
``fleet.readback|queue.slot_wait``).  An idle instant with no such span open
on any thread is unattributed.  A trace with no ``repro.*`` span at all
(a program that has none) attributes nothing and reads ``None``.
"""
from __future__ import annotations

import collections
from typing import Dict, List, Optional, Sequence, Tuple

from chipbench import trace_reduce

PREFIX = "repro."

Event = Tuple[str, float, float]            # name, start s, end s


def read_threads(path: str) -> Dict[str, List[Event]]:
    """The ``repro.*`` events of each host thread of an ``.xplane.pb``, in
    seconds on the trace's clock, keyed ``<plane>/<line index>:<name>``."""
    from jax.profiler import ProfileData

    out: Dict[str, List[Event]] = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            evs = [(ev.name, ev.start_ns * 1e-9,
                    (ev.start_ns + ev.duration_ns) * 1e-9)
                   for ev in line.events if ev.name.startswith(PREFIX)]
            if evs:
                out[f"{plane.name}/{i}:{line.name}"] = evs
    return out


def innermost(events: Sequence[Event]) -> List[Event]:
    """One thread's spans as disjoint sorted segments, each named after the
    innermost span open in it (the latest started)."""
    cuts = sorted({t for _, s, e in events for t in (s, e)})
    starts = sorted(events, key=lambda ev: ev[1])
    out: List[Event] = []
    active: List[Event] = []
    i = 0
    for a, b in zip(cuts, cuts[1:]):
        while i < len(starts) and starts[i][1] <= a:
            active.append(starts[i])
            i += 1
        active = [ev for ev in active if ev[2] > a]
        if not active:
            continue
        name = max(active, key=lambda ev: ev[1])[0][len(PREFIX):]
        if out and out[-1][0] == name and out[-1][2] == a:
            out[-1] = (name, out[-1][1], b)
        else:
            out.append((name, a, b))
    return out


def attribute(idle: Sequence[Tuple[float, float]],
              threads: Dict[str, List[Event]]
              ) -> Tuple[collections.Counter, float]:
    """(seconds of ``idle`` per label, seconds with no span open) for one
    device's disjoint sorted idle intervals."""
    segs = [innermost(evs) for evs in threads.values()]
    cuts = sorted({t for s, e in idle for t in (s, e)}
                  | {t for seg in segs for _, s, e in seg for t in (s, e)})
    ptr = [0] * len(segs)
    by_label: collections.Counter = collections.Counter()
    unattributed, gi = 0.0, 0
    for a, b in zip(cuts, cuts[1:]):
        mid = (a + b) / 2
        while gi < len(idle) and idle[gi][1] <= mid:
            gi += 1
        if gi == len(idle):
            break
        if idle[gi][0] > mid:
            continue
        labels = set()
        for j, seg in enumerate(segs):
            while ptr[j] < len(seg) and seg[ptr[j]][2] <= mid:
                ptr[j] += 1
            if ptr[j] < len(seg) and seg[ptr[j]][1] <= mid:
                labels.add(seg[ptr[j]][0])
        if labels:
            by_label["|".join(sorted(labels))] += b - a
        else:
            unattributed += b - a
    return by_label, unattributed


def reduce_events(device_ops: Dict[str, List[Event]],
                  spans: Dict[str, List[Tuple[float, float]]],
                  threads: Dict[str, List[Event]]) -> Optional[Dict]:
    """``idle_by_span`` (the ``TOP`` labels by idle seconds),
    ``idle_unattributed_s`` and ``idle_s``, summed over the devices that
    ran anything in the window; None without a window or a device op.
    With no ``repro.*`` span in the trace the two attributions read
    ``[]`` and ``None``."""
    if not spans.get(trace_reduce.WINDOW):
        return None
    t0, t1 = spans[trace_reduce.WINDOW][0]
    by_label: collections.Counter = collections.Counter()
    idle_s = unattributed = 0.0
    ran = False
    for ops in device_ops.values():
        busy = trace_reduce.union(((s, e) for _, s, e in ops), t0, t1)
        if not busy:
            continue
        ran = True
        idle = trace_reduce.gaps(busy, t0, t1)
        idle_s += sum(e - s for s, e in idle)
        labels, none = attribute(idle, threads)
        by_label.update(labels)
        unattributed += none
    if not ran:
        return None
    return {"idle_s": idle_s,
            "idle_by_span": [[n, t] for n, t in
                             by_label.most_common(trace_reduce.TOP)],
            "idle_unattributed_s": unattributed if threads else None}
