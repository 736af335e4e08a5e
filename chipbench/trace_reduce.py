"""Reduce a JAX profiler trace (``*.xplane.pb``) to the device's busy and
idle time, the device ops that took the most time, and the longest idle
gaps, each named after the benchmark span the host had open in it.

The window is the host span ``WINDOW`` that the benchmark opens around the
traced seconds; device events are clipped to it.  A device is a plane
named ``/device:<kind>:<n>`` other than the host's; its ops are the events
of its ``XLA Ops`` line (or, where a backend has none, of every line but
``Steps`` and ``XLA Modules``).  Busy time is the union of those
intervals, so ops that overlap count once; ``busy_s`` is the mean over the
devices that ran anything.
"""
from __future__ import annotations

import collections
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW = "chipbench.traced"
HOST_SPANS = ("chipbench.dispatch", "chipbench.submit")
TOP = 10

Interval = Tuple[float, float]


def union(intervals: Iterable[Interval], t0: float, t1: float
          ) -> List[Interval]:
    """The union of ``intervals`` clipped to [t0, t1], as sorted disjoint
    intervals."""
    out: List[Interval] = []
    for s, e in sorted((max(s, t0), min(e, t1)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def gaps(busy: Sequence[Interval], t0: float, t1: float) -> List[Interval]:
    """The complement of disjoint sorted ``busy`` in [t0, t1]."""
    out, reach = [], t0
    for s, e in busy:
        if s > reach:
            out.append((reach, s))
        reach = max(reach, e)
    if t1 > reach:
        out.append((reach, t1))
    return out


def span_at(spans: Dict[str, List[Interval]], t: float) -> str:
    """The benchmark span open at time ``t`` (the first of ``HOST_SPANS``
    that is), else ``none``."""
    for name in HOST_SPANS:
        for s, e in spans.get(name, ()):
            if s <= t <= e:
                return name.split(".", 1)[1]
    return "none"


def reduce_events(device_ops: Dict[str, List[Tuple[str, float, float]]],
                  spans: Dict[str, List[Interval]]) -> Optional[Dict]:
    """``device_ops``: device → [(op name, start s, end s)]; ``spans``:
    host span name → [(start s, end s)] on the same clock.  Returns None
    when the trace holds no window or no device op in it."""
    if not spans.get(WINDOW):
        return None
    t0, t1 = spans[WINDOW][0]
    busy_by_dev, op_time, all_gaps = [], collections.Counter(), []
    for ops in device_ops.values():
        busy = union(((s, e) for _, s, e in ops), t0, t1)
        if not busy:
            continue
        busy_by_dev.append(sum(e - s for s, e in busy))
        for name, s, e in ops:
            if min(e, t1) > max(s, t0):
                op_time[name] += min(e, t1) - max(s, t0)
        all_gaps += gaps(busy, t0, t1)
    if not busy_by_dev:
        return None
    window = t1 - t0
    busy_s = sum(busy_by_dev) / len(busy_by_dev)
    longest = sorted(all_gaps, key=lambda g: g[0] - g[1])[:TOP]
    return {
        "busy_s": busy_s,
        "window_s": window,
        "idle_share": 1.0 - busy_s / window,
        "devices": len(busy_by_dev),
        "device_ops": [[n, t] for n, t in op_time.most_common(TOP)],
        "idle_gaps": [[span_at(spans, (s + e) / 2), e - s]
                      for s, e in longest],
    }


def _is_device(plane_name: str) -> bool:
    return plane_name.startswith("/device:") \
        and not plane_name.startswith("/device:CPU")


def read_xplane(path: str) -> Tuple[Dict, Dict]:
    """(device ops, host spans) from an ``.xplane.pb``, in seconds on the
    trace's own clock."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device_ops: Dict[str, List[Tuple[str, float, float]]] = {}
    spans: Dict[str, List[Interval]] = collections.defaultdict(list)
    wanted = (WINDOW,) + HOST_SPANS
    for plane in data.planes:
        lines = list(plane.lines)
        if _is_device(plane.name):
            ops_lines = [ln for ln in lines if ln.name == "XLA Ops"] or \
                [ln for ln in lines
                 if ln.name not in ("Steps", "XLA Modules")]
            device_ops[plane.name] = [
                (ev.name, ev.start_ns * 1e-9,
                 (ev.start_ns + ev.duration_ns) * 1e-9)
                for ln in ops_lines for ev in ln.events]
        elif plane.name.startswith("/host:"):
            for ln in lines:
                for ev in ln.events:
                    if ev.name in wanted:
                        spans[ev.name].append(
                            (ev.start_ns * 1e-9,
                             (ev.start_ns + ev.duration_ns) * 1e-9))
    return device_ops, dict(spans)


def reduce_xplane(path: str) -> Optional[Dict]:
    return reduce_events(*read_xplane(path))
