"""Device layer: the share of the traced seconds in which no operation ran
on the device, 1 - (union of device op intervals / window), from the
profiler trace (``chipbench/trace_reduce.py``)."""


def read(ctx):
    t = ctx["trace"]
    return 100.0 * t["idle_share"] if t else None
