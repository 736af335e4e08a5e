"""Host fan-out layer, the select's result path: mean wall time per
coalesced batch of the window in the fleet's ``repro.fleet.ids`` spans
(each partition call's local ids mapped to global ids, row by row) and its
``repro.fleet.merge`` span (each row's ids joined and sorted), from the
program's own trace records (``repro.runtime.trace.records()``).

The window's batches are the newest ``len(ctx["dispatch_s"])`` batch ids
that a ``repro.fleet.merge`` record carries: calls made straight into the
fleet (warm-up, the lane replay) carry none, and the queue's warm-up
batches are older.  Where the ring has dropped some of them, the mean is
over those still present.  None where no batch of the window is found, or
where the program has no ``repro.fleet.ids`` span."""

SPANS = ("repro.fleet.ids", "repro.fleet.merge")


def read(ctx, records=None):
    n = len(ctx["dispatch_s"])
    if records is None:
        try:
            from repro.runtime import trace
        except ImportError:
            return None
        records = trace.records()
    recs = [r for r in records
            if r.name in SPANS and r.ids.get("batch") is not None]
    batches = sorted({r.ids["batch"] for r in recs
                      if r.name == "repro.fleet.merge"})[-n:] if n else []
    window = set(batches)
    mine = [r for r in recs if r.ids["batch"] in window]
    if not any(r.name == "repro.fleet.ids" for r in mine):
        return None
    return 1e3 * sum(r.end - r.start for r in mine) / len(window)
