"""Queue layer (``launch.queue.ServeQueue``): query rows per coalesced
dispatch over the window, from the queue's ``summary`` counts read at the
window's two ends (the summary also counts the warm-up)."""


def read(ctx):
    q = ctx["queue"]
    return q["rows"] / q["batches"] if q.get("batches") else None
