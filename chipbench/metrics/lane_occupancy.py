"""Algorithm layer (the ``core`` engines): the share of frontier lanes that
held a live node, ``Counters.lanes_live / (lanes_live + lanes_padded)``,
summed over a seeded sample of the window's rows replayed in ``max_batch``
groups straight through ``SpatialShards`` after the window."""


def read(ctx):
    lanes = ctx["lanes"]
    if not lanes or not sum(lanes):
        return None
    live, padded = lanes
    return 100.0 * live / (live + padded)
