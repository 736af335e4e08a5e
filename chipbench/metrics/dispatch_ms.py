"""Host fan-out layer (``distributed.spatial_shard.SpatialShards``): mean
wall time of the benchmark's ``chipbench.dispatch`` span, which encloses
each call the queue makes into the fleet for one coalesced batch, over the
calls that started in the window."""


def read(ctx):
    d = ctx["dispatch_s"]
    return 1e3 * sum(d) / len(d) if d else None
