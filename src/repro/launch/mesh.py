"""Device meshes for the spatial query service.

``spatial_mesh`` lays the local devices out as the partition axis
(``model``) of the mesh-sharded engine, or as a ``(data, model)`` grid of
replicas; ``replica_meshes`` splits the devices into one 1-D mesh per
replica.  FUNCTIONS (not module-level constants) so importing this module
never touches jax device state.
"""
from __future__ import annotations

import jax


def spatial_mesh(n_devices=None, replicas: int = 1):
    """Mesh for the spatial query service.  ``replicas == 1``: the historical
    1-D mesh over the ``model`` axis (the partition fan-out axis of the
    mesh-sharded engine, distributed/spatial_shard.enable_mesh).
    ``replicas > 1``: a 2-D ``(data, model)`` grid — ``data`` is the replica
    fan-out axis (each row holds a full copy of the packed forest, see
    ``replica_meshes``), ``model`` the partition axis within a replica.
    Defaults to every local device; force a multi-device CPU with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (tests/CI)."""
    n = n_devices or len(jax.devices())
    if replicas <= 1:
        return jax.make_mesh((n,), ("model",))
    if n % replicas:
        raise ValueError(f"{n} devices do not divide into {replicas} "
                         f"replica groups")
    return jax.make_mesh((replicas, n // replicas), ("data", "model"))


def replica_meshes(replicas=None, n_devices=None, axis: str = "model"):
    """Split the local devices into ``replicas`` disjoint groups — the rows
    of the ``(data, model)`` grid of ``spatial_mesh(replicas=...)`` — and
    return one 1-D ``model`` mesh per group.  Each mesh is an independent
    engine target: the packed forest is replicated onto every group
    (distributed/forest.replicate_forest), so a deadline re-issue
    (runtime/straggler.ShardPool) lands on genuinely distinct devices and
    QPS scales with the data-axis size, not just partitions."""
    import numpy as np
    from jax.sharding import Mesh

    devs = jax.devices()
    n = n_devices or len(devs)
    r = replicas or 1
    if r > n:
        raise ValueError(f"{r} replicas need at least {r} devices, "
                         f"have {n}")
    if n % r:
        raise ValueError(f"{n} devices do not divide into {r} "
                         f"replica groups")
    per = n // r
    return [Mesh(np.asarray(devs[i * per:(i + 1) * per]), (axis,))
            for i in range(r)]
