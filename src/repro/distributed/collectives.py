"""Cross-shard collectives for the mesh-sharded query engine.

The deterministic cross-shard merge primitives the ``shard_map`` traversal
programs run (``core/traversal.make_mesh_engine``): an all-gather along the
partition axis, a (distance, id)-lexicographic top-k merge (the τ merge of
the two-phase kNN/kNN-join), and the partition/shard ``Counters`` folds that
keep ``dispatches`` at O(levels) rather than O(partitions × levels).
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core.counters import Counters


# ---------------------------------------------------------------------------
# Executable cross-shard merge primitives (consumed inside shard_map bodies)
# ---------------------------------------------------------------------------

def topk_by_distance(ids: jax.Array, d: jax.Array, k: int
                     ) -> Tuple[jax.Array, jax.Array]:
    """Deterministic (distance, id) top-k over the last axis.

    ids/d: (..., M) candidate streams (pad: id=-1, d=+inf).  The order is
    ascending lexicographic on (distance, id) — exactly the host merge's
    ``np.lexsort((ids, dists))`` — so the result is invariant under any
    permutation of the candidate axis, which is what makes the cross-shard
    merge independent of partition placement.
    """
    if d.shape[-1] < k:
        pad = k - d.shape[-1]
        d = jnp.concatenate(
            [d, jnp.full(d.shape[:-1] + (pad,), jnp.inf, d.dtype)], -1)
        ids = jnp.concatenate(
            [ids, jnp.full(ids.shape[:-1] + (pad,), -1, ids.dtype)], -1)
    order = jnp.lexsort((ids, d), axis=-1)[..., :k]
    return (jnp.take_along_axis(ids, order, -1),
            jnp.take_along_axis(d, order, -1))


def gather_partitions(x, axis_name: str):
    """All-gather a pytree along the partition mesh axis and fold the device
    dimension into the leading (local-partition) dimension: leaves
    (Pl, ...) → (P, ...) in global partition order (the leading axis is
    sharded contiguously, so device-major concatenation is id order)."""
    def one(a):
        g = jax.lax.all_gather(a, axis_name, axis=0)        # (D, Pl, ...)
        return g.reshape((-1,) + g.shape[2:])
    return jax.tree_util.tree_map(one, x)


_SUM_MAX_FIELDS = ("overflow", "dispatches")


def merge_stacked_counters(ctr: Counters) -> Counters:
    """Fold counters stacked over a local partition axis: work fields sum
    (total algorithmic work across partitions), ``overflow`` is sticky
    (max), and ``dispatches`` takes the max — the partitions execute as one
    vmapped stage sequence, so launches do not scale with partitions."""
    out = {}
    for f in dataclasses.fields(Counters):
        v = getattr(ctr, f.name)
        out[f.name] = (jnp.max(v, axis=0) if f.name in _SUM_MAX_FIELDS
                       else jnp.sum(v, axis=0))
    return Counters(**out)


def psum_counters(ctr: Counters, axis_name: str) -> Counters:
    """Cross-shard counter fold: work fields all-reduce (sum), while
    ``overflow``/``dispatches`` all-reduce with max (the shards run the same
    launch sequence — summing dispatches would misreport the SPMD program as
    O(partitions × levels))."""
    out = {}
    for f in dataclasses.fields(Counters):
        v = getattr(ctr, f.name)
        out[f.name] = (jax.lax.pmax(v, axis_name)
                       if f.name in _SUM_MAX_FIELDS
                       else jax.lax.psum(v, axis_name))
    return Counters(**out)
