"""Resumable distance browsing — incremental kNN à la Hjaltason–Samet,
batched over the SIMD-ified R-tree.

Instead of answering a fixed k, a browse session emits neighbors k at a
time in global distance order: ``next_batch()`` returns the next k nearest
and can be called until the tree is exhausted.  The traversal state — the
scored-candidate pool, the per-level τ-deferred node beams, the lost bound,
and the accumulated counters — lives in a ``traversal.BrowseState`` pytree,
so a session checkpoints/restores with ``jax.tree_util`` and *resumes* the
level-synchronous descent without restarting from the root: a resume
re-activates only the deferred nodes whose MINDIST clears the current pool
bound.

This operator is the extensibility proof of the spec-driven engine: it is a
new ``OperatorSpec`` (this module) plus the ``resume`` entry point on the
engine (traversal.make_browse_engine) — the score stage is *reused* from
the fixed-k kNN spec (knn_vector.make_knn_score) and no new BFS loop
exists anywhere.

Prefix consistency: the first k emitted neighbors equal ``make_knn_bfs(k)``
for every k (up to distance ties), as long as no bounded beam was forced to
drop a candidate that later emission reached (``overflow`` reports exactly
that, per query) — the hypothesis property in tests/test_properties.py.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import numpy as np

from . import caps as caps_policy
from . import layouts
from . import traversal
from .counters import Counters, StageModel
from .knn_vector import make_knn_score
from .rtree import RTree


class BrowseCursor:
    """One browsing session over a batch of query points.

    ``next_batch()`` → (ids (B, k), sq-dists (B, k)) — the next k nearest
    per query in global distance order ((-1, +inf) once exhausted).  A
    descent is only run when the pool cannot provably serve the next batch
    (some deferred subtree could still beat a pooled candidate); otherwise
    emission is a pool slice.

    ``state`` is the full traversal state as a pytree; assigning a
    round-tripped (flattened/unflattened, restored, device-moved) state
    back resumes the session exactly.
    """

    def __init__(self, engine, ctx, state):
        self._engine = engine
        self._ctx = ctx
        self.state = state

    def next_batch(self) -> Tuple[np.ndarray, np.ndarray]:
        if self._engine.needs_descent(self.state):
            self.state = self._engine.resume(self._ctx, self.state)
        ids, d, self.state = self._engine.emit(self.state)
        return np.asarray(ids), np.asarray(d)

    @property
    def counters(self) -> Counters:
        return self.state.ctr

    @property
    def overflow(self) -> np.ndarray:
        """(B,) bool: emission crossed the lost bound — results from that
        row may be approximate-with-bound."""
        return np.asarray(self.state.overflow)


def make_browse_bfs(tree: RTree, k: int, layout: str = "d1",
                    caps: Optional[Sequence[int]] = None,
                    defer_caps: Optional[Sequence[int]] = None,
                    pool_cap: Optional[int] = None,
                    backend: Optional[str] = None):
    """Build the browsing engine for ``tree``: returns ``start(points)`` →
    ``BrowseCursor`` emitting ``k`` neighbors per ``next_batch()``.

    One build compiles once and serves any number of sessions/batches of
    the same query-batch shape.  ``caps``/``defer_caps``/``pool_cap``
    default to the unified browse policy (core/caps.py); ``layout`` /
    ``backend`` route the score stage exactly as in ``make_knn_bfs``.
    """
    if k <= 0:
        raise ValueError("k must be positive")
    ctx, score = make_knn_score(tree, layout, backend)
    d_caps, d_defer, d_pool = caps_policy.browse_caps(
        tree, k, lanes=layouts.layout_lanes(layout))
    caps = tuple(caps) if caps is not None else d_caps
    defer_caps = tuple(defer_caps) if defer_caps is not None else d_defer
    pool_cap = pool_cap if pool_cap is not None else d_pool
    if len(caps) != tree.height - 1:
        raise ValueError(f"need {tree.height - 1} caps, got {len(caps)}")

    engine = traversal.make_browse_engine(
        BROWSE_SPEC, height=tree.height, batch_k=k, caps=caps,
        defer_caps=defer_caps, pool_cap=pool_cap, score=score)

    def start(points) -> BrowseCursor:
        return BrowseCursor(engine, ctx, engine.init(points))

    return start


def browse_knn(tree: RTree, points, k: int, **kwargs) -> BrowseCursor:
    """Convenience: open one browsing session over ``points`` (B, 2),
    emitting ``k`` neighbors per ``next_batch()``.  ``kwargs`` as in
    ``make_browse_bfs``."""
    return make_browse_bfs(tree, k, **kwargs)(points)


# ---------------------------------------------------------------------------
# Distributed browsing — per-partition cursors + cross-shard pool merge
# ---------------------------------------------------------------------------

class ShardedBrowseCursor:
    """One distributed browsing session over a partitioned index fleet.

    The traversal state is a *stacked* ``BrowseState`` pytree — one
    per-partition cursor per row, sharded along the mesh partition axis —
    so the whole fleet's browsing state transfers/checkpoints exactly like
    the single-tree state.  ``next_batch()`` runs ONE ``shard_map`` program:
    each shard resumes its local cursors until their pools can provably
    serve ``k`` (a traced while-loop — no host round-trips), the per-
    partition pool heads are merged across shards by (distance, global id),
    and exactly the globally selected entries are popped from their home
    pools.  The emitted stream is therefore the same global distance order
    the single-tree cursor produces.
    """

    def __init__(self, step, states):
        self._step = step
        self.states = states

    def next_batch(self) -> Tuple[np.ndarray, np.ndarray]:
        ids, d, self.states = self._step(self.states)
        return np.asarray(ids), np.asarray(d)

    @property
    def overflow(self) -> np.ndarray:
        """(B,) bool: some emitted neighbor crossed a partition's lost
        bound — that row may be approximate-with-bound."""
        return np.asarray(self.states.overflow).any(axis=0)

    @property
    def descents(self) -> int:
        """Total resume descents across the fleet (work accounting)."""
        return int(np.asarray(self.states.descents).sum())


def make_sharded_browse(stacked_tree, ids_map, k: int, *, mesh,
                        axis: str = "model", layout: str = "d1",
                        backend: Optional[str] = None):
    """Build the distributed browsing engine over a packed forest.

    ``stacked_tree``/``ids_map`` come from ``distributed/forest.py``: an
    RTree pytree with a leading (P,) partition axis and the local→global id
    map.  Returns ``start(points)`` → :class:`ShardedBrowseCursor`.  Each
    ``next_batch()`` is one SPMD program; the per-partition engines are the
    ordinary browse spec instantiated under vmap — no second traversal loop
    exists for the distributed mode.
    """
    import dataclasses

    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.core.geometry import DIST_PAD, DIST_VALID_MAX
    from repro.distributed import collectives as coll

    if k <= 0:
        raise ValueError("k must be positive")
    p_total = ids_map.shape[0]
    n_dev = mesh.shape[axis]
    if p_total % n_dev:
        raise ValueError(f"partition count {p_total} not a multiple of the "
                         f"mesh axis {axis!r} size {n_dev}")

    def _engine_for(tree):
        ctx, score = make_knn_score(tree, layout, backend)
        d_caps, d_defer, d_pool = caps_policy.browse_caps(
            tree, k, lanes=layouts.layout_lanes(layout))
        eng = traversal.make_browse_engine(
            BROWSE_SPEC, height=tree.height, batch_k=k, caps=d_caps,
            defer_caps=d_defer, pool_cap=d_pool, score=score)
        return ctx, eng

    def _init_body(tree_blk, points):
        def one(tree):
            _, eng = _engine_for(tree)
            return eng.init(points)
        return jax.vmap(one)(tree_blk)

    def _step_body(tree_blk, idmap_blk, states):
        def one(tree, idmap, st):
            ctx, eng = _engine_for(tree)
            # resume until the local pool can provably serve k — the global
            # k-th is never better than the local k-th, so a locally
            # serveable pool is globally serveable
            st = jax.lax.while_loop(eng.needs_descent_fn,
                                    lambda s: eng.resume(ctx, s), st)
            cl = st.pool_ids[:, :k]
            cd = st.pool_d[:, :k]
            cg = jnp.where(cl >= 0,
                           idmap[jnp.maximum(cl, 0)].astype(jnp.int32), -1)
            cd = jnp.where(cd < DIST_VALID_MAX, cd, jnp.inf)
            return st, cg, cd, st.lost

        states, cg, cd, lost = jax.vmap(one)(tree_blk, idmap_blk, states)
        b = cg.shape[1]
        g_ids, g_d = coll.gather_partitions((cg, cd), axis)      # (P, B, k)
        sel_ids, sel_d = coll.topk_by_distance(
            g_ids.transpose(1, 0, 2).reshape(b, -1),
            g_d.transpose(1, 0, 2).reshape(b, -1), k)
        # selection threshold: the k-th pick under (distance, id) order —
        # a local candidate is popped iff it is lexicographically ≤ it
        thr_d = sel_d[:, k - 1][None, :, None]
        thr_i = sel_ids[:, k - 1][None, :, None]
        le = (cd < thr_d) | ((cd == thr_d) & (cg <= thr_i))      # (Pl, B, k)
        finite = jnp.isfinite(cd)
        n_emit = (le & finite).sum(-1).astype(jnp.int32)
        crossed = (le & finite & (cd >= lost[:, :, None])).any(-1)
        crossed_g = jax.lax.pmax(crossed.any(axis=0).astype(jnp.int32),
                                 axis) > 0                       # (B,)

        def pop(st, sel, ne):
            # drop EXACTLY the globally selected positions — with distance
            # ties the (d, id)-selected entries need not be a positional
            # prefix of the distance-sorted pool, and a prefix pop would
            # re-emit an unselected tie while losing a selected one
            pc = st.pool_d.shape[1]
            b = sel.shape[0]
            drop = jnp.concatenate(
                [sel, jnp.zeros((b, pc - sel.shape[1]), bool)], axis=1)
            pd = jnp.where(drop, DIST_PAD, st.pool_d)
            pi = jnp.where(drop, -1, st.pool_ids)
            neg, pos = jax.lax.top_k(-pd, pc)
            pd = -neg
            pi = jnp.take_along_axis(pi, pos, axis=1)
            pi = jnp.where(pd < DIST_VALID_MAX, pi, -1)
            pd = jnp.where(pd < DIST_VALID_MAX, pd, DIST_PAD)
            return dataclasses.replace(st, pool_ids=pi, pool_d=pd,
                                       emitted=st.emitted + ne)

        states = jax.vmap(pop)(states, le, n_emit)
        ctr = dataclasses.replace(
            states.ctr, overflow=states.ctr.overflow
            | crossed_g.any().astype(jnp.int32))
        states = dataclasses.replace(
            states, overflow=states.overflow | crossed_g[None, :], ctr=ctr)
        return sel_ids, sel_d, states

    init_prog = jax.jit(jax.shard_map(
        _init_body, mesh=mesh, in_specs=(P(axis), P()), out_specs=P(axis),
        check_vma=False))
    step_prog = jax.jit(jax.shard_map(
        _step_body, mesh=mesh, in_specs=(P(axis), P(axis), P(axis)),
        out_specs=(P(), P(), P(axis)), check_vma=False))

    def start(points) -> ShardedBrowseCursor:
        states = init_prog(stacked_tree, jnp.asarray(points))
        step = lambda st: step_prog(stacked_tree, jnp.asarray(ids_map), st)
        return ShardedBrowseCursor(step, states)

    return start


# Stage model per resume descent: every internal level runs the score
# kernel, the τ top-k, and three bounded beam merges (deferred inject,
# frontier keep, reject stash) at 2 launches each (top-k + gather) → 8;
# the leaf runs score + the pool beam merge → 3.  No fused generation yet
# (the in-kernel beam lowering would mirror the kNN fused path).
BROWSE_SPEC = traversal.register(traversal.OperatorSpec(
    name="browse", kind="distance",
    stage_model=StageModel(inner=8, leaf=3, fused=None),
    builder=make_browse_bfs, caps_policy=caps_policy.browse_caps,
    query_width=2,
    description="resumable distance browsing: incremental kNN whose "
                "frontier/τ/pool state round-trips through a pytree"))
