"""Distributed spatial query processing: partition the dataset spatially,
build one R-tree per partition, fan queries out, merge results.

Partitioning follows the STR idea one level up: sort by x into vertical
slabs, then by y within each slab — every partition is a contiguous spatial
tile holding ~N/P rects, so most range queries touch few partitions (the
partition MBRs act as a replicated, tiny "root router" level).

Two execution paths share one public API (``range_select`` / ``knn`` /
``knn_join`` / ``knn_filtered`` / ``join`` / ``browse``):

  host fallback — one compiled engine per partition (spec registry), a
      Python loop fanning routed query subsets out and merging with NumPy.
      One jit round-trip per touched partition per phase; kept as the
      reference semantics and for single-partition debugging.
  mesh path (``enable_mesh``) — the P partition trees are packed into ONE
      stacked pytree (distributed/forest.py) sharded over the mesh's
      ``model`` axis, and a whole query batch executes as ONE ``shard_map``
      program (core/traversal.make_mesh_engine): in-program routing from
      the stacked root MBRs, per-partition spec-driven BFS under vmap, and
      cross-shard merging with collectives (distributed/collectives.py).
      For the distance operators the two routing phases *overlap* inside
      the program: phase 2 descends under the collective phase-1 τ bound
      (seeded as ``tau_init``) with no host barrier, so per-batch dispatch
      count is O(levels) instead of O(partitions × levels).  Results are
      bit-exact vs the host path and invariant under partition permutation
      (tests/oracle.assert_sharded_parity).

Host results and mesh results agree because both reduce to the same total
order: candidates merge by (distance, global id), select/join rows by
sorted global id — orders with no dependence on partition placement.

Spans (runtime/trace.py): each public operator runs inside
``repro.fleet.<operator>``.  On the host path it splits into
``repro.fleet.route`` (router matrix, primary pick), ``repro.fleet.phase1``
and ``repro.fleet.phase2`` (the partition loops) and ``repro.fleet.merge``
(merging partition answers); every engine call is one
``repro.fleet.enqueue`` (bucket, host-to-device copy, the call until it
returns) and one ``repro.fleet.partition_calls``.  ``repro.fleet.readback``
is the blocking copy of answers and overflow flags back to the host: once
per call in ``range_select`` and ``join`` and in a distance operator's
phase 2, once for all of a distance operator's phase-1 calls, which are
launched before any is read back (``repro.fleet.pipelined_calls`` counts
the calls of such a shared read-back of two or more).  ``range_select`` maps each call's local
ids to global ids per row in ``repro.fleet.ids``, and counts the ids it
returns in ``repro.fleet.result_ids`` and the rows a result cap cut short
in ``repro.fleet.overflowed_rows``.  The calls' Counters are collected as
they return and summed once per operator call, as one compiled program
(``_tally``): that sum is ``repro.fleet.counters``.  The mesh path has
one enqueue and one readback per program call.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Tuple

import numpy as np

from repro.core import counters, rtree, traversal
from repro.core.geometry import intersects as np_intersects
from repro.core.geometry import mindist_matrix_np, mindist_rect_matrix_np
from repro.runtime import trace


class SelectRows(list):
    """``range_select``'s answer: one sorted array of global ids per query
    row, and ``overflowed``, the rows that held more ids than a partition's
    result cap, whose arrays are therefore incomplete."""

    def __init__(self, rows, overflowed):
        super().__init__(rows)
        self.overflowed = overflowed


@dataclasses.dataclass
class Partition:
    tree: "rtree.RTree"
    mbr: np.ndarray            # (4,)
    offset: int                # global id of local rect 0
    ids: np.ndarray            # (n_local,) global rect ids


class SpatialShards:
    def __init__(self, partitions: List[Partition], fanout: int,
                 layout: str = "d1"):
        from repro.core.layouts import layout_lanes
        layout_lanes(layout)           # validate the name early (ValueError)
        self.partitions = partitions
        self.fanout = fanout
        # fleet-wide physical node layout: injected into every engine /
        # mesh-program build, so the whole serving surface (select, join,
        # the distance operators, browse) runs one consistent layout
        self.layout = layout
        self.router_mbrs = np.stack([p.mbr for p in partitions])
        # one compiled-engine cache for every operator, keyed by
        # (spec name, partition, build params) through the spec registry —
        # adding an operator adds a registry entry, not another cache
        self._engines = {}
        # mesh path state (enable_mesh): packed forest + compiled programs
        self._mesh = None
        self._mesh_axis = "model"
        self._forest = None
        self._mesh_programs = {}
        self._browse_starts = {}
        # merged Counters of the last batch: mesh programs set it from the
        # collective merge; host fallbacks sum the per-partition Counters
        # (_tally; so scalar flags like overflow become "how many
        # partition-batches tripped it" — use truthiness, and .occupancy()
        # for lane waste)
        self.last_counters = None

    @classmethod
    def build(cls, rects: np.ndarray, n_partitions: int, fanout: int = 64,
              sort_key: Optional[str] = None,
              mesh=None, layout: str = "d1") -> "SpatialShards":
        n = len(rects)
        cx = (rects[:, 0] + rects[:, 2]) / 2
        cy = (rects[:, 1] + rects[:, 3]) / 2
        slabs = int(np.ceil(np.sqrt(n_partitions)))
        per_slab = int(np.ceil(n_partitions / slabs))
        order = np.argsort(cx, kind="stable")
        slab_size = int(np.ceil(n / slabs))
        parts: List[Partition] = []
        for si in range(slabs):
            sl = order[si * slab_size:(si + 1) * slab_size]
            if len(sl) == 0:
                continue
            sl = sl[np.argsort(cy[sl], kind="stable")]
            tile = int(np.ceil(len(sl) / per_slab))
            for ti in range(per_slab):
                ids = sl[ti * tile:(ti + 1) * tile]
                if len(ids) == 0:
                    continue
                sub = rects[ids]
                tree = rtree.build_rtree(sub, fanout=fanout,
                                         sort_key=sort_key)
                mbr = np.array([sub[:, 0].min(), sub[:, 1].min(),
                                sub[:, 2].max(), sub[:, 3].max()],
                               rects.dtype)
                parts.append(Partition(tree=tree, mbr=mbr, offset=len(parts),
                                       ids=ids))
        out = cls(parts, fanout, layout=layout)
        if mesh is not None:
            out.enable_mesh(mesh)
        return out

    def _layout_params(self, params: dict) -> dict:
        """Inject the fleet layout into engine build params.  d1 (the
        default) adds nothing, so historical cache keys and traces are
        untouched."""
        if self.layout != "d1":
            params = dict(params)
            params.setdefault("layout", self.layout)
        return params

    # ------------------------------------------------------------------
    # mesh dispatcher
    # ------------------------------------------------------------------

    @property
    def mesh_enabled(self) -> bool:
        return self._forest is not None

    def enable_mesh(self, mesh=None, axis: str = "model",
                    min_height: Optional[int] = None) -> "SpatialShards":
        """Pack the partition fleet into mesh-sharded pytree arrays and
        route the public API through the one-program SPMD path.  ``mesh``
        defaults to a 1-D mesh over all local devices (works on a single
        device too — the consolidation from O(partitions) dispatches to one
        program does not need multiple devices, only the fan-*out* does)."""
        import jax

        from repro.distributed import forest as forest_mod

        if mesh is None:
            mesh = jax.make_mesh((len(jax.devices()),), (axis,))
        packed = forest_mod.pack_forest(
            [p.tree for p in self.partitions],
            [p.ids for p in self.partitions],
            n_shards=mesh.shape[axis], min_height=min_height)
        self._mesh, self._mesh_axis = mesh, axis
        self._forest = packed.device_put(mesh, axis)
        self._mesh_programs = {}
        self._browse_starts = {}
        return self

    def disable_mesh(self) -> "SpatialShards":
        self._mesh = self._forest = None
        self._mesh_programs = {}
        self._browse_starts = {}
        return self

    def host_view(self) -> "SpatialShards":
        """A host-path engine over the same partitions — the serving
        stack's degradation target when every mesh replica is quarantined
        (launch/queue.ServeQueue ``fallback=``).  When this object already
        serves on the host path it IS the fallback; when mesh-enabled, the
        view is a twin that *shares* the partition list and the compiled
        host-engine cache (so falling back never recompiles what the host
        path already traced) but carries no mesh state — using it cannot
        flip this object's operators off the mesh path."""
        if not self.mesh_enabled:
            return self
        twin = SpatialShards(self.partitions, self.fanout,
                             layout=self.layout)
        twin._engines = self._engines
        return twin

    def replicate(self, replicas: Optional[int] = None, meshes=None,
                  axis: str = "model") -> List["SpatialShards"]:
        """Replica fan-out on the data axis: R independent mesh engines over
        disjoint device groups, each serving the full public API against a
        complete copy of the fleet.

        The partition list and the host-side forest pack are shared (packed
        ONCE, device_put per replica mesh — distributed/forest.
        replicate_forest); only device placement and compiled-program caches
        differ, so dispatches to different replicas overlap on real hardware.
        These are the engines that make the straggler pool's deadline
        re-issue meaningful (a re-issue targets a *different* replica's
        devices) and let serving QPS scale with devices, not just
        partitions.  ``meshes`` defaults to ``launch/mesh.replica_meshes
        (replicas)`` — the rows of the ``(data, model)`` serving grid.
        ``self`` is left untouched (host path or current mesh state), so it
        stays usable as the parity reference."""
        from repro.distributed import forest as forest_mod

        if meshes is None:
            from repro.launch.mesh import replica_meshes
            meshes = replica_meshes(replicas or 1, axis=axis)
        packed = forest_mod.pack_forest(
            [p.tree for p in self.partitions],
            [p.ids for p in self.partitions],
            n_shards=meshes[0].shape[axis])
        forests = forest_mod.replicate_forest(packed, meshes, axis=axis)
        reps = []
        for mesh, fst in zip(meshes, forests):
            rep = SpatialShards(self.partitions, self.fanout,
                                layout=self.layout)
            rep._mesh, rep._mesh_axis = mesh, axis
            rep._forest = fst
            reps.append(rep)
        return reps

    def _mesh_program(self, op: str, outer_tree=None, **params):
        params = self._layout_params(params)
        key = (op, tuple(sorted(params.items())),
               None if outer_tree is None else id(outer_tree))
        if key not in self._mesh_programs:
            if outer_tree is not None:
                # programs close over their outer tree: keep only the
                # latest per (op, params) so a caller streaming fresh probe
                # relations cannot grow the cache (and pin every past
                # probe's arrays) without bound
                stale = [s for s in self._mesh_programs
                         if s[:2] == key[:2] and s[2] is not None]
                for s in stale:
                    del self._mesh_programs[s]
            self._mesh_programs[key] = traversal.make_mesh_engine(
                op, self._forest.tree, self._forest.ids_map,
                mesh=self._mesh, axis=self._mesh_axis,
                outer_tree=outer_tree, **params)
        return self._mesh_programs[key]

    def _mesh_distance(self, op: str, queries: np.ndarray, k: int
                       ) -> Tuple[np.ndarray, np.ndarray, bool]:
        import jax.numpy as jnp
        prog = self._mesh_program(op, k=k)
        with trace.span("repro.fleet.enqueue"):
            ids, d, ctr = prog(jnp.asarray(queries))
        self.last_counters = ctr
        with trace.span("repro.fleet.readback"):
            return (np.asarray(ids).astype(np.int64),
                    np.asarray(d, np.float64), bool(int(ctr.overflow)))

    # ------------------------------------------------------------------
    # routing + per-partition engines (host fallback)
    # ------------------------------------------------------------------

    def route(self, queries: np.ndarray) -> np.ndarray:
        """(B, 4) queries → (B, P) bool routing matrix from partition MBRs
        (the replicated root-router step)."""
        q = queries
        m = self.router_mbrs
        return np_intersects(q[:, None, 0], q[:, None, 1], q[:, None, 2],
                             q[:, None, 3], m[None, :, 0], m[None, :, 1],
                             m[None, :, 2], m[None, :, 3])

    def engine_for(self, op: str, pi: int, **params):
        """The compiled engine of registered operator ``op`` for partition
        ``pi``, built through the spec registry (traversal.build) and cached
        per build params; jax.jit retraces per batch shape on its own."""
        params = self._layout_params(params)
        key = (op, pi, tuple(sorted(params.items())))
        if key not in self._engines:
            self._engines[key] = traversal.build(
                op, self.partitions[pi].tree, **params)
        return self._engines[key]

    @property
    def _max_calls(self) -> int:
        """The most engine calls one host-path operator call makes: two
        per partition, the distance operators' two phases."""
        return 2 * len(self.partitions)

    def _tally(self, ctrs) -> None:
        """Set ``last_counters`` to the sum of one host-path operator
        call's per-partition Counters (unchanged when it made no engine
        call), timed as ``repro.fleet.counters``.  The sum is one compiled
        program (``counters.total``) padded to ``_max_calls`` terms, so it
        compiles once per operator and stays on the device: one launch per
        operator call, where adding the calls one by one cost an eager
        dispatch per field per call."""
        if ctrs:
            with trace.span("repro.fleet.counters"):
                self.last_counters = counters.total(ctrs, self._max_calls)

    @staticmethod
    def _bucket(queries: np.ndarray) -> np.ndarray:
        """Pad a query subset to its next power-of-two row count so a
        (partition, params) pair compiles at most log2(max batch)+1 traces.
        Pads with copies of a real query, not zeros: the overflow flag is
        any() over all rows, and an arbitrary all-zeros row could overflow
        the frontier caps even when no real query does — a false "results
        may be approximate" warning."""
        b = len(queries)
        bucket = 1 << (b - 1).bit_length()
        if bucket > b:
            pad = np.repeat(queries[:1], bucket - b, axis=0)
            queries = np.concatenate([queries, pad], axis=0)
        return queries

    @trace.spanned("repro.fleet.range_select")
    def range_select(self, queries: np.ndarray, result_cap: int = 4096
                     ) -> SelectRows:
        """Batched distributed select → per-query global rect id arrays
        (a ``SelectRows``: rows cut short by ``result_cap`` are listed in
        its ``overflowed``)."""
        import jax.numpy as jnp
        if self.mesh_enabled:
            prog = self._mesh_program("select", result_cap=result_cap)
            with trace.span("repro.fleet.enqueue"):
                ids, counts, ctr = prog(jnp.asarray(queries, np.float32))
            self.last_counters = ctr
            with trace.span("repro.fleet.readback"):
                ids = np.asarray(ids)
                counts = np.asarray(counts)
            over = (counts > result_cap).any(axis=0)
            with trace.span("repro.fleet.merge"):
                rows = [np.sort(np.concatenate(
                    [ids[p, qi, :counts[p, qi]]
                     for p in range(ids.shape[0])]).astype(np.int64))
                    for qi in range(len(queries))]
            return self._select_rows(rows, over)
        with trace.span("repro.fleet.route"):
            routing = self.route(queries)
        results = [[] for _ in range(len(queries))]
        over = np.zeros(len(queries), bool)
        ctrs = []
        with trace.span("repro.fleet.phase1"):
            for pi, part in enumerate(self.partitions):
                hit = np.nonzero(routing[:, pi])[0]
                if len(hit) == 0:
                    continue
                sel = self.engine_for("select", pi, result_cap=result_cap)
                with trace.span("repro.fleet.enqueue"):
                    ids, counts, ctr = sel(jnp.asarray(
                        self._bucket(queries[hit])))
                trace.add("repro.fleet.partition_calls")
                ctrs.append(ctr)
                with trace.span("repro.fleet.readback"):
                    ids = np.asarray(ids)
                    counts = np.asarray(counts)
                with trace.span("repro.fleet.ids"):
                    over[hit] |= counts[:len(hit)] > result_cap
                    for qi, local_q in enumerate(hit):
                        found = ids[qi, :counts[qi]]
                        results[local_q].append(part.ids[found])
        self._tally(ctrs)
        with trace.span("repro.fleet.merge"):
            rows = [np.sort(np.concatenate(r)) if r else
                    np.empty((0,), np.int64) for r in results]
        return self._select_rows(rows, over)

    @staticmethod
    def _select_rows(rows: List[np.ndarray], over: np.ndarray) -> SelectRows:
        """Count one select call's ids and cut-short rows; wrap its answer."""
        trace.add("repro.fleet.result_ids", sum(len(r) for r in rows))
        overflowed = np.nonzero(over)[0]
        if len(overflowed):
            trace.add("repro.fleet.overflowed_rows", len(overflowed))
        return SelectRows(rows, overflowed)

    # ------------------------------------------------------------------
    # spatial join (probe rects × partitioned data)
    # ------------------------------------------------------------------

    @trace.spanned("repro.fleet.join")
    def join(self, probe, result_cap: int = 1 << 17, o3: bool = False,
             o4: bool = False) -> Tuple[np.ndarray, bool]:
        """Distributed spatial join of a probe relation against the
        partitioned data: returns ((K, 2) int64 pairs (probe id, global
        data id) sorted lexicographically, overflow flag).  ``probe`` is a
        (M, 4) rect array or a pre-built RTree (its rect order defines the
        probe ids).  ``o3``/``o4`` enable the sorted-key pruning — both the
        probe tree and the partition trees must then be built with
        ``sort_key='lx'`` (pass a pre-built probe tree; the fleet needs
        ``SpatialShards.build(..., sort_key='lx')``)."""
        import jax.numpy as jnp
        jn_params = self._layout_params(
            dict(result_cap=result_cap, o3=o3, o4=o4))
        probe_tree = probe if isinstance(probe, rtree.RTree) else \
            rtree.build_rtree(np.asarray(probe, np.float32),
                              fanout=self.fanout,
                              sort_key="lx" if (o3 or o4) else None)
        if self.mesh_enabled:
            if probe_tree.height > self._forest.height:
                # taller probe: re-pack the forest with matching chain
                # elevation so no tree is elevated under trace
                self.enable_mesh(self._mesh, self._mesh_axis,
                                 min_height=probe_tree.height)
            from repro.core.join_scalar import elevate
            # pre-elevate host-side: inside the traced program both
            # relations already share the forest height, so the join
            # builder's elevate is a no-op on tracers.  Memoized so the
            # program cache (keyed on the probe object) hits across
            # repeated joins of the same probe relation.
            ck = ("elevated_probe", self._forest.height)
            cached = self._engines.get(ck)
            if cached is None or cached[0] is not probe_tree:
                cached = (probe_tree,
                          elevate(probe_tree, self._forest.height))
                self._engines[ck] = cached
            probe_tree = cached[1]
            prog = self._mesh_program("join", outer_tree=probe_tree,
                                      **jn_params)
            with trace.span("repro.fleet.enqueue"):
                pairs, counts, ctr = prog()
            self.last_counters = ctr
            with trace.span("repro.fleet.readback"):
                pairs = np.asarray(pairs)
                counts = np.asarray(counts)
                ovf = bool(int(ctr.overflow))
            rows = [pairs[p, :counts[p]] for p in range(pairs.shape[0])]
        else:
            rows = []
            ovf = False
            ctrs = []
            for pi, part in enumerate(self.partitions):
                # join engines close over BOTH trees, so the cache entry is
                # valid only for the same probe-tree object
                key = ("join", pi, tuple(sorted(jn_params.items())))
                cached = self._engines.get(key)
                if cached is None or cached[0] is not probe_tree:
                    cached = (probe_tree, traversal.build(
                        "join", probe_tree, part.tree, **jn_params))
                    self._engines[key] = cached
                jn = cached[1]
                with trace.span("repro.fleet.enqueue"):
                    pr, n_pairs, ctr = jn()
                trace.add("repro.fleet.partition_calls")
                ctrs.append(ctr)
                with trace.span("repro.fleet.readback"):
                    pr = np.asarray(pr[:int(n_pairs)])
                    ovf |= bool(int(ctr.overflow))
                rows.append(np.stack(
                    [pr[:, 0], part.ids[pr[:, 1]]], axis=1))
            self._tally(ctrs)
        with trace.span("repro.fleet.merge"):
            cat = np.concatenate(rows).astype(np.int64) if rows else \
                np.empty((0, 2), np.int64)
            order = np.lexsort((cat[:, 1], cat[:, 0]))
            return cat[order], ovf

    # ------------------------------------------------------------------
    # distance operators (kNN / kNN-join / filtered kNN)
    # ------------------------------------------------------------------

    def _launch(self, op: str, pi: int, queries: np.ndarray, k: int):
        """Launch one partition's batched distance engine and start the
        host copies of its answer and overflow flag; read nothing back.

        Query subsets ride power-of-two buckets (``_bucket``) so each
        partition only does work proportional to the queries actually
        routed to it (phase-1 subsets partition the batch; phase-2 subsets
        are usually tiny).  Returns ``(pi, rows, device outputs, ctr)``
        for ``_collect``.
        """
        import jax
        import jax.numpy as jnp
        fn = self.engine_for(op, pi, k=k)
        with trace.span("repro.fleet.enqueue"):
            ids, dists, ctr = fn(jnp.asarray(self._bucket(queries)))
            out = (ids, dists, ctr.overflow)
            for x in out:          # a wrapper may hand back host values
                if isinstance(x, jax.Array):
                    x.copy_to_host_async()
        trace.add("repro.fleet.partition_calls")
        return pi, len(queries), out, ctr

    def _collect(self, launched) -> list:
        """Read back every ``_launch``ed call in one blocking
        ``jax.device_get``; per call → ``(global ids, float64 sq-dists,
        overflow, ctr)``, in launch order.  Shared by every distance
        operator — the padding/overflow subtleties live in one place."""
        import jax
        if len(launched) > 1:
            trace.add("repro.fleet.pipelined_calls", len(launched))
        with trace.span("repro.fleet.readback"):
            host = jax.device_get([out for _, _, out, _ in launched])
        results = []
        for (pi, b, _, ctr), (ids, dists, ovf) in zip(launched, host):
            ids = ids[:b]
            dists = np.asarray(dists, np.float64)[:b]
            gids = np.where(ids >= 0,
                            self.partitions[pi].ids[np.maximum(ids, 0)], -1)
            results.append((gids, dists, bool(ovf), ctr))
        return results

    @trace.spanned("repro.fleet.knn")
    def knn(self, points: np.ndarray, k: int
            ) -> Tuple[np.ndarray, np.ndarray, bool]:
        """Distributed exact kNN → (global ids (B, k), sq-dists (B, k),
        overflow flag).

        Two-phase routing on the partition MBRs (the replicated root-router
        one level up): phase 1 answers every query on its *primary* partition
        (smallest MBR MINDIST) which yields a k-th-distance bound τ; phase 2
        re-asks only partitions whose MBR MINDIST ≤ τ — for point data and
        ≥ a few partitions, most queries never leave their primary shard.
        The per-query top-k streams are merged by (distance, id).

        On the mesh path the same two phases run *inside one SPMD program*
        with the τ merge as a collective (no host barrier).

        ``overflow`` mirrors the single-tree Counters.overflow: True means
        some partition's frontier cap truncated to its best-first beam and
        the result may be approximate-with-bound (rebuild with larger
        ``knn_frontier_caps`` to clear).
        """
        points = np.asarray(points, np.float32)
        if self.mesh_enabled:
            return self._mesh_distance("knn", points, k)
        return self._two_phase_knn(points, k, "knn", mindist_matrix_np,
                                   points)

    @trace.spanned("repro.fleet.knn_join")
    def knn_join(self, qrects: np.ndarray, k: int
                 ) -> Tuple[np.ndarray, np.ndarray, bool]:
        """Distributed kNN-join → (global ids (B, k), sq-dists (B, k),
        overflow flag): for each outer rect, its k nearest data rects across
        all partitions under squared rect-to-rect MINDIST.  Routing exactly
        as ``knn`` with the router matrix generalized to rect-to-MBR
        MINDIST."""
        qrects = np.asarray(qrects, np.float32)
        if self.mesh_enabled:
            return self._mesh_distance("knn_join", qrects, k)
        return self._two_phase_knn(qrects, k, "knn_join",
                                   mindist_rect_matrix_np, qrects)

    @trace.spanned("repro.fleet.knn_filtered")
    def knn_filtered(self, queries: np.ndarray, k: int
                     ) -> Tuple[np.ndarray, np.ndarray, bool]:
        """Distributed filtered kNN (core/knn_filtered.py): rows are
        (px, py, wlx, wly, whx, why) — the k nearest data rects
        intersecting the per-query window.  Routed like ``knn`` on the
        point columns: the partition-MBR MINDIST lower-bounds every
        (filtered or not) candidate distance, so the τ bound stays sound
        under the predicate mask."""
        queries = np.asarray(queries, np.float32)
        if self.mesh_enabled:
            return self._mesh_distance("knn_filtered", queries, k)
        return self._two_phase_knn(queries, k, "knn_filtered",
                                   mindist_matrix_np, queries[:, :2])

    def _two_phase_knn(self, queries: np.ndarray, k: int, op: str,
                       router_dist, route_rows: np.ndarray
                       ) -> Tuple[np.ndarray, np.ndarray, bool]:
        """Host-fallback two-phase routing for the distance operators:
        primary-partition answer → τ bound → τ-bounded secondary fan-out →
        deterministic cross-shard top-k merge.

        ``router_dist(route_rows, router_mbrs)`` gives the (B, P) exact
        query-to-partition-MBR squared MINDISTs; ``op`` resolves the
        per-partition engine through the registry.
        """
        b = len(queries)
        p = len(self.partitions)
        with trace.span("repro.fleet.route"):
            dmat = router_dist(route_rows, self.router_mbrs)     # (B, P)
            primary = np.argmin(dmat, axis=1)
        cand_ids = np.full((b, k), -1, np.int64)
        cand_d = np.full((b, k), np.inf)
        overflow = False
        ctrs = []
        # ---- phase 1: primary partitions ----
        # every primary call is launched before any is read back: the
        # subsets partition the batch, so no call waits on another's answer
        with trace.span("repro.fleet.phase1"):
            sels = {int(pi): np.nonzero(primary == pi)[0]
                    for pi in np.unique(primary)}
            launched = [self._launch(op, pi, queries[sel], k)
                        for pi, sel in sels.items()]
            for sel, (gids, dists, ovf, ctr) in zip(
                    sels.values(), self._collect(launched)):
                ctrs.append(ctr)
                cand_ids[sel], cand_d[sel] = gids, dists
                overflow |= ovf
        # τ: current k-th best (inf when the primary held < k rects)
        tau = cand_d[:, k - 1].copy()
        # ---- phase 2: secondary partitions within τ ----
        # τ slack: partition distances are f32 (jax) while the router matrix
        # is exact f64, so widen the bound a hair — only ever *adds* fan-out,
        # never skips a partition that could hold a true k-th neighbor.
        # One call at a time: each tightens τ for the next one's routing.
        with trace.span("repro.fleet.phase2"):
            for pi in range(p):
                tau_cmp = tau * (1.0 + 1e-5) + 1e-30
                sel = np.nonzero((primary != pi)
                                 & (dmat[:, pi] <= tau_cmp))[0]
                if len(sel) == 0:
                    continue
                (gids, dists, ovf, ctr), = self._collect(
                    [self._launch(op, pi, queries[sel], k)])
                ctrs.append(ctr)
                overflow |= ovf
                with trace.span("repro.fleet.merge"):
                    merged_d = np.concatenate([cand_d[sel], dists], axis=1)
                    merged_i = np.concatenate([cand_ids[sel], gids], axis=1)
                    # top-k merge ordered by (distance, global id) —
                    # deterministic under cross-shard distance ties
                    order = np.lexsort((merged_i, merged_d))[:, :k]
                    cand_d[sel] = np.take_along_axis(merged_d, order, axis=1)
                    cand_ids[sel] = np.take_along_axis(merged_i, order,
                                                       axis=1)
                    tau[sel] = cand_d[sel, k - 1]
        self._tally(ctrs)
        return cand_ids, cand_d, overflow

    # ------------------------------------------------------------------
    # distributed distance browsing
    # ------------------------------------------------------------------

    @trace.spanned("repro.fleet.browse")
    def browse(self, points: np.ndarray, k: int):
        """Open a distributed browsing session: per-partition
        ``BrowseState`` cursors with a cross-shard pool merge on every
        ``next_batch()`` (core/knn_browse.make_sharded_browse).  The
        sharded program serves any device count, so it doubles as the
        single-device path — there is no separate host browse loop, which
        is why this requires ``enable_mesh()`` first (an implicit enable
        here would silently flip every OTHER operator on this object from
        the host path to the mesh path)."""
        from repro.core import knn_browse

        if not self.mesh_enabled:
            raise RuntimeError(
                "distributed browsing runs on the mesh path — call "
                "enable_mesh() first (works on a single device too)")
        if k not in self._browse_starts:
            self._browse_starts[k] = knn_browse.make_sharded_browse(
                self._forest.tree, self._forest.ids_map, k,
                mesh=self._mesh, axis=self._mesh_axis, layout=self.layout)
        return self._browse_starts[k](np.asarray(points, np.float32))

    # ------------------------------------------------------------------
    # warmup — registry-keyed, one path for every operator
    # ------------------------------------------------------------------

    def warm(self, op: str, batch: int, k: Optional[int] = None,
             result_cap: int = 4096, probe=None, **op_params) -> None:
        """Pre-compile operator ``op`` so serving loops never pay an XLA
        compile.  Registry-keyed: the spec supplies the query width and
        engine kind, so one warmup covers select, join, every distance
        operator, and browse.

        Host path: every partition's engine at every power-of-two bucket up
        to ``batch`` (routed subsets can land in any bucket ≤ the full
        batch's), then ``_tally``'s sum of their Counters.  Mesh path: the
        single SPMD program at the serving batch shape (subsets never change
        shape there).  ``join`` warms against
        ``probe`` (rects or RTree) — its engines close over the probe tree.
        """
        import jax.numpy as jnp
        spec = traversal.get_spec(op)
        if k is None and (spec.kind == "distance" or op == "browse"):
            raise ValueError(f"warming {op!r} needs k")
        if op == "join":
            if probe is None:
                raise ValueError("join warmup needs the probe relation")
            self.join(probe, result_cap=result_cap, **op_params)
            return
        if op == "browse":
            cur = self.browse(np.zeros((batch, 2), np.float32), k)
            cur.next_batch()
            return
        params = {"k": k} if spec.kind == "distance" else \
            {"result_cap": result_cap}
        width = spec.query_width
        if self.mesh_enabled:
            q = np.zeros((batch, width), np.float32)
            prog = self._mesh_program(op, **params)
            prog(jnp.asarray(q))
            return
        buckets = []
        bucket = 1 << (max(batch, 1) - 1).bit_length()
        while bucket >= 1:
            buckets.append(bucket)
            bucket //= 2
        for pi in range(len(self.partitions)):
            fn = self.engine_for(op, pi, **params)
            for bk in buckets:
                ctr = fn(jnp.asarray(np.zeros((bk, width), np.float32)))[-1]
        counters.total([ctr], self._max_calls)

    # preserved spellings of the historical per-operator warmups
    def warm_knn(self, batch: int, k: int) -> None:
        self.warm("knn", batch, k=k)

    def warm_knn_join(self, batch: int, k: int) -> None:
        self.warm("knn_join", batch, k=k)
