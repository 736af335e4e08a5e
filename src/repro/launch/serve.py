"""Spatial query service driver — the paper's technique as a deployed
feature.

Builds a spatially-partitioned index fleet (distributed/spatial_shard.py),
then serves batched requests for any operator in the traversal spec
registry (core/traversal.py): range select (with deadline-based straggler
re-issue, runtime/straggler.py), spatial join, kNN and kNN-join (two-phase
τ-bounded routing), and resumable distance browsing (k-at-a-time kNN).

    PYTHONPATH=src python -m repro.launch.serve --n 200000 --partitions 8 \
        --batches 20 --batch-size 64 --selectivity 0.001

``--mode`` resolves through the spec registry — a newly registered
``OperatorSpec`` must come with a serve runner (registry/runner coverage is
asserted on every spatial serve run and by tests/test_serve_modes.py), so
the served surface can never silently lag the operator family.  ``--dryrun``
shrinks every size for the CI smoke that instantiates each registered spec
end-to-end.  ``--mode lm`` drives the LM decode path (reduced config) as a
batched token service — both serving styles share the launcher.

``--queue`` switches the queueable operators to async continuous batching
(launch/queue.ServeQueue): ``--clients`` concurrent closed-loop clients
submit small requests that coalesce into pow2-bucketed batches, one mesh
dispatch per batch, double-buffered ``--depth`` deep.  ``--replicas R``
fans the packed forest out to R disjoint replica engines
(SpatialShards.replicate) that the queue round-robins across and the
straggler pool re-issues between.  ``--dryrun --queue`` asserts every
queued response bit-exact against the direct host-path call.

``--chaos <spec>`` injects seeded deterministic faults into the queued
replicas (runtime/faults.py grammar — e.g. ``kill:r1@5,slow:r0@0:0.2``)
to exercise the robustness stack end-to-end: health circuit breaking
quarantines the failing replica, dispatch retries + straggler re-issues
absorb the faults, and if every replica's breaker opens the queue
degrades to a host-loop fallback engine (SpatialShards.host_view) — so
the run must finish with ZERO client-visible failures and (under
``--dryrun``) bit-exact parity with the fault-free host path.
"""
from __future__ import annotations

import argparse
import time

import numpy as np

from repro.core import str_pack, traversal
from repro.core.layouts import layout_names
from repro.distributed.spatial_shard import SpatialShards
from repro.runtime.straggler import ShardPool

# CLI mode → registered spec name (CLI keeps the historical hyphenated
# spellings; 'spatial' is the historical alias for select)
MODE_TO_SPEC = {
    "spatial": "select",
    "select": "select",
    "join": "join",
    "knn": "knn",
    "knn-join": "knn_join",
    "knn-filtered": "knn_filtered",
    "browse": "browse",
}


def _use_mesh(args) -> bool:
    """Route through the mesh dispatcher?  ``--mesh on`` always, ``off``
    never, ``auto`` (default) whenever more than one device is visible
    (force a multi-device CPU with
    XLA_FLAGS=--xla_force_host_platform_device_count=N)."""
    if args.mesh == "on":
        return True
    if args.mesh == "off":
        return False
    import jax
    return len(jax.devices()) > 1


def make_queries(n: int, batch: int, selectivity: float, seed: int = 1):
    rng = np.random.default_rng(seed)
    side = float(np.sqrt(selectivity))
    lo = rng.random((n, batch, 2), dtype=np.float32) * (1 - side)
    return np.concatenate([lo, lo + side], axis=-1)


def _build_shards(args, sort_key=None):
    rng = np.random.default_rng(args.seed)
    pts = rng.random((args.n, 2), dtype=np.float32)
    rects = str_pack.points_to_rects(pts)
    t0 = time.time()
    shards = SpatialShards.build(rects, args.partitions, fanout=args.fanout,
                                 sort_key=sort_key, layout=args.layout)
    note = ""
    if _use_mesh(args):
        from .mesh import spatial_mesh
        mesh = spatial_mesh()
        shards.enable_mesh(mesh)
        note = (f", mesh path over {mesh.shape['model']} device(s) "
                f"(one SPMD program per batch)")
    print(f"built {len(shards.partitions)} partitions over {args.n} rects "
          f"in {time.time() - t0:.2f}s{note}")
    return rng, rects, shards


def _replica_fleet(args, shards):
    """The engine list the straggler pool / serve queue dispatches over:
    ``--replicas R`` on the mesh path fans the packed forest out over R
    disjoint device groups (SpatialShards.replicate — the data axis), so a
    deadline re-issue targets a genuinely distinct engine.  Off the mesh
    path (or R <= 1) the single fleet serves alone and the pool skips the
    pointless self-re-issue."""
    r = getattr(args, "replicas", 1)
    if r > 1 and _use_mesh(args):
        replicas = shards.replicate(replicas=r)
        print(f"replica fan-out: {r} engines × "
              f"{replicas[0]._mesh.shape['model']} device(s) each "
              f"(data axis)")
        return replicas
    return [shards]


def _serve_select(args, spec):
    """Distributed range select behind the straggler pool — one pool shard
    per replica engine, round-robin primaries, deadline re-issue to the
    next replica."""
    rng, _, shards = _build_shards(args)
    qs = make_queries(args.batches, args.batch_size, args.selectivity,
                      args.seed + 1)
    engines = _replica_fleet(args, shards)
    # warm the compiled selects (per-partition engines / mesh programs)
    for e in engines:
        e.warm("select", args.batch_size)

    def shard_call(payload, e):
        rows = e.range_select(payload)
        return rows, bool(int(e.last_counters.overflow))

    with ShardPool(
            shards=[(lambda payload, e=e: shard_call(payload, e))
                    for e in engines],
            deadline_s=args.deadline) as pool:
        t0 = time.time()
        total = 0
        answers = []
        overflowed = False
        for b in range(args.batches):
            res, ovf = pool.query(b % len(engines), qs[b])
            total += sum(len(r) for r in res)
            answers.append(res)
            overflowed |= ovf
        dt = time.time() - t0
    qps = args.batches * args.batch_size / dt
    print(f"served {args.batches} batches × {args.batch_size} queries in "
          f"{dt:.2f}s → {qps:,.0f} q/s, {total} result rows, "
          f"{pool.reissues} straggler re-issues, {pool.failures} failures"
          + (", WARNING: result-cap overflow" if overflowed else ""))
    return {"qps": qps, "results": total, "failures": pool.failures,
            "overflow": overflowed, "queries": qs, "answers": answers}


def _serve_knn(args, spec):
    """Batched k-nearest-neighbor service over the partitioned index fleet:
    per-query primary-partition answer + τ-bounded secondary fan-out with
    cross-shard top-k merge (distributed/spatial_shard.py)."""
    rng, _, shards = _build_shards(args)
    qs = rng.random((args.batches, args.batch_size, 2), dtype=np.float32)
    # compile the kNN path at this batch bucket up front so no XLA compile
    # (or spurious straggler re-issue) lands in the timed loop
    shards.warm("knn", args.batch_size, k=args.k)

    # single engine, no spare replica: ShardPool's deadline re-issue could
    # only resubmit the identical call to the same host, so the batches are
    # served directly (spatial mode keeps the pool — its re-issue stat is
    # meaningful once real replicas back it)
    t0 = time.time()
    returned = 0
    overflowed = False
    answers = []
    for b in range(args.batches):
        ids, dists, ovf = shards.knn(qs[b], args.k)
        returned += int((ids >= 0).sum())
        overflowed |= ovf
        answers.append((ids, dists))
    dt = time.time() - t0
    qps = args.batches * args.batch_size / dt
    print(f"served {args.batches} batches × {args.batch_size} kNN queries "
          f"(k={args.k}) in {dt:.2f}s → {qps:,.0f} q/s, {returned} neighbor "
          f"rows"
          + (", WARNING: frontier overflow — results may be approximate"
             if overflowed else ""))
    return {"qps": qps, "neighbors": returned, "overflow": overflowed,
            "queries": qs, "answers": answers}


def _serve_knn_join(args, spec):
    """Batched kNN-join service: for each outer query rect, its k nearest
    indexed rects across the partition fleet (rect-to-rect MINDIST) — the
    all-pairs distance operator as a served endpoint, two-phase routed with
    τ-bounded secondary fan-out (distributed/spatial_shard.py)."""
    rng, _, shards = _build_shards(args)
    eps = np.float32(args.query_eps)
    centers = rng.random((args.batches, args.batch_size, 2), dtype=np.float32)
    qs = np.concatenate([centers - eps, centers + eps], axis=-1)
    shards.warm("knn_join", args.batch_size, k=args.k)

    t0 = time.time()
    returned = 0
    overflowed = False
    answers = []
    for b in range(args.batches):
        ids, dists, ovf = shards.knn_join(qs[b], args.k)
        returned += int((ids >= 0).sum())
        overflowed |= ovf
        answers.append((ids, dists))
    dt = time.time() - t0
    qps = args.batches * args.batch_size / dt
    print(f"served {args.batches} batches × {args.batch_size} kNN-join "
          f"queries (k={args.k}, eps={args.query_eps}) in {dt:.2f}s → "
          f"{qps:,.0f} q/s, {returned} neighbor rows"
          + (", WARNING: beam truncation — results may be approximate"
             if overflowed else ""))
    return {"qps": qps, "neighbors": returned, "overflow": overflowed,
            "queries": qs, "answers": answers}


def _serve_join(args, spec):
    """Spatial-join service: the probe relation joined against the
    partitioned data fleet (host fallback: one pair engine per partition;
    mesh: the probe tree replicated into the single SPMD program)."""
    from repro.core import rtree

    # sort_key='lx' fleet + probe so the O3/O4 sorted-key pruning applies
    rng, _, shards = _build_shards(args, sort_key="lx")
    n_probe = max(args.n // 10, 64)
    probe_pts = rng.random((n_probe, 2), dtype=np.float32)
    eps = np.float32(args.query_eps)
    probes = np.concatenate([probe_pts - eps, probe_pts + eps], axis=-1)
    probe_tree = rtree.build_rtree(probes, fanout=args.fanout,
                                   sort_key="lx")
    shards.warm("join", args.batch_size, probe=probe_tree,
                result_cap=args.join_cap, o3=True, o4=True)
    t0 = time.time()
    total = 0
    overflowed = False
    answers = []
    for _ in range(args.batches):
        pairs, ovf = shards.join(probe_tree, result_cap=args.join_cap,
                                 o3=True, o4=True)
        total += len(pairs)
        overflowed |= ovf
        answers.append(pairs)
    dt = time.time() - t0
    jps = args.batches / dt
    print(f"served {args.batches} joins × {n_probe} probes in {dt:.2f}s → "
          f"{jps:,.2f} joins/s, {total} pair rows"
          + (", WARNING: pair-frontier overflow" if overflowed else ""))
    return {"joins_per_s": jps, "pairs": total, "overflow": overflowed,
            "queries": probes, "answers": answers}


def _serve_knn_filtered(args, spec):
    """Filtered-kNN service: k nearest neighbors among the data rects
    intersecting a per-query filter window (core/knn_filtered.py) — the
    predicate-composed distance spec served through the same two-phase
    router / mesh dispatcher as plain kNN, with zero operator-specific
    serving code."""
    rng, _, shards = _build_shards(args)
    eps = np.float32(args.filter_eps)
    pts = rng.random((args.batches, args.batch_size, 2), dtype=np.float32)
    qs = np.concatenate([pts, pts - eps, pts + eps], axis=-1)
    shards.warm("knn_filtered", args.batch_size, k=args.k)

    t0 = time.time()
    returned = 0
    overflowed = False
    answers = []
    for b in range(args.batches):
        ids, dists, ovf = shards.knn_filtered(qs[b], args.k)
        returned += int((ids >= 0).sum())
        overflowed |= ovf
        answers.append((ids, dists))
    dt = time.time() - t0
    qps = args.batches * args.batch_size / dt
    print(f"served {args.batches} batches × {args.batch_size} filtered-kNN "
          f"queries (k={args.k}, window ±{args.filter_eps}) in {dt:.2f}s → "
          f"{qps:,.0f} q/s, {returned} neighbor rows"
          + (", WARNING: frontier overflow — results may be approximate"
             if overflowed else ""))
    return {"qps": qps, "neighbors": returned, "overflow": overflowed,
            "queries": qs, "answers": answers}


def _serve_browse(args, spec):
    """Distance-browsing service: each request opens a resumable session
    over its query batch and streams ``--browse-steps`` batches of k
    neighbors — the incremental operator the fixed-k endpoints can't serve
    without restarting from the root.  On the mesh path the session is a
    distributed cursor: per-partition BrowseStates with a cross-shard pool
    merge per batch (one SPMD program per ``next_batch``)."""
    import jax.numpy as jnp
    from repro.core import knn_browse, rtree

    if _use_mesh(args):
        rng, _, shards = _build_shards(args)
        qs = rng.random((args.batches, args.batch_size, 2), dtype=np.float32)
        shards.warm("browse", args.batch_size, k=args.k)
        t0 = time.time()
        returned = 0
        overflowed = False
        answers = []
        for b in range(args.batches):
            cursor = shards.browse(qs[b], args.k)
            steps = [cursor.next_batch() for _ in range(args.browse_steps)]
            returned += sum(int((ids >= 0).sum()) for ids, _ in steps)
            overflowed |= bool(cursor.overflow.any())
            answers.append(steps)
        dt = time.time() - t0
        qps = args.batches * args.batch_size / dt
        print(f"served {args.batches} distributed browse sessions × "
              f"{args.batch_size} queries × {args.browse_steps} batches of "
              f"k={args.k} in {dt:.2f}s → {qps:,.0f} sessions·q/s, "
              f"{returned} neighbor rows"
              + (", WARNING: lost-bound crossed — results may be approximate"
                 if overflowed else ""))
        return {"qps": qps, "neighbors": returned, "overflow": overflowed,
                "queries": qs, "answers": answers}

    rng = np.random.default_rng(args.seed)
    pts = rng.random((args.n, 2), dtype=np.float32)
    rects = str_pack.points_to_rects(pts)
    t0 = time.time()
    tree = rtree.build_rtree(rects, fanout=args.fanout)
    print(f"built tree over {args.n} rects in {time.time() - t0:.2f}s")
    start = knn_browse.make_browse_bfs(tree, k=args.k, layout=args.layout)
    qs = rng.random((args.batches, args.batch_size, 2), dtype=np.float32)
    # warm: one full session at the serving shape
    warm = start(jnp.asarray(qs[0]))
    for _ in range(args.browse_steps):
        warm.next_batch()

    t0 = time.time()
    returned = 0
    overflowed = False
    answers = []
    for b in range(args.batches):
        cursor = start(jnp.asarray(qs[b]))
        steps = [cursor.next_batch() for _ in range(args.browse_steps)]
        returned += sum(int((ids >= 0).sum()) for ids, _ in steps)
        overflowed |= bool(cursor.overflow.any())
        answers.append(steps)
    dt = time.time() - t0
    qps = args.batches * args.batch_size / dt
    print(f"served {args.batches} browse sessions × {args.batch_size} "
          f"queries × {args.browse_steps} batches of k={args.k} in "
          f"{dt:.2f}s → {qps:,.0f} sessions·q/s, {returned} neighbor rows"
          + (", WARNING: lost-bound crossed — results may be approximate"
             if overflowed else ""))
    return {"qps": qps, "neighbors": returned, "overflow": overflowed,
            "queries": qs, "answers": answers}


def _queued_payloads(args, op, rng):
    """The per-request query arrays (and operator params) for the queued
    runner — same distributions as the synchronous runners."""
    if op == "select":
        qs = make_queries(args.batches, args.batch_size, args.selectivity,
                          args.seed + 1)
        return list(qs), {}
    if op == "knn":
        pts = rng.random((args.batches, args.batch_size, 2),
                         dtype=np.float32)
        return list(pts), {"k": args.k}
    if op == "knn_join":
        eps = np.float32(args.query_eps)
        centers = rng.random((args.batches, args.batch_size, 2),
                             dtype=np.float32)
        return list(np.concatenate([centers - eps, centers + eps],
                                   axis=-1)), {"k": args.k}
    if op == "knn_filtered":
        eps = np.float32(args.filter_eps)
        pts = rng.random((args.batches, args.batch_size, 2),
                         dtype=np.float32)
        return list(np.concatenate([pts, pts - eps, pts + eps],
                                   axis=-1)), {"k": args.k}
    raise ValueError(f"no queued payload builder for {op!r}")


def _serve_queued(args, spec):
    """Async continuous-batching service: ``--clients`` closed-loop client
    threads submit their requests through ONE ServeQueue (launch/queue.py),
    which coalesces concurrent arrivals into power-of-two buckets and
    amortizes a single mesh dispatch over all of them — with ``--replicas``
    engines round-robined behind the straggler pool, double-buffered at
    ``--depth`` in-flight batches per replica."""
    import concurrent.futures as cf

    from .queue import ServeQueue

    op = spec.name
    rng, _, shards = _build_shards(args)
    payloads, qparams = _queued_payloads(args, op, rng)
    engines = _replica_fleet(args, shards)
    # warm every pow2 bucket a coalesced batch can land in
    bucket_cap = 1 << (args.max_batch - 1).bit_length()
    bk = 1 << (args.batch_size - 1).bit_length()
    while bk <= bucket_cap:
        for e in engines:
            e.warm(op, bk, **qparams)
        bk <<= 1

    injector = None
    if args.chaos:
        from repro.runtime.faults import FaultInjector, FaultPlan
        injector = FaultInjector(FaultPlan.from_spec(args.chaos,
                                                     seed=args.seed))
        print(f"chaos: injecting {injector.plan} (seed {args.seed})")

    n_clients = max(1, min(args.clients, args.batches))

    with ServeQueue(engines, op, max_batch=args.max_batch,
                    max_delay_s=args.max_delay, depth=args.depth,
                    deadline_s=args.deadline, injector=injector,
                    fallback=shards.host_view(), seed=args.seed,
                    **qparams) as q:

        errors = []

        def client(cid):
            # closed loop: each client waits for its response before
            # issuing the next request (sorted results keyed by index)
            out = []
            for i in range(cid, args.batches, n_clients):
                try:
                    out.append((i, q.query(payloads[i])))
                except Exception as exc:     # counted as a failed request
                    errors.append((i, exc))
            return out

        t0 = time.time()
        with cf.ThreadPoolExecutor(n_clients) as ex:
            parts = list(ex.map(client, range(n_clients)))
        dt = time.time() - t0
        results = dict(pair for part in parts for pair in part)
        summary = q.summary

    if errors and not args.chaos:
        # without injection a request failure is a real bug — keep it loud
        raise errors[0][1]

    if args.dryrun:
        # bit-exact parity with direct per-request calls on the base fleet
        for i, p in enumerate(payloads):
            if i not in results:
                continue                     # failed under chaos (asserted)
            if op == "select":
                ref = shards.range_select(p)
                for got_row, ref_row in zip(results[i], ref):
                    np.testing.assert_array_equal(got_row, ref_row)
            else:
                ids, d, _ = results[i]
                ref_ids, ref_d, _ = getattr(shards, op)(p, args.k)
                np.testing.assert_array_equal(ids, ref_ids)
                np.testing.assert_array_equal(d, ref_d)

    qps = args.batches * args.batch_size / dt
    print(f"queued {args.batches} requests × {args.batch_size} rows from "
          f"{n_clients} clients over {len(engines)} replica(s) in "
          f"{dt:.2f}s → {qps:,.0f} q/s; "
          f"{summary.get('batches', 0)} dispatches, "
          f"{summary.get('rows_per_dispatch', 0):.0f} rows/dispatch, "
          f"{summary['reissues']} re-issues, {summary['failures']} failures")
    out = {"qps": qps, "dispatches": summary.get("batches", 0),
           "rows_per_dispatch": summary.get("rows_per_dispatch", 0.0),
           "reissues": summary["reissues"],
           "failures": summary["failures"],
           "dispatch_failures": summary["dispatch_failures"],
           "degraded_dispatches": summary["degraded_dispatches"],
           "failed_requests": len(errors),
           "queries": payloads,
           "answers": [results.get(i) for i in range(len(payloads))]}
    # frontier occupancy across the fleet: each replica's last_counters
    # carries the per-step live/padded lane tallies its engines recorded,
    # so live/(live+padded) is the padded-work fraction the adaptive caps
    # policy is shaving (1.0 when no engine recorded occupancy)
    # fetched to the host first: replicas live on disjoint device groups
    import jax
    ctrs = [jax.device_get(e.last_counters) for e in engines
            if getattr(e, "last_counters", None) is not None]
    if ctrs:
        total = ctrs[0]
        for c in ctrs[1:]:
            total = total + c
        occ = total.occupancy()
        esc = int(np.asarray(total.escalations).sum())
        out["occupancy"] = occ
        out["escalations"] = esc
        print(f"frontier occupancy {occ:.1%} "
              f"(live/(live+padded) lanes over the last batch per replica); "
              f"{esc} overflow escalation(s)")
    if args.chaos:
        print(f"chaos: {injector.injected['exceptions']} injected "
              f"exceptions, {injector.injected['delays']} injected delays "
              f"→ {summary['retries']} retries, {summary['quarantines']} "
              f"quarantine(s), {summary['degraded_dispatches']} degraded "
              f"dispatches, {summary['deadline_exceeded']} deadline "
              f"failures; health: {summary['health']}; "
              f"{out['failed_requests']} failed requests")
        out.update(
            injected_exceptions=injector.injected["exceptions"],
            injected_delays=injector.injected["delays"],
            retries=summary["retries"],
            quarantines=summary["quarantines"],
            deadline_exceeded=summary["deadline_exceeded"])
        # the robustness contract: chaos must never surface to clients
        assert out["failed_requests"] == 0, \
            f"{out['failed_requests']} requests failed under chaos"
        if args.dryrun:
            # a smoke whose plan never fired proves nothing — the CI specs
            # are sized (batches / max_batch above) so their clauses arm
            assert injector.injected["exceptions"] \
                + injector.injected["delays"] > 0, \
                "chaos dryrun injected nothing — plan never armed"
    return out


# spec name → serve runner; every registered OperatorSpec must be servable
RUNNERS = {
    "select": _serve_select,
    "join": _serve_join,
    "knn": _serve_knn,
    "knn_join": _serve_knn_join,
    "knn_filtered": _serve_knn_filtered,
    "browse": _serve_browse,
}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", default="spatial",
                    choices=sorted(MODE_TO_SPEC) + ["lm"])
    ap.add_argument("--k", type=int, default=8,
                    help="neighbors per query/batch (knn / knn-join / "
                         "browse modes)")
    ap.add_argument("--query-eps", type=float, default=0.002,
                    help="half-extent of the outer query rects "
                         "(knn-join / join modes)")
    ap.add_argument("--filter-eps", type=float, default=0.2,
                    help="half-extent of the per-query filter window "
                         "(knn-filtered mode)")
    ap.add_argument("--mesh", default="auto", choices=("auto", "on", "off"),
                    help="mesh dispatcher: one shard_map program per batch "
                         "over the model axis (auto: when devices > 1; "
                         "force devices with XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N)")
    ap.add_argument("--queue", action="store_true",
                    help="async continuous-batching service: coalesce "
                         "concurrent client requests into pow2 buckets and "
                         "amortize one mesh dispatch over all of them "
                         "(launch/queue.py; select/knn/knn-join/"
                         "knn-filtered)")
    ap.add_argument("--clients", type=int, default=8,
                    help="closed-loop client threads driving the queue")
    ap.add_argument("--chaos", default="",
                    help="seeded fault-injection spec for the queued "
                         "replicas (runtime/faults.py): comma-separated "
                         "kill:rI@N, crash:rI@N, slow:rI@N:SECS, "
                         "flaky:rI:P, spike:rI:P:SECS — the run asserts "
                         "zero client-visible failures")
    ap.add_argument("--replicas", type=int, default=1,
                    help="replica fan-out on the data mesh axis: R engine "
                         "copies over disjoint device groups (mesh path "
                         "only) — the straggler pool re-issues across them")
    ap.add_argument("--max-batch", type=int, default=256,
                    help="coalescing target in query rows per dispatch")
    ap.add_argument("--max-delay", type=float, default=0.002,
                    help="max seconds the queue waits to fill a batch")
    ap.add_argument("--depth", type=int, default=2,
                    help="in-flight dispatches per replica (2 = double-"
                         "buffered)")
    ap.add_argument("--browse-steps", type=int, default=4,
                    help="next_batch() calls per browse session")
    ap.add_argument("--join-cap", type=int, default=1 << 17,
                    help="result-pair capacity (join mode)")
    ap.add_argument("--n", type=int, default=200_000)
    ap.add_argument("--partitions", type=int, default=8)
    ap.add_argument("--fanout", type=int, default=64)
    ap.add_argument("--layout", default="d1", choices=layout_names(),
                    help="physical node layout for the whole fleet (d3: "
                         "uint16-quantized MBRs, ~4x children per memory "
                         "block, conservative prune + exact leaf re-check)")
    ap.add_argument("--batches", type=int, default=20)
    ap.add_argument("--batch-size", type=int, default=64)
    ap.add_argument("--selectivity", type=float, default=0.001)
    ap.add_argument("--deadline", type=float, default=5.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--dryrun", action="store_true",
                    help="tiny sizes: the CI smoke that instantiates every "
                         "registered OperatorSpec through serve")
    args = ap.parse_args(argv)

    if args.dryrun:
        args.n = min(args.n, 2000)
        args.partitions = min(args.partitions, 2)
        args.fanout = min(args.fanout, 16)
        # chaos smokes need enough dispatches for @N clauses to arm and for
        # the breaker to trip (quarantine_after consecutive failures), and
        # coalescing must not fold the whole run into a handful of
        # dispatches — cap the batch at one request per dispatch
        args.batches = min(args.batches,
                           20 if args.chaos else (4 if args.queue else 2))
        args.batch_size = min(args.batch_size, 8)
        args.k = min(args.k, 4)
        args.browse_steps = min(args.browse_steps, 2)
        args.join_cap = min(args.join_cap, 1 << 15)
        args.max_batch = min(args.max_batch,
                             args.batch_size if args.chaos else 32)
        args.clients = min(args.clients, 4)
        # CI smoke boxes are slow and shared: a lapsed deadline would only
        # add spurious re-issue work to the dryrun, never find a bug
        args.deadline = max(args.deadline, 60.0)

    from . import compile_cache
    compile_cache.enable()
    if args.mode == "lm":
        return _serve_lm(args)
    spec = traversal.get_spec(MODE_TO_SPEC[args.mode])
    missing = set(traversal.spec_names()) - set(RUNNERS)
    assert not missing, f"registered specs without a serve runner: {missing}"
    if args.queue:
        from .queue import QUEUEABLE_OPS
        if spec.name in QUEUEABLE_OPS:
            return _serve_queued(args, spec)
        print(f"--queue: {spec.name} does not coalesce (session/query-less "
              f"operator); serving synchronously")
    return RUNNERS[spec.name](args, spec)


def _serve_lm(args):
    import jax
    import jax.numpy as jnp
    from repro.configs import registry
    from repro.models.model import Model
    from repro.serve.serve_step import generate

    cfg = registry.reduced_config(registry.get("tinyllama-1.1b"))
    model = Model(cfg)
    params = model.init_params(jax.random.PRNGKey(args.seed))
    rng = np.random.default_rng(args.seed)
    toks = rng.integers(0, cfg.vocab, (args.batch_size, 32),
                        dtype=np.int32)
    t0 = time.time()
    out = generate(model, params, {"tokens": jnp.asarray(toks)}, n_new=16)
    dt = time.time() - t0
    tps = args.batch_size * 16 / dt
    print(f"LM decode service: {args.batch_size} seqs × 16 new tokens in "
          f"{dt:.2f}s → {tps:,.0f} tok/s; sample: {np.asarray(out[0])[:8]}")
    return {"tok_per_s": tps}


if __name__ == "__main__":
    main()
