"""Spec-driven BFS traversal engine — one level-synchronous core for every
R-tree operator.

The paper's central observation is that all R-tree query operators reduce to
the same SIMD skeleton: score a node block, prune, emit, descend.  This
module is that skeleton, once:

  ``OperatorSpec``   — the static description of an operator: its score
                       stage kind (intersect-mask vs MINDIST/MINMAXDIST),
                       its per-level dispatch ``StageModel``, its default
                       caps policy, its builder, and serve metadata.  Specs
                       live in a registry (``register``/``get_spec``) so
                       distributed sharding and the serve launcher resolve
                       operators by name instead of hard-coded imports.
  ``make_mask_engine``     — the level loop for the mask operators (range
                       select, spatial join): score → compress-store
                       compaction → descend.  The join's pair frontier is
                       the same loop with two parallel id streams.
  ``make_distance_engine`` — the level loop for the distance operators
                       (kNN, kNN-join): score → τ top-k tightening →
                       MINDIST prune → best-first beam enqueue → leaf
                       top-k.
  ``make_browse_engine``   — the *resume* entry point: the same distance
                       level loop, run from a ``BrowseState`` pytree
                       (candidate pool + per-level deferred beams + lost
                       bound) so distance browsing (Hjaltason–Samet
                       incremental NN) emits k at a time without
                       restarting from the root.  No operator defines a
                       BFS loop of its own.

Both engines also own the fused whole-level routing (``fused=True`` runs
one device program per level and consumes only compacted outputs + tallies)
and derive ``Counters.dispatches`` from the owning spec's ``StageModel`` —
the single source of truth the tests validate against.

Operator modules register their spec at import time; use ``build(name,
*trees, **params)`` as the generic engine entry point (the preserved
``make_*_bfs`` wrappers route through the same builders, so the two entries
are bit-identical — asserted across the oracle matrix by tests/oracle.py).
"""
from __future__ import annotations

import dataclasses
import importlib
from typing import Callable, Dict, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from .compaction import _scatter_compact, beam_rows, scatter_append
from .counters import OCC_STEPS, Counters, StageModel, occupancy_zeros
from .geometry import DIST_PAD, DIST_VALID_MAX
from ..runtime import trace


def _occ_record(occ_live, occ_padded, *, step: int, valid, width: int,
                batch: int):
    """Fold one level's frontier occupancy into the per-step vectors:
    ``valid`` is the (B, width) liveness mask of the frontier the level
    scored; padded slots are the allocated-but-empty remainder of the
    ``width`` slots scored per row (a device scalar for the blocked leaf
    step, which scores only the blocks that hold a live node)."""
    slot = min(step, OCC_STEPS - 1)
    live = valid.sum().astype(jnp.int32)
    total = jnp.asarray(batch * width, jnp.int32)
    return (occ_live.at[slot].add(live),
            occ_padded.at[slot].add(total - live))


# ---------------------------------------------------------------------------
# Operator specs + registry
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class OperatorSpec:
    """Static description of one traversal operator.

    ``kind`` selects the engine: 'mask' (boolean qualify + compress-store
    emission) or 'distance' (MINDIST/MINMAXDIST scoring + τ/beam emission).
    ``stage_model`` is the per-level dispatch accounting the engine charges
    (see counters.StageModel).  ``builder`` is the public factory — the
    ``make_*_bfs`` wrapper — so ``build(name, ...)`` and the wrapper are the
    same code path.  ``caps_policy`` is the operator's default frontier-caps
    function (core/caps.py).  ``query_width`` is serve metadata: columns per
    query row (2 points, 4 rects, None for the query-less join), and
    ``leaf_enqueue`` marks mask operators whose final-level emission counts
    into ``Counters.enqueued`` (the join's result pairs are enqueued work;
    select's leaf hits are results, not queue insertions).
    """
    name: str
    kind: str
    stage_model: StageModel
    builder: Callable
    caps_policy: Optional[Callable] = None
    query_width: Optional[int] = None
    leaf_enqueue: bool = False
    description: str = ""


_REGISTRY: Dict[str, OperatorSpec] = {}

# modules that register specs on import — imported lazily so the registry
# is complete whenever it is consulted, without import cycles
_OPERATOR_MODULES = (
    "repro.core.select_vector",
    "repro.core.join_vector",
    "repro.core.knn_vector",
    "repro.core.knn_join_vector",
    "repro.core.knn_filtered",
    "repro.core.knn_browse",
)


def register(spec: OperatorSpec) -> OperatorSpec:
    _REGISTRY[spec.name] = spec
    return spec


def _ensure_registered() -> None:
    for mod in _OPERATOR_MODULES:
        importlib.import_module(mod)


def get_spec(name: str) -> OperatorSpec:
    _ensure_registered()
    if name not in _REGISTRY:
        raise KeyError(
            f"unknown operator spec {name!r}; registered: "
            f"{sorted(_REGISTRY)}")
    return _REGISTRY[name]


def spec_names() -> Tuple[str, ...]:
    _ensure_registered()
    return tuple(sorted(_REGISTRY))


def specs() -> Tuple[OperatorSpec, ...]:
    _ensure_registered()
    return tuple(_REGISTRY[n] for n in sorted(_REGISTRY))


def build(name: str, *trees, **params):
    """Generic engine entry point: build operator ``name`` over ``trees``
    with the spec's builder (identical to calling the ``make_*_bfs``
    wrapper directly)."""
    return get_spec(name).builder(*trees, **params)


# ---------------------------------------------------------------------------
# Mask-kind engine (range select, spatial join)
# ---------------------------------------------------------------------------

def _apply_delta(acc: dict, delta: Optional[dict], *, fcnt, f, stages, hits):
    """Fold one level's score-stage counter contributions into ``acc``.

    ``delta=None`` selects the default dense model (every frontier node
    evaluates all F lanes over ``stages`` compare stages); a spec whose
    score stage models pruned work (the join's O3/O4/O5) returns its own
    partial tallies instead.
    """
    if delta is None:
        n = fcnt.sum()
        acc["nodes_visited"] = acc["nodes_visited"] + n
        acc["predicates"] = acc["predicates"] + n * f * stages
        acc["vector_ops"] = acc["vector_ops"] + n * stages
        acc["masked_waste"] = acc["masked_waste"] + n * f - hits
    else:
        for key, val in delta.items():
            acc[key] = acc[key] + val


def _live_extent_blocks(valid: jax.Array, block: int) -> jax.Array:
    """Blocks of ``block`` frontier slots, from slot 0, that cover the last
    live slot of any row of the (B, W) liveness mask ``valid``."""
    slot = jnp.arange(1, valid.shape[1] + 1, dtype=jnp.int32)
    extent = jnp.max(jnp.where(valid, slot, 0))
    return (extent + block - 1) // block


def _blocked_leaf(score, ctx, frontier, qargs, *, block: int,
                  n_blocks: jax.Array, result_cap: int, emit: bool):
    """The leaf step of a mask engine, ``block`` frontier slots at a time.

    Scores blocks 0 .. ``n_blocks`` - 1 of the (B, W) leaf frontier (W
    padded to whole blocks) in a ``lax.while_loop`` and appends each
    block's hits to a (B, result_cap + 1) buffer at each row's running
    count, overflow parking in the spare slot as ``_scatter_compact`` does.
    Blocks are visited in frontier order, so the ids and their order are
    those of one dense compaction of the whole level; memory is one block's
    (B, block * F) lanes plus the result buffer, whatever W.  Returns
    (values | None, counts (B,) — totals, may exceed result_cap, hits, f,
    stages)."""
    b, w = frontier[0].shape
    pad = -w % block
    frontier = tuple(jnp.pad(fr, ((0, 0), (0, pad)), constant_values=-1)
                     for fr in frontier)
    static = {}

    def body(carry):
        j, bufs, counts = carry
        blk = tuple(jax.lax.dynamic_slice_in_dim(fr, j * block, block, 1)
                    for fr in frontier)
        mask, values, f, stages, delta = score(ctx, 0, blk, qargs)
        if delta is not None:
            raise ValueError("the blocked leaf step takes the dense "
                             "counter model only")
        static.update(f=f, stages=stages)
        if emit:
            bufs, c = scatter_append(bufs, values, mask, counts, -1)
        else:
            c = mask.sum(axis=1).astype(jnp.int32)
        return j + 1, tuple(bufs), counts + c

    bufs = tuple(jnp.full((b, result_cap + 1), -1, jnp.int32)
                 for _ in frontier) if emit else ()
    _, bufs, counts = jax.lax.while_loop(
        lambda carry: carry[0] < n_blocks, body,
        (jnp.int32(0), bufs, jnp.zeros((b,), jnp.int32)))
    res = tuple(buf[:, :result_cap] for buf in bufs) if emit else None
    return res, counts, counts.sum(), static["f"], static["stages"]


def make_mask_engine(spec: OperatorSpec, *, height: int,
                     caps: Sequence[int], result_cap: int, score,
                     fused_level=None, count_only: bool = False,
                     n_streams: int = 1, leaf_block: Optional[int] = None):
    """Build the jitted level loop for a mask operator.

    ``score(ctx, li, frontier, qargs)`` → (mask (B, M) bool, values — an
    ``n_streams``-tuple of (B, M) int32 to compact under the mask, f,
    stages, delta).  ``fused_level(ctx, li, frontier, qargs, cap)`` → the
    whole-level alternative: (values — tuple of (B, cap), qcnt (B,),
    overflow (B,), f, stages, delta); the engine then only routes compacted
    frontiers.  ``leaf_block``: an unfused leaf frontier wider than this
    many slots is scored in blocks of it (``_blocked_leaf``), only as far
    as its last live slot; the occupancy counters then count the slots of
    the blocks scored.  Returns ``run(ctx, *qargs)`` → (values | None,
    counts, Counters).
    """
    caps = tuple(caps)
    sm = spec.stage_model

    @jax.jit
    def run(ctx, *qargs):
        b = qargs[0].shape[0] if qargs else 1
        frontier = tuple(jnp.zeros((b, 1), jnp.int32)
                         for _ in range(n_streams))  # root
        acc = {k: jnp.int32(0) for k in
               ("nodes_visited", "predicates", "vector_ops", "masked_waste",
                "pruned_outer", "pruned_inner")}
        enq = jnp.int32(0)
        disp = 0
        ovf = jnp.zeros((b,), bool)
        counts = jnp.zeros((b,), jnp.int32)
        occ_live = occupancy_zeros()
        occ_padded = occupancy_zeros()
        res = None
        for li in range(height - 1, -1, -1):
            leaf = li == 0
            cap = result_cap if leaf else caps[height - 1 - li]
            fvalid = frontier[0] >= 0
            fcnt = fvalid.sum(axis=1)
            width = frontier[0].shape[1]
            blocked = (leaf and fused_level is None and leaf_block is not None
                       and width > leaf_block)
            if blocked:
                n_blocks = _live_extent_blocks(fvalid, leaf_block)
                width = n_blocks * leaf_block
            occ_live, occ_padded = _occ_record(
                occ_live, occ_padded, step=height - 1 - li, valid=fvalid,
                width=width, batch=b)
            if fused_level is not None:
                vals, qcnt, o, f, stages, delta = fused_level(
                    ctx, li, frontier, qargs, cap)
                hits = qcnt.sum()
                disp += sm.fused
                if leaf:
                    counts = qcnt
                    if not count_only:
                        res = vals
                        ovf = ovf | o
                    if spec.leaf_enqueue:
                        enq = enq + hits
                else:
                    frontier = vals
                    ovf = ovf | o
                    enq = enq + hits
            else:
                if blocked:
                    outs, qcnt, hits, f, stages = _blocked_leaf(
                        score, ctx, frontier, qargs, block=leaf_block,
                        n_blocks=n_blocks, result_cap=result_cap,
                        emit=not count_only)
                    delta = None
                else:
                    mask, values, f, stages, delta = score(ctx, li, frontier,
                                                           qargs)
                    hits = mask.sum()
                    qcnt = mask.sum(axis=1).astype(jnp.int32)
                disp += sm.leaf if leaf else sm.inner
                if leaf:
                    counts = qcnt
                    if not count_only:
                        if not blocked:
                            outs = _scatter_compact(values, mask,
                                                    result_cap, -1)[0]
                        res = tuple(outs)
                        ovf = ovf | (counts > result_cap)
                    if spec.leaf_enqueue:
                        enq = enq + hits
                else:
                    outs, _, o = _scatter_compact(values, mask, cap, -1)
                    frontier = tuple(outs)
                    ovf = ovf | o
                    enq = enq + hits
            _apply_delta(acc, delta, fcnt=fcnt, f=f, stages=stages,
                         hits=hits)
        ctr = Counters(enqueued=enq, overflow=ovf.any().astype(jnp.int32),
                       dispatches=jnp.int32(disp), lanes_live=occ_live,
                       lanes_padded=occ_padded, **acc)
        return res, counts, ctr

    return run


# ---------------------------------------------------------------------------
# Distance-kind engine (kNN, kNN-join) — fixed-k descent
# ---------------------------------------------------------------------------

def make_distance_engine(spec: OperatorSpec, *, height: int, k: int,
                         caps: Sequence[int], score, fused_level=None):
    """Build the jitted level loop for a distance operator.

    ``score(ctx, li, ids, queries, leaf)`` → (mindist (B, C, F),
    minmaxdist (B, C, F) | None at the leaf, child_ids (B, C, F), stages)
    with DIST_PAD on invalid lanes.  The engine owns τ tightening to the
    k-th smallest MINMAXDIST, MINDIST pruning, the best-first beam enqueue
    (overflow degrades to approximate-with-bound), leaf top-k extraction,
    and all counter accounting — so τ soundness and beam semantics can
    never drift between the distance operators.

    ``fused_level(ctx, li, ids, queries, tau, leaf, cap)`` runs the whole
    level — scoring AND the τ/prune/beam emission — as one device program:
      internal → (next_ids (B, cap), τ (B,), valid_cnt (B,), keep_cnt (B,))
      leaf     → (res_ids (B, k), res_d (B, k), valid_cnt (B,))
    Counter semantics stay identical to the unfused path except
    ``dispatches``.

    The returned ``run(ctx, queries, tau_init=None, active=None)`` accepts
    two optional per-query SPMD hooks used by the mesh path
    (``make_mesh_engine``): ``tau_init`` (B,) seeds the pruning bound below
    DIST_PAD (sound whenever the seed upper-bounds the query's k-th
    neighbor — the phase-2 refinement descends under the collective phase-1
    τ), and ``active`` (B,) bool masks queries out of the descent entirely
    (their root frontier starts empty, so they cost no node visits and
    return (-1, +inf) rows).  Both default to the historical behaviour.
    """
    caps = tuple(caps)
    sm = spec.stage_model

    @jax.jit
    def run(ctx, queries: jax.Array, tau_init=None, active=None):
        b = queries.shape[0]
        ids = jnp.zeros((b, 1), jnp.int32)  # root frontier
        if active is not None:
            ids = jnp.where(active[:, None], ids, -1)
        tau = jnp.full((b,), DIST_PAD, jnp.float32)
        if tau_init is not None:
            tau = jnp.minimum(tau, jnp.asarray(tau_init, jnp.float32))
        nodes = jnp.int32(0)
        preds = jnp.int32(0)
        vops = jnp.int32(0)
        enq = jnp.int32(0)
        pruned = jnp.int32(0)
        waste = jnp.int32(0)
        disp = 0
        ovf = jnp.zeros((b,), bool)
        occ_live = occupancy_zeros()
        occ_padded = occupancy_zeros()
        res_ids = res_d = None
        for li in range(height - 1, -1, -1):
            leaf = li == 0
            fvalid = ids >= 0
            fcnt = fvalid.sum(axis=1)
            nodes = nodes + fcnt.sum()
            occ_live, occ_padded = _occ_record(
                occ_live, occ_padded, step=height - 1 - li, valid=fvalid,
                width=ids.shape[1], batch=b)
            if fused_level is not None:
                cap = k if leaf else caps[height - 1 - li]
                out = fused_level(ctx, li, ids, queries, tau, leaf, cap)
                f = out[-1]
                out = out[:-1]
                stages = 4                      # fused kernels are D1-only
                ev = stages if leaf else 2 * stages
                preds = preds + fcnt.sum() * f * ev
                vops = vops + fcnt.sum() * ev
                disp += sm.fused
                if leaf:
                    res_ids, res_d, valid_cnt = out
                    waste = waste + fcnt.sum() * f - valid_cnt.sum()
                else:
                    ids, tau, valid_cnt, keep_cnt = out
                    waste = waste + fcnt.sum() * f - valid_cnt.sum()
                    pruned = pruned + (valid_cnt.sum() - keep_cnt.sum())
                    enq = enq + keep_cnt.sum()
                    ovf = ovf | (keep_cnt > cap)
                continue
            md, mmd, ptr, stages = score(ctx, li, ids, queries, leaf)
            f = md.shape[-1]
            # internal levels evaluate BOTH mindist and minmaxdist per lane
            # (the scalar baseline counts both too); the leaf needs only
            # mindist — keep the scalar-vs-vector predicate ratio honest
            ev = stages if leaf else 2 * stages
            preds = preds + fcnt.sum() * f * ev
            vops = vops + fcnt.sum() * ev
            entry_valid = md < DIST_VALID_MAX
            waste = waste + fcnt.sum() * f - entry_valid.sum()
            flat_d = md.reshape(b, -1)
            flat_ptr = ptr.reshape(b, -1)
            if leaf:
                disp += sm.leaf
                if flat_d.shape[1] < k:   # k > total leaf candidates
                    pad = k - flat_d.shape[1]
                    flat_d = jnp.concatenate(
                        [flat_d, jnp.full((b, pad), DIST_PAD, flat_d.dtype)],
                        axis=1)
                    flat_ptr = jnp.concatenate(
                        [flat_ptr, jnp.full((b, pad), -1, flat_ptr.dtype)],
                        axis=1)
                neg_d, pos = jax.lax.top_k(-flat_d, k)
                res_d = -neg_d
                res_ids = jnp.take_along_axis(flat_ptr, pos, axis=1)
                found = res_d < DIST_VALID_MAX
                res_ids = jnp.where(found, res_ids, -1)
                res_d = jnp.where(found, res_d, jnp.inf)
            else:
                disp += sm.inner
                mflat = mmd.reshape(b, -1)
                # τ soundness needs k *distinct* children within the bound
                # (each guarantees one object).  With fewer than k lanes the
                # truncated quantile would only guarantee C·F objects, so
                # skip tightening; when lanes ≥ k but valid children < k the
                # DIST_PAD lanes push the k-th value huge — no-op, sound.
                if mflat.shape[1] >= k:
                    kth = -jax.lax.top_k(-mflat, k)[0][:, k - 1]
                    tau = jnp.minimum(tau, kth)
                keep = entry_valid & (md <= tau[:, None, None])
                pruned = pruned + (entry_valid.sum() - keep.sum())
                cap = caps[height - 1 - li]
                # best-first beam enqueue: on overflow keep the cap best-
                # MINDIST children per query (approximate-with-bound) instead
                # of dropping by lane position
                ids, _, o = beam_rows(flat_ptr, flat_d, keep.reshape(b, -1),
                                      cap)
                ovf = ovf | o
                enq = enq + keep.sum()
        ctr = Counters(nodes_visited=nodes, predicates=preds, vector_ops=vops,
                       enqueued=enq, pruned_inner=pruned, masked_waste=waste,
                       overflow=ovf.any().astype(jnp.int32),
                       dispatches=jnp.int32(disp), lanes_live=occ_live,
                       lanes_padded=occ_padded)
        return res_ids, res_d, ctr

    return run


# ---------------------------------------------------------------------------
# Two-tier overflow-escalating engines
# ---------------------------------------------------------------------------

def make_escalating_engine(build, tight_caps: Sequence[int],
                           full_caps: Sequence[int], *,
                           stick_after: int = 3):
    """Wrap an operator's engine builder into a two-tier overflow-escalating
    runner.

    ``build(caps)`` must return the operator's bound runner (``run(*args,
    **kw) → (..., Counters)``) compiled for the given frontier caps.  The
    tight tier is compiled immediately from the occupancy-adaptive caps
    (core/caps.adaptive_caps — sized from the tree's true per-level node
    counts and lane floors); the full static-caps tier is compiled lazily,
    the first time a batch escalates.

    Every batch runs on the tight tier first.  Overflow is detected
    in-program — the engines' ``Counters.overflow`` flag covers frontier,
    beam, and result-tally overflow — and read back as one scalar; an
    overflowed batch is re-run on the full tier, whose result *is* the
    static-caps result.  A batch that does not overflow on the tight tier
    is bit-identical to the static path by construction: every live entry
    survived compaction in the same relative order, and padded slots never
    reach an emission stage (asserted across the oracle matrix per
    layout × operator cell).  The escalated run's ``Counters.escalations``
    is bumped so the serve/bench layers can see the fallback rate.  The
    overflow read-back is the span ``repro.engine.overflow_check``.

    Hysteresis guard: a workload whose frontiers chronically exceed the
    tight caps would otherwise pay BOTH tiers on every batch.  After
    ``stick_after`` consecutive escalations the runner pins itself to the
    full tier (steady-state latency equals the static engine, recorded via
    ``stuck()``); the occupancy-adaptive sizing is a bet on the common
    case, never a tax on the adversarial one.  A pinned call runs the full
    tier once, with no read-back, and is no escalation.  The counter
    ``repro.engine.full_tier_calls`` counts every run of the full tier,
    escalated or pinned.

    The returned runner exposes ``tight_caps`` / ``full_caps``,
    ``escalation_count()`` and ``stuck()`` for observability.  It is a
    host-side wrapper (it branches on a device scalar), so it must not be
    called under a trace — mesh/shard_map paths build single-tier engines
    instead (``make_mesh_engine`` pins ``caps_mode='static'``).
    """
    tight_caps = tuple(int(c) for c in tight_caps)
    full_caps = tuple(int(c) for c in full_caps)
    tight = build(tight_caps)
    state = {"full": None, "escalations": 0, "streak": 0}

    def run(*args, **kw):
        if state["streak"] >= stick_after:
            trace.add("repro.engine.full_tier_calls")
            return state["full"](*args, **kw)
        out = tight(*args, **kw)
        with trace.span("repro.engine.overflow_check"):
            overflowed = bool(jax.device_get(out[-1].overflow))
        if overflowed:
            if state["full"] is None:
                state["full"] = build(full_caps)
            trace.add("repro.engine.full_tier_calls")
            out = state["full"](*args, **kw)
            state["escalations"] += 1
            state["streak"] += 1
            ctr = dataclasses.replace(
                out[-1], escalations=out[-1].escalations + 1)
            out = out[:-1] + (ctr,)
        else:
            state["streak"] = 0
        return out

    run.tight_caps = tight_caps
    run.full_caps = full_caps
    run.escalation_count = lambda: state["escalations"]
    run.stuck = lambda: state["streak"] >= stick_after
    return run


def maybe_escalating(build, tight_caps, full_caps):
    """``make_escalating_engine`` unless the two tiers coincide (small
    trees where the node-count clamp already equals the static caps) — then
    the single-tier engine is returned directly."""
    tight_caps = tuple(int(c) for c in tight_caps)
    full_caps = tuple(int(c) for c in full_caps)
    if tight_caps == full_caps:
        return build(tight_caps)
    return make_escalating_engine(build, tight_caps, full_caps)


# ---------------------------------------------------------------------------
# Mesh entry point — the whole partition fan-out as ONE SPMD program
# ---------------------------------------------------------------------------

def _route_mindist(spec: OperatorSpec, queries: jax.Array, mbrs: jax.Array):
    """(B, P) squared MINDIST from each query to each partition MBR — the
    replicated root-router step, computed in-program.  ``query_width``
    selects the distance form: 4 → rect-to-rect, otherwise the leading two
    columns are a point (covers kNN and the filtered-kNN 6-column rows)."""
    from .geometry import mindist, mindist_rect
    if spec.query_width == 4:
        return mindist_rect(
            queries[:, 0, None], queries[:, 1, None], queries[:, 2, None],
            queries[:, 3, None], mbrs[None, :, 0], mbrs[None, :, 1],
            mbrs[None, :, 2], mbrs[None, :, 3])
    return mindist(queries[:, 0, None], queries[:, 1, None],
                   mbrs[None, :, 0], mbrs[None, :, 1],
                   mbrs[None, :, 2], mbrs[None, :, 3])


def make_mesh_engine(name: str, stacked_tree, ids_map, *, mesh,
                     axis: str = "model", outer_tree=None, **params):
    """Build the mesh-sharded SPMD program for any registered operator.

    ``stacked_tree`` is an ``RTree`` pytree whose leaves carry a leading
    partition axis (P, ...) — P partition trees padded to one shape and
    chain-elevated to one height (distributed/forest.pack_forest), with P a
    multiple of the mesh axis size.  ``ids_map`` (P, n_max) maps each
    partition's local rect ids to global ids (-1 pad).  ``outer_tree`` is an
    optional *replicated* second tree (the spatial join's outer relation).

    The returned callable runs the whole batch as ONE ``shard_map`` program
    over ``axis``: each shard vmaps the spec's builder over its local
    partition block (the registry supplies the per-partition engine — no
    per-operator code here), and cross-shard merging happens with
    collectives (distributed/collectives.py), never on the host:

      mask kind     — every shard answers the full batch against its
                      partitions (a non-intersecting partition yields zero
                      rows by construction); local results are mapped to
                      global ids and all-gathered → (P, ...) stacked rows.
      distance kind — overlapped two-phase routing: phase 1 answers each
                      query on its primary partition (arg-min router
                      MINDIST, computed in-program from the stacked root
                      MBRs); the per-query k-th distance is merged with an
                      all-gather + (distance, id) top-k, and phase 2
                      re-descends only (query, partition) pairs within the
                      collective τ bound — seeded into the engine as
                      ``tau_init`` so refinement prunes under phase-1's
                      result instead of re-discovering it.  There is no
                      host barrier between the phases; both run inside the
                      same program, so per-batch dispatches stay O(levels)
                      (2 descents of the spec's StageModel), not
                      O(partitions × levels).

    Returns ``run(queries)`` → distance kind: (global ids (B, k), dists
    (B, k), merged Counters); mask kind: (global values (P, B?, cap) per
    stream, counts, merged Counters) — the host dispatcher flattens rows.
    Counters merge work fields across partitions and shards but keep
    ``dispatches``/``overflow`` as max (see collectives.psum_counters).
    """
    from jax.sharding import PartitionSpec as P

    from repro.distributed import collectives as coll

    spec = get_spec(name)
    if name == "browse":
        raise ValueError("browse is resumable, not one-shot — use "
                         "knn_browse.make_sharded_browse for the "
                         "distributed cursor")
    n_dev = mesh.shape[axis]
    p_total = ids_map.shape[0]
    if p_total % n_dev:
        raise ValueError(f"partition count {p_total} not a multiple of the "
                         f"mesh axis {axis!r} size {n_dev}")
    p_local = p_total // n_dev
    k = params.get("k")
    # escalation branches on a host scalar — impossible under the shard_map
    # trace — so mesh engines always compile the single static-caps tier
    # (bit-identical to the escalating host path by construction)
    params = dict(params)
    params.setdefault("caps_mode", "static")

    def _local_engine(tree, active=None, tau_init=None, queries=None):
        """Instantiate the spec's builder on one partition's tree and run
        it — called under vmap over the local partition block."""
        trees = (outer_tree, tree) if outer_tree is not None else (tree,)
        fn = spec.builder(*trees, **params)
        if spec.kind == "distance":
            return fn(queries, tau_init=tau_init, active=active)
        return fn(queries) if queries is not None else fn()

    def _globalize(ids, idmap):
        return jnp.where(ids >= 0,
                         idmap[jnp.maximum(ids, 0)].astype(jnp.int32), -1)

    # ---- mask kind: full-batch fan-out + all-gather ----
    def _mask_body(tree_blk, idmap_blk, *qargs):
        queries = qargs[0] if qargs else None

        def one(tree_leaves, idmap):
            out = _local_engine(tree_leaves, queries=queries)
            if name == "join":
                pairs, n_pairs, ctr = out
                gpairs = jnp.stack(
                    [pairs[:, 0], _globalize(pairs[:, 1], idmap)], axis=1)
                return gpairs, n_pairs, ctr
            ids, counts, ctr = out
            return _globalize(ids, idmap), counts, ctr

        vals, counts, ctr = jax.vmap(one)(tree_blk, idmap_blk)
        vals = coll.gather_partitions(vals, axis)
        counts = coll.gather_partitions(counts, axis)
        ctr = coll.psum_counters(coll.merge_stacked_counters(ctr), axis)
        return vals, counts, ctr

    # ---- distance kind: overlapped two-phase inside one program ----
    def _dist_body(tree_blk, idmap_blk, queries):
        b = queries.shape[0]
        mbr_local = tree_blk.levels[-1].node_mbr[:, 0, :]      # (Pl, 4)
        mbrs = coll.gather_partitions(mbr_local, axis)         # (P, 4)
        dmat = _route_mindist(spec, queries, mbrs)             # (B, P)
        primary = jnp.argmin(dmat, axis=1).astype(jnp.int32)
        gidx = (jax.lax.axis_index(axis) * p_local
                + jnp.arange(p_local, dtype=jnp.int32))        # (Pl,)
        # same math as the gathered columns, no cross-shard gather needed
        dmat_local = _route_mindist(spec, queries, mbr_local).T  # (Pl, B)

        def one(tree_leaves, idmap, active, tau0):
            ids, d, ctr = _local_engine(tree_leaves, active=active,
                                        tau_init=tau0, queries=queries)
            return _globalize(ids, idmap), d, ctr

        def shard_merge(gids, d):
            """(Pl, B, k) per-partition streams → replicated (B, k) global
            top-k by (distance, id)."""
            l_ids, l_d = coll.topk_by_distance(
                gids.transpose(1, 0, 2).reshape(b, -1),
                d.transpose(1, 0, 2).reshape(b, -1), k)
            g_ids, g_d = coll.gather_partitions((l_ids[None], l_d[None]),
                                                axis)
            return coll.topk_by_distance(
                g_ids.transpose(1, 0, 2).reshape(b, -1),
                g_d.transpose(1, 0, 2).reshape(b, -1), k)

        # phase 1: primary partitions only
        act1 = primary[None, :] == gidx[:, None]               # (Pl, B)
        g1, d1, c1 = jax.vmap(one, in_axes=(0, 0, 0, None))(
            tree_blk, idmap_blk, act1, None)
        p1_ids, p1_d = shard_merge(g1, d1)
        # collective τ bound: the k-th best distance after phase 1, widened
        # by the same hair as the host router (f32 distances vs the bound)
        tau = p1_d[:, k - 1] * (1.0 + 1e-5) + 1e-30
        # phase 2: τ-bounded secondary fan-out, seeded with the bound so the
        # refinement descends under phase-1's result — no host barrier
        act2 = (~act1) & (dmat_local <= tau[None, :])
        g2, d2, c2 = jax.vmap(one, in_axes=(0, 0, 0, None))(
            tree_blk, idmap_blk, act2, tau)
        p2_ids, p2_d = shard_merge(g2, d2)
        f_ids, f_d = coll.topk_by_distance(
            jnp.concatenate([p1_ids, p2_ids], axis=1),
            jnp.concatenate([p1_d, p2_d], axis=1), k)
        # fold partitions within each phase (dispatches: max — one vmapped
        # stage sequence), then ADD the phases (two real descents), then
        # fold shards (psum work / pmax dispatches)
        m1 = coll.merge_stacked_counters(c1)
        m2 = coll.merge_stacked_counters(c2)
        ctr = dataclasses.replace(
            m1 + m2, overflow=jnp.maximum(m1.overflow, m2.overflow))
        ctr = coll.psum_counters(ctr, axis)
        return f_ids, f_d, ctr

    body = _dist_body if spec.kind == "distance" else _mask_body
    # the replicated outer relation (join) rides as a closure constant;
    # P(axis) is a pytree prefix: every stacked-tree leaf shards its
    # leading partition axis
    tree_spec = P(axis)
    in_specs = (tree_spec, P(axis)) + ((P(),) if spec.query_width else ())
    program = jax.jit(jax.shard_map(body, mesh=mesh,
                                    in_specs=in_specs,
                                    out_specs=(P(), P(), P()),
                                    check_vma=False))

    def run(*qargs):
        return program(stacked_tree, ids_map, *qargs)

    return run


# ---------------------------------------------------------------------------
# Resumable distance browsing — the engine's resume entry point
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class BrowseState:
    """Complete traversal state of a distance-browsing session, as a pytree.

    Round-trips through ``jax.tree_util`` (checkpoint, device transfer,
    shard_map, …) and back into ``resume`` without restarting from the
    root:

      queries   — (B, Q) query coordinates (2 points / 4 rects)
      pool_ids/pool_d — (B, pool_cap) scored-but-unemitted leaf candidates,
                  distance-sorted ascending
      def_ids/def_d   — per level (0 … height-1): τ-deferred node beams —
                  children pruned by a past descent, kept with their
                  MINDIST so a later batch can re-activate them
      lost      — (B,) smallest distance ever dropped from any bounded
                  beam; emission at or beyond it flags ``overflow``
                  (approximate-with-bound, mirroring fixed-k semantics)
      emitted   — (B,) neighbors emitted so far
      overflow  — (B,) bool, sticky
      ctr       — accumulated Counters across descents
      descents  — number of resume descents run (dispatch validation)
    """
    queries: jax.Array
    pool_ids: jax.Array
    pool_d: jax.Array
    def_ids: Tuple[jax.Array, ...]
    def_d: Tuple[jax.Array, ...]
    lost: jax.Array
    emitted: jax.Array
    overflow: jax.Array
    ctr: Counters
    descents: jax.Array

    def tree_flatten(self):
        return ((self.queries, self.pool_ids, self.pool_d, self.def_ids,
                 self.def_d, self.lost, self.emitted, self.overflow,
                 self.ctr, self.descents), None)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


class BrowseEngine(NamedTuple):
    """The resumable-browsing engine entry points (see make_browse_engine)."""
    init: Callable
    needs_descent: Callable
    needs_descent_fn: Callable
    resume: Callable
    emit: Callable


def _beam_with_bound(ids: jax.Array, d: jax.Array, mask: jax.Array,
                     cap: int):
    """beam_rows that also returns the kept distances and the smallest
    *dropped* distance (+inf when nothing was dropped) — the browse
    engine's lost-bound bookkeeping."""
    b, m = ids.shape
    d = jnp.where(mask, d, DIST_PAD)
    v = jnp.where(mask, ids, -1)
    if m < cap + 1:
        padn = cap + 1 - m
        d = jnp.concatenate([d, jnp.full((b, padn), DIST_PAD, d.dtype)], 1)
        v = jnp.concatenate([v, jnp.full((b, padn), -1, v.dtype)], 1)
    neg_d, pos = jax.lax.top_k(-d, cap + 1)
    dd = -neg_d
    vv = jnp.take_along_axis(v, pos, axis=1)
    kept_d = dd[:, :cap]
    kept_v = jnp.where(kept_d < DIST_VALID_MAX, vv[:, :cap], -1)
    kept_d = jnp.where(kept_d < DIST_VALID_MAX, kept_d, DIST_PAD)
    dropped = dd[:, cap]
    bound = jnp.where(dropped < DIST_VALID_MAX, dropped, jnp.inf)
    return kept_v, kept_d, bound


def make_browse_engine(spec: OperatorSpec, *, height: int, batch_k: int,
                       caps: Sequence[int], defer_caps: Sequence[int],
                       pool_cap: int, score):
    """Build the resumable distance-browsing engine: the distance level
    loop, parameterized to run *from* and *into* a ``BrowseState``.

    Per resume descent (root → leaf, the same level-synchronous sweep as
    ``make_distance_engine`` — this module defines no second loop shape):

      inject — merge each level's τ-activated deferred nodes
               (MINDIST ≤ τ) into the active frontier
      score  — the operator's score stage, unchanged
      τ      — init to the batch_k-th pool distance (the pool holds real
               objects), tightened per level by the k-th smallest child
               MINMAXDIST — both individually sound bounds on the batch_k-th
               unexplored neighbor
      prune  — children with MINDIST > τ are *stashed* into the level's
               deferred beam instead of discarded
      leaf   — all valid candidates beam-merge into the pool

    Every bounded beam folds its smallest dropped distance into
    ``state.lost``; emission only flags ``overflow`` when an emitted
    distance reaches that bound — exactness is tracked, not assumed.

    Returns a ``BrowseEngine`` namedtuple:
      init(queries)        → fresh BrowseState (root deferred at the top)
      needs_descent(state) → host bool: can the pool safely serve batch_k?
      needs_descent_fn     → the traced () bool predicate behind it — the
                             sharded browse path runs it as a
                             ``lax.while_loop`` condition inside one SPMD
                             program (core/knn_browse.make_sharded_browse)
      resume(ctx, state)   → state after one full descent
      emit(state)          → (ids (B, batch_k), d (B, batch_k), state)
    """
    caps = tuple(caps)
    defer_caps = tuple(defer_caps)
    if len(defer_caps) != height:
        raise ValueError(f"need {height} defer caps, got {len(defer_caps)}")
    if pool_cap < batch_k:
        raise ValueError("pool_cap must be >= batch_k")
    sm = spec.stage_model

    def init(queries: jax.Array) -> BrowseState:
        b = queries.shape[0]
        def_ids = []
        def_d = []
        for lj in range(height):
            dc = defer_caps[lj]
            if lj == height - 1:
                # the root is the initial deferred node, at distance 0
                def_ids.append(jnp.zeros((b, dc), jnp.int32))
                def_d.append(jnp.zeros((b, dc), jnp.float32))
            else:
                def_ids.append(jnp.full((b, dc), -1, jnp.int32))
                def_d.append(jnp.full((b, dc), DIST_PAD, jnp.float32))
        zero = jnp.int32(0)
        return BrowseState(
            queries=jnp.asarray(queries),
            pool_ids=jnp.full((b, pool_cap), -1, jnp.int32),
            pool_d=jnp.full((b, pool_cap), DIST_PAD, jnp.float32),
            def_ids=tuple(def_ids), def_d=tuple(def_d),
            lost=jnp.full((b,), jnp.inf, jnp.float32),
            emitted=jnp.zeros((b,), jnp.int32),
            overflow=jnp.zeros((b,), bool),
            # occupancy vectors must take their (OCC_STEPS,) shape up front:
            # the sharded browse loop carries this state through a
            # lax.while_loop, so the pytree shapes are pinned at init
            ctr=Counters(*([zero] * 10), lanes_live=occupancy_zeros(),
                         lanes_padded=occupancy_zeros(), escalations=zero),
            descents=jnp.int32(0))

    @jax.jit
    def _needs_descent(state: BrowseState) -> jax.Array:
        min_def = jnp.full(state.lost.shape, DIST_PAD, jnp.float32)
        for lj in range(height):
            min_def = jnp.minimum(min_def, state.def_d[lj].min(axis=1))
        pool_kth = state.pool_d[:, batch_k - 1]
        pool_kth = jnp.where(pool_kth < DIST_VALID_MAX, pool_kth, jnp.inf)
        return ((min_def < DIST_VALID_MAX) & (min_def <= pool_kth)).any()

    def needs_descent(state: BrowseState) -> bool:
        return bool(_needs_descent(state))

    @jax.jit
    def resume(ctx, state: BrowseState) -> BrowseState:
        queries = state.queries
        b = queries.shape[0]
        # τ init: the batch_k-th pool distance — the pool holds real
        # objects, so batch_k of the next neighbors lie within it
        pool_kth = state.pool_d[:, batch_k - 1]
        tau = jnp.where(pool_kth < DIST_VALID_MAX, pool_kth, DIST_PAD)
        frontier = jnp.full((b, 1), -1, jnp.int32)
        fdist = jnp.full((b, 1), DIST_PAD, jnp.float32)
        pool_ids, pool_d = state.pool_ids, state.pool_d
        def_ids = list(state.def_ids)
        def_d = list(state.def_d)
        lost = state.lost
        nodes = preds = vops = enq = pruned = waste = jnp.int32(0)
        occ_live = occupancy_zeros()
        occ_padded = occupancy_zeros()
        disp = 0
        for li in range(height - 1, -1, -1):
            leaf = li == 0
            fcap = 1 if li == height - 1 else caps[height - 2 - li]
            # inject: activate this level's deferred nodes within τ
            act = (def_ids[li] >= 0) & (def_d[li] <= tau[:, None])
            comb_ids = jnp.concatenate([frontier, def_ids[li]], axis=1)
            comb_d = jnp.concatenate(
                [fdist, jnp.where(act, def_d[li], DIST_PAD)], axis=1)
            ids, idd, bound = _beam_with_bound(
                comb_ids, comb_d, comb_d < DIST_VALID_MAX, fcap)
            lost = jnp.minimum(lost, bound)
            def_ids[li] = jnp.where(act, -1, def_ids[li])
            def_d[li] = jnp.where(act, DIST_PAD, def_d[li])
            # score — identical stage to the fixed-k engine
            fvalid = ids >= 0
            fcnt = fvalid.sum(axis=1)
            nodes = nodes + fcnt.sum()
            occ_live, occ_padded = _occ_record(
                occ_live, occ_padded, step=height - 1 - li, valid=fvalid,
                width=ids.shape[1], batch=b)
            md, mmd, ptr, stages = score(ctx, li, ids, queries, leaf)
            f = md.shape[-1]
            ev = stages if leaf else 2 * stages
            preds = preds + fcnt.sum() * f * ev
            vops = vops + fcnt.sum() * ev
            entry_valid = md < DIST_VALID_MAX
            waste = waste + fcnt.sum() * f - entry_valid.sum()
            flat_d = md.reshape(b, -1)
            flat_ptr = ptr.reshape(b, -1)
            if leaf:
                disp += sm.leaf
                # every scored candidate is a real object: pool it
                pool_ids2 = jnp.concatenate([pool_ids, flat_ptr], axis=1)
                pool_d2 = jnp.concatenate([pool_d, flat_d], axis=1)
                pool_ids, pool_d, bound = _beam_with_bound(
                    pool_ids2, pool_d2, pool_d2 < DIST_VALID_MAX, pool_cap)
                lost = jnp.minimum(lost, bound)
            else:
                disp += sm.inner
                mflat = mmd.reshape(b, -1)
                if mflat.shape[1] >= batch_k:   # same soundness gate
                    kth = -jax.lax.top_k(-mflat, batch_k)[0][:, batch_k - 1]
                    tau = jnp.minimum(tau, kth)
                keep = entry_valid & (md <= tau[:, None, None])
                pruned = pruned + (entry_valid.sum() - keep.sum())
                cap = caps[height - 1 - li]
                frontier, fdist, bound = _beam_with_bound(
                    flat_ptr, flat_d, keep.reshape(b, -1), cap)
                lost = jnp.minimum(lost, bound)
                enq = enq + keep.sum()
                # stash: τ-pruned children stay reachable for later batches
                rej = (entry_valid & ~keep).reshape(b, -1)
                dj_ids = jnp.concatenate([def_ids[li - 1], flat_ptr], axis=1)
                dj_d = jnp.concatenate(
                    [def_d[li - 1], jnp.where(rej, flat_d, DIST_PAD)],
                    axis=1)
                def_ids[li - 1], def_d[li - 1], bound = _beam_with_bound(
                    dj_ids, dj_d, dj_d < DIST_VALID_MAX,
                    defer_caps[li - 1])
                lost = jnp.minimum(lost, bound)
        dctr = Counters(nodes_visited=nodes, predicates=preds,
                        vector_ops=vops, enqueued=enq, pruned_inner=pruned,
                        masked_waste=waste, dispatches=jnp.int32(disp),
                        lanes_live=occ_live, lanes_padded=occ_padded)
        return dataclasses.replace(
            state, pool_ids=pool_ids, pool_d=pool_d,
            def_ids=tuple(def_ids), def_d=tuple(def_d), lost=lost,
            ctr=state.ctr + dctr, descents=state.descents + 1)

    @jax.jit
    def emit(state: BrowseState):
        b = state.pool_ids.shape[0]
        d = state.pool_d[:, :batch_k]
        ids = state.pool_ids[:, :batch_k]
        found = d < DIST_VALID_MAX
        out_ids = jnp.where(found, ids, -1)
        out_d = jnp.where(found, d, jnp.inf)
        crossed = (found & (d >= state.lost[:, None])).any(axis=1)
        pad_i = jnp.full((b, batch_k), -1, jnp.int32)
        pad_d = jnp.full((b, batch_k), DIST_PAD, jnp.float32)
        # mirror the crossing into Counters.overflow — the flag every other
        # operator's consumers read to detect approximate results
        ctr = dataclasses.replace(
            state.ctr,
            overflow=state.ctr.overflow | crossed.any().astype(jnp.int32))
        new = dataclasses.replace(
            state,
            pool_ids=jnp.concatenate([state.pool_ids[:, batch_k:], pad_i], 1),
            pool_d=jnp.concatenate([state.pool_d[:, batch_k:], pad_d], 1),
            emitted=state.emitted + found.sum(axis=1).astype(jnp.int32),
            overflow=state.overflow | crossed, ctr=ctr)
        return out_ids, out_d, new

    return BrowseEngine(init=init, needs_descent=needs_descent,
                        needs_descent_fn=_needs_descent, resume=resume,
                        emit=emit)
