"""Vectorized range select (paper §3).

Variant map (paper → here):

  V        — recursive traversal, SIMD predicate per node
             → ``make_select_dfs_vector``: sequential DFS stack, one dense
               (4, F) vector compare per node, compaction push.
  V-O1     — queue/BFS traversal, compress-store enqueue
             → ``make_select_bfs``: *batched level-synchronous* BFS; the
               paper's per-query queue generalizes to a (B, cap) frontier and
               compress-store to mask→cumsum compaction (compaction.py).
  V-O1+O2  — + software prefetching of queued nodes
             → the Pallas kernel path (kernels/rtree_select.py): the frontier
               rides the scalar-prefetch operand so node blocks are DMA'd
               HBM→VMEM ahead of the compute that consumes them.

All three consume any of the physical layouts D0/D1/D2; layout-specific
predicate evaluation matches the paper's instruction sequences (D1: 4 compare
stages; D2: 2 compare stages on interleaved pairs + pair reduction; D0:
strided de-interleave first — the SIMD-hostile case).

The BFS level loop itself lives in core/traversal.py (the spec-driven
engine); this module contributes the *select spec*: the layout-specific
intersect-mask score stage, the compress-store emission kind, the caps
policy, and the kernel handles.
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from . import caps as caps_policy
from . import traversal
from .compaction import compact_1d
from .counters import Counters, StageModel
from .flat import FlatTree
from .geometry import intersects
from .layouts import (LevelD0, LevelD1, LevelD2, LevelD3, d0_unpack,
                      d3_dequantize, layout_lanes, tree_layout)
from .rtree import RTree


# Leaf frontier slots the jnp path scores per step of its leaf loop: with a
# fanout of 64, one block is 256 x 64 = 16,384 lanes a query row (128 lane
# rows of the v5e's 128-wide vregs), and the loop stops at the last block
# that holds a live leaf (traversal._blocked_leaf).
LEAF_BLOCK = 256

# ---------------------------------------------------------------------------
# Layout-specific batched predicate evaluation
# ---------------------------------------------------------------------------

def _masks_for_level(layer, ids: jax.Array, queries: jax.Array):
    """Evaluate the select predicate for frontier ``ids`` of one level.

    ids: (B, C) node ids (-1 pad); queries: (B, 4).
    Returns (mask (B, C, F), child_ids (B, C, F), n_compare_stages).
    """
    safe = jnp.maximum(ids, 0)
    valid = (ids >= 0)[:, :, None]
    qlx = queries[:, 0, None, None]
    qly = queries[:, 1, None, None]
    qhx = queries[:, 2, None, None]
    qhy = queries[:, 3, None, None]
    if isinstance(layer, LevelD1):
        c = layer.coords[safe]                      # (B, C, 4, F)
        m = intersects(qlx, qly, qhx, qhy,
                       c[:, :, 0], c[:, :, 1], c[:, :, 2], c[:, :, 3])
        ptr = layer.ptr[safe]
        stages = 4
    elif isinstance(layer, LevelD2):
        lo = layer.lo[safe]                         # (B, C, 2F) interleaved
        hi = layer.hi[safe]
        b, cc, f2 = lo.shape
        lo = lo.reshape(b, cc, f2 // 2, 2)
        hi = hi.reshape(b, cc, f2 // 2, 2)
        qlo = jnp.stack([queries[:, 0], queries[:, 1]], -1)[:, None, None, :]
        qhi = jnp.stack([queries[:, 2], queries[:, 3]], -1)[:, None, None, :]
        m = ((qlo <= hi) & (qhi >= lo)).all(axis=-1)
        ptr = layer.ptr[safe]
        stages = 2
    elif isinstance(layer, LevelD0):
        e = layer.entries[safe]                     # (B, C, F, 5)
        lx, ly, hx, hy, ptr = d0_unpack(e)
        m = intersects(qlx, qly, qhx, qhy, lx, ly, hx, hy)
        stages = 4
    else:
        raise TypeError(type(layer))
    m = m & valid & (ptr >= 0)
    return m, ptr, stages


def _d3_masks_for_level(layer: LevelD3, ids: jax.Array, queries: jax.Array,
                        rects: jax.Array, leaf: bool):
    """Select predicate over a quantized level.

    Internal levels test the dequantized (conservatively enlarged) boxes —
    the mask can only over-approximate, never drop a qualifying child.
    The leaf level re-checks EXACT rect geometry (gathered through ptr), so
    emitted ids are bit-identical to the D1 path: extra leaf nodes admitted
    by the quantized internal prune contribute no rects, and compaction
    preserves the shared relative order of the real ones.
    """
    safe = jnp.maximum(ids, 0)
    valid = (ids >= 0)[:, :, None]
    ptr = layer.ptr[safe]
    if leaf:
        r = rects[jnp.maximum(ptr, 0)]              # (B, C, F, 4)
        lx, ly, hx, hy = r[..., 0], r[..., 1], r[..., 2], r[..., 3]
        stages = 4
    else:
        lx, ly, hx, hy = d3_dequantize(layer.qlo[safe], layer.qhi[safe],
                                       layer.scale[safe], layer.bias[safe])
        stages = 2                                  # two packed code streams
    m = intersects(queries[:, 0, None, None], queries[:, 1, None, None],
                   queries[:, 2, None, None], queries[:, 3, None, None],
                   lx, ly, hx, hy)
    m = m & valid & (ptr >= 0)
    return m, ptr, stages


def frontier_caps(tree: RTree, result_cap: int, slack: int = 4,
                  min_cap: int = 128, lanes: int = None,
                  policy: str = "static") -> Tuple[int, ...]:
    """Frontier capacity entering each level (root-1 … leaf) + result cap —
    the unified policy (core/caps.py); ``policy='adaptive'`` selects the
    occupancy-adaptive tight tier."""
    kw = {} if lanes is None else dict(lanes=lanes)
    return caps_policy.select_frontier_caps(tree, result_cap, slack=slack,
                                            min_cap=min_cap, policy=policy,
                                            **kw)


def make_select_bfs(tree: RTree, layout: str = "d1", result_cap: int = 4096,
                    caps: Optional[Sequence[int]] = None,
                    count_only: bool = False, backend: Optional[str] = None,
                    fused: bool = False, caps_mode: str = "adaptive"):
    """Build the jitted batched BFS select: queries (B,4) → results.

    ``backend``: None → layout-specific jnp math; 'pallas'/'pallas_interpret'/
    'xla' → route mask evaluation through kernels/ops.py (D1 only) — the
    V-O1+O2 path whose node blocks ride the scalar-prefetch DMA pipeline.

    ``fused=True`` (requires a kernel backend): one fused whole-level step
    per level — the predicate AND the compress-store enqueue run inside one
    device program (kernels/ops.select_level_fused), so the host loop
    consumes only the compacted (B, cap) frontier and per-query counts; no
    (B, C, F) mask intermediate exists and ``Counters.dispatches`` drops
    from 3 per level to 1.  Results are bit-compatible with the unfused
    path.

    ``caps_mode`` (used only when ``caps`` is None): 'adaptive' builds the
    two-tier overflow-escalating engine — occupancy-adaptive tight caps,
    escalating to the static caps on in-program overflow, bit-identical to
    the static path; 'static' builds the single static-caps engine.

    Returns fn(queries) → (ids (B, result_cap), counts (B,), Counters)
    (ids omitted in count_only mode).
    """
    if backend is not None and layout not in ("d1", "d3"):
        raise ValueError("kernel backend requires layout d1 or d3")
    if fused and backend is None:
        raise ValueError("fused select requires a kernel backend")
    layers = tree_layout(tree, layout)
    levels = tree.levels if backend is not None else None
    rects = tree.rects if layout == "d3" and backend is None else None

    def score(ctx, li, frontier, qargs):
        layers_, levels_, rects_ = ctx
        ids, queries = frontier[0], qargs[0]
        b = queries.shape[0]
        if backend is not None and layout == "d3" and li > 0:
            from repro.kernels import ops as _kops
            lvl3 = layers_[li]
            mask = _kops.select_level_masks_d3(
                ids, queries, lvl3.qlo, lvl3.qhi, lvl3.scale, lvl3.bias,
                lvl3.ptr, backend=backend).astype(bool)
            ptr = lvl3.ptr[jnp.maximum(ids, 0)]
            stages = 2
        elif backend is not None:
            # d3 leaf rows fall through here: level 0's SoA arrays ARE the
            # exact rect coords grouped by leaf node, so the d1 kernel is
            # the exact leaf re-check
            from repro.kernels import ops as _kops
            lvl = levels_[li]
            mask = _kops.select_level_masks(
                ids, queries, lvl.lx, lvl.ly, lvl.hx, lvl.hy,
                lvl.child, backend=backend).astype(bool)
            ptr = lvl.child[jnp.maximum(ids, 0)]
            stages = 4
        elif isinstance(layers_[li], LevelD3):
            mask, ptr, stages = _d3_masks_for_level(
                layers_[li], ids, queries, rects_, leaf=(li == 0))
        else:
            mask, ptr, stages = _masks_for_level(ids=ids, queries=queries,
                                                 layer=layers_[li])
        f = mask.shape[-1]
        return (mask.reshape(b, -1), (ptr.reshape(b, -1),), f, stages, None)

    def fused_level(ctx, li, frontier, qargs, cap):
        from repro.kernels import ops as _kops
        layers_, levels_, _ = ctx
        ids, queries = frontier[0], qargs[0]
        if layout == "d3" and li > 0:
            lvl3 = layers_[li]
            f = lvl3.ptr.shape[1]
            nxt, qcnt, o = _kops.select_level_fused_d3(
                ids, queries, lvl3.qlo, lvl3.qhi, lvl3.scale, lvl3.bias,
                lvl3.ptr, cap=cap, backend=backend)
            return (nxt,), qcnt, o, f, 2, None
        lvl = levels_[li]
        f = lvl.lx.shape[1]
        nxt, qcnt, o = _kops.select_level_fused(
            ids, queries, lvl.lx, lvl.ly, lvl.hx, lvl.hy, lvl.child,
            cap=cap, backend=backend)
        return (nxt,), qcnt, o, f, 4, None

    ctx = (layers, levels, rects)

    def build(caps_):
        caps_ = tuple(caps_)
        if len(caps_) != tree.height - 1:
            raise ValueError(
                f"need {tree.height - 1} caps, got {len(caps_)}")
        run = traversal.make_mask_engine(
            SELECT_SPEC, height=tree.height, caps=caps_,
            result_cap=result_cap, score=score,
            fused_level=fused_level if fused else None,
            count_only=count_only,
            leaf_block=LEAF_BLOCK if backend is None else None)
        if count_only:
            def fn(queries: jax.Array):
                _, counts, ctr = run(ctx, queries)
                return counts, ctr
        else:
            def fn(queries: jax.Array):
                res, counts, ctr = run(ctx, queries)
                return res[0], counts, ctr
        return fn

    if caps is not None:
        return build(caps)
    ll = layout_lanes(layout)
    full = frontier_caps(tree, result_cap, lanes=ll)
    if caps_mode == "static":
        return build(full)
    tight = frontier_caps(tree, result_cap, lanes=ll, policy="adaptive")
    return traversal.maybe_escalating(build, tight, full)


SELECT_SPEC = traversal.register(traversal.OperatorSpec(
    name="select", kind="mask",
    stage_model=StageModel(inner=3, leaf=3, fused=1),
    builder=make_select_bfs, caps_policy=frontier_caps, query_width=4,
    description="batched range select: intersect-mask score, "
                "compress-store emission"))


# ---------------------------------------------------------------------------
# V: sequential DFS traversal with a vectorized per-node predicate
# ---------------------------------------------------------------------------

def make_select_dfs_vector(flat: FlatTree, result_cap: int,
                           stack_cap: int = 1024):
    """Paper's partially-vectorized variant: recursion → explicit stack,
    one dense vector compare per visited node, compaction push."""
    f = flat.fanout

    @jax.jit
    def run(flat_: FlatTree, q: jax.Array):
        qlx, qly, qhx, qhy = q[0], q[1], q[2], q[3]
        idx = jnp.arange(f, dtype=jnp.int32)

        def body(st):
            stack, sp, res, rc, nodes, vops, ovf = st
            sp = sp - 1
            nid = stack[sp]
            leaf = flat_.is_leaf[nid]
            mask = intersects(qlx, qly, qhx, qhy, flat_.lx[nid], flat_.ly[nid],
                              flat_.hx[nid], flat_.hy[nid])
            ch = flat_.child[nid]
            mask = mask & (ch >= 0)
            comp, k, _ = compact_1d(ch, mask, f)
            rpos = jnp.where((idx < k) & leaf, rc + idx, result_cap + 1)
            res = res.at[rpos].set(comp, mode="drop")
            rc = rc + jnp.where(leaf, k, 0)
            spos = jnp.where((idx < k) & ~leaf, sp + idx, stack_cap + 1)
            stack = stack.at[spos].set(comp, mode="drop")
            sp = sp + jnp.where(leaf, 0, k)
            ovf = ovf | (sp > stack_cap) | (rc > result_cap)
            return stack, sp, res, rc, nodes + 1, vops + 4, ovf

        stack = jnp.zeros((stack_cap,), jnp.int32).at[0].set(flat_.root)
        init = (stack, jnp.int32(1), jnp.full((result_cap,), -1, jnp.int32),
                jnp.int32(0), jnp.int32(0), jnp.int32(0), jnp.bool_(False))
        _, _, res, rc, nodes, vops, ovf = jax.lax.while_loop(
            lambda st: st[1] > 0, body, init)
        ctr = Counters(nodes_visited=nodes, vector_ops=vops,
                       predicates=nodes * f * 4,
                       overflow=ovf.astype(jnp.int32),
                       dispatches=jnp.int32(1))  # one fused while-loop program
        return res, rc, ctr

    return functools.partial(run, flat)
