"""Pallas TPU kernel: R-tree kNN-join BFS level step (pair distances).

The kNN distance kernel (rtree_knn.py) generalized to rect queries: one grid
step scores ``chunk`` (outer rect, frontier-node) cells — squared
rect-to-rect MINDIST and rect MINMAXDIST of every child MBR of the inner
node against the outer query rect.  Frontier node ids ride the scalar-prefetch operand
(`PrefetchScalarGridSpec`) exactly as in the select/kNN kernels, so node
blocks are DMA'd HBM→VMEM one grid step ahead of the VPU math.

Two variants share the scoring sequence (``rtree_knn.dist_row_fn``):

  generic — MINDIST + MINMAXDIST from one DMA of the four key-excerpt rows
            (internal levels: the τ bound consumes MINMAXDIST).
  leaf    — MINDIST only, skipping the MINMAXDIST math *and its output
            store*: the leaf level (the largest frontier) never consumes the
            bound.  The jnp path DCEs the waste under jit; an opaque
            pallas_call cannot, hence the explicit variant (ROADMAP item).

The fused whole-level generation (``knn_join_level_fused`` /
``knn_join_leaf_fused``) reuses the point-kNN fused machinery
(rtree_knn.fused_inner_call / fused_leaf_call) with the rect-to-rect
distance formulas: one pallas_call per BFS level with the τ top-k, MINDIST
pruning, and best-first beam emission fused in-kernel — the host receives
only the compacted (B, cap) frontier, τ, and counter tallies.

Layout: consumes the level-global D1 (SoA) arrays.  Invalid lanes (padded
children, -1 frontier slots) carry DIST_PAD, never a qualifying distance.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.geometry import mindist_rect, minmaxdist_rect

from . import fused_common as fc
from .rtree_knn import (d3_dist_row_fn, dist_row_fn, fused_inner_call,
                        fused_leaf_call)


def _rect_md(q, lx, ly, hx, hy):
    return mindist_rect(q[0], q[1], q[2], q[3], lx, ly, hx, hy)


def _rect_mmd(q, lx, ly, hx, hy):
    return minmaxdist_rect(q[0], q[1], q[2], q[3], lx, ly, hx, hy)


@functools.partial(jax.jit, static_argnames=("leaf", "chunk", "interpret"))
def knn_join_level_dists(ids, qrects, lx, ly, hx, hy, child, *,
                         leaf: bool = False, chunk: int = 8,
                         interpret: bool):
    """Score one BFS level for a batch of kNN-join outer rects.

    ids:    (B, C) int32 frontier node ids (-1 pad) — scalar-prefetched.
    qrects: (B, 4) outer query rects.
    lx..hy: (N, F) level-global SoA child MBR arrays (f32).
    child:  (N, F) int32 child ids.
    → (mindist (B, C, F), minmaxdist (B, C, F) | None) f32, DIST_PAD on
    invalid lanes; ``leaf=True`` selects the MINMAXDIST-free variant and
    returns None for the bound.
    """
    out = fc.score_rows(
        dist_row_fn(_rect_md, None if leaf else _rect_mmd), ids, qrects,
        [lx, ly, hx, hy, child], n_out=1 if leaf else 2,
        out_dtype=jnp.float32, chunk=chunk, interpret=interpret)
    return (out[0], None) if leaf else tuple(out)


# ---------------------------------------------------------------------------
# D3 quantized-layout kernel (rect-query analogue of rtree_knn's — packed
# uint16 code streams, in-register dequantization, slack-corrected
# MINMAXDIST; internal levels only, the leaf re-checks through the exact
# D1 kernel)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def knn_join_level_dists_d3(ids, qrects, qlo, qhi, scale, bias, slack, ptr,
                            *, chunk: int = 8, interpret: bool):
    """Score one quantized BFS level for a batch of kNN-join outer rects —
    contract as ``knn_level_dists_d3`` with rect queries: (admissible
    MINDIST lower bound, slack-corrected MINMAXDIST upper bound)."""
    return tuple(fc.score_rows(
        d3_dist_row_fn(_rect_md, _rect_mmd), ids, qrects,
        [qlo, qhi, scale, bias, slack, ptr], n_out=2,
        out_dtype=jnp.float32, chunk=chunk, interpret=interpret))


# ---------------------------------------------------------------------------
# Fused whole-level kernels (rect-query instantiation of the shared
# machinery in rtree_knn.py)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit,
                   static_argnames=("cap", "k", "tighten", "chunk",
                                    "interpret"))
def knn_join_level_fused(ids, qrects, lx, ly, hx, hy, child, tau, *,
                         cap: int, k: int, tighten: bool, chunk: int = 8,
                         interpret: bool):
    """Fused internal-level step for kNN-join outer rects: (B, C) frontier →
    compacted (B, cap) next frontier + tightened τ + valid/keep tallies, one
    pallas_call (see rtree_knn.py module docstring)."""
    return fused_inner_call(ids, qrects, lx, ly, hx, hy, child, tau,
                            cap=cap, k=k, tighten=tighten, chunk=chunk,
                            interpret=interpret, md_fn=_rect_md,
                            mmd_fn=_rect_mmd)


@functools.partial(jax.jit, static_argnames=("k", "chunk", "interpret"))
def knn_join_leaf_fused(ids, qrects, lx, ly, hx, hy, child, *, k: int,
                        chunk: int = 8, interpret: bool):
    """Fused leaf-level step for kNN-join: the k best (id, squared rect
    MINDIST) per outer rect, one pallas_call — structurally leaf-specialized
    (no MINMAXDIST path exists in the leaf machinery at all)."""
    return fused_leaf_call(ids, qrects, lx, ly, hx, hy, child, k=k,
                           chunk=chunk, interpret=interpret, md_fn=_rect_md)
