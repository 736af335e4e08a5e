"""Pallas TPU kernels: R-tree kNN BFS level step (V-O1+O2 for distances).

Two generations of kernel live here:

**Per-cell (unfused)** — ``knn_level_dists``: one grid step scores
``chunk`` (query, frontier-node) cells (squared MINDIST + squared
MINMAXDIST of every child MBR against the query point) and hands the raw
(B, C, F) distance tensors back to XLA for τ tightening, pruning, and beam
compaction.  The frontier node ids ride the **scalar-prefetch operand**
(`PrefetchScalarGridSpec`) so node blocks are DMA'd HBM→VMEM one grid step
ahead of the VPU math — the paper's software prefetching (O2) mapped onto
the TPU DMA pipeline.  ``leaf=True`` selects the leaf-specialized variant
(no MINMAXDIST math or store — the τ bound is never consumed below the
leaves).

**Whole-level (fused)** — ``knn_level_fused`` / ``knn_leaf_fused``: one
``pallas_call`` processes an *entire* BFS level.  The grid tiles over
(query, τ-pass/emit-pass, frontier-chunk); each step DMAs a multi-row node
block (``chunk`` frontier rows as parallel scalar-prefetched streams) and
the emission stage runs *inside* the kernel:

  pass 0 — running k smallest squared MINMAXDIST in VMEM scratch across
           the frontier chunks; at the last chunk τ is tightened to the
           k-th smallest (min with the carried-in τ) and written out.
  pass 1 — MINDIST ≤ τ pruning, then a running best-first beam (distance,
           child-id) of width ``cap`` merged chunk-by-chunk in VMEM scratch
           (``fused_common.merge_smallest`` — a stable merge, so ties
           resolve exactly as one flat top-k over the level would); at the
           last chunk the compacted (cap,) frontier row and the per-query
           valid/keep tallies land in the outputs.

The host loop therefore receives only the compacted (B, cap) frontier, τ,
and two counter tallies per level — no (B, C, F) HBM intermediate and no
per-level XLA round-trips (compare ``ref.knn_level_fused_ref``, the
bit-compatible jnp twin).  The leaf kernel is the single-pass analogue that
merges a running (distance, id) top-k of the *results* and never touches
MINMAXDIST.

The generic machinery (`fused_inner_call` / `fused_leaf_call`) is shared
with the rect-query kNN-join kernels, which pass their own distance
formulas — one implementation, two operand widths.

Layout: all kernels consume the level-global D1 (SoA) arrays, one (1, F)
row per key excerpt per node.  Invalid lanes (padded children, -1 frontier
slots) carry DIST_PAD, never a qualifying distance.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.geometry import DIST_PAD, DIST_VALID_MAX, mindist, minmaxdist
from repro.core.layouts import d3_slacked_upper

from . import fused_common as fc

# Python floats: traced as literals, not captured consts, inside the kernels.
_PAD = float(DIST_PAD)
_VMAX = float(DIST_VALID_MAX)


def dist_row_fn(md_fn, mmd_fn):
    """Unfused D1 row scorer: (q, slot refs, ok) → (MINDIST[, MINMAXDIST])
    rows with DIST_PAD on invalid lanes; ``mmd_fn=None`` is the leaf
    variant (no MINMAXDIST math or store).  The shared geometry formulas
    are pure jnp and trace inside the kernel body, so the kernel can never
    drift from the ref path it is parity-tested against."""
    def row(q, refs, ok):
        lx, ly, hx, hy, child = (ref[...] for ref in refs)
        valid = (child >= 0) & ok
        out = (jnp.where(valid, md_fn(q, lx, ly, hx, hy), _PAD),)
        if mmd_fn is not None:
            out += (jnp.where(valid, mmd_fn(q, lx, ly, hx, hy), _PAD),)
        return out
    return row


def d3_dist_row_fn(md_fn, mmd_fn):
    """Unfused D3 row scorer: MINDIST on the dequantized (enlarged) boxes
    (an admissible lower bound) and the slack-corrected MINMAXDIST
    (core.layouts.d3_slacked_upper — a sound upper bound)."""
    def row(q, refs, ok):
        lx, ly, hx, hy = fc.d3_row_boxes(*refs[:4])
        sl = refs[4]
        disp = sl[0, 0] + sl[0, 1]
        valid = (refs[5][...] >= 0) & ok
        md = md_fn(q, lx, ly, hx, hy)
        mmd = d3_slacked_upper(mmd_fn(q, lx, ly, hx, hy), disp)
        return jnp.where(valid, md, _PAD), jnp.where(valid, mmd, _PAD)
    return row


def _point_md(q, lx, ly, hx, hy):
    return mindist(q[0], q[1], lx, ly, hx, hy)


def _point_mmd(q, lx, ly, hx, hy):
    return minmaxdist(q[0], q[1], lx, ly, hx, hy)


@functools.partial(jax.jit, static_argnames=("leaf", "chunk", "interpret"))
def knn_level_dists(ids, points, lx, ly, hx, hy, child, *,
                    leaf: bool = False, chunk: int = 8, interpret: bool):
    """Score one BFS level for a batch of kNN queries.

    ids:    (B, C) int32 frontier node ids (-1 pad) — scalar-prefetched.
    points: (B, 2) query points.
    lx..hy: (N, F) level-global SoA child MBR arrays (f32).
    child:  (N, F) int32 child ids.
    → (mindist (B, C, F), minmaxdist (B, C, F) | None) f32, DIST_PAD on
    invalid lanes; ``leaf=True`` selects the MINMAXDIST-free variant and
    returns None for the bound.
    """
    out = fc.score_rows(
        dist_row_fn(_point_md, None if leaf else _point_mmd), ids, points,
        [lx, ly, hx, hy, child], n_out=1 if leaf else 2,
        out_dtype=jnp.float32, chunk=chunk, interpret=interpret)
    return (out[0], None) if leaf else tuple(out)


# ---------------------------------------------------------------------------
# D3 quantized-layout kernel: the node block streams two packed-uint16 code
# rows (4 bytes per child MBR — ~4x the children per DMA'd block) plus the
# (1, 2) scale/bias/slack rows; boxes are dequantized in-register.  Internal
# levels only — the operators route leaf rows through the exact D1 kernel.
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def knn_level_dists_d3(ids, points, qlo, qhi, scale, bias, slack, ptr, *,
                       chunk: int = 8, interpret: bool):
    """Score one quantized BFS level for a batch of kNN queries.

    ids:     (B, C) int32 frontier node ids (-1 pad) — scalar-prefetched.
    points:  (B, 2) query points.
    qlo/qhi: (N, F) uint16 packed per-axis code rows.
    scale/bias/slack: (N, 2) f32 per-node quantization params.
    ptr:     (N, F) int32 child ids.
    → (mindist (B, C, F) lower bound, slacked minmaxdist (B, C, F) upper
    bound) f32, DIST_PAD on invalid lanes.
    """
    return tuple(fc.score_rows(
        d3_dist_row_fn(_point_md, _point_mmd), ids, points,
        [qlo, qhi, scale, bias, slack, ptr], n_out=2,
        out_dtype=jnp.float32, chunk=chunk, interpret=interpret))


# ---------------------------------------------------------------------------
# Fused whole-level kernels (shared point / rect machinery)
# ---------------------------------------------------------------------------

def fused_inner_call(ids, queries, lx, ly, hx, hy, child, tau, *,
                     cap: int, k: int, tighten: bool, chunk: int,
                     interpret: bool, md_fn, mmd_fn):
    """One fused pallas_call for an internal BFS level (generic over the
    query operand: ``md_fn``/``mmd_fn`` map (query scalars, lx, ly, hx, hy)
    → (R, F) distances).  Returns (next_ids (B, cap), tau (B,),
    valid_cnt (B,), keep_cnt (B,)) — the bit-compatible fusion of
    score → τ top-k → prune → beam_rows (see ref.knn_level_fused_ref).
    """
    qw = queries.shape[1]
    ids, r, nc = fc.pad_frontier(ids, chunk)
    cp = r * nc
    arrays = [lx, ly, hx, hy, child]
    # τ rides the per-query SMEM operand as one more query column
    qt = jnp.concatenate([queries.astype(jnp.float32),
                          tau.astype(jnp.float32)[:, None]], axis=1)

    def call(ids_g, q_g):
        g = ids_g.shape[0]

        def kernel(ids_ref, q_ref, *rest):
            row_refs = rest[:5 * r]
            out_ref, tau_ref, stats_ref = rest[5 * r:5 * r + 3]
            topk_ref, beam_d_ref, beam_v_ref, cnt_sm, tau_sm = rest[5 * r + 3:]
            bi = pl.program_id(0)
            ps = pl.program_id(1)
            ci = pl.program_id(2)
            last = ci == nc - 1

            @pl.when((ps == 0) & (ci == 0))
            def _():
                tau_sm[0] = q_ref[bi * (qw + 1) + qw]
                cnt_sm[0] = 0
                cnt_sm[1] = 0
                topk_ref[...] = jnp.full((1, k), _PAD, jnp.float32)
                beam_d_ref[...] = jnp.full((1, cap), _PAD, jnp.float32)
                beam_v_ref[...] = jnp.full((1, cap), -1, jnp.int32)

            glx, gly, ghx, ghy, child_t = fc.chunk_tile(row_refs, r)
            valid = (child_t >= 0) & fc.slot_ok(ids_ref, bi * cp + ci * r, r)
            q = fc.query_scalars(q_ref, bi, qw + 1)[:qw]

            @pl.when(ps == 0)
            def _():
                # τ pass: running k smallest of the MINMAXDIST bound.  The
                # set of k smallest values is chunk-order invariant, so the
                # k-th value is bitwise the one a flat top-k would produce.
                if tighten:
                    mmd = jnp.where(valid, mmd_fn(q, glx, gly, ghx, ghy),
                                    _PAD)
                    d, _ = fc.merge_smallest(
                        topk_ref[...], jnp.zeros((1, k), jnp.int32), mmd,
                        jnp.zeros(mmd.shape, jnp.int32), k)
                    topk_ref[...] = d

                    @pl.when(last)
                    def _():
                        tau_sm[0] = jnp.minimum(tau_sm[0],
                                                jnp.max(topk_ref[...]))

                @pl.when(last)
                def _():
                    tau_ref[bi] = tau_sm[0]

            @pl.when(ps == 1)
            def _():
                # emit pass: MINDIST ≤ τ prune, then stable best-first beam
                # merge — kept entries precede the new chunk, so ties
                # resolve exactly as the flat beam_rows order.
                md = jnp.where(valid, md_fn(q, glx, gly, ghx, ghy), _PAD)
                keep = valid & (md <= tau_sm[0])
                cnt_sm[0] = cnt_sm[0] + jnp.sum(valid.astype(jnp.int32))
                cnt_sm[1] = cnt_sm[1] + jnp.sum(keep.astype(jnp.int32))
                d, v = fc.merge_smallest(
                    beam_d_ref[...], beam_v_ref[...],
                    jnp.where(keep, md, _PAD), jnp.where(keep, child_t, -1),
                    cap)
                beam_d_ref[...] = d
                beam_v_ref[...] = v

                @pl.when(last)
                def _():
                    found = beam_d_ref[...] < _VMAX
                    out_ref[...] = jnp.where(found, beam_v_ref[...], -1)
                    stats_ref[2 * bi] = cnt_sm[0]
                    stats_ref[2 * bi + 1] = cnt_sm[1]

        def slot(i, bi, ps, ci, s, qs):
            return s[bi * cp + ci * r + i]

        smem = pl.BlockSpec(memory_space=pltpu.SMEM)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(g, 2, nc),
            in_specs=fc.row_specs(arrays, r, slot),
            out_specs=[pl.BlockSpec((None, 1, cap),
                                    lambda bi, ps, ci, s, qs: (bi, 0, 0)),
                       smem, smem],
            scratch_shapes=[
                pltpu.VMEM((1, k), jnp.float32),      # running MINMAXDIST top-k
                pltpu.VMEM((1, cap), jnp.float32),    # beam distances
                pltpu.VMEM((1, cap), jnp.int32),      # beam child ids
                pltpu.SMEM((2,), jnp.int32),          # valid / keep tallies
                pltpu.SMEM((1,), jnp.float32),        # τ carried across passes
            ],
        )
        out, tau_out, stats = pl.pallas_call(
            kernel, grid_spec=grid_spec,
            out_shape=[jax.ShapeDtypeStruct((g, 1, cap), jnp.int32),
                       jax.ShapeDtypeStruct((g,), jnp.float32),
                       jax.ShapeDtypeStruct((2 * g,), jnp.int32)],
            interpret=interpret,
        )(ids_g.reshape(-1), q_g.reshape(-1), *fc.row_operands(arrays, r))
        return out[:, 0], tau_out, stats.reshape(g, 2)

    out_ids, tau_out, stats = fc.by_groups(call, [ids, qt], [-1, 0],
                                            cp + qw + 1)
    return out_ids, tau_out, stats[:, 0], stats[:, 1]


def fused_leaf_call(ids, queries, lx, ly, hx, hy, child, *, k: int,
                    chunk: int, interpret: bool, md_fn):
    """One fused pallas_call for the leaf level: running (distance, id)
    top-k of the results merged across frontier chunks — MINDIST only (the
    leaf never consumes the MINMAXDIST bound, so the specialization is
    structural here, not a variant flag).  Returns (res_ids (B, k),
    res_d (B, k), valid_cnt (B,)); missing neighbours are (-1, +inf)."""
    qw = queries.shape[1]
    ids, r, nc = fc.pad_frontier(ids, chunk)
    cp = r * nc
    arrays = [lx, ly, hx, hy, child]
    queries = queries.astype(jnp.float32)

    def call(ids_g, q_g):
        g = ids_g.shape[0]

        def kernel(ids_ref, q_ref, *rest):
            row_refs = rest[:5 * r]
            ids_out, d_out, stats_ref = rest[5 * r:5 * r + 3]
            beam_d_ref, beam_v_ref, cnt_sm = rest[5 * r + 3:]
            bi = pl.program_id(0)
            ci = pl.program_id(1)

            @pl.when(ci == 0)
            def _():
                cnt_sm[0] = 0
                beam_d_ref[...] = jnp.full((1, k), _PAD, jnp.float32)
                beam_v_ref[...] = jnp.full((1, k), -1, jnp.int32)

            glx, gly, ghx, ghy, child_t = fc.chunk_tile(row_refs, r)
            valid = (child_t >= 0) & fc.slot_ok(ids_ref, bi * cp + ci * r, r)
            q = fc.query_scalars(q_ref, bi, qw)
            md = jnp.where(valid, md_fn(q, glx, gly, ghx, ghy), _PAD)
            cnt_sm[0] = cnt_sm[0] + jnp.sum(valid.astype(jnp.int32))
            # result ids ride unmasked (as in the flat top-k twin): any entry
            # still at DIST_PAD is masked to (-1, inf) at the end, so invalid
            # lanes can never surface a qualifying id
            d, v = fc.merge_smallest(beam_d_ref[...], beam_v_ref[...], md,
                                     child_t, k)
            beam_d_ref[...] = d
            beam_v_ref[...] = v

            @pl.when(ci == nc - 1)
            def _():
                found = beam_d_ref[...] < _VMAX
                ids_out[...] = jnp.where(found, beam_v_ref[...], -1)
                d_out[...] = jnp.where(found, beam_d_ref[...], jnp.inf)
                stats_ref[bi] = cnt_sm[0]

        def slot(i, bi, ci, s, qs):
            return s[bi * cp + ci * r + i]

        def row_out(bi, ci, s, qs):
            return (bi, 0, 0)

        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(g, nc),
            in_specs=fc.row_specs(arrays, r, slot),
            out_specs=[pl.BlockSpec((None, 1, k), row_out),
                       pl.BlockSpec((None, 1, k), row_out),
                       pl.BlockSpec(memory_space=pltpu.SMEM)],
            scratch_shapes=[
                pltpu.VMEM((1, k), jnp.float32),      # result beam distances
                pltpu.VMEM((1, k), jnp.int32),        # result beam ids
                pltpu.SMEM((1,), jnp.int32),          # valid tally
            ],
        )
        out_ids, out_d, stats = pl.pallas_call(
            kernel, grid_spec=grid_spec,
            out_shape=[jax.ShapeDtypeStruct((g, 1, k), jnp.int32),
                       jax.ShapeDtypeStruct((g, 1, k), jnp.float32),
                       jax.ShapeDtypeStruct((g,), jnp.int32)],
            interpret=interpret,
        )(ids_g.reshape(-1), q_g.reshape(-1), *fc.row_operands(arrays, r))
        return out_ids[:, 0], out_d[:, 0], stats

    return fc.by_groups(call, [ids, queries], [-1, 0], cp + qw)


@functools.partial(jax.jit,
                   static_argnames=("cap", "k", "tighten", "chunk",
                                    "interpret"))
def knn_level_fused(ids, points, lx, ly, hx, hy, child, tau, *, cap: int,
                    k: int, tighten: bool, chunk: int = 8,
                    interpret: bool):
    """Fused internal-level step for point kNN: (B, C) frontier → compacted
    (B, cap) next frontier + tightened τ + valid/keep tallies, one
    pallas_call (see module docstring)."""
    return fused_inner_call(ids, points, lx, ly, hx, hy, child, tau,
                            cap=cap, k=k, tighten=tighten, chunk=chunk,
                            interpret=interpret, md_fn=_point_md,
                            mmd_fn=_point_mmd)


@functools.partial(jax.jit, static_argnames=("k", "chunk", "interpret"))
def knn_leaf_fused(ids, points, lx, ly, hx, hy, child, *, k: int,
                   chunk: int = 8, interpret: bool):
    """Fused leaf-level step for point kNN: (B, C) leaf frontier → the k
    best (id, squared distance) per query, one pallas_call."""
    return fused_leaf_call(ids, points, lx, ly, hx, hy, child, k=k,
                           chunk=chunk, interpret=interpret,
                           md_fn=_point_md)
