"""Pallas TPU kernels: R-tree select BFS level step (paper §3, V-O1+O2).

**Per-cell (unfused)** — ``select_level_masks``: one grid step evaluates the
select predicate of ``chunk`` (query, frontier-node) cells.  The frontier
node ids ride the **scalar-prefetch operand** (`PrefetchScalarGridSpec`):
the BlockSpec index maps translate the id in SMEM into the HBM row of the
node's SoA arrays, so Pallas' pipelined DMA fetches the node blocks of grid
step k+1 *while step k computes* — the TPU-native equivalent of the paper's
`pf_distance` software prefetching (O2).  The queue itself (O1) is the
frontier array; compaction (compress-store analogue) runs as XLA
cumsum+scatter outside the kernel (compaction.py) over a materialized
(B, C, F) mask.

**Whole-level (fused)** — ``select_level_fused``: one ``pallas_call``
processes the entire BFS level.  The grid tiles over (query,
frontier-chunk), and the compress-store enqueue runs *inside* the kernel:
mask → per-row lane-gather compaction → append at a running per-query
offset (SMEM) into the (rows, 128) output frontier block, which stays
resident in VMEM across the query's chunks — the TPU analogue of the
paper's one-instruction ``_mm512_mask_compress_store`` enqueue (O1), with
no (B, C, F) HBM intermediate and no post-kernel XLA round-trip.
Bit-compatible with ``compact_rows`` over the flat level (same positions,
same overflow parking); see ``ref.select_level_fused_ref`` for the jnp
twin.  The in-kernel compaction handles node rows of at most 128 children.

Layout: the kernels consume the level-global D1 (SoA) arrays — one (1, F)
row per key excerpt per node (fused_common describes the block plumbing).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import fused_common as fc


def _select_row(q, refs, ok):
    lx, ly, hx, hy, child = (ref[...] for ref in refs)
    # D1 predicate: 4 vector compares over the F child lanes.
    m = (q[0] <= hx) & (q[2] >= lx) & (q[1] <= hy) & (q[3] >= ly)
    return ((m & (child >= 0) & ok).astype(jnp.int32),)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def select_level_masks(ids, queries, lx, ly, hx, hy, child, *,
                       chunk: int = 8, interpret: bool):
    """Evaluate one BFS level for a batch of queries.

    ids:     (B, C) int32 frontier node ids (-1 pad) — scalar-prefetched.
    queries: (B, 4) query rects.
    lx..hy:  (N, F) level-global SoA child MBR arrays.
    child:   (N, F) int32 child ids.
    → mask (B, C, F) int32 qualify bitmask.
    """
    return fc.score_rows(_select_row, ids, queries,
                         [lx, ly, hx, hy, child], n_out=1,
                         out_dtype=jnp.int32, chunk=chunk,
                         interpret=interpret)[0]


# ---------------------------------------------------------------------------
# Fused whole-level kernel: predicate + in-kernel compress-store enqueue
# ---------------------------------------------------------------------------

def _fused_select_call(tile_fn, ids, queries, arrays, *, cap: int,
                       chunk: int, interpret: bool):
    """The fused select level over any node layout: ``tile_fn(row_refs, r)``
    → (lx, ly, hx, hy, ptr) each (R, F) for the chunk's slots."""
    b, _ = ids.shape
    ids, r, nc = fc.pad_frontier(ids, chunk)
    cp = r * nc
    na = len(arrays)
    rows = fc.out_rows(cap)

    def call(ids_g, q_g):
        g = ids_g.shape[0]

        def kernel(ids_ref, q_ref, *rest):
            row_refs = rest[:na * r]
            out_ref, cnt_ref, cnt_sm, aux_ref = rest[na * r:]
            bi = pl.program_id(0)
            ci = pl.program_id(1)

            @pl.when(ci == 0)
            def _():
                cnt_sm[0] = 0
                out_ref[...] = jnp.full((rows, fc.LANES), -1, jnp.int32)

            glx, gly, ghx, ghy, ptr_t = tile_fn(row_refs, r)
            valid = (ptr_t >= 0) & fc.slot_ok(ids_ref, bi * cp + ci * r, r)
            qlx, qly, qhx, qhy = fc.query_scalars(q_ref, bi, 4)
            m = (qlx <= ghx) & (qhx >= glx) & (qly <= ghy) & (qhy >= gly)
            comp, counts = fc.compact_tile(m & valid, [ptr_t])
            fc.append_rows(comp, counts, [out_ref], cnt_sm, aux_ref, cap)
            cnt_ref[bi] = cnt_sm[0]

        def slot(i, bi, ci, s, qs):
            return s[bi * cp + ci * r + i]

        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(g, nc),
            in_specs=fc.row_specs(arrays, r, slot),
            out_specs=[
                pl.BlockSpec((None, rows, fc.LANES),
                             lambda bi, ci, s, qs: (bi, 0, 0)),
                pl.BlockSpec(memory_space=pltpu.SMEM)],
            scratch_shapes=[pltpu.SMEM((1,), jnp.int32),
                            pltpu.VMEM((r, fc.LANES), jnp.int32)],
        )
        out, counts = pl.pallas_call(
            kernel, grid_spec=grid_spec,
            out_shape=[jax.ShapeDtypeStruct((g, rows, fc.LANES), jnp.int32),
                       jax.ShapeDtypeStruct((g,), jnp.int32)],
            interpret=interpret,
        )(ids_g.reshape(-1), q_g.reshape(-1), *fc.row_operands(arrays, r))
        return out.reshape(g, rows * fc.LANES)[:, :cap], counts

    out_ids, counts = fc.by_groups(call, [ids, queries], [-1, 0],
                                    cp + 4)
    return out_ids, counts, counts > cap


@functools.partial(jax.jit, static_argnames=("cap", "chunk", "interpret"))
def select_level_fused(ids, queries, lx, ly, hx, hy, child, *, cap: int,
                       chunk: int = 8, interpret: bool):
    """Evaluate one BFS level AND compact the qualifying children, fused.

    ids:     (B, C) int32 frontier node ids (-1 pad) — scalar-prefetched.
    queries: (B, 4) query rects.
    lx..hy:  (N, F) level-global SoA child MBR arrays.
    child:   (N, F) int32 child ids.
    → (next_ids (B, cap) compacted child ids (-1 pad), counts (B,) total
    qualifying children (may exceed cap), overflow (B,) bool) — exactly
    ``compact_rows``'s contract applied to the level's flat (C·F) lanes.
    """
    return _fused_select_call(fc.chunk_tile, ids, queries,
                              [lx, ly, hx, hy, child], cap=cap, chunk=chunk,
                              interpret=interpret)


# ---------------------------------------------------------------------------
# D3 quantized-layout kernels: the node block streams two packed-uint16 code
# rows (4 bytes per child MBR instead of D1's 16 — ~4x the children per
# DMA'd block) plus the tiny (1, 2) scale/bias rows, and the predicate runs
# on boxes dequantized in-register.  Dequantization is conservative (lo
# codes floored, hi codes ceiled at build time), so the mask only ever
# over-approximates the exact D1 mask; the operators re-check exact leaf
# geometry through the D1 kernel.
# ---------------------------------------------------------------------------

def _select_d3_row(q, refs, ok):
    lx, ly, hx, hy = fc.d3_row_boxes(*refs[:4])
    m = (q[0] <= hx) & (q[2] >= lx) & (q[1] <= hy) & (q[3] >= ly)
    return ((m & (refs[4][...] >= 0) & ok).astype(jnp.int32),)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def select_level_masks_d3(ids, queries, qlo, qhi, scale, bias, ptr, *,
                          chunk: int = 8, interpret: bool):
    """Evaluate one quantized BFS level for a batch of queries.

    ids:     (B, C) int32 frontier node ids (-1 pad) — scalar-prefetched.
    queries: (B, 4) query rects.
    qlo/qhi: (N, F) uint16 packed per-axis code rows.
    scale:   (N, 2) f32 power-of-two steps; bias: (N, 2) f32 node-lo corner.
    ptr:     (N, F) int32 child ids.
    → mask (B, C, F) int32 conservative qualify bitmask (superset of the
    exact D1 mask on the true child boxes).
    """
    return fc.score_rows(_select_d3_row, ids, queries,
                         [qlo, qhi, scale, bias, ptr], n_out=1,
                         out_dtype=jnp.int32, chunk=chunk,
                         interpret=interpret)[0]


@functools.partial(jax.jit, static_argnames=("cap", "chunk", "interpret"))
def select_level_fused_d3(ids, queries, qlo, qhi, scale, bias, ptr, *,
                          cap: int, chunk: int = 8, interpret: bool):
    """Fused quantized level: stream the packed uint16 code blocks, dequantize
    in-register, and compress-store the qualifying children — one
    pallas_call, same contract as ``select_level_fused`` (compact_rows over
    the flat level's conservative mask).
    """
    return _fused_select_call(fc.d3_chunk_tile, ids, queries,
                              [qlo, qhi, scale, bias, ptr], cap=cap,
                              chunk=chunk, interpret=interpret)
