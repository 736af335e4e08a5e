"""Pallas TPU kernel: R-tree join pair-frontier tile step (paper §4).

One grid step evaluates a (F_out × TI) slab of the child cross-product
predicate for one (outer node, inner node) frontier pair.  TI=128 lanes
carry inner children; each outer child is one sublane row whose four
coordinates load as scalars and broadcast across the lanes — the paper's
one-to-many broadcast, stacked into a native 2-D tile (DESIGN.md §2 — the
TPU adaptation of O5).

Sorted-key pruning is honored at (TO, TI) tile granularity via
scalar-prefetch metadata computed in a cheap XLA pre-pass:

  alive_cnt[p]    — O3: number of leading outer children that can intersect
                    any inner child (outer sorted by low_x);
  flip_max[p, a]  — O4/O5: per outer tile ``a``, the max flip index (number
                    of eligible leading inner children, inner sorted by
                    low_x) over the tile's outer rows.

A tile whose outer rows are all O3-pruned or whose inner lanes lie entirely
beyond ``flip_max`` skips the 4-stage predicate entirely (`pl.when`) and
writes zeros — the instruction-saving the paper measures, realized as
skipped VPU work on TPU.  The (outer, inner) node blocks themselves arrive
via scalar-prefetched DMA (O2, as in the select kernel).

**Whole-level (fused)** — ``join_level_fused``: each grid step evaluates
one pair's full (F_out × F_in) predicate tile (O3/O4/O5 skipping applied as
dense masks) and compress-stores the qualifying (outer-child, inner-child)
id pairs at a running offset (SMEM) into shared (rows, 128) output blocks
that stay resident in VMEM across the grid — bit-compatible with
``compact_pairs`` over the flat (P·F_out·F_in) lanes, with no
(P, F_out, F_in) HBM mask intermediate.  A pair frontier whose prefetched
metadata outgrows SMEM runs as a chain of such calls (``lax.scan``), each
resuming the running offset and the aliased output blocks of the last.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import fused_common as fc


def _outer_row_mask(o_ref, i_ref, r: int):
    """Predicate row of outer child ``r`` against the inner block's lanes:
    the outer child's four coordinates load as scalars (its (1, 4, F) node
    block in VMEM), the inner children ride the lanes — the one-to-many
    broadcast of the paper, one sublane at a time."""
    olx, oly, ohx, ohy = (o_ref[a, r] for a in range(4))
    return ((olx <= i_ref[2:3, :]) & (ohx >= i_ref[0:1, :])
            & (oly <= i_ref[3:4, :]) & (ohy >= i_ref[1:2, :]))


def _tiles(o_coords, i_coords, flip_max, to: int):
    p = flip_max.shape[0]
    fo, fi = o_coords.shape[2], i_coords.shape[2]
    to = min(to, fo)
    if fo % to:
        raise ValueError(f"outer fanout {fo} not divisible by tile {to}")
    na = fo // to
    if flip_max.shape != (p, na):
        raise ValueError(f"flip_max must be {(p, na)}, got {flip_max.shape}")
    return fo, fi, to, na


@functools.partial(jax.jit, static_argnames=("to", "ti", "interpret"))
def join_pair_masks(o_ids, i_ids, alive_cnt, flip_max,
                    o_coords, i_coords, *, to: int = 8, ti: int = 128,
                    interpret: bool):
    """Tile-evaluate the join predicate for a pair frontier.

    o_ids/i_ids: (P,) int32 node ids (-1 pad) — scalar-prefetched.
    alive_cnt:   (P,) int32 O3 bound (pass F_out to disable O3 skipping).
    flip_max:    (P, ceil(F_out/to)) int32 O4/O5 tile bound (pass F_in to
                 disable).
    o_coords/i_coords: (N, 4, F) D1 coords arrays of the two levels
                 (rows: lx, ly, hx, hy).
    → mask (P, F_out, F_in) int32.
    """
    fo, fi, to, na = _tiles(o_coords, i_coords, flip_max, to)
    ti = min(ti, fi)
    if fi % ti:
        raise ValueError(f"inner fanout {fi} not divisible by tile {ti}")
    nb = fi // ti

    def call(so, si, ac, fm):
        g = so.shape[0]

        def kernel(so_ref, si_ref, ac_ref, fm_ref, o_ref, i_ref, mask_ref):
            p = pl.program_id(0)
            b = pl.program_id(1)
            valid_pair = (so_ref[p] >= 0) & (si_ref[p] >= 0)
            for a in range(na):
                active = valid_pair & (a * to < ac_ref[p]) & \
                    (b * ti < fm_ref[p * na + a])

                @pl.when(active)
                def _():
                    for t in range(a * to, (a + 1) * to):
                        mask_ref[t:t + 1, :] = _outer_row_mask(
                            o_ref, i_ref, t).astype(jnp.int32)

                @pl.when(jnp.logical_not(active))
                def _():
                    mask_ref[a * to:(a + 1) * to, :] = jnp.zeros(
                        (to, ti), jnp.int32)

        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(g, nb),
            in_specs=[
                pl.BlockSpec((None, 4, fo), lambda pi, bi, so, si, ac, fm:
                             (jnp.maximum(so[pi], 0), 0, 0)),
                pl.BlockSpec((None, 4, ti), lambda pi, bi, so, si, ac, fm:
                             (jnp.maximum(si[pi], 0), 0, bi)),
            ],
            out_specs=pl.BlockSpec((None, fo, ti),
                                   lambda pi, bi, so, si, ac, fm: (pi, 0, bi)),
        )
        return pl.pallas_call(
            kernel, grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((g, fo, fi), jnp.int32),
            interpret=interpret,
        )(so, si, ac, fm.reshape(-1), o_coords, i_coords)

    return fc.by_groups(call, [o_ids, i_ids, alive_cnt, flip_max],
                        [-1, -1, 0, 0], 3 + na)


# ---------------------------------------------------------------------------
# Fused whole-level kernel: tile predicate + in-kernel pair compress-store
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("cap", "to", "interpret"))
def join_level_fused(o_ids, i_ids, alive_cnt, flip_max, o_coords, i_coords,
                     o_ptr, i_ptr, *, cap: int, to: int = 8,
                     interpret: bool):
    """Evaluate AND compact one pair-frontier level, fused.

    o_ids/i_ids: (P,) int32 node ids (-1 pad) — scalar-prefetched.
    alive_cnt / flip_max: O3 / O4-O5 pruning bounds (join_prune_metadata).
    o_coords/i_coords: (N, 4, F) D1 coords arrays; o_ptr/i_ptr: (N, F) int32
    child-id arrays of the two levels.
    → (out_o (cap,), out_i (cap,) compacted child-id pairs (-1 pad),
    count (may exceed cap), overflow bool) — ``compact_pairs``'s contract
    applied to the flat (P·F_out·F_in) lanes.
    """
    fo, fi, to, na = _tiles(o_coords, i_coords, flip_max, to)
    ti = min(128, fi)
    rows = fc.out_rows(cap)
    meta = jnp.concatenate(
        [o_ids[:, None], i_ids[:, None], alive_cnt[:, None], flip_max],
        axis=1).astype(jnp.int32)
    n_groups = -(-meta.shape[0] * (3 + na) // fc.SMEM_WORDS)
    g = -(-meta.shape[0] // n_groups)
    pad = n_groups * g - meta.shape[0]
    if pad:
        fill = jnp.concatenate([jnp.full((pad, 2), -1, jnp.int32),
                                jnp.zeros((pad, 1 + na), jnp.int32)], axis=1)
        meta = jnp.concatenate([meta, fill], axis=0)

    def kernel(base_ref, meta_ref, o_ref, i_ref, op_ref, ip_ref,
               oo_in, oi_in, oo_ref, oi_ref, cnt_ref, cnt_sm, aux_ref):
        p = pl.program_id(0)
        w = 3 + na
        so, si, ac = meta_ref[p * w], meta_ref[p * w + 1], meta_ref[p * w + 2]

        @pl.when(p == 0)
        def _():
            cnt_sm[0] = base_ref[0]
            oo_ref[...] = oo_in[...]
            oi_ref[...] = oi_in[...]

        valid_pair = (so >= 0) & (si >= 0)
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, fi), 1)
        iptr = ip_ref[...]
        masks, optrs = [], []
        for r in range(fo):
            a = r // to
            optr = op_ref[0, r]
            # O3/O4/O5 skipping as dense masks — identical semantics to the
            # per-tile `pl.when` skip of the unfused kernel (a skipped tile
            # is an all-zero tile either way)
            ok = valid_pair & (a * to < ac) & (optr >= 0)
            m = _outer_row_mask(o_ref, i_ref, r) & ok & (iptr >= 0) & \
                ((lane // ti) * ti < meta_ref[p * w + 3 + a])
            masks.append(m.astype(jnp.int32))
            optrs.append(jnp.full((1, fi), optr, jnp.int32))
        mask = jnp.concatenate(masks, axis=0) > 0
        comp, counts = fc.compact_tile(
            mask, [jnp.concatenate(optrs, axis=0),
                   jnp.broadcast_to(iptr, (fo, fi))])
        fc.append_rows(comp, counts, [oo_ref, oi_ref], cnt_sm, aux_ref, cap)
        cnt_ref[0] = cnt_sm[0]

    def node(col):
        def imap(pi, base, m):
            return (jnp.maximum(m[pi * (3 + na) + col], 0), 0, 0)
        return imap

    def shared(pi, base, m):
        return (0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(g,),
        in_specs=[pl.BlockSpec((None, 4, fo), node(0)),
                  pl.BlockSpec((None, 4, fi), node(1)),
                  pl.BlockSpec((None, 1, fo), node(0)),
                  pl.BlockSpec((None, 1, fi), node(1)),
                  pl.BlockSpec((rows, fc.LANES), shared),
                  pl.BlockSpec((rows, fc.LANES), shared)],
        out_specs=[pl.BlockSpec((rows, fc.LANES), shared),
                   pl.BlockSpec((rows, fc.LANES), shared),
                   pl.BlockSpec(memory_space=pltpu.SMEM)],
        scratch_shapes=[pltpu.SMEM((1,), jnp.int32),
                        pltpu.VMEM((fo, fc.LANES), jnp.int32)],
    )
    level = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((rows, fc.LANES), jnp.int32)] * 2
        + [jax.ShapeDtypeStruct((1,), jnp.int32)],
        input_output_aliases={6: 0, 7: 1},
        interpret=interpret,
    )

    def group(carry, meta_g):
        oo, oi, cnt = carry
        oo, oi, cnt = level(cnt, meta_g.reshape(-1), o_coords, i_coords,
                            fc.row_view(o_ptr), fc.row_view(i_ptr), oo, oi)
        return (oo, oi, cnt), None

    init = (jnp.full((rows, fc.LANES), -1, jnp.int32),
            jnp.full((rows, fc.LANES), -1, jnp.int32),
            jnp.zeros((1,), jnp.int32))
    (oo, oi, cnt), _ = jax.lax.scan(group, init,
                                    meta.reshape(n_groups, g, 3 + na))
    count = cnt[0]
    return (oo.reshape(-1)[:cap], oi.reshape(-1)[:cap], count, count > cap)
