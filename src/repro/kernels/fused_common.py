"""Shared plumbing for the R-tree level kernels.

Every kernel streams node rows through the scalar-prefetch DMA pipeline:
the frontier ids ride SMEM (``PrefetchScalarGridSpec``) and each BlockSpec
index map turns the id of frontier slot ``i`` into the HBM row of that
node, so Pallas fetches grid step k+1's node blocks while step k computes
(the paper's software prefetching, O2).  A grid step covers ``chunk``
frontier rows of one query; a BlockSpec block is one contiguous region, so
the R rows arrive as R replicated operands with per-row index maps.

TPU tiling rules shape the operands:

  * node arrays (N, W) are viewed as (N, 1, W), so a one-row block's last
    two dims equal the array's and satisfy the (8, 128) block rule;
  * per-query scalars (query coordinates, carried τ) ride SMEM with the
    ids, flattened to 1-D;
  * SMEM holds 1 MiB, so a level whose prefetched words outgrow
    ``SMEM_WORDS`` runs as a ``lax.map`` over query groups (``by_groups``)
    — one pallas_call per group inside one program.

The emission helpers below replace ops the TPU compiler has no lowering
for (in-kernel ``cumsum``, scatter, ``top_k``): ``compact_tile`` is a
lane-gather compaction (an MXU prefix sum + binary search), ``append_rows``
the running compress-store into a (rows, 128) output block, and
``merge_smallest`` a stable iterative min-extraction merge.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# prefetched 32-bit SMEM words per pallas_call (the core has 1 MiB = 256K)
SMEM_WORDS = 1 << 16
LANES = 128


def pad_frontier(ids, chunk: int):
    """Pad the frontier columns to a multiple of the chunk width with -1.
    Returns (padded ids, rows per chunk, number of chunks)."""
    b, c = ids.shape
    r = min(chunk, c)
    cpad = -(-c // r) * r
    if cpad != c:
        ids = jnp.concatenate(
            [ids, jnp.full((b, cpad - c), -1, ids.dtype)], axis=1)
    return ids, r, cpad // r


def row_view(a):
    """(N, W) node array → (N, 1, W): one node row is then a legal block."""
    return a.reshape(a.shape[0], 1, a.shape[1])


def row_specs(arrays, r: int, row_index):
    """In-specs streaming one row of every array per frontier slot of a
    chunk: ``row_index(i, *grid_and_prefetch)`` → the node id of slot i
    (clamped non-negative, so padding never DMAs out of bounds).  Operand
    order is slot-major: (slot 0: every array, slot 1: …)."""
    specs = []
    for i in range(r):
        for a in arrays:
            def imap(*args, i=i):
                return (jnp.maximum(row_index(i, *args), 0), 0, 0)
            specs.append(pl.BlockSpec((None, 1, a.shape[1]), imap))
    return specs


def row_operands(arrays, r: int):
    views = [row_view(a) for a in arrays]
    return [v for _ in range(r) for v in views]


def by_groups(call, operands, fills, words_per_row: int):
    """Run ``call(*group_operands)`` over row groups (queries, or pairs of
    a join frontier) small enough that one call's prefetched SMEM words
    fit ``SMEM_WORDS``.  ``operands`` share a leading row axis; a padded
    last group is filled with ``fills`` (-1 ids make padded rows invalid).
    ``call`` returns a pytree whose leaves lead with the group's row axis;
    the groups are re-joined in row order."""
    b = operands[0].shape[0]
    if words_per_row > SMEM_WORDS:
        raise ValueError(
            f"{words_per_row} prefetched SMEM words per row exceed the "
            f"{SMEM_WORDS}-word budget")
    g = max(1, min(b, SMEM_WORDS // words_per_row))
    if g >= b:
        return call(*operands)
    n = -(-b // g)
    pad = n * g - b
    if pad:
        operands = [jnp.concatenate(
            [x, jnp.full((pad,) + x.shape[1:], fill, x.dtype)], axis=0)
            for x, fill in zip(operands, fills)]
    out = jax.lax.map(lambda a: call(*a),
                      tuple(x.reshape((n, g) + x.shape[1:])
                            for x in operands))
    return jax.tree_util.tree_map(
        lambda o: o.reshape((n * g,) + o.shape[2:])[:b], out)


def query_scalars(q_ref, bi, qw: int):
    """The ``qw`` coordinates of query ``bi`` from the flat SMEM operand."""
    return tuple(q_ref[bi * qw + j] for j in range(qw))


def stack_rows(refs):
    """R one-row blocks → one (R, W) tile."""
    if len(refs) == 1:
        return refs[0][...]
    return jnp.concatenate([ref[...] for ref in refs], axis=0)


def slot_ok(ids_ref, base, r: int):
    """(R, 1) validity of the chunk's frontier slots (raw id sign)."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (r, 1), 0)
    raw = jnp.full((r, 1), -1, jnp.int32)
    for i in range(r):
        raw = jnp.where(rows == i, ids_ref[base + i], raw)
    return raw >= 0


def chunk_tile(row_refs, r: int):
    """D1 chunk: (lx, ly, hx, hy, child) each (R, F) from the slot-major
    one-row blocks."""
    return tuple(stack_rows(row_refs[a::5]) for a in range(5))


def d3_row_boxes(qlo_ref, qhi_ref, sc_ref, bs_ref):
    """Dequantize one D3 node row in-register: (lx, ly, hx, hy) (1, F).
    bias + code * pow2-scale is exact, so the boxes match
    ``core.layouts.d3_dequantize`` bitwise."""
    qlo = qlo_ref[...].astype(jnp.int32)
    qhi = qhi_ref[...].astype(jnp.int32)
    sx, sy = sc_ref[0, 0], sc_ref[0, 1]
    bx, by = bs_ref[0, 0], bs_ref[0, 1]
    return (bx + (qlo >> 8).astype(jnp.float32) * sx,
            by + (qlo & 0xFF).astype(jnp.float32) * sy,
            bx + (qhi >> 8).astype(jnp.float32) * sx,
            by + (qhi & 0xFF).astype(jnp.float32) * sy)


def d3_chunk_tile(row_refs, r: int):
    """D3 chunk: per slot the two packed-uint16 code rows, the (1, 2)
    scale/bias rows and the ptr row → (lx, ly, hx, hy, ptr) each (R, F)."""
    boxes = [d3_row_boxes(*row_refs[5 * i:5 * i + 4]) for i in range(r)]
    out = [jnp.concatenate([bx[a] for bx in boxes], axis=0) if r > 1
           else boxes[0][a] for a in range(4)]
    return tuple(out) + (stack_rows(row_refs[4::5]),)


def _pad_lanes(x, fill):
    w = x.shape[1]
    if w == LANES:
        return x
    if w > LANES:
        raise ValueError(f"in-kernel compaction supports rows of at most "
                         f"{LANES} lanes, got {w}")
    return jnp.concatenate(
        [x, jnp.full((x.shape[0], LANES - w), fill, x.dtype)], axis=1)


def take_lanes(x, idx):
    """Row-wise lane gather ``x[r, idx[r, j]]`` of (R, 128) tiles.  The TPU
    compiler lowers no lane gather of a one-row tile, so that case (a
    one-node frontier chunk: the root level) gathers a doubled tile and
    keeps its first row."""
    if x.shape[0] > 1:
        return jnp.take_along_axis(x, idx, axis=1)
    two = jnp.take_along_axis(jnp.concatenate([x, x]),
                              jnp.concatenate([idx, idx]), axis=1)
    return two[:1]


def compact_tile(mask, vals):
    """Per-row stream compaction of an (R, W) tile, W <= 128: row r's
    qualifying ``vals`` move, in lane order, to lanes [0, cnt_r) of an
    (R, 128) tile.  Returns (compacted vals list, counts (R, 1) int32).

    The inclusive prefix count comes from one MXU pass against an upper-
    triangular ones matrix (0/1 operands: exact in bf16 with f32
    accumulation); lane j then gathers the element whose prefix first
    reaches j + 1, found by a 7-step binary search of lane gathers."""
    m = _pad_lanes(mask.astype(jnp.int32), 0)
    r = m.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 1)
    upper = (row <= col).astype(jnp.bfloat16)
    pre = jnp.dot(m.astype(jnp.bfloat16), upper,
                  preferred_element_type=jnp.float32).astype(jnp.int32)
    want = jax.lax.broadcasted_iota(jnp.int32, (r, LANES), 1) + 1
    pos = jnp.zeros((r, LANES), jnp.int32)
    step = LANES // 2
    while step:
        probe = take_lanes(pre, pos + (step - 1))
        pos = jnp.where(probe < want, pos + step, pos)
        step //= 2
    comp = [take_lanes(_pad_lanes(v, -1), pos) for v in vals]
    return comp, jnp.sum(m, axis=1, keepdims=True)


def append_rows(comp, counts, out_refs, cnt_sm, aux_ref, cap: int):
    """Running compress-store: append each row of the compacted (R, 128)
    tiles (``compact_tile``) at the offset carried in SMEM ``cnt_sm[0]``,
    into the (rows, 128) VMEM-resident output blocks, flat position p at
    (p // 128, p % 128).  Positions >= ``cap`` are dropped while the count
    keeps running — ``compaction._scatter_compact``'s overflow parking, so
    the two stay bit-compatible.  ``aux_ref`` is an (R, 128) int32 VMEM
    scratch used to move the per-row counts into scalar registers."""
    r = counts.shape[0]
    rows_out = out_refs[0].shape[0]
    aux_ref[...] = jnp.broadcast_to(counts, (r, LANES))
    # per-row start offsets (exclusive prefix over rows), vectorized so the
    # lane rotation of every row is one gather
    rr = jax.lax.broadcasted_iota(jnp.int32, (r, r), 0)
    cc = jax.lax.broadcasted_iota(jnp.int32, (r, r), 1)
    lower = (cc < rr).astype(jnp.bfloat16)
    # counts <= 128 are exact in bf16; the f32 accumulation is exact too
    before = jnp.dot(lower, jnp.broadcast_to(counts, (r, LANES)).astype(
        jnp.bfloat16), preferred_element_type=jnp.float32)
    start = cnt_sm[0] + before[:, :1].astype(jnp.int32)        # (R, 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, (r, LANES), 1)
    src = (lane - (start & (LANES - 1))) & (LANES - 1)
    rot = [take_lanes(c, src) for c in comp]
    lane1 = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    for i in range(r):
        base = cnt_sm[0]
        n = aux_ref[i, 0]
        q = base // LANES
        sh = base - q * LANES
        qc = jnp.minimum(q, rows_out - 2)
        pos0 = q * LANES + lane1
        in0 = (lane1 >= sh) & (lane1 < sh + n) & (pos0 < cap)
        in1 = (lane1 < sh + n - LANES) & (pos0 + LANES < cap)
        for vals, out_ref in zip(rot, out_refs):
            v = vals[i:i + 1, :]
            out_ref[pl.ds(qc, 1), :] = jnp.where(
                in0, v, out_ref[pl.ds(qc, 1), :])
            out_ref[pl.ds(qc + 1, 1), :] = jnp.where(
                in1, v, out_ref[pl.ds(qc + 1, 1), :])
        cnt_sm[0] = base + n


def out_rows(cap: int) -> int:
    """Rows of a (rows, 128) compress-store block holding ``cap`` slots
    (+1 so a row's spill into the next row never indexes out of bounds)."""
    return -(-cap // LANES) + 1


def merge_smallest(keep_d, keep_v, cand_d, cand_v, n: int):
    """The ``n`` smallest (distance, value) entries of the union of a kept
    (1, K) list and an (R, F) candidate tile, ascending — equal distances
    resolve kept-first, then by row-major candidate index, exactly as
    ``lax.top_k`` over the concatenation [kept, candidates.ravel()]
    orders them.  Iterative min-extraction (n static rounds of
    reductions): the TPU compiler lowers no in-kernel sort or top_k."""
    k = keep_d.shape[1]
    rc, fc = cand_d.shape
    kl = jax.lax.broadcasted_iota(jnp.int32, (1, k), 1)
    ci = (jax.lax.broadcasted_iota(jnp.int32, (rc, fc), 0) * fc
          + jax.lax.broadcasted_iota(jnp.int32, (rc, fc), 1))
    ol = jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)
    big = jnp.int32(2 ** 30)

    def rmin(x):
        return jnp.min(jnp.min(x, axis=1, keepdims=True), axis=0,
                       keepdims=True)

    def rsum(x):
        return jnp.sum(jnp.sum(x, axis=1, keepdims=True), axis=0,
                       keepdims=True)

    def body(t, carry):
        kd, cd, od, ov = carry
        mk = rmin(kd)
        mc = rmin(cd)
        from_keep = mk <= mc
        m = jnp.minimum(mk, mc)
        ik = rmin(jnp.where(kd == m, kl, big))
        ic = rmin(jnp.where(cd == m, ci, big))
        vk = rsum(jnp.where(kl == ik, keep_v, 0))
        vc = rsum(jnp.where(ci == ic, cand_v, 0))
        od = jnp.where(ol == t, m, od)
        ov = jnp.where(ol == t, jnp.where(from_keep, vk, vc), ov)
        kd = jnp.where(from_keep & (kl == ik), jnp.inf, kd)
        cd = jnp.where(jnp.logical_not(from_keep) & (ci == ic), jnp.inf, cd)
        return kd, cd, od, ov

    init = (keep_d, cand_d, jnp.zeros((1, n), jnp.float32),
            jnp.zeros((1, n), jnp.int32))
    _, _, od, ov = jax.lax.fori_loop(0, n, body, init)
    return od, ov


def score_rows(row_fn, ids, queries, arrays, *, n_out: int, out_dtype,
               chunk: int, interpret: bool):
    """Unfused level scoring shared by every score kernel: grid (query,
    frontier chunk), one (1, F) output row per (query, frontier slot) and
    output.  ``row_fn(q, refs, ok)`` maps the query scalars, the slot's
    one-row blocks (one per array) and the slot's validity to ``n_out``
    (1, F) rows.  Returns ``n_out`` arrays (B, C, F)."""
    b, c = ids.shape
    f = arrays[-1].shape[1]
    ids_p, r, nc = pad_frontier(ids, chunk)
    cp = r * nc
    qw = queries.shape[1]
    na = len(arrays)

    def call(ids_g, q_g):
        g = ids_g.shape[0]

        def kernel(ids_ref, q_ref, *refs):
            in_refs, out_refs = refs[:na * r], refs[na * r:]
            bi = pl.program_id(0)
            ci = pl.program_id(1)
            q = query_scalars(q_ref, bi, qw)
            for i in range(r):
                ok = ids_ref[bi * cp + ci * r + i] >= 0
                rows = row_fn(q, in_refs[i * na:(i + 1) * na], ok)
                for o_ref, v in zip(out_refs, rows):
                    o_ref[i] = v

        def slot(i, bi, ci, s, qs):
            return s[bi * cp + ci * r + i]

        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(g, nc),
            in_specs=row_specs(arrays, r, slot),
            out_specs=[pl.BlockSpec((r, 1, f),
                                    lambda bi, ci, s, qs: (bi * nc + ci,
                                                           0, 0))] * n_out)
        outs = pl.pallas_call(
            kernel, grid_spec=grid_spec,
            out_shape=[jax.ShapeDtypeStruct((g * cp, 1, f), out_dtype)]
            * n_out,
            interpret=interpret,
        )(ids_g.reshape(-1), q_g.reshape(-1), *row_operands(arrays, r))
        return [o.reshape(g, cp, f)[:, :c] for o in outs]

    return by_groups(call, [ids_p, queries], [-1, 0], cp + qw)
