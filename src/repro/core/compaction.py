"""Mask → contiguous compaction: the TPU analogue of AVX-512 compress-store.

The paper's O1 queue insertion uses ``_mm512_mask_compress_store`` to append
up to W qualifying child pointers with one instruction.  TPUs have no
compress-store; the idiomatic equivalent is ``mask → exclusive prefix-sum →
scatter-at-positions`` which XLA lowers to vector ops with no data-dependent
branches.  This module is shared by the select frontier, the join pair
frontier, and the MoE token dispatch (DESIGN.md §5 — the one piece of the
paper's machinery that generalizes to the LM substrate).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _scatter_compact(arrays, mask: jax.Array, cap: int, fill: int):
    """Shared mask→prefix-sum→scatter core: compact each (B, M) array of
    ``arrays`` under one mask into ``cap`` slots (the positions — the
    expensive part — are computed once).  Returns (outs, count, overflow)
    with count the per-row qualifying total (may exceed cap)."""
    b = mask.shape[0]
    bufs = [jnp.full((b, cap + 1), fill, vals.dtype) for vals in arrays]
    bufs, count = scatter_append(bufs, arrays, mask, None, fill)
    return [out[:, :cap] for out in bufs], count, count > cap


# Rows per scatter: the TPU compiler takes about 15 s over one (256, 16K)
# scatter into a (256, 16K) buffer, and about 1 s over two of 128 rows.
SCATTER_ROWS = 128


def scatter_append(bufs, arrays, mask: jax.Array, base, fill: int):
    """Append each (B, M) array of ``arrays`` where ``mask`` holds to its
    (B, cap + 1) buffer in ``bufs``, in lane order, from each row's slot
    ``base`` (B,) on (None: slot 0).  Entries that land past slot cap - 1
    park in the spare last slot, which the caller drops; slots not written
    keep what the buffer held.  Returns (bufs, count) with count the
    row's qualifying entries in this call."""
    mask = mask.astype(jnp.bool_)
    b, m = mask.shape
    if b > SCATTER_ROWS:
        parts = [scatter_append(
            [buf[i:i + SCATTER_ROWS] for buf in bufs],
            [vals[i:i + SCATTER_ROWS] for vals in arrays],
            mask[i:i + SCATTER_ROWS],
            None if base is None else base[i:i + SCATTER_ROWS], fill)
            for i in range(0, b, SCATTER_ROWS)]
        return ([jnp.concatenate([p[0][k] for p in parts])
                 for k in range(len(bufs))],
                jnp.concatenate([p[1] for p in parts]))
    pos = jnp.cumsum(mask, axis=1) - 1                      # inclusive-1 scan
    if base is not None:
        pos = pos + base[:, None]
    cap = bufs[0].shape[1] - 1
    pos = jnp.where(mask, pos, cap)                         # park invalids
    pos = jnp.minimum(pos, cap)                             # overflow parks too
    rows = jnp.broadcast_to(jnp.arange(b)[:, None], (b, m))
    outs = []
    for buf, vals in zip(bufs, arrays):
        if vals.shape != (b, m):
            raise ValueError(f"values must be {(b, m)}, got {vals.shape}")
        outs.append(buf.at[rows, pos].set(
            jnp.where(mask, vals, fill).astype(buf.dtype), mode="drop",
            unique_indices=False))
    return outs, mask.sum(axis=1).astype(jnp.int32)


def compact_rows(vals: jax.Array, mask: jax.Array, cap: int, fill: int = -1):
    """Row-wise compaction of ``vals`` where ``mask`` into ``cap`` slots.

    vals: (B, M) int32, mask: (B, M) bool →
      out: (B, cap) compacted values (fill-padded),
      count: (B,) number of qualifying entries (may exceed cap),
      overflow: (B,) bool — True where entries were dropped.
    """
    if vals.ndim != 2:
        raise ValueError("compact_rows expects (B, M)")
    (out,), count, ovf = _scatter_compact((vals,), mask, cap, fill)
    return out, count, ovf


def beam_rows(vals: jax.Array, dists: jax.Array, mask: jax.Array, cap: int,
              fill: int = -1):
    """Best-first beam compaction: the ``cap`` smallest-``dists`` qualifying
    entries per row, distance-ordered (``lax.top_k`` on negated distances —
    ties resolve to the lowest lane, mirroring the oracle's stable argsort).

    Same contract as ``compact_rows`` → (out (B, cap), count (B,), overflow
    (B,)): when ``count <= cap`` the kept *set* is identical to compact_rows'
    (only the intra-row order differs); on overflow the drop is best-first —
    every dropped entry's distance is ≥ the worst kept one, so downstream
    results degrade to an approximate beam with that distance bound instead
    of losing arbitrary entries.

    vals: (B, M) int32; dists: (B, M) float32 (DIST_* convention of
    geometry.py); mask: (B, M) bool.
    """
    from .geometry import DIST_PAD, DIST_VALID_MAX
    if vals.ndim != 2:
        raise ValueError("beam_rows expects (B, M)")
    b, m = vals.shape
    mask = mask.astype(jnp.bool_)
    d = jnp.where(mask, dists, DIST_PAD)
    v = jnp.where(mask, vals, fill)
    if m < cap:
        d = jnp.concatenate(
            [d, jnp.full((b, cap - m), DIST_PAD, d.dtype)], axis=1)
        v = jnp.concatenate(
            [v, jnp.full((b, cap - m), fill, v.dtype)], axis=1)
    neg_d, pos = jax.lax.top_k(-d, cap)
    out = jnp.take_along_axis(v, pos, axis=1)
    out = jnp.where(-neg_d < DIST_VALID_MAX, out, fill)
    count = mask.sum(axis=1).astype(jnp.int32)
    return out, count, count > cap


def compact_1d(vals: jax.Array, mask: jax.Array, cap: int, fill: int = -1):
    """1-D compaction (single queue): (M,) → (cap,), count, overflow."""
    out, count, ovf = compact_rows(vals[None], mask[None], cap, fill)
    return out[0], count[0], ovf[0]


def compact_pairs(a: jax.Array, b_: jax.Array, mask: jax.Array, cap: int,
                  fill: int = -1):
    """Compact two parallel (B, M) id arrays under one mask (join pairs)."""
    (oa, ob), count, ovf = _scatter_compact((a, b_), mask, cap, fill)
    return oa, ob, count, ovf
