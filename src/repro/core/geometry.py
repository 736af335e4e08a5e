"""MBR geometry primitives.

All functions are pure jnp and operate on the paper's 2-D MBR key excerpts
``(low_x, low_y, high_x, high_y)``.  The paper evaluates intersection with
four comparisons for node layout D1 (one per key excerpt) and two
pair-interleaved comparisons for D2; both forms are provided here so the
layout-specific operators (and their Pallas kernels) share one definition of
the predicate.

Padding convention: invalid / absent children carry an *empty* MBR
(``low = +PAD, high = -PAD``) so every intersection predicate evaluates to
False without a separate validity mask.  This mirrors the paper's write-mask
trick with compress-store: padding lanes simply never qualify.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

# Large-but-finite padding values (finite so int paths and fp paths behave the
# same and so Pallas interpret mode never sees inf arithmetic surprises).
_F32_PAD = np.float32(3.0e38)
_I32_PAD = np.int32(2**31 - 2)


def pad_values(dtype) -> tuple:
    """Return ``(lo_pad, hi_pad)`` such that the padded MBR is empty."""
    dtype = np.dtype(dtype)
    if dtype.kind == "f":
        return dtype.type(_F32_PAD), dtype.type(-_F32_PAD)
    if dtype.kind == "i":
        return dtype.type(_I32_PAD), dtype.type(-_I32_PAD)
    raise TypeError(f"unsupported key dtype {dtype}")


def intersects(qlx, qly, qhx, qhy, lx, ly, hx, hy):
    """Rect/rect intersection, broadcast over array args.

    The paper's D1 predicate: 4 SIMD compares ANDed.  Written exactly as the
    four key-excerpt comparisons so the vectorized operators and the scalar
    reference agree bit-for-bit (closed intervals, as in Guttman's R-tree).
    """
    return (qlx <= hx) & (qhx >= lx) & (qly <= hy) & (qhy >= ly)


def intersects_pairs(q_lo, q_hi, lo, hi):
    """D2-form predicate on interleaved ``(x, y)`` pairs.

    ``q_lo/q_hi``: (..., 2) query corner pairs; ``lo/hi``: (..., 2) MBR corner
    pairs.  Two compares + a pair-reduction, mirroring the paper's 2-stage D2
    evaluation.
    """
    m = (q_lo <= hi) & (q_hi >= lo)  # (..., 2) per-component masks
    return m[..., 0] & m[..., 1]


def contains_point(qlx, qly, qhx, qhy, px, py):
    return (qlx <= px) & (px <= qhx) & (qly <= py) & (py <= qhy)


def mbr_of(rects: np.ndarray) -> np.ndarray:
    """Enclosing MBR of an (N, 4) array of rects (numpy, build-time)."""
    return np.array(
        [rects[:, 0].min(), rects[:, 1].min(), rects[:, 2].max(), rects[:, 3].max()],
        dtype=rects.dtype,
    )


def area(lx, ly, hx, hy):
    return jnp.maximum(hx - lx, 0) * jnp.maximum(hy - ly, 0)


# ---------------------------------------------------------------------------
# Point-to-rect distance primitives (kNN subsystem)
#
# All distances are SQUARED Euclidean: the k-NN ordering is invariant under
# sqrt, and dropping it keeps the per-entry work at the paper's
# compare/fma-only instruction mix (no transcendentals on the VPU hot path).
# Axis deltas are clamped to _DELTA_CLAMP before squaring so padded (empty)
# MBRs produce a large-but-finite distance instead of f32 inf — same
# finite-padding policy as pad_values above.
# ---------------------------------------------------------------------------

_DELTA_CLAMP = np.float32(1.0e18)      # clamp²=1e36 < f32 max, still "huge"
_SQ_CLAMP = np.float32(1.0e36)
DIST_PAD = np.float32(3.0e38)          # distance slot for invalid lanes
# d < this ⇔ lane held a real entry.  Must sit strictly between the largest
# computable real distance (2·_DELTA_CLAMP² = 2e36) and DIST_PAD: invalid
# lanes are always *explicitly* set to DIST_PAD by the operators, so the
# threshold only needs to separate those from genuine (possibly clamped)
# distances.
DIST_VALID_MAX = np.float32(1.0e37)


def _sq(d):
    """Square of a clamped axis gap.  The no-op ``minimum`` (d <= clamp, so
    d*d <= clamp²) keeps the product out of FMA contraction: every caller's
    sum of squares then rounds the same way in every fusion context (the
    fused kernel bodies and their jnp twins stay bit-identical)."""
    return jnp.minimum(d * d, _SQ_CLAMP)


def _axis_gap(p, lo, hi):
    """Per-axis outside-gap max(lo-p, p-hi, 0), clamped finite."""
    return jnp.minimum(jnp.maximum(jnp.maximum(lo - p, p - hi), 0),
                       _DELTA_CLAMP)


def mindist(px, py, lx, ly, hx, hy):
    """Squared MINDIST(point, rect) (Roussopoulos & Kelley): 0 inside the
    rect, else squared distance to the nearest face/corner.  Broadcasts over
    array args; 2 gap stages + 2 fma — the D1-form SIMD sequence."""
    return _sq(_axis_gap(px, lx, hx)) + _sq(_axis_gap(py, ly, hy))


def mindist_pairs(p, lo, hi):
    """D2-form squared MINDIST on interleaved ``(x, y)`` pairs.

    ``p``: (..., 2) query point pairs; ``lo``/``hi``: (..., 2) MBR corner
    pairs.  One gap stage over the pair + pair-reduction, mirroring the
    paper's 2-stage D2 evaluation."""
    d = _axis_gap(p, lo, hi)
    d = d * d
    return d[..., 0] + d[..., 1]


def minmaxdist(px, py, lx, ly, hx, hy):
    """Squared MINMAXDIST(point, rect) (Roussopoulos & Kelley).

    The minimum over axes k of (distance to the *nearer* face on axis k)² +
    Σ_{i≠k} (distance to the *farther* face on axis i)².  Any non-empty rect
    is guaranteed to contain an object within this distance, which makes the
    k-th smallest MINMAXDIST over a frontier a sound upper bound for k-NN
    pruning.  For degenerate (point) rects it equals mindist."""
    cx = (lx + hx) * 0.5
    cy = (ly + hy) * 0.5
    # nearer face per axis
    rmx = jnp.where(px <= cx, lx, hx)
    rmy = jnp.where(py <= cy, ly, hy)
    # farther face per axis
    rMx = jnp.where(px >= cx, lx, hx)
    rMy = jnp.where(py >= cy, ly, hy)
    dmx = jnp.minimum(jnp.abs(px - rmx), _DELTA_CLAMP)
    dmy = jnp.minimum(jnp.abs(py - rmy), _DELTA_CLAMP)
    dMx = jnp.minimum(jnp.abs(px - rMx), _DELTA_CLAMP)
    dMy = jnp.minimum(jnp.abs(py - rMy), _DELTA_CLAMP)
    return jnp.minimum(_sq(dmx) + _sq(dMy), _sq(dmy) + _sq(dMx))


# ---------------------------------------------------------------------------
# Rect-to-rect distance primitives (kNN-join subsystem)
#
# The kNN-join generalizes the point-query gap to an interval gap: the
# distance from query interval [a_lo, a_hi] to MBR interval [b_lo, b_hi] is
# max(a_lo - b_hi, b_lo - a_hi, 0).  With a degenerate (point) query every
# rect primitive reduces exactly to its point twin above, so the two operator
# families share one distance semantics.
# ---------------------------------------------------------------------------


def rect_axis_gap(a_lo, a_hi, b_lo, b_hi):
    """Per-axis interval-to-interval outside gap, clamped finite."""
    return jnp.minimum(jnp.maximum(jnp.maximum(a_lo - b_hi, b_lo - a_hi), 0),
                       _DELTA_CLAMP)


def mindist_rect(qlx, qly, qhx, qhy, lx, ly, hx, hy):
    """Squared MINDIST(rect, rect): 0 when the rects intersect, else the
    squared distance between their nearest faces/corners.  Broadcasts over
    array args; 2 gap stages + 2 fma — the D1-form SIMD sequence."""
    return (_sq(rect_axis_gap(qlx, qhx, lx, hx))
            + _sq(rect_axis_gap(qly, qhy, ly, hy)))


def mindist_rect_pairs(q_lo, q_hi, lo, hi):
    """D2-form squared MINDIST(rect, rect) on interleaved ``(x, y)`` pairs.

    ``q_lo/q_hi``: (..., 2) query corner pairs; ``lo/hi``: (..., 2) MBR corner
    pairs.  One gap stage over the pair + pair-reduction."""
    d = rect_axis_gap(q_lo, q_hi, lo, hi)
    d = d * d
    return d[..., 0] + d[..., 1]


def _face_gap(a_lo, a_hi, face):
    """Gap from query interval [a_lo, a_hi] to the coordinate ``face``."""
    return jnp.minimum(jnp.maximum(jnp.maximum(a_lo - face, face - a_hi), 0),
                       _DELTA_CLAMP)


def minmaxdist_rect(qlx, qly, qhx, qhy, lx, ly, hx, hy):
    """Squared MINMAXDIST(rect, rect) — the Roussopoulos bound generalized to
    rect queries.

    Every face of a (tight) MBR touches at least one object; an object on the
    nearer x-face sits at gap ``min(gap(lx), gap(hx))`` on x and at most
    ``max(gap(ly), gap(hy))`` on y (the interval gap is convex in the
    coordinate, so its max over the MBR interval is attained at a face).
    Minimizing over the axis choice gives an upper bound on the distance to
    *some* object inside the MBR, which makes the k-th smallest value over a
    frontier a sound kNN-join τ.  Degenerate point queries reduce exactly to
    ``minmaxdist``."""
    gxl = _face_gap(qlx, qhx, lx)
    gxh = _face_gap(qlx, qhx, hx)
    gyl = _face_gap(qly, qhy, ly)
    gyh = _face_gap(qly, qhy, hy)
    ngx, mgx = jnp.minimum(gxl, gxh), jnp.maximum(gxl, gxh)
    ngy, mgy = jnp.minimum(gyl, gyh), jnp.maximum(gyl, gyh)
    return jnp.minimum(_sq(ngx) + _sq(mgy), _sq(ngy) + _sq(mgx))


def mindist_np(px, py, lx, ly, hx, hy) -> np.ndarray:
    """Numpy twin of ``mindist`` for host-side code (the scalar baseline's
    heap loop and the shard router), unclamped — host paths never see the
    padded-MBR sentinel coordinates.  Broadcasts over array args."""
    dx = np.maximum(np.maximum(lx - px, px - hx), 0.0)
    dy = np.maximum(np.maximum(ly - py, py - hy), 0.0)
    return dx * dx + dy * dy


def minmaxdist_np(px, py, lx, ly, hx, hy) -> np.ndarray:
    """Numpy twin of ``minmaxdist`` (see there for the bound's semantics)."""
    cx = (lx + hx) * 0.5
    cy = (ly + hy) * 0.5
    dmx = np.abs(px - np.where(px <= cx, lx, hx))
    dmy = np.abs(py - np.where(py <= cy, ly, hy))
    dMx = np.abs(px - np.where(px >= cx, lx, hx))
    dMy = np.abs(py - np.where(py >= cy, ly, hy))
    return np.minimum(dmx * dmx + dMy * dMy, dmy * dmy + dMx * dMx)


def mindist_rect_np(qlx, qly, qhx, qhy, lx, ly, hx, hy) -> np.ndarray:
    """Numpy twin of ``mindist_rect`` (host-side, unclamped)."""
    dx = np.maximum(np.maximum(qlx - hx, lx - qhx), 0.0)
    dy = np.maximum(np.maximum(qly - hy, ly - qhy), 0.0)
    return dx * dx + dy * dy


def minmaxdist_rect_np(qlx, qly, qhx, qhy, lx, ly, hx, hy) -> np.ndarray:
    """Numpy twin of ``minmaxdist_rect`` (see there for the bound)."""
    def face_gap(a_lo, a_hi, face):
        return np.maximum(np.maximum(a_lo - face, face - a_hi), 0.0)
    gxl, gxh = face_gap(qlx, qhx, lx), face_gap(qlx, qhx, hx)
    gyl, gyh = face_gap(qly, qhy, ly), face_gap(qly, qhy, hy)
    ngx, mgx = np.minimum(gxl, gxh), np.maximum(gxl, gxh)
    ngy, mgy = np.minimum(gyl, gyh), np.maximum(gyl, gyh)
    return np.minimum(ngx * ngx + mgy * mgy, ngy * ngy + mgx * mgx)


def mindist_matrix_np(points, rects) -> np.ndarray:
    """Squared point-to-rect MINDIST matrix (numpy, host-side).

    points: (B, 2) or (2,); rects: (N, 4) → (B, N) float64.  The one shared
    definition behind the brute-force oracle and the shard router (the jnp
    operators use ``mindist`` above).
    """
    pts = np.atleast_2d(np.asarray(points, np.float64))
    r = np.asarray(rects, np.float64)
    return mindist_np(pts[:, 0, None], pts[:, 1, None], r[None, :, 0],
                      r[None, :, 1], r[None, :, 2], r[None, :, 3])


def mindist_rect_matrix_np(rects_a, rects_b) -> np.ndarray:
    """Squared rect-to-rect MINDIST matrix (numpy, host-side).

    rects_a: (B, 4) or (4,); rects_b: (N, 4) → (B, N) float64.  The shared
    definition behind the kNN-join oracle and the shard router."""
    a = np.atleast_2d(np.asarray(rects_a, np.float64))
    b = np.asarray(rects_b, np.float64)
    return mindist_rect_np(a[:, 0, None], a[:, 1, None], a[:, 2, None],
                           a[:, 3, None], b[None, :, 0], b[None, :, 1],
                           b[None, :, 2], b[None, :, 3])


def _k_nearest(d: np.ndarray, k: int):
    """Row-wise k smallest of a (B, N) distance matrix, sorted by distance
    with ties broken by id; rows are padded with (-1, inf) when k > N."""
    b, n = d.shape
    kk = min(k, n)
    order = np.argsort(d, axis=1, kind="stable")[:, :kk]     # ties → low id
    ids = np.full((b, k), -1, np.int64)
    out = np.full((b, k), np.inf, np.float64)
    ids[:, :kk] = order
    out[:, :kk] = np.take_along_axis(d, order, axis=1)
    return ids, out


def brute_force_knn(rects, points, k):
    """Oracle: k nearest rects to each query point (numpy, O(B·N)).

    rects: (N, 4); points: (B, 2) or (2,).  Returns (ids (B, k), sq-dists
    (B, k)) sorted by distance (ties broken by id); rows are padded with
    (-1, inf) when k > N.
    """
    return _k_nearest(mindist_matrix_np(points, rects), k)


def brute_force_knn_join(outer_rects, inner_rects, k):
    """Oracle: k nearest inner rects to each outer rect (numpy, O(B·N)).

    outer_rects: (B, 4) or (4,); inner_rects: (N, 4).  Returns (ids (B, k),
    sq-dists (B, k)) sorted by distance (ties broken by id); rows are padded
    with (-1, inf) when k > N.
    """
    return _k_nearest(mindist_rect_matrix_np(outer_rects, inner_rects), k)


def brute_force_select(rects, query):
    """Oracle: ids of all rects intersecting ``query`` (numpy)."""
    lx, ly, hx, hy = rects[:, 0], rects[:, 1], rects[:, 2], rects[:, 3]
    qlx, qly, qhx, qhy = query
    m = (qlx <= hx) & (qhx >= lx) & (qly <= hy) & (qhy >= ly)
    return np.nonzero(m)[0]


def brute_force_join(rects_a, rects_b):
    """Oracle: all intersecting (i, j) id pairs between two rect sets (numpy).

    O(N*M); intended for small property-test instances only.
    """
    alx, aly, ahx, ahy = (rects_a[:, k, None] for k in range(4))
    blx, bly, bhx, bhy = (rects_b[None, :, k] for k in range(4))
    m = (alx <= bhx) & (ahx >= blx) & (aly <= bhy) & (ahy >= bly)
    ii, jj = np.nonzero(m)
    return np.stack([ii, jj], axis=1)
