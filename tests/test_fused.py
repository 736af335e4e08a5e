"""Fused whole-level kernels ≡ unfused jitted ref path, bit-exact.

The fused operators (one pallas_call per BFS level with in-kernel
compaction / τ top-k / beam emission) must be indistinguishable from the
unfused path: same result arrays bit-for-bit, same counts, same overflow
flag, same algorithmic counters — the only permitted difference is
``Counters.dispatches`` (the whole point of the fusion).  Swept over the
kernel backends ('xla' twin and 'pallas_interpret' kernel), including the
overflow/beam and τ-tightening edge cases.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (join_vector, knn_join_vector, knn_vector, rtree,
                        select_vector)

from conftest import uniform_rects
from oracle import KERNEL_BACKENDS, OracleMatrix, cell_id, oracle_cells

COUNTERS_EXCEPT_DISPATCHES = (
    "nodes_visited", "predicates", "vector_ops", "enqueued", "pruned_outer",
    "pruned_inner", "masked_waste", "overflow", "branches")


def _assert_counters_match(c0, c1, ctx):
    for f in COUNTERS_EXCEPT_DISPATCHES:
        assert int(getattr(c0, f)) == int(getattr(c1, f)), (ctx, f)


@pytest.fixture(scope="module")
def tree_and_queries():
    rng = np.random.default_rng(41)
    rects = uniform_rects(rng, 2500, eps=0.002)
    tree = rtree.build_rtree(rects, fanout=16)
    assert tree.height >= 3
    pts = rng.random((6, 2)).astype(np.float32)
    lo = rng.random((4, 2)).astype(np.float32) * 0.94
    qrects = np.concatenate([lo, lo + np.float32(0.06)], axis=1)
    lo_big = rng.random((4, 2)).astype(np.float32) * 0.7
    qrects_big = np.concatenate([lo_big, lo_big + np.float32(0.3)], axis=1)
    outer = uniform_rects(rng, 6, eps=0.01)
    return tree, pts, qrects, qrects_big, outer


# ---------------------------------------------------------------------------
# differential-oracle matrix: fused cells on both kernel backends
# ---------------------------------------------------------------------------

# the fused D3 variant exists for select only (KERNEL_CELLS) — the
# harness skips the unsupported d3 fused cells for knn / knn_join
FUSED_OPS = ("select", "knn", "knn_join")
FUSED_CELLS = {op: oracle_cells(op, ("d1", "d3"), backends=KERNEL_BACKENDS,
                                fused=(True,))
               for op in FUSED_OPS}


@pytest.fixture(scope="module")
def fused_matrices():
    return {op: OracleMatrix(op, 11, FUSED_CELLS[op]) for op in FUSED_OPS}


@pytest.mark.parametrize("op,cell", [
    pytest.param(op, cell, id=f"{op}-{cell_id(cell)}")
    for op in FUSED_OPS for cell in FUSED_CELLS[op]])
def test_fused_matches_oracle(fused_matrices, op, cell):
    expect = 2 if op == "select" else 1
    assert len(FUSED_CELLS[op]) == expect * len(KERNEL_BACKENDS)
    fused_matrices[op].check(*cell)


# ---------------------------------------------------------------------------
# bit-exact fused-vs-unfused parity (results + counters except dispatches)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", KERNEL_BACKENDS)
@pytest.mark.parametrize("result_cap", [2048, 64])   # 64 forces overflow
def test_select_fused_parity(tree_and_queries, backend, result_cap):
    # the small-cap cell pairs with the big query rects (~hundreds of hits
    # per query) so the overflow path actually fires
    tree, _, qrects, qrects_big, _ = tree_and_queries
    q = jnp.asarray(qrects_big if result_cap == 64 else qrects)
    r0, c0, t0 = select_vector.make_select_bfs(
        tree, result_cap=result_cap, backend="xla")(q)
    r1, c1, t1 = select_vector.make_select_bfs(
        tree, result_cap=result_cap, backend=backend, fused=True)(q)
    np.testing.assert_array_equal(np.asarray(r0), np.asarray(r1))
    np.testing.assert_array_equal(np.asarray(c0), np.asarray(c1))
    _assert_counters_match(t0, t1, f"select {backend} cap={result_cap}")
    if result_cap == 64:
        assert int(t1.overflow) == 1           # the edge case actually fired
    assert int(t1.dispatches) < int(t0.dispatches)


@pytest.mark.parametrize("backend", KERNEL_BACKENDS)
@pytest.mark.parametrize("k", [1, 8, 64])
def test_knn_fused_parity(tree_and_queries, backend, k):
    # k=64 > root lanes (C·F = 16) exercises the τ-tightening skip gate
    tree, pts, _, _, _ = tree_and_queries
    q = jnp.asarray(pts)
    i0, d0, t0 = knn_vector.make_knn_bfs(tree, k=k, backend="xla")(q)
    i1, d1, t1 = knn_vector.make_knn_bfs(tree, k=k, backend=backend,
                                         fused=True)(q)
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
    np.testing.assert_array_equal(np.asarray(d0), np.asarray(d1))
    _assert_counters_match(t0, t1, f"knn {backend} k={k}")


@pytest.mark.parametrize("backend", KERNEL_BACKENDS)
def test_knn_fused_parity_beam_overflow(tree_and_queries, backend):
    """Tiny custom caps force the best-first beam: the fused in-kernel beam
    merge must reproduce beam_rows' drop set and order bit-for-bit, and the
    overflow flag must survive."""
    tree, pts, _, _, _ = tree_and_queries
    q = jnp.asarray(pts)
    caps = (2, 3)                              # deliberately ragged + tiny
    i0, d0, t0 = knn_vector.make_knn_bfs(tree, k=8, caps=caps,
                                         backend="xla")(q)
    i1, d1, t1 = knn_vector.make_knn_bfs(tree, k=8, caps=caps,
                                         backend=backend, fused=True)(q)
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
    np.testing.assert_array_equal(np.asarray(d0), np.asarray(d1))
    _assert_counters_match(t0, t1, f"knn beam {backend}")
    assert int(t1.overflow) == 1


@pytest.mark.parametrize("backend", KERNEL_BACKENDS)
@pytest.mark.parametrize("k", [3, 20])
def test_knn_fused_parity_root_leaf(backend, k):
    """Height-1 tree (the root is the leaf) and k > n: the fused leaf kernel
    alone answers the query, padding missing neighbours as (-1, inf)."""
    rng = np.random.default_rng(43)
    rects = uniform_rects(rng, 10, eps=0.002)
    tree = rtree.build_rtree(rects, fanout=16)
    assert tree.height == 1
    q = jnp.asarray(rng.random((5, 2)).astype(np.float32))
    i0, d0, _ = knn_vector.make_knn_bfs(tree, k=k, backend="xla")(q)
    i1, d1, _ = knn_vector.make_knn_bfs(tree, k=k, backend=backend,
                                        fused=True)(q)
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
    np.testing.assert_array_equal(np.asarray(d0), np.asarray(d1))


@pytest.mark.parametrize("backend", KERNEL_BACKENDS)
@pytest.mark.parametrize("k,caps", [(8, None), (8, (2, 3)), (32, None)])
def test_knn_join_fused_parity(tree_and_queries, backend, k, caps):
    tree, _, _, _, outer = tree_and_queries
    q = jnp.asarray(outer)
    i0, d0, t0 = knn_join_vector.make_knn_join_bfs(
        tree, k=k, caps=caps, backend="xla")(q)
    i1, d1, t1 = knn_join_vector.make_knn_join_bfs(
        tree, k=k, caps=caps, backend=backend, fused=True)(q)
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
    np.testing.assert_array_equal(np.asarray(d0), np.asarray(d1))
    _assert_counters_match(t0, t1, f"knn_join {backend} k={k} caps={caps}")
    if caps is not None:
        assert int(t1.overflow) == 1


@pytest.mark.parametrize("backend", KERNEL_BACKENDS)
@pytest.mark.parametrize("o3,o4", [(False, False), (True, True)])
def test_join_fused_parity(backend, o3, o4):
    rng = np.random.default_rng(44)
    ra = uniform_rects(rng, 400, eps=0.012)
    rb = uniform_rects(rng, 400, eps=0.012)
    ta = rtree.build_rtree(ra, fanout=16, sort_key="lx")
    tb = rtree.build_rtree(rb, fanout=16, sort_key="lx")
    p0, n0, t0 = join_vector.make_join_bfs(
        ta, tb, result_cap=8192, o3=o3, o4=o4, backend="xla")()
    p1, n1, t1 = join_vector.make_join_bfs(
        ta, tb, result_cap=8192, o3=o3, o4=o4, backend=backend,
        fused=True)()
    np.testing.assert_array_equal(np.asarray(p0), np.asarray(p1))
    assert int(n0) == int(n1)
    _assert_counters_match(t0, t1, f"join {backend} o3={o3}")


# ---------------------------------------------------------------------------
# dispatch accounting: the headline claim, asserted
# ---------------------------------------------------------------------------

def test_dispatch_reduction_at_height_3(tree_and_queries):
    """≥ 3× fewer device-program launches per query batch for select and
    kNN at tree height ≥ 3 (the fused kernels collapse each level's
    score→emit pipeline to one launch)."""
    tree, pts, qrects, _, _ = tree_and_queries
    assert tree.height >= 3
    _, _, ts0 = select_vector.make_select_bfs(
        tree, result_cap=2048, backend="xla")(jnp.asarray(qrects))
    _, _, ts1 = select_vector.make_select_bfs(
        tree, result_cap=2048, backend="xla", fused=True)(jnp.asarray(qrects))
    assert int(ts0.dispatches) >= 3 * int(ts1.dispatches)
    _, _, tk0 = knn_vector.make_knn_bfs(
        tree, k=8, backend="xla")(jnp.asarray(pts))
    _, _, tk1 = knn_vector.make_knn_bfs(
        tree, k=8, backend="xla", fused=True)(jnp.asarray(pts))
    assert int(tk0.dispatches) >= 3 * int(tk1.dispatches)
    # one launch per level in fused mode, exactly
    assert int(ts1.dispatches) == tree.height
    assert int(tk1.dispatches) == tree.height


# The frontier-caps lane-alignment regression lives with the unified caps
# policy in tests/test_traversal.py (test_caps_lane_round_in_one_place).
