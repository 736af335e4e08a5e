"""kNN subsystem: geometry primitives, scalar best-first ≡ brute force,
batched vector BFS ≡ brute force across layouts/k, kernel backend parity,
ties, k > n, sharded ≡ single-tree."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import knn_scalar, knn_vector, rtree
from repro.core.geometry import (brute_force_knn, mindist, mindist_matrix_np,
                                 mindist_pairs, minmaxdist)
from repro.distributed.spatial_shard import SpatialShards

from conftest import uniform_rects
from oracle import (KERNEL_BACKENDS, LAYOUTS, OracleMatrix,
                    assert_matches_oracle, cell_id, oracle_cells)


def _true_sq_dist(rects, p, ids):
    return mindist_matrix_np(p, rects[ids])[0]


# ---------------------------------------------------------------------------
# geometry primitives
# ---------------------------------------------------------------------------

def test_mindist_values():
    # inside → 0; axis gap → dx²; corner gap → dx²+dy²
    assert float(mindist(0.5, 0.5, 0.0, 0.0, 1.0, 1.0)) == 0.0
    assert float(mindist(-0.5, 0.5, 0.0, 0.0, 1.0, 1.0)) == pytest.approx(0.25)
    assert float(mindist(2.0, 3.0, 0.0, 0.0, 1.0, 1.0)) == pytest.approx(5.0)


def test_mindist_pairs_matches_d1_form():
    rng = np.random.default_rng(0)
    lo = rng.random((64, 2)).astype(np.float32)
    hi = lo + rng.random((64, 2)).astype(np.float32) * 0.2
    p = rng.random(2).astype(np.float32)
    d1 = mindist(p[0], p[1], lo[:, 0], lo[:, 1], hi[:, 0], hi[:, 1])
    d2 = mindist_pairs(p, lo, hi)
    np.testing.assert_allclose(np.asarray(d1), np.asarray(d2), rtol=1e-6)


def test_minmaxdist_properties():
    rng = np.random.default_rng(1)
    lo = rng.random((256, 2)).astype(np.float32)
    hi = lo + rng.random((256, 2)).astype(np.float32) * 0.3
    p = rng.random(2).astype(np.float32)
    md = np.asarray(mindist(p[0], p[1], lo[:, 0], lo[:, 1],
                            hi[:, 0], hi[:, 1]))
    mmd = np.asarray(minmaxdist(p[0], p[1], lo[:, 0], lo[:, 1],
                                hi[:, 0], hi[:, 1]))
    assert (mmd >= md - 1e-7).all()
    # MINMAXDIST upper-bounds the distance to the farthest corner
    cx = np.maximum(np.abs(p[0] - lo[:, 0]), np.abs(p[0] - hi[:, 0]))
    cy = np.maximum(np.abs(p[1] - lo[:, 1]), np.abs(p[1] - hi[:, 1]))
    assert (mmd <= cx * cx + cy * cy + 1e-6).all()
    # degenerate (point) rects: minmaxdist == mindist == true distance
    mmd_pt = np.asarray(minmaxdist(p[0], p[1], lo[:, 0], lo[:, 1],
                                   lo[:, 0], lo[:, 1]))
    d_pt = (p[0] - lo[:, 0]) ** 2 + (p[1] - lo[:, 1]) ** 2
    np.testing.assert_allclose(mmd_pt, d_pt, rtol=1e-5, atol=1e-7)


# ---------------------------------------------------------------------------
# scalar best-first ≡ brute force
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tree_and_rects():
    rng = np.random.default_rng(30)
    rects = uniform_rects(rng, 12_000, eps=0.002)
    return rtree.build_rtree(rects, fanout=64), rects


@pytest.mark.parametrize("k", [1, 8, 64])
def test_scalar_best_first(tree_and_rects, k):
    t, rects = tree_and_rects
    rng = np.random.default_rng(31)
    pts = rng.random((6, 2)).astype(np.float32)
    oids, od = brute_force_knn(rects, pts, k)
    for i, p in enumerate(pts):
        ids, d, ctr = knn_scalar.knn_best_first(t, p, k)
        np.testing.assert_allclose(d, od[i], rtol=1e-5, atol=1e-9)
        assert ctr.nodes_visited > 0
        # best-first opens a tiny fraction of the tree
        assert ctr.nodes_visited < t.n_nodes_total()


# ---------------------------------------------------------------------------
# batched vector BFS ≡ brute force (all layouts × k) — via the shared
# differential-oracle harness (tests/oracle.py), which also checks that
# returned ids really sit at the reported distances and are distinct
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("layout", LAYOUTS)
@pytest.mark.parametrize("k", [1, 8, 64])
def test_vector_knn_matches_oracle(layout, k):
    assert_matches_oracle("knn", layouts=(layout,), backends=(None,),
                          seeds=(32,), k=k)


def test_vector_counters_show_pruning(tree_and_rects):
    t, _ = tree_and_rects
    rng = np.random.default_rng(33)
    pts = rng.random((4, 2)).astype(np.float32)
    fn = knn_vector.make_knn_bfs(t, k=8)
    _, _, ctr = fn(jnp.asarray(pts))
    assert int(ctr.pruned_inner) > 0
    assert int(ctr.nodes_visited) < 4 * t.n_nodes_total()


KNN_KERNEL_CELLS = oracle_cells("knn", ("d1", "d3"),
                                backends=KERNEL_BACKENDS)


@pytest.fixture(scope="module")
def knn_kernel_matrix():
    return OracleMatrix("knn", 34, KNN_KERNEL_CELLS, k=8)


@pytest.mark.parametrize("cell", KNN_KERNEL_CELLS, ids=cell_id)
def test_kernel_backend_matches_oracle(knn_kernel_matrix, cell):
    knn_kernel_matrix.check(*cell)


# ---------------------------------------------------------------------------
# edge cases
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("k", [3, 7])          # k cuts through tie groups
def test_ties_duplicate_points(k):
    rng = np.random.default_rng(35)
    base = rng.random((40, 2)).astype(np.float32)
    pts = np.repeat(base, 5, axis=0)            # every point 5×
    rects = np.concatenate([pts, pts], axis=1)
    t = rtree.build_rtree(rects, fanout=16)
    q = rng.random((4, 2)).astype(np.float32)
    _, od = brute_force_knn(rects, q, k)
    fn = knn_vector.make_knn_bfs(t, k=k)
    ids, d, _ = fn(jnp.asarray(q))
    np.testing.assert_allclose(np.sort(np.asarray(d), axis=1),
                               np.sort(od, axis=1), rtol=1e-5)
    for i in range(len(q)):
        sids, sd, _ = knn_scalar.knn_best_first(t, q[i], k)
        np.testing.assert_allclose(sd, od[i], rtol=1e-5)


def test_k_exceeds_n_rects():
    rng = np.random.default_rng(36)
    rects = uniform_rects(rng, 7)
    t = rtree.build_rtree(rects, fanout=4)
    q = rng.random((2, 2)).astype(np.float32)
    fn = knn_vector.make_knn_bfs(t, k=12)
    ids, d, _ = fn(jnp.asarray(q))
    ids, d = np.asarray(ids), np.asarray(d)
    assert (np.sort(ids[:, :7], axis=1) == np.arange(7)).all()
    assert (ids[:, 7:] == -1).all() and np.isinf(d[:, 7:]).all()
    sids, sd, _ = knn_scalar.knn_best_first(t, q[0], 12)
    assert (sids[7:] == -1).all() and np.isinf(sd[7:]).all()
    np.testing.assert_allclose(np.sort(d[0, :7]), np.sort(sd[:7]), rtol=1e-5)


LANE_COUNT_SIZES = (52, 200)


def _lane_count_instance(n):
    """The (rects, points) of size ``n``, drawn in LANE_COUNT_SIZES order
    from one stream, so each size sees the same data whichever case runs."""
    rng = np.random.default_rng(23)
    for m in LANE_COUNT_SIZES:
        rects = uniform_rects(rng, m, eps=0.01)
        pts = rng.random((4, 2)).astype(np.float32)
        if m == n:
            return rects, pts
    raise KeyError(n)


@pytest.mark.parametrize("n", LANE_COUNT_SIZES)
@pytest.mark.parametrize("sort_key", [None, "lx"])
def test_k_exceeds_lane_count(sort_key, n):
    # k > fanout: upper levels have fewer than k lanes, so the τ bound must
    # not tighten there (regression: truncated k-th MINMAXDIST guaranteed
    # only C·F objects and silently pruned true neighbors)
    rects, pts = _lane_count_instance(n)
    t = rtree.build_rtree(rects, fanout=4, sort_key=sort_key)
    fn = knn_vector.make_knn_bfs(t, k=32)
    ids, d, ctr = fn(jnp.asarray(pts))
    assert not bool(ctr.overflow)
    _, od = brute_force_knn(rects, pts, 32)
    np.testing.assert_allclose(np.sort(np.asarray(d), axis=1),
                               np.sort(od, axis=1), rtol=1e-4, atol=1e-9)


def test_single_node_tree():
    rects = np.array([[0.1, 0.1, 0.2, 0.2], [0.8, 0.8, 0.9, 0.9]],
                     np.float32)
    t = rtree.build_rtree(rects, fanout=8)      # height 1: root is the leaf
    fn = knn_vector.make_knn_bfs(t, k=1)
    ids, d, _ = fn(jnp.asarray(np.array([[0.12, 0.12], [0.85, 0.85]],
                                        np.float32)))
    assert np.asarray(ids)[:, 0].tolist() == [0, 1]
    np.testing.assert_allclose(np.asarray(d)[:, 0], [0.0, 0.0], atol=1e-7)


# ---------------------------------------------------------------------------
# sharded ≡ single tree
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def sharded_fleet():
    rng = np.random.default_rng(37)
    rects = uniform_rects(rng, 20_000, eps=0.003)
    t = rtree.build_rtree(rects, fanout=32)
    shards = SpatialShards.build(rects, n_partitions=6, fanout=32)
    q = rng.random((10, 2)).astype(np.float32)
    return rects, t, shards, q


@pytest.mark.parametrize("k", [1, 8])
def test_sharded_matches_single_tree(sharded_fleet, k):
    rects, t, shards, q = sharded_fleet
    assert len(shards.partitions) >= 2
    gids, gd, ovf = shards.knn(q, k)
    assert not ovf
    fn = knn_vector.make_knn_bfs(t, k=k)
    _, d, _ = fn(jnp.asarray(q))
    np.testing.assert_allclose(np.sort(gd, axis=1),
                               np.sort(np.asarray(d), axis=1), rtol=1e-4)
    _, od = brute_force_knn(rects, q, k)
    np.testing.assert_allclose(np.sort(gd, axis=1), np.sort(od, axis=1),
                               rtol=1e-4)
    for i, p in enumerate(q):
        np.testing.assert_allclose(_true_sq_dist(rects, p, gids[i]),
                                   gd[i], rtol=1e-4, atol=1e-9)
