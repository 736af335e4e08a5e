"""kNN-join subsystem: rect-distance primitives, scalar nested best-first ≡
brute force, batched vector BFS ≡ brute force across layouts/backends via
the differential-oracle harness, beam fallback on undersized caps,
all-pairs tree convenience, sharded two-phase ≡ single tree."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import knn_join_scalar, knn_join_vector, rtree
from repro.core.geometry import (brute_force_knn_join, mindist,
                                 mindist_rect, mindist_rect_matrix_np,
                                 mindist_rect_pairs, minmaxdist,
                                 minmaxdist_rect)
from repro.distributed.spatial_shard import SpatialShards

from conftest import uniform_rects
from oracle import (KERNEL_BACKENDS, LAYOUTS, OracleMatrix,
                    assert_matches_oracle, cell_id, oracle_cells)


def _true_sq_dist(rects, q, ids):
    return mindist_rect_matrix_np(q, rects[ids])[0]


# ---------------------------------------------------------------------------
# rect-to-rect geometry primitives
# ---------------------------------------------------------------------------

def test_mindist_rect_values():
    # overlapping → 0; axis gap → dx²; corner gap → dx²+dy²
    assert float(mindist_rect(0.0, 0.0, 1.0, 1.0, 0.5, 0.5, 2.0, 2.0)) == 0.0
    assert float(mindist_rect(0.0, 0.0, 1.0, 1.0, 1.5, 0.0, 2.0, 1.0)) == \
        pytest.approx(0.25)
    assert float(mindist_rect(0.0, 0.0, 1.0, 1.0, 2.0, 3.0, 2.5, 3.5)) == \
        pytest.approx(5.0)


def test_mindist_rect_reduces_to_point_form():
    rng = np.random.default_rng(0)
    lo = rng.random((64, 2)).astype(np.float32)
    hi = lo + rng.random((64, 2)).astype(np.float32) * 0.2
    p = rng.random(2).astype(np.float32)
    d_pt = mindist(p[0], p[1], lo[:, 0], lo[:, 1], hi[:, 0], hi[:, 1])
    d_rc = mindist_rect(p[0], p[1], p[0], p[1],
                        lo[:, 0], lo[:, 1], hi[:, 0], hi[:, 1])
    np.testing.assert_allclose(np.asarray(d_pt), np.asarray(d_rc), rtol=1e-6)
    d2 = mindist_rect_pairs(p, p, lo, hi)
    np.testing.assert_allclose(np.asarray(d_rc), np.asarray(d2), rtol=1e-6)


def test_minmaxdist_rect_properties():
    rng = np.random.default_rng(1)
    lo = rng.random((256, 2)).astype(np.float32)
    hi = lo + rng.random((256, 2)).astype(np.float32) * 0.3
    q = np.array([0.3, 0.4, 0.45, 0.6], np.float32)
    md = np.asarray(mindist_rect(q[0], q[1], q[2], q[3],
                                 lo[:, 0], lo[:, 1], hi[:, 0], hi[:, 1]))
    mmd = np.asarray(minmaxdist_rect(q[0], q[1], q[2], q[3],
                                     lo[:, 0], lo[:, 1], hi[:, 0],
                                     hi[:, 1]))
    assert (mmd >= md - 1e-7).all()
    # the bound never exceeds the farthest-corner gap (an upper bound on
    # the distance to ANY point of the MBR)
    def face_gap(a_lo, a_hi, v):
        return np.maximum(np.maximum(a_lo - v, v - a_hi), 0)
    mgx = np.maximum(face_gap(q[0], q[2], lo[:, 0]),
                     face_gap(q[0], q[2], hi[:, 0]))
    mgy = np.maximum(face_gap(q[1], q[3], lo[:, 1]),
                     face_gap(q[1], q[3], hi[:, 1]))
    assert (mmd <= mgx * mgx + mgy * mgy + 1e-6).all()
    # degenerate (point) inner rects: minmaxdist_rect == mindist_rect
    mmd_pt = np.asarray(minmaxdist_rect(q[0], q[1], q[2], q[3],
                                        lo[:, 0], lo[:, 1], lo[:, 0],
                                        lo[:, 1]))
    md_pt = np.asarray(mindist_rect(q[0], q[1], q[2], q[3],
                                    lo[:, 0], lo[:, 1], lo[:, 0], lo[:, 1]))
    np.testing.assert_allclose(mmd_pt, md_pt, rtol=1e-5, atol=1e-7)


def test_minmaxdist_rect_reduces_to_point_form():
    rng = np.random.default_rng(2)
    lo = rng.random((128, 2)).astype(np.float32)
    hi = lo + rng.random((128, 2)).astype(np.float32) * 0.3
    p = rng.random(2).astype(np.float32)
    classic = np.asarray(minmaxdist(p[0], p[1], lo[:, 0], lo[:, 1],
                                    hi[:, 0], hi[:, 1]))
    rectform = np.asarray(minmaxdist_rect(p[0], p[1], p[0], p[1], lo[:, 0],
                                          lo[:, 1], hi[:, 0], hi[:, 1]))
    np.testing.assert_allclose(classic, rectform, rtol=1e-6, atol=1e-7)


# ---------------------------------------------------------------------------
# oracle matrix (acceptance criterion): D0/D1/D2 × {None, xla,
# pallas_interpret} via the shared differential harness
# ---------------------------------------------------------------------------

LAYOUT_SEEDS = (40, 41)


@pytest.mark.parametrize("seed", LAYOUT_SEEDS)
@pytest.mark.parametrize("layout", LAYOUTS)
def test_knn_join_matches_oracle_layouts(layout, seed):
    # a one-layout matrix per seed; the first seed also checks the
    # generic engine entry point
    cell = (layout, None, False)
    OracleMatrix("knn_join", seed, [cell],
                 engine_parity=seed == LAYOUT_SEEDS[0], k=8).check(*cell)


KNN_JOIN_KERNEL_CELLS = oracle_cells("knn_join", ("d1", "d3"),
                                     backends=KERNEL_BACKENDS)


@pytest.fixture(scope="module")
def knn_join_kernel_matrix():
    return OracleMatrix("knn_join", 42, KNN_JOIN_KERNEL_CELLS, k=8)


@pytest.mark.parametrize("cell", KNN_JOIN_KERNEL_CELLS, ids=cell_id)
def test_knn_join_matches_oracle_kernel_backends(knn_join_kernel_matrix,
                                                 cell):
    knn_join_kernel_matrix.check(*cell)


@pytest.mark.parametrize("k", [1, 64])
def test_knn_join_matches_oracle_k_sweep(k):
    assert_matches_oracle("knn_join", layouts=("d1",), backends=(None,),
                          seeds=(43,), k=k)


@pytest.mark.slow
def test_knn_join_oracle_matrix_extended():
    """The full matrix at larger instances — the slow-lane sweep."""
    cells = assert_matches_oracle(
        "knn_join", layouts=LAYOUTS, backends=(None,) + KERNEL_BACKENDS,
        seeds=(0, 1, 2), fused=(False, True), n=12_000, batch=10, k=16,
        fanout=32)
    # 3 seeds × (4 layouts jnp + 2 d1 kernel backends × unfused/fused
    #            + 2 d3 kernel backends unfused)
    assert cells == 3 * (len(LAYOUTS) + 2 * 2 + 2)


# ---------------------------------------------------------------------------
# scalar nested best-first ≡ brute force
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tree_and_rects():
    rng = np.random.default_rng(50)
    rects = uniform_rects(rng, 6_000, eps=0.002)
    return rtree.build_rtree(rects, fanout=32), rects


@pytest.mark.parametrize("k", [1, 8])
def test_scalar_knn_join_best_first(tree_and_rects, k):
    t, rects = tree_and_rects
    rng = np.random.default_rng(51)
    outer = uniform_rects(rng, 5, eps=0.01)
    oids, od = brute_force_knn_join(outer, rects, k)
    ids, d, ctr = knn_join_scalar.knn_join_best_first(t, outer, k)
    np.testing.assert_allclose(d, od, rtol=1e-5, atol=1e-12)
    assert ctr.nodes_visited > 0
    # best-first opens a tiny fraction of the tree per query
    assert ctr.nodes_visited < len(outer) * t.n_nodes_total()


# ---------------------------------------------------------------------------
# beam fallback: undersized caps degrade to approximate-with-bound
# ---------------------------------------------------------------------------

def test_beam_fallback_undersized_caps(tree_and_rects):
    t, rects = tree_and_rects
    rng = np.random.default_rng(52)
    outer = uniform_rects(rng, 6, eps=0.01)
    k = 8
    _, od = brute_force_knn_join(outer, rects, k)
    caps = tuple(2 for _ in range(t.height - 1))   # deliberately undersized
    fn = knn_join_vector.make_knn_join_bfs(t, k=k, caps=caps)
    ids, d, ctr = fn(jnp.asarray(outer))
    ids, d = np.asarray(ids), np.asarray(d)
    assert bool(ctr.overflow)                      # beam engaged
    # approximate-with-bound: every returned distance is ≥ the exact one
    # (the beam can only lose candidates, never invent closer ones) ...
    assert (np.sort(d, axis=1) >= np.sort(od, axis=1) - 1e-6).all()
    # ... and every returned id is a real entry at its true distance
    for i in range(len(outer)):
        valid = ids[i] >= 0
        assert valid.any()
        np.testing.assert_allclose(
            _true_sq_dist(rects, outer[i], ids[i][valid]), d[i][valid],
            rtol=1e-4, atol=1e-9)


def test_point_knn_beam_fallback(tree_and_rects):
    """The retrofit: point-kNN overflow is now a best-first beam too."""
    from repro.core import knn_vector
    from repro.core.geometry import brute_force_knn, mindist_matrix_np
    t, rects = tree_and_rects
    rng = np.random.default_rng(53)
    pts = rng.random((6, 2)).astype(np.float32)
    k = 8
    _, od = brute_force_knn(rects, pts, k)
    caps = tuple(2 for _ in range(t.height - 1))
    fn = knn_vector.make_knn_bfs(t, k=k, caps=caps)
    ids, d, ctr = fn(jnp.asarray(pts))
    ids, d = np.asarray(ids), np.asarray(d)
    assert bool(ctr.overflow)
    assert (np.sort(d, axis=1) >= np.sort(od, axis=1) - 1e-6).all()
    for i, p in enumerate(pts):
        valid = ids[i] >= 0
        assert valid.any()
        np.testing.assert_allclose(mindist_matrix_np(p, rects[ids[i][valid]])[0],
                                   d[i][valid], rtol=1e-4, atol=1e-9)


# ---------------------------------------------------------------------------
# all-pairs convenience + edge cases
# ---------------------------------------------------------------------------

def test_all_pairs_tree_join(tree_and_rects):
    t, rects = tree_and_rects
    rng = np.random.default_rng(54)
    outer_rects = uniform_rects(rng, 70, eps=0.005)
    to = rtree.build_rtree(outer_rects, fanout=16)
    # chunked streaming (batch < n_outer) must still answer every row
    ids, d, ctr = knn_join_vector.knn_join(to, t, k=4, batch=32)
    assert not bool(ctr.overflow)
    _, od = brute_force_knn_join(np.asarray(to.rects), rects, 4)
    np.testing.assert_allclose(np.sort(d, axis=1), np.sort(od, axis=1),
                               rtol=1e-4, atol=1e-9)


def test_k_exceeds_inner_size():
    rng = np.random.default_rng(55)
    inner = uniform_rects(rng, 7)
    t = rtree.build_rtree(inner, fanout=4)
    outer = uniform_rects(rng, 2, eps=0.02)
    fn = knn_join_vector.make_knn_join_bfs(t, k=12)
    ids, d, _ = fn(jnp.asarray(outer))
    ids, d = np.asarray(ids), np.asarray(d)
    assert (np.sort(ids[:, :7], axis=1) == np.arange(7)).all()
    assert (ids[:, 7:] == -1).all() and np.isinf(d[:, 7:]).all()
    sids, sd, _ = knn_join_scalar.knn_join_best_first(t, outer, 12)
    assert (sids[:, 7:] == -1).all() and np.isinf(sd[:, 7:]).all()


def test_overlapping_outer_rect_zero_distances():
    # an outer rect covering many inner rects: k nearest all at distance 0
    rng = np.random.default_rng(56)
    inner = uniform_rects(rng, 500, eps=0.001)
    t = rtree.build_rtree(inner, fanout=16)
    outer = np.array([[0.2, 0.2, 0.8, 0.8]], np.float32)
    fn = knn_join_vector.make_knn_join_bfs(t, k=8)
    ids, d, ctr = fn(jnp.asarray(outer))
    assert not bool(ctr.overflow)
    np.testing.assert_allclose(np.asarray(d)[0], np.zeros(8), atol=1e-7)


# ---------------------------------------------------------------------------
# sharded two-phase ≡ single tree ≡ oracle
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def sharded_fleet():
    rng = np.random.default_rng(57)
    rects = uniform_rects(rng, 12_000, eps=0.003)
    t = rtree.build_rtree(rects, fanout=32)
    shards = SpatialShards.build(rects, n_partitions=6, fanout=32)
    outer = uniform_rects(rng, 9, eps=0.01)
    return rects, t, shards, outer


@pytest.mark.parametrize("k", [1, 8])
def test_sharded_knn_join_matches_single_tree(sharded_fleet, k):
    rects, t, shards, outer = sharded_fleet
    assert len(shards.partitions) >= 2
    gids, gd, ovf = shards.knn_join(outer, k)
    assert not ovf
    fn = knn_join_vector.make_knn_join_bfs(t, k=k)
    _, d, _ = fn(jnp.asarray(outer))
    np.testing.assert_allclose(np.sort(gd, axis=1),
                               np.sort(np.asarray(d), axis=1),
                               rtol=1e-4)
    _, od = brute_force_knn_join(outer, rects, k)
    np.testing.assert_allclose(np.sort(gd, axis=1), np.sort(od, axis=1),
                               rtol=1e-4)
    for i in range(len(outer)):
        np.testing.assert_allclose(
            _true_sq_dist(rects, outer[i], gids[i]), gd[i], rtol=1e-4,
            atol=1e-9)
