"""Range select: every variant ≡ brute force; counter semantics."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import flat as flatmod
from repro.core import rtree, select_scalar, select_vector

from conftest import brute_select, uniform_rects
from oracle import LAYOUTS, OracleMatrix, cell_id, oracle_cells

SELECT_CELLS = oracle_cells("select", LAYOUTS, backends=(None, "xla"))


@pytest.fixture(scope="module")
def select_matrix():
    return OracleMatrix("select", 5, SELECT_CELLS)


@pytest.mark.parametrize("cell", SELECT_CELLS, ids=cell_id)
def test_select_matches_oracle_harness(select_matrix, cell):
    """One cell of the layout × backend matrix via the shared differential
    harness (tests/oracle.py)."""
    select_matrix.check(*cell)


def _queries(rng, b, side):
    lo = rng.random((b, 2)).astype(np.float32) * (1 - side)
    return np.concatenate([lo, lo + side], axis=1).astype(np.float32)


@pytest.fixture(scope="module")
def tree_and_rects():
    rng = np.random.default_rng(3)
    rects = uniform_rects(rng, 20_000)
    return rtree.build_rtree(rects, fanout=64), rects


def test_scalar_recursive(tree_and_rects):
    t, rects = tree_and_rects
    rng = np.random.default_rng(4)
    for q in _queries(rng, 8, 0.05):
        ids, ctr = select_scalar.select_recursive_py(t, q)
        assert np.array_equal(np.sort(ids), brute_select(rects, q))
        assert ctr.nodes_visited > 0


def test_scalar_logical_vs_bitwise_counters(tree_and_rects):
    t, rects = tree_and_rects
    q = np.array([0.4, 0.4, 0.5, 0.5], np.float32)
    ids_l, ctr_l = select_scalar.select_recursive_py(t, q, variant="logical")
    ids_b, ctr_b = select_scalar.select_recursive_py(t, q, variant="bitwise")
    assert np.array_equal(np.sort(ids_l), np.sort(ids_b))
    # bitwise evaluates all 4 conditions → more predicate work, fewer
    # branch points (paper §3)
    assert ctr_b.predicates >= ctr_l.predicates
    assert ctr_b.branches <= ctr_l.branches


@pytest.mark.parametrize("layout", ["d0", "d1", "d2"])
def test_bfs_batched(tree_and_rects, layout):
    t, rects = tree_and_rects
    rng = np.random.default_rng(5)
    qs = _queries(rng, 16, 0.04)
    sel = select_vector.make_select_bfs(t, layout=layout, result_cap=4096)
    res, counts, ctr = sel(jnp.asarray(qs))
    assert not bool(ctr.overflow)
    for i, q in enumerate(qs):
        got = np.sort(np.asarray(res[i][:int(counts[i])]))
        assert np.array_equal(got, brute_select(rects, q))


def test_bfs_kernel_backend_matches_jnp(tree_and_rects):
    t, rects = tree_and_rects
    rng = np.random.default_rng(6)
    qs = _queries(rng, 4, 0.03)
    a = select_vector.make_select_bfs(t, layout="d1", result_cap=4096)
    b = select_vector.make_select_bfs(t, layout="d1", result_cap=4096,
                                      backend="pallas_interpret")
    ra, ca, _ = a(jnp.asarray(qs))
    rb, cb, _ = b(jnp.asarray(qs))
    assert np.array_equal(np.asarray(ca), np.asarray(cb))
    for i in range(len(qs)):
        assert np.array_equal(np.sort(np.asarray(ra[i][:int(ca[i])])),
                              np.sort(np.asarray(rb[i][:int(cb[i])])))


def test_dfs_vector(tree_and_rects):
    t, rects = tree_and_rects
    ft = flatmod.flatten_tree(t)
    rng = np.random.default_rng(7)
    for q in _queries(rng, 6, 0.04):
        dfs = select_vector.make_select_dfs_vector(ft, result_cap=4096)
        res, rc, ctr = dfs(jnp.asarray(q))
        got = np.sort(np.asarray(res[:int(rc)]))
        assert np.array_equal(got, brute_select(rects, q))


def test_count_only(tree_and_rects):
    t, rects = tree_and_rects
    rng = np.random.default_rng(8)
    qs = _queries(rng, 8, 0.05)
    sel = select_vector.make_select_bfs(t, count_only=True)
    counts, _ = sel(jnp.asarray(qs))
    for i, q in enumerate(qs):
        assert int(counts[i]) == len(brute_select(rects, q))


def test_overflow_flag():
    rng = np.random.default_rng(9)
    rects = uniform_rects(rng, 5000)
    t = rtree.build_rtree(rects, fanout=32)
    sel = select_vector.make_select_bfs(t, result_cap=16)
    q = np.array([[0.0, 0.0, 1.0, 1.0]], np.float32)   # selects everything
    res, counts, ctr = sel(jnp.asarray(q))
    assert bool(ctr.overflow)


def test_empty_result():
    rng = np.random.default_rng(10)
    rects = uniform_rects(rng, 1000)
    t = rtree.build_rtree(rects, fanout=16)
    sel = select_vector.make_select_bfs(t, result_cap=64)
    q = np.array([[2.0, 2.0, 3.0, 3.0]], np.float32)   # off the data space
    res, counts, ctr = sel(jnp.asarray(q))
    assert int(counts[0]) == 0

# the hypothesis property sweep lives in test_properties.py (skipped when
# hypothesis is not installed, so plain tests here always collect)
