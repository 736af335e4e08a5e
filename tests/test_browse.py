"""Resumable distance browsing (core/knn_browse.py).

Prefix consistency, multi-descent resume, pytree state round-trip,
exhaustion padding, counters/dispatch validation, and the lost-bound
overflow semantics under a deliberately tiny pool.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import knn_browse, knn_vector, rtree, traversal
from repro.core.knn_browse import BROWSE_SPEC
from repro.core.layouts import layout_names

from conftest import uniform_rects


@pytest.fixture(scope="module")
def tree_and_points():
    rng = np.random.default_rng(17)
    rects = uniform_rects(rng, 2500, eps=0.002)
    tree = rtree.build_rtree(rects, fanout=16)
    assert tree.height >= 3
    pts = rng.random((5, 2)).astype(np.float32)
    return tree, rects, pts


def _browse_all(tree, pts, kb, steps, **kwargs):
    cur = knn_browse.browse_knn(tree, jnp.asarray(pts), k=kb, **kwargs)
    ids, ds = [], []
    for _ in range(steps):
        i, d = cur.next_batch()
        ids.append(i)
        ds.append(d)
    return np.concatenate(ids, axis=1), np.concatenate(ds, axis=1), cur


@pytest.fixture(scope="module")
def browsed_120(tree_and_points):
    tree, _, pts = tree_and_points
    return _browse_all(tree, pts, 4, steps=30)          # 120 neighbors


@pytest.mark.parametrize("k", [1, 3, 4, 11, 40, 120])
def test_prefix_consistency_spans_descents(tree_and_points, browsed_120, k):
    """Concatenated browse batches equal fixed-k kNN for every prefix —
    including prefixes deep enough that the session had to re-activate
    deferred subtrees (multi-descent resume)."""
    tree, rects, pts = tree_and_points
    ids, d, cur = browsed_120
    assert int(cur.state.descents) > 1, \
        "test too shallow: the resume path never ran"
    assert not cur.overflow.any()
    fi, fd, fc = knn_vector.make_knn_bfs(tree, k=k)(jnp.asarray(pts))
    assert int(fc.overflow) == 0
    np.testing.assert_array_equal(d[:, :k], np.asarray(fd))
    diff = ids[:, :k] != np.asarray(fi)
    if diff.any():                              # ids may differ only at ties
        np.testing.assert_array_equal(d[:, :k][diff], np.asarray(fd)[diff])


def test_emission_is_globally_sorted_and_distinct(tree_and_points):
    tree, _, pts = tree_and_points
    ids, d, _ = _browse_all(tree, pts, 8, steps=6)
    dd = np.where(np.isfinite(d), d, np.float64(1e30))
    assert (np.diff(dd, axis=1) >= 0).all()
    for row in ids:
        v = row[row >= 0]
        assert len(set(v.tolist())) == len(v)


def test_state_round_trips_through_pytree(tree_and_points):
    """Flatten → unflatten mid-session and keep browsing: identical output
    to the uninterrupted session."""
    tree, _, pts = tree_and_points
    kb = 4
    start = knn_browse.make_browse_bfs(tree, k=kb)
    a, b = start(jnp.asarray(pts)), start(jnp.asarray(pts))
    for step in range(12):
        ia, da = a.next_batch()
        leaves, treedef = jax.tree_util.tree_flatten(b.state)
        b.state = jax.tree_util.tree_unflatten(treedef, leaves)
        ib, db = b.next_batch()
        np.testing.assert_array_equal(ia, ib)
        np.testing.assert_array_equal(da, db)
    assert isinstance(b.state, traversal.BrowseState)


def test_exhaustion_pads_like_fixed_k(tree_and_points):
    """A tree smaller than the total ask: every rect is emitted exactly
    once, then (-1, +inf) padding — same convention as make_knn_bfs."""
    _, rects, pts = tree_and_points
    small = rtree.build_rtree(rects[:30], fanout=16)
    ids, d, cur = _browse_all(small, pts[:3], 8, steps=6)   # ask 48 of 30
    valid = ids >= 0
    assert (valid.sum(axis=1) == 30).all()
    assert np.isinf(d[~valid]).all()
    for row in range(3):
        assert set(ids[row][valid[row]].tolist()) == set(range(30))


def test_counters_accumulate_and_validate(tree_and_points):
    tree, _, pts = tree_and_points
    _, _, cur = _browse_all(tree, pts, 4, steps=30)
    cur.state.ctr.validate_dispatches(
        BROWSE_SPEC.stage_model, tree.height,
        descents=int(cur.state.descents))
    assert int(cur.state.ctr.nodes_visited) > 0
    assert int(cur.state.emitted.sum()) == 30 * 4 * len(pts)


def test_tiny_pool_flags_overflow_not_silent_loss(tree_and_points):
    """A pool too small to hold the scored candidates must either stay
    exact or raise the per-row overflow flag once emission reaches the
    lost bound — never silently wrong."""
    tree, rects, pts = tree_and_points
    kb = 4
    cur = knn_browse.browse_knn(tree, jnp.asarray(pts), k=kb, pool_cap=kb)
    fi, fd, _ = knn_vector.make_knn_bfs(tree, k=40)(jnp.asarray(pts))
    fd = np.asarray(fd)
    for step in range(10):
        i, d = cur.next_batch()
        ok = ~cur.overflow
        np.testing.assert_array_equal(
            d[ok], fd[ok, step * kb:(step + 1) * kb],
            err_msg=f"non-flagged row diverged at step {step}")
    assert cur.overflow.any(), "tiny pool never tripped the lost bound"
    # the crossing must also surface through the operator-family contract
    assert int(cur.counters.overflow) == 1


@pytest.fixture(scope="module")
def browsed_base(tree_and_points):
    tree, _, pts = tree_and_points
    return _browse_all(tree, pts, 4, steps=5)


@pytest.mark.parametrize("kwargs", [
    *(dict(layout=lo) for lo in layout_names() if lo != "d1"),
    dict(backend="xla"), dict(backend="pallas_interpret")],
    ids=lambda kw: "-".join(f"{k}={v}" for k, v in kw.items()))
def test_backend_and_layout_cells_agree(tree_and_points, browsed_base,
                                        kwargs):
    tree, _, pts = tree_and_points
    base_i, base_d, _ = browsed_base
    ids, d, cur = _browse_all(tree, pts, 4, steps=5, **kwargs)
    assert not cur.overflow.any()
    np.testing.assert_allclose(d, base_d, rtol=1e-6, atol=1e-12,
                               err_msg=str(kwargs))


def test_browse_registered_and_generic_entry(tree_and_points):
    tree, _, pts = tree_and_points
    spec = traversal.get_spec("browse")
    assert spec.kind == "distance"
    start = traversal.build("browse", tree, k=4)
    cur = start(jnp.asarray(pts))
    i, d = cur.next_batch()
    base_i, base_d, _ = _browse_all(tree, pts, 4, steps=1)
    np.testing.assert_array_equal(i, base_i)


def test_browse_rejects_bad_params(tree_and_points):
    tree, _, _ = tree_and_points
    with pytest.raises(ValueError):
        knn_browse.make_browse_bfs(tree, k=0)
    with pytest.raises(ValueError):
        knn_browse.make_browse_bfs(tree, k=4, pool_cap=2)
    with pytest.raises(ValueError):
        knn_browse.make_browse_bfs(tree, k=4, caps=(128,) * 7)
    with pytest.raises(ValueError):
        knn_browse.make_browse_bfs(tree, k=4, backend="xla", layout="d0")
