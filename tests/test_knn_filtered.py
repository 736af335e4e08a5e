"""Filtered kNN (core/knn_filtered.py): oracle matrix over layouts, the
full-universe-window reduction to plain kNN, and window semantics."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import knn_filtered, knn_vector, rtree

from conftest import uniform_rects
from oracle import LAYOUTS, OracleMatrix, cell_id, oracle_cells

# kernel backends are not implemented for the filtered spec (jnp-only
# window masks), so the matrix is layouts × seeds
FILTERED_CELLS = oracle_cells("knn_filtered")
FILTERED_SEEDS = (0, 1)


@pytest.fixture(scope="module")
def filtered_matrices():
    # the first seed also checks the generic engine entry point
    return {seed: OracleMatrix("knn_filtered", seed, FILTERED_CELLS,
                               engine_parity=seed == FILTERED_SEEDS[0])
            for seed in FILTERED_SEEDS}


@pytest.mark.parametrize("seed", FILTERED_SEEDS)
@pytest.mark.parametrize("cell", FILTERED_CELLS, ids=cell_id)
def test_filtered_matches_oracle_layouts(filtered_matrices, cell, seed):
    assert len(FILTERED_CELLS) * len(FILTERED_SEEDS) == len(LAYOUTS) * 2
    filtered_matrices[seed].check(*cell)


def test_full_window_reduces_to_plain_knn():
    rng = np.random.default_rng(3)
    rects = uniform_rects(rng, 3000, eps=0.002)
    tree = rtree.build_rtree(rects, fanout=16)
    pts = rng.random((5, 2)).astype(np.float32)
    qs = np.concatenate(
        [pts, np.zeros((5, 2), np.float32), np.ones((5, 2), np.float32)],
        axis=1)
    fi, fd, fctr = knn_filtered.make_knn_filtered_bfs(tree, k=8)(
        jnp.asarray(qs))
    ki, kd, kctr = knn_vector.make_knn_bfs(tree, k=8)(jnp.asarray(pts))
    assert not bool(fctr.overflow) and not bool(kctr.overflow)
    np.testing.assert_array_equal(np.asarray(fi), np.asarray(ki))
    np.testing.assert_array_equal(np.asarray(fd), np.asarray(kd))


def test_empty_window_returns_nothing():
    rng = np.random.default_rng(4)
    rects = uniform_rects(rng, 2000, eps=0.002)
    tree = rtree.build_rtree(rects, fanout=16)
    pts = rng.random((4, 2)).astype(np.float32)
    # a window far outside the unit square intersects no data rect
    win = np.full((4, 4), 5.0, np.float32)
    win[:, 2:] = 5.5
    qs = np.concatenate([pts, win], axis=1)
    ids, d, ctr = knn_filtered.make_knn_filtered_bfs(tree, k=8)(
        jnp.asarray(qs))
    assert (np.asarray(ids) == -1).all()
    assert np.isinf(np.asarray(d)).all()


def test_kernel_backend_rejected():
    rng = np.random.default_rng(5)
    rects = uniform_rects(rng, 500, eps=0.002)
    tree = rtree.build_rtree(rects, fanout=16)
    with pytest.raises(ValueError):
        knn_filtered.make_knn_filtered_bfs(tree, k=4, backend="xla")
    with pytest.raises(ValueError):
        knn_filtered.make_knn_filtered_bfs(tree, k=4, fused=True)
