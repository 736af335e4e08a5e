"""The select engine's blocked leaf step (core/traversal._blocked_leaf):
the leaf frontier scored ``select_vector.LEAF_BLOCK`` slots at a time, only
as far as its last live slot.  Its answers are the brute-force oracle's,
and bit-identical to the dense leaf step it replaced (kept here as the
reference: ``LEAF_BLOCK = None`` scores the whole leaf frontier at once);
its memory does not grow with the leaf frontier's width times the fanout.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import rtree, select_vector
from repro.distributed.spatial_shard import SpatialShards

from conftest import brute_select, uniform_rects
from oracle import assert_matches_oracle

SMALL_BLOCK = 4          # leaf slots per block: a 0.2-wide window spans many
RESULT_CAP = 160
ALGO_FIELDS = ("nodes_visited", "predicates", "vector_ops", "enqueued",
               "masked_waste", "overflow", "dispatches", "escalations")


@pytest.fixture(scope="module")
def instance():
    rng = np.random.default_rng(1501)
    rects = uniform_rects(rng, 4000, eps=0.001)
    tree = rtree.build_rtree(rects, fanout=16)
    assert tree.height >= 3
    return rects, tree


def _queries(rng, n):
    """n real rows: mixed window sizes (row 0 a wide one), one window that
    holds no rect and one that overflows the result cap; then padded to
    the pow2 bucket the fleet would send (copies of row 0)."""
    side = rng.choice(np.float32([0.02, 0.08, 0.2]), n)[:, None]
    side[0] = 0.2                                # spans several blocks
    lo = rng.random((n, 2)).astype(np.float32) * (1 - side)
    q = np.concatenate([lo, lo + side], axis=1).astype(np.float32)
    if n > 1:
        q[1] = [2.0, 2.0, 3.0, 3.0]              # no hit
    if n > 2:
        q[2] = [0.0, 0.0, 0.5, 0.5]              # ~1000 rects > RESULT_CAP
    return SpatialShards._bucket(q)


def _engine(tree, layout, caps_mode, block, monkeypatch):
    monkeypatch.setattr(select_vector, "LEAF_BLOCK", block)
    return select_vector.make_select_bfs(tree, layout=layout,
                                         result_cap=RESULT_CAP,
                                         caps_mode=caps_mode)


@pytest.mark.parametrize("bucket", [1, 7, 64])
@pytest.mark.parametrize("caps_mode", ["static", "adaptive"])
@pytest.mark.parametrize("layout", ["d1", "d3"])
def test_blocked_leaf_matches_oracle_and_dense_step(instance, layout,
                                                    caps_mode, bucket,
                                                    monkeypatch):
    rects, tree = instance
    rng = np.random.default_rng(bucket)
    q = _queries(rng, bucket)
    ids, counts, ctr = _engine(tree, layout, caps_mode, SMALL_BLOCK,
                               monkeypatch)(jnp.asarray(q))
    dense = _engine(tree, layout, caps_mode, None, monkeypatch)(
        jnp.asarray(q))
    ids, counts = np.asarray(ids), np.asarray(counts)

    # the oracle: every row's count, and every id of a row that fits
    for i, row in enumerate(q):
        want = brute_select(rects, row)
        assert counts[i] == len(want)
        if counts[i] <= RESULT_CAP:
            np.testing.assert_array_equal(np.sort(ids[i, :counts[i]]), want)
            assert (ids[i, counts[i]:] == -1).all()
    assert bool(ctr.overflow) == bool((counts > RESULT_CAP).any())
    if bucket > 2:
        assert counts[1] == 0 and counts[2] > RESULT_CAP
        assert bool(ctr.overflow)
    # padding rows repeat row 0's answer
    for i in range(len(q)):
        if (q[i] == q[0]).all():
            np.testing.assert_array_equal(ids[i], ids[0])

    # bit-identical to the dense leaf step: ids in the same order, counts,
    # and every counter but the leaf step's lane tallies
    np.testing.assert_array_equal(ids, np.asarray(dense[0]))
    np.testing.assert_array_equal(counts, np.asarray(dense[1]))
    for f in ALGO_FIELDS:
        assert int(getattr(ctr, f)) == int(getattr(dense[2], f)), f
    leaf = tree.height - 1
    live = np.asarray(ctr.lanes_live)
    np.testing.assert_array_equal(live, np.asarray(dense[2].lanes_live))
    np.testing.assert_array_equal(np.asarray(ctr.lanes_padded)[:leaf],
                                  np.asarray(dense[2].lanes_padded)[:leaf])
    # the leaf step scored whole blocks, several of them, and no more
    # than the dense step's full width
    scanned = int(live[leaf]) + int(np.asarray(ctr.lanes_padded)[leaf])
    assert scanned % (len(q) * SMALL_BLOCK) == 0
    dense_scanned = int(live[leaf]) + int(
        np.asarray(dense[2].lanes_padded)[leaf])
    assert len(q) * 2 * SMALL_BLOCK <= scanned <= dense_scanned + (
        len(q) * SMALL_BLOCK)
    monkeypatch.setattr(select_vector, "LEAF_BLOCK", SMALL_BLOCK)
    assert_matches_oracle("select", layouts=(layout,), seeds=(bucket,),
                          batch=bucket, side=0.2)


def test_count_only_blocked_leaf_counts_the_same(instance, monkeypatch):
    rects, tree = instance
    q = _queries(np.random.default_rng(3), 8)
    monkeypatch.setattr(select_vector, "LEAF_BLOCK", SMALL_BLOCK)
    counts, _ = select_vector.make_select_bfs(
        tree, result_cap=RESULT_CAP, count_only=True)(jnp.asarray(q))
    np.testing.assert_array_equal(
        np.asarray(counts), [len(brute_select(rects, r)) for r in q])


def _temp_bytes(n_points, block, monkeypatch):
    """(leaf nodes, temporary bytes) of the compiled static-caps select
    engine over ``n_points`` uniform points at B = 256, result cap 16,384."""
    monkeypatch.setattr(select_vector, "LEAF_BLOCK", block)
    rects = uniform_rects(np.random.default_rng(n_points), n_points)
    tree = rtree.build_rtree(rects, fanout=64)
    fn = select_vector.make_select_bfs(tree, result_cap=16384,
                                       caps_mode="static")
    q = jax.ShapeDtypeStruct((256, 4), jnp.float32)
    mem = jax.jit(fn).lower(q).compile().memory_analysis()
    return tree.levels[0].n_nodes, mem.temp_size_in_bytes


def test_leaf_memory_does_not_scale_with_frontier_times_fanout(monkeypatch):
    """From 50K to 400K points the leaf frontier widens by thousands of
    nodes.  The blocked step's temporaries grow by about the frontier's
    own int32 slots, B x 4 bytes a leaf node; the dense step's grow by
    more than the gathered rects alone, B x F x 16 bytes a leaf node."""
    b, f, block = 256, 64, select_vector.LEAF_BLOCK
    n0, t0 = _temp_bytes(50_000, block, monkeypatch)
    n1, t1 = _temp_bytes(400_000, block, monkeypatch)
    assert n1 - n0 > 5000
    assert t1 - t0 <= 1.25 * b * 4 * (n1 - n0)
    _, d0 = _temp_bytes(50_000, None, monkeypatch)
    _, d1 = _temp_bytes(400_000, None, monkeypatch)
    assert d1 - d0 > b * f * 16 * (n1 - n0)
