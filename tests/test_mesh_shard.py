"""Mesh-sharded SPMD engine: host-vs-mesh bit-exact parity, partition-
permutation invariance, O(levels) dispatch accounting, distributed browse.

These tests run at ANY device count: the mesh path packs P partitions onto
however many devices the mesh axis has (blocks of P/D per shard), so the
same assertions hold on the 1-device tier-1 run and on the CI multi-device
step (XLA_FLAGS=--xla_force_host_platform_device_count=4).
"""
import numpy as np
import pytest

from repro.core import knn_vector, rtree, traversal

from conftest import uniform_rects
from oracle import SHARDED_OPS, _shards_for, assert_sharded_parity


@pytest.mark.parametrize("op", SHARDED_OPS)
def test_host_vs_mesh_parity_and_permutation(op):
    assert assert_sharded_parity(op, seeds=(0,)) == 1


@pytest.mark.parametrize("op", SHARDED_OPS)
def test_host_vs_mesh_parity_d3(op):
    """The quantized-layout fleet must agree with itself across dispatch
    paths AND with a d1 fleet bit-for-bit (oracle.assert_sharded_parity's
    layout axis) — conservative quantized pruning never changes answers."""
    assert assert_sharded_parity(op, seeds=(0,), layout="d3") == 1


def test_sharded_browse_d3_matches_d1():
    rng = np.random.default_rng(31)
    rects = uniform_rects(rng, 4000, eps=0.002)
    qs = rng.random((4, 2)).astype(np.float32)
    a = _shards_for(rects, 4, 16).browse(qs, 8)
    b = _shards_for(rects, 4, 16, layout="d3").browse(qs, 8)
    for _ in range(3):
        ia, da = a.next_batch()
        ib, db = b.next_batch()
        np.testing.assert_array_equal(ia, ib)
        np.testing.assert_array_equal(da, db)


def test_sharded_dispatch_is_o_levels_not_o_partitions():
    """One shard_map program per batch: the merged dispatch tally equals
    the spec's StageModel for TWO descents (overlapped phase 1 + phase 2)
    of the padded height — and does not grow with the partition count."""
    rng = np.random.default_rng(7)
    rects = uniform_rects(rng, 4000, eps=0.002)
    qs = rng.random((6, 2)).astype(np.float32)
    sm = traversal.get_spec("knn").stage_model
    got = []
    for n_partitions in (2, 4):
        shards = _shards_for(rects, n_partitions, 16)
        shards.knn(qs, 8)
        ctr = shards.last_counters
        h = shards._forest.height
        ctr.validate_dispatches(sm, h, descents=2)
        got.append(int(ctr.dispatches))
    assert got[0] == got[1], got      # independent of partition fan-out

    # mask kind: one descent of the select StageModel, same invariance
    sm_sel = traversal.get_spec("select").stage_model
    lo = rng.random((4, 2)).astype(np.float32) * 0.9
    q4 = np.concatenate([lo, lo + 0.05], axis=1).astype(np.float32)
    got = []
    for n_partitions in (2, 4):
        shards = _shards_for(rects, n_partitions, 16)
        shards.range_select(q4)
        ctr = shards.last_counters
        ctr.validate_dispatches(sm_sel, shards._forest.height)
        got.append(int(ctr.dispatches))
    assert got[0] == got[1], got


def test_sharded_browse_prefix_matches_single_tree():
    """The distributed cursor's emitted stream equals the single-tree
    fixed-k answer on every prefix: distances bit-for-bit (each partition
    engine scores the same (query, rect) pairs in the same f32 math), ids
    whenever the distances are distinct."""
    rng = np.random.default_rng(11)
    rects = uniform_rects(rng, 5000, eps=0.002)
    qs = rng.random((5, 2)).astype(np.float32)
    k, steps = 8, 3
    shards = _shards_for(rects, 4, 16)
    cur = shards.browse(qs, k)
    import jax.numpy as jnp
    tree = rtree.build_rtree(rects, fanout=16)
    ref_ids, ref_d, _ = knn_vector.make_knn_bfs(tree, k=k * steps)(
        jnp.asarray(qs))
    got_i, got_d = [], []
    for _ in range(steps):
        i, d = cur.next_batch()
        got_i.append(i)
        got_d.append(d)
    gi = np.concatenate(got_i, axis=1)
    gd = np.concatenate(got_d, axis=1).astype(np.float32)
    assert not cur.overflow.any()
    np.testing.assert_array_equal(np.asarray(ref_d), gd)
    np.testing.assert_array_equal(np.asarray(ref_ids), gi)


def test_sharded_browse_tied_distances_no_duplicates():
    """Distance ties across the pool-pop boundary: the (d, id)-selected
    entries need not be a positional prefix of the distance-sorted pool,
    so the pop must remove exactly the selected positions — a prefix pop
    would re-emit an unselected tie and silently lose a selected one."""
    rng = np.random.default_rng(29)
    base = rng.random((200, 2)).astype(np.float32)
    pts = np.repeat(base, 8, axis=0)            # 8-way ties everywhere
    rects = np.concatenate([pts, pts], axis=1).astype(np.float32)
    qs = rng.random((4, 2)).astype(np.float32)
    import jax.numpy as jnp
    cur = _shards_for(rects, 4, 16).browse(qs, 8)
    got_i, got_d = [], []
    for _ in range(4):
        i, d = cur.next_batch()
        got_i.append(i)
        got_d.append(d)
    gi = np.concatenate(got_i, axis=1)
    gd = np.concatenate(got_d, axis=1).astype(np.float32)
    tree = rtree.build_rtree(rects, fanout=16)
    _, ref_d, _ = knn_vector.make_knn_bfs(tree, k=32)(jnp.asarray(qs))
    np.testing.assert_array_equal(np.asarray(ref_d), gd)
    for r in range(len(qs)):
        v = gi[r][gi[r] >= 0]
        assert len(set(v.tolist())) == len(v), "duplicate emission"
        true_d = ((qs[r] - pts[v]) ** 2).sum(axis=1)
        np.testing.assert_allclose(true_d, gd[r][gi[r] >= 0], rtol=1e-5,
                                   atol=1e-12)


def test_sharded_browse_permutation_invariant():
    rng = np.random.default_rng(13)
    rects = uniform_rects(rng, 4000, eps=0.002)
    qs = rng.random((4, 2)).astype(np.float32)
    a = _shards_for(rects, 4, 16).browse(qs, 8)
    perm = rng.permutation(4)
    b = _shards_for(rects, 4, 16, order=perm).browse(qs, 8)
    for _ in range(3):
        ia, da = a.next_batch()
        ib, db = b.next_batch()
        np.testing.assert_array_equal(ia, ib)
        np.testing.assert_array_equal(da, db)


def test_partition_count_not_multiple_of_devices():
    """P is padded up to a multiple of the mesh axis with structurally
    empty partitions; results must not notice."""
    rng = np.random.default_rng(17)
    rects = uniform_rects(rng, 3000, eps=0.002)
    qs = rng.random((5, 2)).astype(np.float32)
    host = _shards_for(rects, 3, 16, mesh=False)
    meshed = _shards_for(rects, 3, 16)
    assert meshed._forest.n_real == len(host.partitions)
    hi, hd, _ = host.knn(qs, 8)
    mi, md, _ = meshed.knn(qs, 8)
    np.testing.assert_array_equal(hi, mi)
    np.testing.assert_array_equal(hd, md)


@pytest.fixture(scope="module")
def tiny_fleets():
    rng = np.random.default_rng(23)
    rects = uniform_rects(rng, 40, eps=0.002)
    qs = rng.random((3, 2)).astype(np.float32)
    return (qs, _shards_for(rects, 4, 8, mesh=False),
            _shards_for(rects, 4, 8))


@pytest.mark.parametrize("rows,k", [(3, 1), (3, 16), (3, 64), (1, 4)],
                         ids=["k1", "k16", "k64", "b1"])
def test_knn_edges_k_exceeds_partitions_and_b1(tiny_fleets, rows, k):
    """k beyond the partition (even the dataset) size: phase-1 τ stays inf,
    phase 2 fans out everywhere, the merge pads with (-1, +inf) — exactly
    like the host path.  Also the B=1 batch."""
    qs, host, meshed = tiny_fleets
    hi, hd, _ = host.knn(qs[:rows], k)
    mi, md, _ = meshed.knn(qs[:rows], k)
    np.testing.assert_array_equal(hi, mi)
    np.testing.assert_array_equal(hd, md)


@pytest.mark.parametrize("mesh", [False, None], ids=["host", "mesh"])
def test_warm_covers_every_registered_operator(mesh):
    """The registry-keyed warmup accepts every spec (select/join included —
    the operators that historically had no warm path)."""
    rng = np.random.default_rng(19)
    rects = uniform_rects(rng, 2000, eps=0.002)
    lo = rng.random((32, 2)).astype(np.float32) * 0.9
    probe = np.concatenate([lo, lo + 0.01], axis=1).astype(np.float32)
    shards = _shards_for(rects, 2, 16, mesh=mesh)
    for op in traversal.spec_names():
        kw = dict(k=4) if traversal.get_spec(op).kind == "distance" \
            or op == "browse" else {}
        if op == "join":
            kw = dict(probe=probe, result_cap=1 << 14)
        if op == "browse" and not shards.mesh_enabled:
            # distributed browsing refuses to silently flip the object
            # onto the mesh path
            with pytest.raises(RuntimeError):
                shards.warm(op, batch=8, **kw)
            continue
        shards.warm(op, batch=8, **kw)
    # the historical spellings still work
    shards.warm_knn(8, 4)
    shards.warm_knn_join(8, 4)
