"""Spatial join: every variant/optimization ≡ brute force; counters show
the paper's pruning claims (O3 prunes outer entries, O4/O5 prune inner)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import join_scalar, join_vector, rtree

from conftest import brute_join, uniform_rects
from oracle import (KERNEL_BACKENDS, LAYOUTS, OracleMatrix, cell_id,
                    oracle_cells)


@pytest.fixture(scope="module")
def trees():
    rng = np.random.default_rng(11)
    ra = uniform_rects(rng, 2000, eps=0.012)
    rb = uniform_rects(rng, 2000, eps=0.012)
    ta = rtree.build_rtree(ra, fanout=32, sort_key="lx")
    tb = rtree.build_rtree(rb, fanout=32, sort_key="lx")
    return ta, tb, ra, rb


def test_scalar_join(trees):
    ta, tb, ra, rb = trees
    pairs, ctr = join_scalar.join_recursive_py(ta, tb)
    assert set(map(tuple, pairs)) == brute_join(ra, rb)


def test_scalar_join_o3_prunes(trees):
    ta, tb, ra, rb = trees
    pairs0, c0 = join_scalar.join_recursive_py(ta, tb)
    pairs3, c3 = join_scalar.join_recursive_py(ta, tb, o3=True)
    assert set(map(tuple, pairs3)) == set(map(tuple, pairs0))
    assert c3.predicates < c0.predicates          # paper §5.2.2


VARIANTS = [
    dict(layout="d0"),
    dict(layout="d1"),
    dict(layout="d2"),
    dict(layout="d1", o3=True),
    dict(layout="d1", o3=True, o4=True),
    dict(layout="d1", o3=True, o5="dense"),
    dict(layout="d1", o3=True, o5="gather"),
    dict(layout="d2", o3=True, o4=True),
    dict(layout="d1", backend="pallas_interpret"),
    dict(layout="d1", o3=True, o5="dense", backend="pallas_interpret"),
]


@pytest.mark.parametrize("kw", VARIANTS,
                         ids=lambda kw: "-".join(f"{k}={v}" for k, v in
                                                 kw.items()))
def test_vector_join_variants(trees, kw):
    ta, tb, ra, rb = trees
    jn = join_vector.make_join_bfs(ta, tb, result_cap=65536, **kw)
    pairs, n, ctr = jn()
    got = set(map(tuple, np.asarray(pairs[:int(n)])))
    assert got == brute_join(ra, rb)
    assert not bool(ctr.overflow)


# the plain layouts on jnp math, then D1 on each kernel backend
JOIN_CELLS = (oracle_cells("join", LAYOUTS, backends=(None,))
              + oracle_cells("join", ("d1",), backends=KERNEL_BACKENDS))


@pytest.fixture(scope="module")
def join_matrix():
    return OracleMatrix("join", 11, JOIN_CELLS)


@pytest.mark.parametrize("cell", JOIN_CELLS, ids=cell_id)
def test_join_matches_oracle_harness(join_matrix, cell):
    """One cell of the plain layout × backend matrix via the shared
    differential harness (optimization-flag variants stay in VARIANTS
    above)."""
    join_matrix.check(*cell)


def test_o3_o4_reduce_predicates(trees):
    ta, tb, _, _ = trees
    preds = {}
    for name, kw in [("none", {}), ("o3", dict(o3=True)),
                     ("o3o4", dict(o3=True, o4=True)),
                     ("o3o5", dict(o3=True, o5="dense"))]:
        jn = join_vector.make_join_bfs(ta, tb, layout="d1",
                                       result_cap=65536, **kw)
        _, _, ctr = jn()
        preds[name] = int(ctr.predicates)
    assert preds["o3"] < preds["none"]
    assert preds["o3o4"] < preds["o3"]
    assert preds["o3o5"] <= preds["o3o4"] * 1.05   # same tile pruning bound


def test_unsorted_tree_rejects_o3(trees):
    rng = np.random.default_rng(12)
    ra = uniform_rects(rng, 500, eps=0.01)
    ta = rtree.build_rtree(ra, fanout=16)          # no sort_key
    tb = rtree.build_rtree(ra, fanout=16)
    with pytest.raises(ValueError):
        join_vector.make_join_bfs(ta, tb, o3=True)


def test_self_join(trees):
    rng = np.random.default_rng(13)
    ra = uniform_rects(rng, 800, eps=0.01)
    ta = rtree.build_rtree(ra, fanout=16, sort_key="lx")
    jn = join_vector.make_join_bfs(ta, ta, layout="d1", result_cap=65536,
                                   o3=True)
    pairs, n, _ = jn()
    got = set(map(tuple, np.asarray(pairs[:int(n)])))
    assert got == brute_join(ra, ra)
    assert all((i, i) in got for i in range(len(ra)))


@pytest.mark.parametrize("tall_outer", [True, False])
def test_different_heights(tall_outer):
    rng = np.random.default_rng(14)
    ra = uniform_rects(rng, 4000, eps=0.01)      # height 3 @ fanout 16
    rb = uniform_rects(rng, 100, eps=0.02)       # height 2
    ta = rtree.build_rtree(ra, fanout=16, sort_key="lx")
    tb = rtree.build_rtree(rb, fanout=16, sort_key="lx")
    o, i = (ta, tb) if tall_outer else (tb, ta)
    jn = join_vector.make_join_bfs(o, i, result_cap=1 << 17, o3=True)
    pairs, n, _ = jn()
    got = set(map(tuple, np.asarray(pairs[:int(n)])))
    ref = brute_join(np.asarray(o.rects), np.asarray(i.rects))
    assert got == ref

# the hypothesis property sweep lives in test_properties.py (skipped when
# hypothesis is not installed, so plain tests here always collect)
