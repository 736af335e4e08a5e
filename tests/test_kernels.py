"""Pallas kernels (``pallas_interpret`` on CPU) ≡ pure-jnp oracles, swept
over shapes and dtypes."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


def _nodes(rng, n, f, dtype):
    lx = rng.random((n, f)).astype(dtype)
    ly = rng.random((n, f)).astype(dtype)
    hx = (lx + rng.random((n, f)) * 0.3).astype(dtype)
    hy = (ly + rng.random((n, f)) * 0.3).astype(dtype)
    child = rng.integers(-1, 500, (n, f)).astype(np.int32)
    return lx, ly, hx, hy, child


@pytest.mark.parametrize("b,c,f", [(1, 1, 128), (4, 8, 128), (3, 5, 256),
                                   (2, 7, 64), (8, 2, 512)])
@pytest.mark.parametrize("dtype", [np.float32, np.int32])
def test_select_kernel_sweep(b, c, f, dtype):
    rng = np.random.default_rng(f * b + c)
    n = 32
    lx, ly, hx, hy, child = _nodes(rng, n, f, np.float32)
    if dtype == np.int32:
        lx, ly, hx, hy = [(a * 1e6).astype(np.int32) for a in
                          (lx, ly, hx, hy)]
    ids = rng.integers(-1, n, (b, c)).astype(np.int32)
    qs = rng.random((b, 4)).astype(np.float32)
    qs[:, 2:] = qs[:, :2] + 0.2
    if dtype == np.int32:
        qs = (qs * 1e6).astype(np.int32)
    got = ops.select_level_masks(ids, qs, lx, ly, hx, hy, child,
                                 backend="pallas_interpret")
    exp = ref.select_level_masks_ref(ids, qs, lx, ly, hx, hy, child)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(exp))


@pytest.mark.parametrize("b,c,f", [(1, 1, 128), (4, 8, 128), (3, 5, 256),
                                   (2, 7, 64)])
@pytest.mark.parametrize("leaf", [False, True])
def test_knn_kernel_sweep(b, c, f, leaf):
    """Pallas point-distance kernel ≡ ref.py XLA path for both the generic
    and the leaf-specialized (no MINMAXDIST store) variants (the leaf
    variant ported from the pair-distance kernel).  MINDIST is bit-exact;
    the MINMAXDIST bound is compared to 1 ULP — its ``d·d + d·d`` form is
    FMA-contractible and XLA contracts differently for the kernel's (F,)
    row trace than for the ref's (B, C, F) gather trace (pre-existing
    since PR 1; τ pruning is sound under either rounding)."""
    import functools

    import jax
    rng = np.random.default_rng(f * b + c + 2 * leaf)
    n = 32
    lx, ly, hx, hy, child = _nodes(rng, n, f, np.float32)
    ids = rng.integers(-1, n, (b, c)).astype(np.int32)
    pts = rng.random((b, 2)).astype(np.float32)
    got = ops.knn_level_dists(ids, pts, lx, ly, hx, hy, child, leaf=leaf,
                              backend="pallas_interpret")
    ref_fn = jax.jit(functools.partial(ref.knn_level_dists_ref, leaf=leaf))
    exp = ref_fn(ids, jnp.asarray(pts), lx, ly, hx, hy, child)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(exp[0]))
    if leaf:
        assert got[1] is None and exp[1] is None
    else:
        np.testing.assert_allclose(np.asarray(got[1]), np.asarray(exp[1]),
                                   rtol=2e-7, atol=0)


def test_knn_leaf_variant_matches_generic_mindist():
    """The point-kNN leaf specialization changes what is *stored*, never the
    MINDIST values themselves."""
    rng = np.random.default_rng(8)
    n, b, c, f = 16, 3, 4, 128
    lx, ly, hx, hy, child = _nodes(rng, n, f, np.float32)
    ids = rng.integers(-1, n, (b, c)).astype(np.int32)
    pts = rng.random((b, 2)).astype(np.float32)
    md_leaf, _ = ops.knn_level_dists(ids, pts, lx, ly, hx, hy, child,
                                     leaf=True, backend="pallas_interpret")
    md_gen, _ = ops.knn_level_dists(ids, pts, lx, ly, hx, hy, child,
                                    leaf=False, backend="pallas_interpret")
    np.testing.assert_array_equal(np.asarray(md_leaf), np.asarray(md_gen))


@pytest.mark.parametrize("b,c,f", [(1, 1, 128), (4, 8, 128), (3, 5, 256),
                                   (2, 7, 64)])
@pytest.mark.parametrize("leaf", [False, True])
def test_knn_join_kernel_sweep(b, c, f, leaf):
    """Pallas pair-distance kernel ≡ ref.py XLA path, bit-exact on float32,
    for both the generic and the leaf-specialized (no MINMAXDIST store)
    variants.  The ref runs under jit — exactly how the operators consume it
    (backend='xla' inside the jitted BFS) — so both sides see the same XLA
    FMA contraction; the eager ref differs by 1 ULP."""
    import functools

    import jax
    rng = np.random.default_rng(f * b + c + leaf)
    n = 32
    lx, ly, hx, hy, child = _nodes(rng, n, f, np.float32)
    ids = rng.integers(-1, n, (b, c)).astype(np.int32)
    qs = rng.random((b, 4)).astype(np.float32)
    qs[:, 2:] = qs[:, :2] + 0.15
    got = ops.knn_join_level_dists(ids, qs, lx, ly, hx, hy, child,
                                   leaf=leaf, backend="pallas_interpret")
    ref_fn = jax.jit(functools.partial(ref.knn_join_level_dists_ref,
                                       leaf=leaf))
    exp = ref_fn(ids, jnp.asarray(qs), lx, ly, hx, hy, child)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(exp[0]))
    if leaf:
        assert got[1] is None and exp[1] is None
    else:
        np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(exp[1]))


def test_knn_join_leaf_variant_matches_generic_mindist():
    """The leaf specialization changes what is *stored*, never the MINDIST
    values themselves."""
    rng = np.random.default_rng(7)
    n, b, c, f = 16, 3, 4, 128
    lx, ly, hx, hy, child = _nodes(rng, n, f, np.float32)
    ids = rng.integers(-1, n, (b, c)).astype(np.int32)
    qs = rng.random((b, 4)).astype(np.float32)
    qs[:, 2:] = qs[:, :2] + 0.1
    md_leaf, _ = ops.knn_join_level_dists(ids, qs, lx, ly, hx, hy, child,
                                          leaf=True,
                                          backend="pallas_interpret")
    md_gen, _ = ops.knn_join_level_dists(ids, qs, lx, ly, hx, hy, child,
                                         leaf=False,
                                         backend="pallas_interpret")
    np.testing.assert_array_equal(np.asarray(md_leaf), np.asarray(md_gen))


@pytest.mark.parametrize("p,fo,fi", [(1, 8, 128), (5, 16, 128),
                                     (3, 32, 256), (7, 8, 256),
                                     (2, 64, 128)])
@pytest.mark.parametrize("dtype", [np.float32, np.int32])
def test_join_kernel_sweep(p, fo, fi, dtype):
    rng = np.random.default_rng(p * fo + fi)
    n = 24
    oc = rng.random((n, 4, fo)).astype(np.float32)
    ic = rng.random((n, 4, fi)).astype(np.float32)
    oc[:, 2:] = oc[:, :2] + rng.random((n, 2, fo)) * 0.3
    ic[:, 2:] = ic[:, :2] + rng.random((n, 2, fi)) * 0.3
    if dtype == np.int32:
        oc = (oc * 1e6).astype(np.int32)
        ic = (ic * 1e6).astype(np.int32)
    o_ids = rng.integers(-1, n, (p,)).astype(np.int32)
    i_ids = rng.integers(-1, n, (p,)).astype(np.int32)
    ac, fm = ops.join_prune_metadata(o_ids, i_ids, jnp.asarray(oc),
                                     jnp.asarray(ic), to=8)
    got = ops.join_pair_masks(o_ids, i_ids, ac, fm, oc, ic, to=8, ti=128,
                              backend="pallas_interpret")
    exp = ref.join_pair_masks_ref(o_ids, i_ids, ac, fm, oc, ic, to=8,
                                  ti=128)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(exp))


def test_join_kernel_disabled_pruning():
    """alive_cnt=F_out, flip_max=F_in disables tile skipping entirely."""
    rng = np.random.default_rng(99)
    n, p, fo, fi = 8, 4, 16, 128
    oc = rng.random((n, 4, fo)).astype(np.float32)
    ic = rng.random((n, 4, fi)).astype(np.float32)
    oc[:, 2:] += oc[:, :2]
    ic[:, 2:] += ic[:, :2]
    o_ids = rng.integers(0, n, (p,)).astype(np.int32)
    i_ids = rng.integers(0, n, (p,)).astype(np.int32)
    ac = np.full((p,), fo, np.int32)
    fm = np.full((p, fo // 8), fi, np.int32)
    got = ops.join_pair_masks(o_ids, i_ids, ac, fm, oc, ic,
                              backend="pallas_interpret")
    exp = ref.join_pair_masks_ref(o_ids, i_ids, ac, fm, oc, ic)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(exp))


def test_backend_resolution():
    assert ops.resolve_backend("auto") in ("pallas", "xla")
    with pytest.raises(ValueError):
        ops.resolve_backend("bogus")


def test_compiled_pallas_refuses_off_tpu():
    """backend='pallas' means the kernel compiled for the chip: off a TPU
    it raises, naming the stage, instead of quietly interpreting."""
    import jax
    if jax.default_backend() == "tpu":
        pytest.skip("on a TPU the compiled kernels run")
    rng = np.random.default_rng(3)
    lx, ly, hx, hy, child = _nodes(rng, 8, 128, np.float32)
    ids = np.zeros((1, 1), np.int32)
    qs = np.zeros((1, 4), np.float32)
    with pytest.raises(RuntimeError, match="select/score"):
        ops.select_level_masks(ids, qs, lx, ly, hx, hy, child,
                               backend="pallas")
