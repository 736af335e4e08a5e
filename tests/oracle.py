"""Differential-oracle harness for the operator family.

One check — ``OracleMatrix.check(layout, backend, fused)`` — runs any
operator cell (physical layout × kernel backend × data seed × fused)
against its brute-force numpy oracle, so every new operator / layout /
backend cell is verified the same way: build a random instance, run the
vectorized cell, compare exactly (select/join id sets) or to distance
tolerance with id-at-reported-distance verification (kNN / kNN-join), and
assert no overflow was flagged.  ``oracle_cells`` lists a matrix's runnable
cells, so a test can give each cell its own parametrised case;
``assert_matches_oracle`` runs a whole matrix in one test.

A second axis — ``assert_sharded_parity(op, seeds)`` — verifies the
distributed dispatcher the same way: the host-orchestrated partition
fan-out and the mesh ``shard_map`` path must return bit-identical results,
and the mesh result must be invariant under a permutation of the
partitions (the cross-shard merges order by (distance, global id) /
sorted global id, which no partition placement can perturb).

Kernel backends exist for the cells in ``KERNEL_CELLS`` — the level-global
D1 SoA arrays carry the full kernel column, the quantized D3 streams carry
score kernels for select/knn/knn_join plus fused select; unsupported
layout × backend cells are skipped rather than errored so callers can
request full matrices.  Fused cells (whole-level kernels with in-kernel
emission) only exist on kernel backends, so fused × backend=None cells are
skipped the same way.  Every D3 cell is additionally asserted bit-exact
against the D1 cell of the same (backend, fused) — the conservative
quantized prune may cost extra node visits but must never change an
emitted answer.

Every cell also validates its ``Counters.dispatches`` tally against the
owning spec's stage model, and (once per layout × backend × fused
combination) re-runs through the generic engine entry point
``traversal.build(name, ...)`` asserting bit-exact parity — results,
counts, and every counter field — with the preserved ``make_*_bfs``
wrapper.
"""
from __future__ import annotations

import itertools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (join_vector, knn_join_vector, knn_vector, rtree,
                        select_vector, traversal)
from repro.core.geometry import (brute_force_knn, brute_force_knn_join,
                                 mindist_matrix_np, mindist_rect_matrix_np)
from repro.core.layouts import layout_names
from repro.kernels.ops import KERNEL_CELLS

from conftest import brute_join, brute_select, uniform_rects

# The layout axis is sourced from the one registry (core/layouts.LAYOUTS),
# so a newly registered physical layout joins every oracle matrix — and
# every CLI/bench choices list — without touching call sites.
LAYOUTS = layout_names()
KERNEL_BACKENDS = ("xla", "pallas_interpret")


def _assert_bitwise_equal(a, b, ctx):
    """Result pytrees (arrays + Counters) must agree bit-for-bit."""
    fa = jax.tree_util.tree_leaves(a)
    fb = jax.tree_util.tree_leaves(b)
    assert len(fa) == len(fb), ctx
    for xa, xb in zip(fa, fb):
        np.testing.assert_array_equal(np.asarray(xa), np.asarray(xb),
                                      err_msg=ctx)


def _check_knn_result(ids, d, oracle_d, rects, queries, dist_matrix_fn, ctx):
    """Shared kNN/kNN-join verification: sorted distances match the oracle,
    returned ids are distinct and really sit at the reported distances."""
    np.testing.assert_allclose(np.sort(d, axis=1), np.sort(oracle_d, axis=1),
                               rtol=1e-4, atol=1e-9, err_msg=ctx)
    for i, q in enumerate(queries):
        valid = ids[i] >= 0
        true_d = dist_matrix_fn(q, rects[ids[i][valid]])[0]
        np.testing.assert_allclose(true_d, d[i][valid], rtol=1e-4,
                                   atol=1e-9, err_msg=ctx)
        assert len(set(ids[i][valid].tolist())) == valid.sum(), ctx


# --------------------------------------------------------------------------
# operator cells: make(seed, **params) → instance; run(inst, layout,
# backend) → result; check(inst, result, ctx)
# --------------------------------------------------------------------------

class _SelectOp:
    spec_name = "select"

    @staticmethod
    def height(inst):
        return inst["tree"].height

    @staticmethod
    def engine_args(inst, layout, backend, fused):
        return (inst["tree"],), dict(layout=layout,
                                     result_cap=inst["cap"],
                                     backend=backend, fused=fused)

    @staticmethod
    def make(seed, n=2000, fanout=16, batch=4, side=0.06, **_):
        rng = np.random.default_rng(seed)
        rects = uniform_rects(rng, n, eps=0.005)
        lo = rng.random((batch, 2)).astype(np.float32) * (1 - side)
        queries = np.concatenate([lo, lo + np.float32(side)], axis=1)
        return dict(rects=rects, queries=queries,
                    tree=rtree.build_rtree(rects, fanout=fanout),
                    cap=max(n, 64))

    @staticmethod
    def run(inst, layout, backend, fused=False):
        sel = select_vector.make_select_bfs(inst["tree"], layout=layout,
                                            result_cap=inst["cap"],
                                            backend=backend, fused=fused)
        return sel(jnp.asarray(inst["queries"]))

    @staticmethod
    def check(inst, result, ctx):
        res, counts, ctr = result
        assert not bool(ctr.overflow), ctx
        for i, q in enumerate(inst["queries"]):
            got = np.sort(np.asarray(res[i][:int(counts[i])]))
            assert np.array_equal(got, brute_select(inst["rects"], q)), ctx


class _JoinOp:
    spec_name = "join"

    @staticmethod
    def height(inst):
        return max(inst["ta"].height, inst["tb"].height)

    @staticmethod
    def engine_args(inst, layout, backend, fused):
        cap = 16384 if fused else 1 << 17
        return (inst["ta"], inst["tb"]), dict(layout=layout,
                                              result_cap=cap,
                                              backend=backend, fused=fused)

    @staticmethod
    def make(seed, n=800, fanout=16, **_):
        rng = np.random.default_rng(seed)
        ra = uniform_rects(rng, n, eps=0.012)
        rb = uniform_rects(rng, n, eps=0.012)
        return dict(ra=ra, rb=rb,
                    ta=rtree.build_rtree(ra, fanout=fanout, sort_key="lx"),
                    tb=rtree.build_rtree(rb, fanout=fanout, sort_key="lx"))

    @staticmethod
    def run(inst, layout, backend, fused=False):
        # fused interpret cells compact in-kernel against the full result
        # buffer every grid step — keep the caps honest (they comfortably
        # clear this instance's pair counts) so the sweep stays tractable
        cap = 16384 if fused else 1 << 17
        jn = join_vector.make_join_bfs(inst["ta"], inst["tb"], layout=layout,
                                       result_cap=cap, backend=backend,
                                       fused=fused)
        return jn()

    @staticmethod
    def check(inst, result, ctx):
        pairs, n, ctr = result
        assert not bool(ctr.overflow), ctx
        got = set(map(tuple, np.asarray(pairs[:int(n)])))
        assert got == brute_join(inst["ra"], inst["rb"]), ctx


class _KnnOp:
    spec_name = "knn"

    @staticmethod
    def height(inst):
        return inst["tree"].height

    @staticmethod
    def engine_args(inst, layout, backend, fused):
        return (inst["tree"],), dict(k=inst["k"], layout=layout,
                                     backend=backend, fused=fused)

    @staticmethod
    def make(seed, n=2500, fanout=16, batch=6, k=8, **_):
        rng = np.random.default_rng(seed)
        rects = uniform_rects(rng, n, eps=0.002)
        queries = rng.random((batch, 2)).astype(np.float32)
        _, od = brute_force_knn(rects, queries, k)
        return dict(rects=rects, queries=queries, k=k, oracle_d=od,
                    tree=rtree.build_rtree(rects, fanout=fanout))

    @staticmethod
    def run(inst, layout, backend, fused=False):
        fn = knn_vector.make_knn_bfs(inst["tree"], k=inst["k"],
                                     layout=layout, backend=backend,
                                     fused=fused)
        return fn(jnp.asarray(inst["queries"]))

    @staticmethod
    def check(inst, result, ctx):
        ids, d, ctr = result
        assert not bool(ctr.overflow), ctx
        _check_knn_result(np.asarray(ids), np.asarray(d), inst["oracle_d"],
                          inst["rects"], inst["queries"], mindist_matrix_np,
                          ctx)


class _KnnJoinOp:
    spec_name = "knn_join"

    @staticmethod
    def height(inst):
        return inst["tree"].height

    @staticmethod
    def engine_args(inst, layout, backend, fused):
        return (inst["tree"],), dict(k=inst["k"], layout=layout,
                                     backend=backend, fused=fused)

    @staticmethod
    def make(seed, n=2500, fanout=16, batch=6, k=8, eps=0.01, **_):
        rng = np.random.default_rng(seed)
        rects = uniform_rects(rng, n, eps=0.002)
        outer = uniform_rects(rng, batch, eps=eps)
        _, od = brute_force_knn_join(outer, rects, k)
        return dict(rects=rects, queries=outer, k=k, oracle_d=od,
                    tree=rtree.build_rtree(rects, fanout=fanout))

    @staticmethod
    def run(inst, layout, backend, fused=False):
        fn = knn_join_vector.make_knn_join_bfs(inst["tree"], k=inst["k"],
                                               layout=layout,
                                               backend=backend, fused=fused)
        return fn(jnp.asarray(inst["queries"]))

    @staticmethod
    def check(inst, result, ctx):
        ids, d, ctr = result
        assert not bool(ctr.overflow), ctx
        _check_knn_result(np.asarray(ids), np.asarray(d), inst["oracle_d"],
                          inst["rects"], inst["queries"],
                          mindist_rect_matrix_np, ctx)


class _KnnFilteredOp:
    spec_name = "knn_filtered"

    @staticmethod
    def height(inst):
        return inst["tree"].height

    @staticmethod
    def engine_args(inst, layout, backend, fused):
        return (inst["tree"],), dict(k=inst["k"], layout=layout,
                                     backend=backend, fused=fused)

    @staticmethod
    def make(seed, n=2500, fanout=16, batch=6, k=8, weps=0.2, **_):
        rng = np.random.default_rng(seed)
        rects = uniform_rects(rng, n, eps=0.002)
        pts = (rng.random((batch, 2)).astype(np.float32) * 0.5
               + np.float32(0.25))
        win = np.concatenate([pts - np.float32(weps),
                              pts + np.float32(weps)], axis=1)
        queries = np.concatenate([pts, win], axis=1).astype(np.float32)
        # oracle: mask out rects not intersecting the window, then kNN
        d = mindist_matrix_np(pts, rects)
        inter = ((win[:, None, 0] <= rects[None, :, 2]) &
                 (win[:, None, 2] >= rects[None, :, 0]) &
                 (win[:, None, 1] <= rects[None, :, 3]) &
                 (win[:, None, 3] >= rects[None, :, 1]))
        d = np.where(inter, d, np.inf)
        order = np.argsort(d, axis=1, kind="stable")[:, :k]
        od = np.take_along_axis(d, order, axis=1)
        return dict(rects=rects, queries=queries, k=k, oracle_d=od,
                    win=win, tree=rtree.build_rtree(rects, fanout=fanout))

    @staticmethod
    def run(inst, layout, backend, fused=False):
        from repro.core import knn_filtered
        fn = knn_filtered.make_knn_filtered_bfs(
            inst["tree"], k=inst["k"], layout=layout, backend=backend,
            fused=fused)
        return fn(jnp.asarray(inst["queries"]))

    @staticmethod
    def check(inst, result, ctx):
        ids, d, ctr = result
        ids, d = np.asarray(ids), np.asarray(d)
        assert not bool(ctr.overflow), ctx
        np.testing.assert_allclose(np.sort(d, axis=1),
                                   np.sort(inst["oracle_d"], axis=1),
                                   rtol=1e-4, atol=1e-9, err_msg=ctx)
        for i, q in enumerate(inst["queries"]):
            valid = ids[i] >= 0
            got = inst["rects"][ids[i][valid]]
            true_d = mindist_matrix_np(q[:2], got)[0]
            np.testing.assert_allclose(true_d, d[i][valid], rtol=1e-4,
                                       atol=1e-9, err_msg=ctx)
            w = inst["win"][i]
            assert ((got[:, 0] <= w[2]) & (got[:, 2] >= w[0]) &
                    (got[:, 1] <= w[3]) & (got[:, 3] >= w[1])).all(), ctx
            assert len(set(ids[i][valid].tolist())) == valid.sum(), ctx


OPS = {
    "select": _SelectOp,
    "join": _JoinOp,
    "knn": _KnnOp,
    "knn_join": _KnnJoinOp,
    "knn_filtered": _KnnFilteredOp,
}


def oracle_cells(op: str, layouts=LAYOUTS, backends=(None,),
                 fused=(False,)):
    """The runnable (layout, backend, fused) cells of ``op``'s matrix, in
    harness order.  ``backends`` entries are None (layout-specific jnp
    math) or kernel backends ('xla' / 'pallas_interpret'); kernel cells
    only exist where ``KERNEL_CELLS`` says the operator implements them,
    and fused cells only exist on kernel backends."""
    kernel_cells = KERNEL_CELLS[op]
    cells = [(layout, backend, fu) for layout, backend, fu
             in itertools.product(layouts, backends, fused)
             if (backend is None and not fu)
             or (backend is not None and (layout, fu) in kernel_cells)]
    assert cells, \
        f"no runnable cells for {op}: {layouts} × {backends} × {fused}"
    return cells


def cell_id(cell) -> str:
    """pytest id of one oracle cell, e.g. ``d1-xla-fused``."""
    layout, backend, fu = cell
    return "-".join([layout, backend or "jnp"] + (["fused"] if fu else []))


class OracleMatrix:
    """One seed's instance of operator ``op`` checked cell by cell against
    its brute-force oracle.  Cell results are memoised, so the parametrised
    cases of one matrix (a module-scoped fixture) build the instance once,
    and a D3 cell compares against the D1 result its matrix already holds.

    ``cells`` is the matrix (``oracle_cells``).  Every cell validates its
    dispatch tally against the operator spec's stage model; a D3 cell whose
    matrix holds the D1 cell of the same (backend, fused) is asserted
    bit-exact against it; with ``engine_parity`` every cell also re-runs
    through the generic engine entry point (traversal.build) and must match
    the wrapper bit-for-bit.  ``params`` tune the instance (n, fanout,
    batch, k, ...)."""

    def __init__(self, op: str, seed: int, cells, engine_parity=True,
                 **params):
        self.op, self.seed, self.cells = op, seed, list(cells)
        self.spec = OPS[op]
        self.engine_parity = engine_parity
        self.inst = self.spec.make(seed, **params)
        self._results = {}

    def result(self, layout, backend, fused):
        key = (layout, backend, fused)
        if key not in self._results:
            self._results[key] = self.spec.run(self.inst, layout, backend,
                                               fused=fused)
        return self._results[key]

    def check(self, layout, backend, fused):
        spec, inst = self.spec, self.inst
        ctx = f"{self.op} layout={layout} backend={backend} " \
              f"seed={self.seed} fused={fused}"
        result = self.result(layout, backend, fused)
        spec.check(inst, result, ctx)
        result[-1].validate_dispatches(
            traversal.get_spec(spec.spec_name).stage_model,
            spec.height(inst), fused=fused)
        # D3's conservative quantized prune may only over-approximate
        # frontiers; after the exact leaf re-check its *emitted* results
        # must be bit-identical to the D1 cell of the same (backend,
        # fused) — counters legitimately differ (less pruning), so only
        # the result leaves are compared.
        if layout == "d3" and ("d1", backend, fused) in self.cells:
            _assert_bitwise_equal(
                result[:-1], self.result("d1", backend, fused)[:-1],
                f"d3-vs-d1 bit-exactness: {ctx}")
        if self.engine_parity:
            args, kwargs = spec.engine_args(inst, layout, backend, fused)
            eng = traversal.build(spec.spec_name, *args, **kwargs)
            qs = inst.get("queries")
            eng_result = eng(jnp.asarray(qs)) if qs is not None else eng()
            _assert_bitwise_equal(result, eng_result,
                                  f"engine-entry parity: {ctx}")


def assert_matches_oracle(op: str, layouts=LAYOUTS, backends=(None,),
                          seeds=(0,), fused=(False,), **params):
    """Run operator ``op`` over the (layout × backend × seed × fused) matrix
    against its brute-force oracle (``oracle_cells`` × ``seeds``, each cell
    checked by ``OracleMatrix.check``); only the first seed's cells run the
    engine-entry parity check.  Returns the number of cells actually
    verified (callers may assert coverage)."""
    cells = oracle_cells(op, layouts, backends, fused)
    for si, seed in enumerate(seeds):
        matrix = OracleMatrix(op, seed, cells, engine_parity=si == 0,
                              **params)
        for cell in cells:
            matrix.check(*cell)
    return len(cells) * len(seeds)


# --------------------------------------------------------------------------
# caps-tier axis: occupancy-adaptive ≡ static, bit-for-bit
# --------------------------------------------------------------------------


def assert_adaptive_static_parity(op: str, layouts=LAYOUTS, seeds=(0,),
                                  **params) -> int:
    """The two-tier capacity system's oracle axis: for every layout ×
    operator cell, the occupancy-adaptive engine (tight caps + overflow
    escalation, ``caps_mode='adaptive'`` — the default) must return
    RESULTS bit-identical to the static-caps engine.  Counters
    legitimately differ (the tight tier pays fewer padded lanes and
    records occupancy/escalations), so only the result leaves are
    compared.  Returns cells verified."""
    spec = OPS[op]
    cells = 0
    for seed in seeds:
        inst = spec.make(seed, **params)
        for layout in layouts:
            ctx = f"adaptive-vs-static {op} layout={layout} seed={seed}"
            args, kwargs = spec.engine_args(inst, layout, None, False)
            adaptive = traversal.build(spec.spec_name, *args,
                                       caps_mode="adaptive", **kwargs)
            static = traversal.build(spec.spec_name, *args,
                                     caps_mode="static", **kwargs)
            qs = inst.get("queries")
            ra = adaptive(jnp.asarray(qs)) if qs is not None else adaptive()
            rs = static(jnp.asarray(qs)) if qs is not None else static()
            _assert_bitwise_equal(ra[:-1], rs[:-1], ctx)
            spec.check(inst, ra, ctx)
            cells += 1
    assert cells > 0
    return cells


# --------------------------------------------------------------------------
# sharded axis: host-orchestrated ≡ mesh-SPMD, invariant under permutation
# --------------------------------------------------------------------------

SHARDED_OPS = ("select", "join", "knn", "knn_join", "knn_filtered")


def _shards_for(rects, n_partitions, fanout, order=None, mesh=None,
                layout="d1"):
    from repro.distributed.spatial_shard import SpatialShards
    s = SpatialShards.build(rects, n_partitions, fanout=fanout,
                            layout=layout)
    if order is not None:
        s.partitions = [s.partitions[i] for i in order]
        s.router_mbrs = np.stack([p.mbr for p in s.partitions])
    if mesh is not False:
        s.enable_mesh(mesh)
    return s


def _sharded_result(op, shards, inst):
    if op == "select":
        return shards.range_select(inst["queries"], result_cap=inst["cap"])
    if op == "join":
        return shards.join(inst["probe"], result_cap=inst["cap"])
    return getattr(shards, op)(inst["queries"], inst["k"])


def _assert_same_result(op, a, b, ctx):
    if op == "select":
        assert len(a) == len(b), ctx
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x, y, err_msg=ctx)
        return
    if op == "join":
        np.testing.assert_array_equal(a[0], b[0], err_msg=ctx)
        assert a[1] == b[1], ctx
        return
    np.testing.assert_array_equal(a[0], b[0], err_msg=ctx)      # global ids
    np.testing.assert_array_equal(a[1], b[1], err_msg=ctx)      # distances
    assert a[2] == b[2], ctx                                    # overflow


def _sharded_instance(op, seed, n, batch, k):
    rng = np.random.default_rng(seed)
    rects = uniform_rects(rng, n, eps=0.002)
    inst = dict(rects=rects, k=k, cap=max(n, 4096))
    if op == "select":
        lo = rng.random((batch, 2)).astype(np.float32) * 0.9
        inst["queries"] = np.concatenate([lo, lo + 0.05], axis=1) \
            .astype(np.float32)
    elif op == "join":
        lo = rng.random((batch * 32, 2)).astype(np.float32) * 0.9
        inst["probe"] = np.concatenate([lo, lo + 0.01], axis=1) \
            .astype(np.float32)
        inst["cap"] = 1 << 15
    elif op == "knn":
        inst["queries"] = rng.random((batch, 2)).astype(np.float32)
    elif op == "knn_join":
        lo = rng.random((batch, 2)).astype(np.float32) * 0.9
        inst["queries"] = np.concatenate([lo, lo + 0.01], axis=1) \
            .astype(np.float32)
    elif op == "knn_filtered":
        pts = (rng.random((batch, 2)).astype(np.float32) * 0.5
               + np.float32(0.25))
        inst["queries"] = np.concatenate(
            [pts, pts - np.float32(0.2), pts + np.float32(0.2)],
            axis=1).astype(np.float32)
    else:
        raise KeyError(op)
    return rng, inst


def assert_sharded_parity(op, seeds=(0,), n=4000, n_partitions=4,
                          fanout=16, batch=6, k=8, mesh=None,
                          layout="d1") -> int:
    """The distributed dispatcher's oracle axis: for each seed, (1) the
    host-orchestrated fan-out and the one-program mesh path return
    bit-identical results, (2) the mesh result is unchanged when the
    partitions are packed in a shuffled order, and (3) under a non-d1
    ``layout`` the whole-fleet result additionally matches a d1 fleet
    bit-for-bit (the quantized D3 prune must never change an answer).
    Returns cells verified."""
    cells = 0
    for seed in seeds:
        rng, inst = _sharded_instance(op, seed, n, batch, k)
        host = _shards_for(inst["rects"], n_partitions, fanout, mesh=False,
                           layout=layout)
        meshed = _shards_for(inst["rects"], n_partitions, fanout, mesh=mesh,
                             layout=layout)
        ctx = f"sharded {op} seed={seed} layout={layout} host-vs-mesh"
        res_host = _sharded_result(op, host, inst)
        res_mesh = _sharded_result(op, meshed, inst)
        _assert_same_result(op, res_host, res_mesh, ctx)
        perm = rng.permutation(len(host.partitions))
        permuted = _shards_for(inst["rects"], n_partitions, fanout,
                               order=perm, mesh=mesh, layout=layout)
        res_perm = _sharded_result(op, permuted, inst)
        _assert_same_result(op, res_mesh, res_perm,
                            f"sharded {op} seed={seed} layout={layout} "
                            f"permutation invariance "
                            f"(perm={perm.tolist()})")
        if layout != "d1":
            base = _shards_for(inst["rects"], n_partitions, fanout,
                               mesh=False)
            _assert_same_result(op, _sharded_result(op, base, inst),
                                res_host,
                                f"sharded {op} seed={seed} "
                                f"{layout}-vs-d1 bit-exactness")
        cells += 1
    assert cells > 0
    return cells
