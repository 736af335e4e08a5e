"""Chip smoke: run the served R-tree query path and every Pallas kernel
once on a TPU at the paper's data size, and check what comes out.

    python chip_smoke.py              # one chip: builds, served path, kernels
    python chip_smoke.py --chips 4    # four chips: the mesh / replica path

One chip:
  gate    — refuse anything but a TPU (non-zero exit, no result line);
  build   — 10,000,000 uniform points from ``--seed`` (paper §5), packed
            into 8 STR partitions at fanout 64, as a D1 and a D3 fleet;
  served  — ``repro.launch.serve`` driven in-process: ``--queue`` for select
            and knn, the synchronous runners for join, knn-join,
            knn-filtered and browse, on D1 (plus select and knn on D3); a
            seeded sample of 64 answers per run is checked against the
            brute-force references of ``core/geometry.py``;
  kernels — every operator's public factory with ``backend="pallas"``
            (fused and unfused, as ``kernels/ops.KERNEL_CELLS`` lists them)
            on one partition tree of each fleet, bit-exact against
            ``backend="xla"``, Counters included except ``dispatches``.

Four chips (``--chips 4``): the mesh dispatcher for select, knn and
browse, and ``serve --queue --replicas 2`` for knn, each bit-exact against
the host path on the same data; the packed forest must sit on 4 devices.

Each phase prints one line (sizes, wall / compile / run seconds, device
bytes in use, the host's peak RSS).  The last line is the JSON result,
printed only when every phase passed.
"""
from __future__ import annotations

import argparse
import concurrent.futures as cf
import dataclasses
import gc
import json
import os
import resource
import sys
import time

import numpy as np

SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")
N_POINTS = 10_000_000
SAMPLE = 64
K = 8
# The kernel phase's join result cap.  The join's pair-frontier caps scale
# with it, and its leaf-node pair frontier (about one pair per data leaf,
# ~20K on a 1.1M-rect partition) needs the caps of a 1M-pair result, as the
# served join's --join-cap does.
JOIN_CAP = 1 << 20
_COMPILE = []        # (start, end) perf_counter intervals of JAX compiles


def _gate(chips: int):
    import jax
    devs = jax.devices()
    d0 = devs[0]
    print(f"device: platform={d0.platform} kind={d0.device_kind} "
          f"count={len(devs)}", flush=True)
    if d0.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU (JAX platform {d0.platform!r}); "
                 f"this smoke runs on the chip only")
    if len(devs) < chips:
        sys.exit(f"chip_smoke: --chips {chips} needs {chips} TPU devices, "
                 f"found {len(devs)}")
    return devs


def _watch_compiles():
    import jax

    def listener(event, duration, **_):
        if event.startswith("/jax/core/compile/"):
            end = time.perf_counter()
            _COMPILE.append((end - duration, end))

    jax.monitoring.register_event_duration_secs_listener(listener)


def _union_s(intervals, t0, t1):
    """Seconds of [t0, t1] covered by the union of ``intervals``: compile
    time as wall time, so compiles that overlap on several threads (the
    per-partition warmup) or nest (tracing inside lowering) count once."""
    total, reach = 0.0, t0
    for s, e in sorted((max(s, t0), min(e, t1)) for s, e in intervals):
        if e > reach:
            total += e - max(s, reach)
            reach = e
    return total


class Phase:
    """Times one phase and prints its progress line; never swallows an
    exception (a failed phase ends the run with a traceback)."""

    def __init__(self, name: str, **sizes):
        self.name, self.sizes = name, sizes

    def __enter__(self):
        gc.collect()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        if exc_type is not None:
            return False
        import jax
        t1 = time.perf_counter()
        wall = t1 - self.t0
        comp = _union_s(_COMPILE, self.t0, t1)
        stats = jax.devices()[0].memory_stats() or {}
        mem = stats.get("bytes_in_use", "not reported")
        sizes = " ".join(f"{k}={v}" for k, v in self.sizes.items())
        peak_mb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss // 1024
        print(f"phase {self.name}: ok {sizes} wall_s={wall:.2f} "
              f"compile_s={comp:.2f} run_s={wall - comp:.2f} "
              f"device_bytes_in_use={mem} host_peak_rss_mb={peak_mb}",
              flush=True)
        return False


def make_rects(seed: int, n: int):
    """The served fleet's data: the same recipe serve.py uses."""
    from repro.core import str_pack
    rng = np.random.default_rng(seed)
    pts = rng.random((n, 2), dtype=np.float32)
    return str_pack.points_to_rects(pts)


# ---------------------------------------------------------------------------
# brute-force checks (core/geometry.py references, host numpy)
# ---------------------------------------------------------------------------

def _pmap(fn, items):
    with cf.ThreadPoolExecutor(min(8, os.cpu_count() or 1)) as ex:
        return list(ex.map(fn, items))


def _check_knn(ids, d, q, rects, k, rect_query, ctx):
    """kNN answer vs the oracle, as tests/oracle._check_knn_result: sorted
    distances match, ids are distinct and sit at the reported distances.

    The oracle runs on the rects within the answer's k-th distance of the
    query (a brute-force select of the query grown by that radius, with a
    margin for f32 distances) instead of on all N: the answer's own ids
    are in that set once their distances are checked, so no rect outside
    it can be among the true k nearest.  That keeps one check at 10M rects
    to a few compares per rect instead of an (N,) float64 pipeline."""
    from repro.core.geometry import (brute_force_knn, brute_force_knn_join,
                                     brute_force_select, mindist_matrix_np,
                                     mindist_rect_matrix_np)
    dist_fn = mindist_rect_matrix_np if rect_query else mindist_matrix_np
    valid = ids >= 0
    assert len(set(ids[valid].tolist())) == valid.sum(), ctx
    true_d = dist_fn(q, rects[ids[valid]])[0]
    np.testing.assert_allclose(true_d, d[valid], rtol=1e-4, atol=1e-9,
                               err_msg=ctx)
    if valid.sum() == min(k, len(rects)):
        r = np.sqrt(d[valid].max()) * (1 + 1e-3) + 1e-6
        lo, hi = (q[:2], q[2:]) if rect_query else (q, q)
        near = brute_force_select(rects, np.concatenate([lo - r, hi + r]))
    else:
        near = np.arange(len(rects))
    if rect_query:
        _, od = brute_force_knn_join(q, rects[near], k)
    else:
        _, od = brute_force_knn(rects[near], q, k)
    np.testing.assert_allclose(np.sort(d), np.sort(od[0]), rtol=1e-4,
                               atol=1e-9, err_msg=ctx)


def _sample(seed: int, n: int):
    rng = np.random.default_rng(seed)
    return np.sort(rng.choice(n, size=min(SAMPLE, n), replace=False))


def _rows(out):
    """(query rows, per-row answers) from a serve runner's return value:
    select rows are id arrays, distance rows (ids, dists) pairs."""
    qs = np.concatenate([np.asarray(q).reshape(-1, np.asarray(q).shape[-1])
                         for q in out["queries"]])
    rows = []
    for ans in out["answers"]:
        assert ans is not None, "a queued request failed"
        if isinstance(ans, list):
            rows.extend(ans)
        else:
            assert len(ans) == 2 or not ans[2], "overflow flag set"
            rows.extend(zip(np.asarray(ans[0]), np.asarray(ans[1])))
    assert len(rows) == len(qs)
    return qs, rows


def check_served(mode, out, rects, seed):
    """A served run passed: no failed, degraded or overflowed answer, and a
    seeded sample of its answers equals the brute-force references."""
    from repro.core.geometry import brute_force_select
    for key in ("failures", "dispatch_failures", "degraded_dispatches",
                "failed_requests"):
        assert out.get(key, 0) == 0, f"{mode}: {key}={out[key]}"
    assert not out.get("overflow", False), f"{mode}: overflow flag set"
    if mode == "join":
        probes = out["queries"]
        pairs = out["answers"][0]
        pick = _sample(seed, len(probes))

        def one(p):
            lo = np.searchsorted(pairs[:, 0], p, "left")
            hi = np.searchsorted(pairs[:, 0], p, "right")
            ref = brute_force_select(rects, probes[p])
            np.testing.assert_array_equal(np.sort(pairs[lo:hi, 1]), ref,
                                          err_msg=f"join probe {p}")
        _pmap(one, pick)
        return len(pick)
    if mode == "browse":
        qs = np.concatenate(out["queries"])
        rows = []
        for steps in out["answers"]:
            ids = np.concatenate([np.asarray(s[0]) for s in steps], axis=1)
            d = np.concatenate([np.asarray(s[1]) for s in steps], axis=1)
            rows.extend(zip(ids, d))
        kk = rows[0][0].shape[0]
        pick = _sample(seed, len(qs))
        _pmap(lambda i: _check_knn(
            rows[i][0], np.asarray(rows[i][1], np.float64), qs[i], rects,
            kk, False, f"browse row {i}"), pick)
        return len(pick)
    qs, rows = _rows(out)
    pick = _sample(seed, len(qs))
    if mode == "select":
        def one(i):
            np.testing.assert_array_equal(
                rows[i], brute_force_select(rects, qs[i]),
                err_msg=f"select row {i}")
    elif mode == "knn-filtered":
        def one(i):
            q = qs[i]
            w = q[2:]
            inside = np.nonzero((rects[:, 0] <= w[2]) & (rects[:, 2] >= w[0])
                                & (rects[:, 1] <= w[3])
                                & (rects[:, 3] >= w[1]))[0]
            ids, d = rows[i]
            local = np.searchsorted(inside, ids[ids >= 0])
            assert np.array_equal(inside[local], ids[ids >= 0]), \
                f"knn-filtered row {i}: id outside its window"
            lids = np.full(ids.shape, -1, np.int64)
            lids[ids >= 0] = local
            _check_knn(lids, np.asarray(d, np.float64), q[:2],
                       rects[inside], K, False, f"knn-filtered row {i}")
    else:
        def one(i):
            ids, d = rows[i]
            _check_knn(np.asarray(ids), np.asarray(d, np.float64), qs[i],
                       rects, K, mode == "knn-join", f"{mode} row {i}")
    _pmap(one, pick)
    return len(pick)


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

SERVED = (
    # (mode, layout, queued)
    ("select", "d1", True), ("knn", "d1", True), ("join", "d1", False),
    ("knn-join", "d1", False), ("knn-filtered", "d1", False),
    ("browse", "d1", False), ("select", "d3", True), ("knn", "d3", True),
)


def serve_argv(mode, layout, queued, n, seed, batch=16):
    argv = ["--mode", mode, "--layout", layout, "--n", str(n),
            "--partitions", "8", "--fanout", "64", "--seed", str(seed),
            "--k", str(K), "--batch-size", str(batch), "--batches", "4",
            "--selectivity", "0.0001"]
    if queued:
        argv += ["--queue", "--clients", "2", "--max-batch", str(batch)]
    if mode == "join":
        # ~1.6 partners per probe: 1M probes fit the 2M-pair result cap
        argv += ["--query-eps", "0.0002", "--join-cap", str(1 << 21),
                 "--batches", "1"]
    if mode == "browse":
        argv += ["--browse-steps", "2"]
    return argv


def served_phase(rects, n, seed, batch=16):
    from repro.launch import serve
    for mode, layout, queued in SERVED:
        argv = serve_argv(mode, layout, queued, n, seed, batch)
        with Phase(f"served/{mode}/{layout}" + ("/queue" if queued else ""),
                   n=n, batch=batch, k=K) as ph:
            out = serve.main(argv)
            ph.sizes["checked"] = check_served(mode, out, rects, seed)
        del out


def _kernel_inputs(mbr, seed, batch):
    """Query batches inside one partition's MBR."""
    rng = np.random.default_rng(seed)
    lo, ext = mbr[:2], mbr[2:] - mbr[:2]
    side = np.float32(0.01)
    corner = (lo + rng.random((batch, 2)) * (ext - side)).astype(np.float32)
    pts = (lo + rng.random((batch, 2)) * ext).astype(np.float32)
    eps = np.float32(0.002)
    return (np.concatenate([corner, corner + side], axis=1), pts,
            np.concatenate([pts - eps, pts + eps], axis=1))


def _engines(op, tree, layout, probe):
    """(factory, positional args, kwargs) of one operator's public
    factory on ``tree``."""
    from repro.core import (join_vector, knn_join_vector, knn_vector,
                            select_vector)
    if op == "select":
        return select_vector.make_select_bfs, (tree,), dict(layout=layout)
    if op == "knn":
        return knn_vector.make_knn_bfs, (tree,), dict(layout=layout, k=K)
    if op == "knn_join":
        return (knn_join_vector.make_knn_join_bfs, (tree,),
                dict(layout=layout, k=K))
    return (join_vector.make_join_bfs, (probe, tree),
            dict(layout=layout, result_cap=JOIN_CAP))


def _assert_same(a, b, ctx):
    from repro.core.counters import Counters
    ra, rb = a[:-1], b[:-1]
    for x, y in zip(ra, rb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=ctx)
    ca, cb = a[-1], b[-1]
    assert isinstance(ca, Counters)
    for f in dataclasses.fields(ca):
        if f.name == "dispatches":
            continue
        np.testing.assert_array_equal(np.asarray(getattr(ca, f.name)),
                                      np.asarray(getattr(cb, f.name)),
                                      err_msg=f"{ctx} counter {f.name}")


def kernel_phase(fleets, seed, batch=SAMPLE, backend="pallas"):
    """Every kernel cell of one partition tree per fleet, ``backend``
    against ``backend="xla"``, results and Counters (but dispatches)
    bit-exact; every ``ops._KERNELS`` stage must have run."""
    import jax.numpy as jnp

    from repro.core import rtree
    from repro.kernels import ops
    covered = set()
    for layout, shards in fleets:
        part = shards.partitions[0]
        tree = part.tree
        sel_q, pts, rq = _kernel_inputs(part.mbr, seed, batch)
        rng = np.random.default_rng(seed + 7)
        lo, ext = part.mbr[:2], part.mbr[2:] - part.mbr[:2]
        n_probe = max(len(part.ids) // 50, 64)
        ppts = (lo + rng.random((n_probe, 2)) * ext).astype(np.float32)
        # probe half-extent for ~1.6 partners per probe (0.0002 at 10M)
        eps = np.float32(0.5 * np.sqrt(1.6 * np.prod(ext) / len(part.ids)))
        probe = rtree.build_rtree(
            np.concatenate([ppts - eps, ppts + eps], axis=1), fanout=64)
        args = {"select": sel_q, "knn": pts, "knn_join": rq}
        for op in ("select", "knn", "knn_join", "join"):
            cells = sorted(f for lay, f in ops.KERNEL_CELLS[op]
                           if lay == layout)
            if not cells:
                continue
            make, pos, kw = _engines(op, tree, layout, probe)
            qargs = (() if op == "join"
                     else (jnp.asarray(args[op]),))
            with Phase(f"kernels/{op}/{layout}/xla", n=len(part.ids),
                       batch=batch):
                want = tuple(make(*pos, backend="xla", **kw)(*qargs))
            for fused in cells:
                name = f"kernels/{op}/{layout}/{backend}" + \
                    ("/fused" if fused else "")
                with Phase(name, n=len(part.ids), batch=batch) as ph:
                    got = tuple(make(*pos, backend=backend, fused=fused,
                                     **kw)(*qargs))
                    _assert_same(got, want, name)
                    ctr = got[-1]
                    assert not int(ctr.overflow), f"{name}: overflow"
                    ph.sizes["results"] = int(np.asarray(got[1]).sum()) \
                        if op in ("select", "join") else \
                        int((np.asarray(got[0]) >= 0).sum())
                covered.update(_stages(op, layout, fused, tree.height))
    missing = set(ops._KERNELS) - covered
    assert not missing, f"kernel stages never run: {sorted(missing)}"


def _stages(op, layout, fused, height):
    """The ops._KERNELS entries an engine of this cell dispatches."""
    if op == "join":
        return {("join", "fused" if fused else "score")}
    if fused:
        if op == "select":
            return {(op, "fused")} | ({(op, "fused_d3")} if layout == "d3"
                                      else set())
        return {(op, "fused"), (op, "fused_leaf")} if height > 1 else \
            {(op, "fused_leaf")}
    out = {(op, "score")}
    if layout == "d3" and height > 1:
        out.add((op, "score_d3"))
    return out


def four_chip_phase(rects, n, seed, devs, batch=16):
    """The mesh dispatcher and replica fan-out on four devices, bit-exact
    against the host path on the same data, at the served phase's batch
    (so the host-path reference engines share its compiled buckets)."""
    import jax

    from repro.distributed.spatial_shard import SpatialShards
    from repro.launch import serve
    from repro.launch.mesh import spatial_mesh

    with Phase("mesh/build", n=n, partitions=8):
        shards = SpatialShards.build(rects, 8, fanout=64)
        shards.enable_mesh(spatial_mesh())
        host = shards.host_view()
        leaves = [x for x in jax.tree_util.tree_leaves(shards._forest.tree)
                  if hasattr(x, "addressable_shards")]
        placed = {s.device for x in leaves for s in x.addressable_shards}
        assert len(placed) == 4 and placed != {devs[0]}, placed
    rng = np.random.default_rng(seed + 3)
    side = np.float32(0.01)
    lo = (rng.random((batch, 2)) * (1 - side)).astype(np.float32)
    sel_q = np.concatenate([lo, lo + side], axis=1)
    pts = rng.random((batch, 2), dtype=np.float32)
    with Phase("mesh/select", n=n, batch=batch):
        got = shards.range_select(sel_q)
        assert not int(shards.last_counters.overflow)
        want = host.range_select(sel_q)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(g, w)
    with Phase("mesh/knn", n=n, batch=batch, k=K):
        gi, gd, govf = shards.knn(pts, K)
        wi, wd, wovf = host.knn(pts, K)
        assert not (govf or wovf)
        np.testing.assert_array_equal(gi, wi)
        np.testing.assert_array_equal(gd, wd)
    with Phase("mesh/browse", n=n, batch=batch, k=K, steps=2):
        cur = shards.browse(pts, K)
        steps = [cur.next_batch() for _ in range(2)]
        assert not bool(np.asarray(cur.overflow).any())
        bd = np.concatenate([np.asarray(s[1]) for s in steps], axis=1)
        _, wd2, _ = host.knn(pts, 2 * K)
        np.testing.assert_array_equal(bd.astype(np.float64), wd2)
    with Phase("mesh/knn/queue/replicas2", n=n, batch=batch, k=K) as ph:
        argv = serve_argv("knn", "d1", True, n, seed, batch) + [
            "--replicas", "2", "--mesh", "on"]
        out = serve.main(argv)
        for key in ("failures", "dispatch_failures", "degraded_dispatches",
                    "failed_requests"):
            assert out[key] == 0, f"{key}={out[key]}"
        for q, ans in zip(out["queries"], out["answers"]):
            ids, d, ovf = ans
            assert not ovf
            wi, wd, _ = host.knn(np.asarray(q), K)
            np.testing.assert_array_equal(ids, wi)
            np.testing.assert_array_equal(d, wd)
        ph.sizes["requests"] = len(out["answers"])


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the multi-chip mesh/replica path")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devs = _gate(args.chips)
    sys.path.insert(0, SRC)
    from repro.launch import compile_cache
    print(f"compile cache: {compile_cache.enable()}", flush=True)
    _watch_compiles()
    import jax

    from repro.distributed.spatial_shard import SpatialShards

    with Phase("data", n=N_POINTS):
        rects = make_rects(args.seed, N_POINTS)
    if args.chips == 4:
        four_chip_phase(rects, N_POINTS, args.seed, devs[:4])
    else:
        fleets = []
        for layout in ("d1", "d3"):
            with Phase(f"build/{layout}", n=N_POINTS, partitions=8,
                       fanout=64):
                fleets.append((layout, SpatialShards.build(
                    rects, 8, fanout=64, layout=layout)))
        served_phase(rects, N_POINTS, args.seed)
        kernel_phase(fleets, args.seed)
    d0 = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
