"""Distributed spatial service: sharded select ≡ single-tree select;
straggler deadline re-issue (winner race / exception re-issue / self-
re-issue regressions); the continuous-batching serve queue (coalesced
responses bit-exact with direct per-request calls); replica fan-out.

Shard fleets are built once per module through a cache keyed by
(n, n_partitions, fanout, seed) — rebuilding 30k-rect fleets per test was
the sharded suite's dominant tier-1 cost.
"""
import time

import jax
import numpy as np
import pytest

from repro.core import traversal
from repro.core.geometry import mindist_matrix_np, mindist_rect_matrix_np
from repro.distributed.spatial_shard import SpatialShards
from repro.launch.queue import QueueClosed, ServeQueue
from repro.runtime.straggler import ShardPool

from conftest import brute_select, uniform_rects

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:          # property tests skip, the rest of the module runs
    HAVE_HYPOTHESIS = False


@pytest.fixture(scope="module")
def shard_cache():
    cache = {}

    def get(n, n_partitions, fanout=64, seed=20, eps=0.004):
        key = (n, n_partitions, fanout, seed, eps)
        if key not in cache:
            rng = np.random.default_rng(seed)
            rects = uniform_rects(rng, n, eps=eps)
            cache[key] = (rects, SpatialShards.build(
                rects, n_partitions=n_partitions, fanout=fanout))
        return cache[key]

    return get


def test_sharded_select_matches_brute(shard_cache):
    rects, shards = shard_cache(30_000, 6, fanout=32)
    assert len(shards.partitions) >= 4
    rng = np.random.default_rng(25)
    lo = rng.random((12, 2)).astype(np.float32) * 0.9
    qs = np.concatenate([lo, lo + 0.07], axis=1).astype(np.float32)
    res = shards.range_select(qs)
    for i, q in enumerate(qs):
        np.testing.assert_array_equal(res[i], brute_select(rects, q))


def test_partition_coverage(shard_cache):
    _, shards = shard_cache(5000, 4, eps=0.0, seed=21)
    total = np.concatenate([p.ids for p in shards.partitions])
    assert len(total) == 5000 and len(set(total.tolist())) == 5000


def test_straggler_reissue():
    calls = {"slow": 0, "spare": 0}

    def slow_shard(payload):
        calls["slow"] += 1
        time.sleep(1.0)
        return "slow-answer"

    def spare(payload):
        calls["spare"] += 1
        return "spare-answer"

    pool = ShardPool([slow_shard], spares=[spare], deadline_s=0.05)
    out = pool.query(0, "q")
    assert out in ("spare-answer", "slow-answer")
    assert pool.reissues == 1
    assert calls["spare"] == 1
    pool.shutdown()


def test_no_reissue_when_fast():
    pool = ShardPool([lambda p: p * 2], deadline_s=2.0)
    assert pool.query(0, 21) == 42
    assert pool.reissues == 0
    pool.shutdown()


# ---------------------------------------------------------------------------
# ShardPool regressions: the three serving-layer bugs
# ---------------------------------------------------------------------------

def test_pool_winner_race_prefers_successful_backup():
    """Bug 1: after a deadline lapse, FIRST_COMPLETED could hand back the
    *failed* primary (it completes — by raising — while the backup runs)
    and re-raise even though the backup succeeded.  The race must return
    the first *successful* completion."""
    def primary(payload):
        time.sleep(0.15)
        raise RuntimeError("primary died after missing its deadline")

    def spare(payload):
        time.sleep(0.25)          # backup lands AFTER the primary failure
        return "spare-answer"

    with ShardPool([primary], spares=[spare], deadline_s=0.02) as pool:
        assert pool.query(0, "q") == "spare-answer"
        assert pool.reissues == 1
        assert pool.failures == 1      # the late primary failure is counted


def test_pool_raises_only_when_every_engine_failed():
    def primary(payload):
        time.sleep(0.1)
        raise RuntimeError("primary died")

    def spare(payload):
        raise ValueError("spare died")

    with ShardPool([primary], spares=[spare], deadline_s=0.02) as pool:
        with pytest.raises((RuntimeError, ValueError)):
            pool.query(0, "q")
        assert pool.failures == 2
        assert pool.reissues == 1


def test_pool_exception_triggers_reissue():
    """Bug 2: a raised shard exception is a re-issue trigger, not a fatal
    answer — the flaky primary crashes immediately, the spare answers."""
    calls = {"flaky": 0, "spare": 0}

    def flaky(payload):
        calls["flaky"] += 1
        raise RuntimeError("shard crashed")

    def spare(payload):
        calls["spare"] += 1
        return "spare-answer"

    with ShardPool([flaky], spares=[spare], deadline_s=5.0) as pool:
        assert pool.query(0, "q") == "spare-answer"
        assert pool.failures == 1
        assert pool.reissues == 1
        assert calls == {"flaky": 1, "spare": 1}


def test_pool_single_shard_skips_self_reissue():
    """Bug 3: with one shard and no spares, a 're-issue' resubmits the
    identical callable to the same engine — the pool must wait the primary
    out instead (and not inflate ``reissues``)."""
    calls = {"n": 0}

    def slow(payload):
        calls["n"] += 1
        time.sleep(0.15)
        return "slow-answer"

    with ShardPool([slow], deadline_s=0.02) as pool:
        assert pool.query(0, "q") == "slow-answer"
        assert pool.reissues == 0
        assert calls["n"] == 1


def test_pool_single_shard_propagates_failure_without_reissue():
    def crash(payload):
        raise RuntimeError("only engine died")

    with ShardPool([crash], deadline_s=1.0) as pool:
        with pytest.raises(RuntimeError):
            pool.query(0, "q")
        assert pool.failures == 1
        assert pool.reissues == 0


def test_pool_reissue_lands_on_distinct_replica():
    """With real replicas (no spares), the deadline re-issue targets the
    NEXT replica, never the engine that missed its deadline."""
    hits = []

    def replica(tag, delay=0.0):
        def call(payload):
            hits.append(tag)
            time.sleep(delay)
            return tag
        return call

    with ShardPool([replica("r0", delay=0.3), replica("r1")],
                   deadline_s=0.02) as pool:
        assert pool.query(0, "q") == "r1"
        assert pool.reissues == 1
        assert hits.count("r1") == 1


def test_pool_context_manager_shuts_down_on_exception():
    with pytest.raises(KeyError):
        with ShardPool([lambda p: p]) as pool:
            raise KeyError("serving loop blew up")
    assert pool._pool._shutdown


def test_pool_query_many_preserves_order():
    with ShardPool([lambda p: ("a", p), lambda p: ("b", p)],
                   deadline_s=5.0) as pool:
        out = pool.query_many([(0, 1), (1, 2), (0, 3), (1, 4)])
    assert out == [("a", 1), ("b", 2), ("a", 3), ("b", 4)]


def test_pool_stats_consistent_snapshot_under_hammering():
    """Satellite regression: ``stats()`` must be a consistent snapshot —
    totals always equal the sum of the per-shard rows, even while
    concurrent query_many calls race failures and re-issues into the
    counters.  Shard r1 fails every call (its failures re-issue to r2);
    snapshots taken mid-hammering must never tear."""
    import threading

    def ok(tag):
        return lambda p: (tag, p)

    def crash(p):
        raise RuntimeError("r1 always dies")

    n_threads, n_queries = 4, 30
    with ShardPool([ok("r0"), crash, ok("r2")], deadline_s=5.0) as pool:
        tears = []

        def hammer(tid):
            rng = np.random.default_rng(tid)
            sids = rng.integers(0, 3, n_queries)
            out = pool.query_many([(int(s), i) for i, s in enumerate(sids)])
            for (sid, i, got) in zip(sids, range(n_queries), out):
                assert got[1] == i          # re-issued answers stay correct
            return int((sids == 1).sum())

        def snapshotter(stop):
            while not stop.is_set():
                s = pool.stats()
                if (s["failures"] != sum(v["failures"]
                                         for v in s["by_shard"].values())
                        or s["reissues"] != sum(
                            v["reissues"] for v in s["by_shard"].values())):
                    tears.append(s)

        import concurrent.futures as cf
        stop = threading.Event()
        watcher = threading.Thread(target=snapshotter, args=(stop,))
        watcher.start()
        with cf.ThreadPoolExecutor(n_threads) as ex:
            r1_hits = sum(ex.map(hammer, range(n_threads)))
        stop.set()
        watcher.join()
        assert tears == []
        # late done-callbacks may lag the last query()'s return briefly
        deadline = time.time() + 2.0
        while pool.failures < r1_hits and time.time() < deadline:
            time.sleep(0.01)
        s = pool.stats()
        assert s["failures"] == r1_hits
        assert s["by_shard"]["r1"]["failures"] == r1_hits
        assert s["by_shard"]["r1"]["reissues"] == r1_hits
        assert s["reissues"] == r1_hits
        assert pool.failures == s["failures"]   # props agree with snapshot


# ---------------------------------------------------------------------------
# Continuous-batching serve queue (launch/queue.py)
# ---------------------------------------------------------------------------

def _queue_fleet(shard_cache):
    return shard_cache(5000, 4, eps=0.0, seed=21)


def test_queue_knn_bitexact_and_ordered(shard_cache):
    rects, shards = _queue_fleet(shard_cache)
    rng = np.random.default_rng(31)
    reqs = [rng.random((m, 2)).astype(np.float32) for m in (1, 3, 2, 5, 1)]
    with ServeQueue(shards, "knn", k=4, max_batch=16,
                    max_delay_s=0.005) as q:
        res = q.query_many(reqs)
        summary = q.summary
    assert summary["requests"] == len(reqs)
    assert summary["failures"] == 0
    for rows, (ids, d, ovf) in zip(reqs, res):
        ref_ids, ref_d, ref_ovf = shards.knn(rows, 4)
        np.testing.assert_array_equal(ids, ref_ids)
        np.testing.assert_array_equal(d, ref_d)
        assert not ovf and not ref_ovf


def test_queue_select_bitexact(shard_cache):
    rects, shards = _queue_fleet(shard_cache)
    rng = np.random.default_rng(37)
    reqs = []
    for m in (2, 1, 4):
        lo = rng.random((m, 2)).astype(np.float32) * 0.9
        reqs.append(np.concatenate([lo, lo + 0.05], axis=1))
    with ServeQueue(shards, "select", max_batch=8,
                    max_delay_s=0.005) as q:
        res = q.query_many(reqs)
    for rows, got in zip(reqs, res):
        ref = shards.range_select(rows)
        assert len(got) == len(rows)
        for got_row, ref_row in zip(got, ref):
            np.testing.assert_array_equal(got_row, ref_row)


def test_queue_rejects_uncoalescable_ops(shard_cache):
    _, shards = _queue_fleet(shard_cache)
    with pytest.raises(ValueError):
        ServeQueue(shards, "join")
    with pytest.raises(ValueError):
        ServeQueue(shards, "browse", k=4)
    with pytest.raises(ValueError):
        ServeQueue(shards, "knn")        # distance op without k


def test_queue_oversized_request_dispatches_whole(shard_cache):
    """A single request larger than max_batch still runs (its own pow2
    bucket), and smaller companions coalesce around it unharmed."""
    rects, shards = _queue_fleet(shard_cache)
    rng = np.random.default_rng(41)
    big = rng.random((23, 2)).astype(np.float32)
    small = rng.random((2, 2)).astype(np.float32)
    with ServeQueue(shards, "knn", k=4, max_batch=8,
                    max_delay_s=0.005) as q:
        res = q.query_many([big, small])
    for rows, (ids, d, _) in zip([big, small], res):
        ref_ids, ref_d, _ = shards.knn(rows, 4)
        np.testing.assert_array_equal(ids, ref_ids)
        np.testing.assert_array_equal(d, ref_d)


class _SlowFake:
    """Pure per-row 'knn' fake with a fixed service time — lets the close()
    races be provoked without a real fleet."""

    def __init__(self, delay_s):
        self.delay_s = delay_s

    def knn(self, batch, k):
        time.sleep(self.delay_s)
        b = np.asarray(batch, np.float32)
        ids = (b[:, 0] * 1e6).astype(np.int64)[:, None] \
            + np.arange(k)[None, :]
        return ids, b[:, 1:2].astype(np.float64), False


def test_queue_close_fails_pending_with_queue_closed():
    """Satellite regression: a client that submitted just before close()
    must never block forever — every future the queue abandons fails with
    QueueClosed, and every future it already served resolves normally."""
    eng = _SlowFake(0.3)
    rng = np.random.default_rng(53)
    reqs = [rng.random((1, 2)).astype(np.float32) for _ in range(6)]
    q = ServeQueue([eng], "knn", k=3, max_batch=1, depth=1)
    futs = [q.submit(r) for r in reqs]
    time.sleep(0.05)                      # first dispatch is in flight
    q.close(drain=False)
    served = closed = 0
    for rows, f in zip(reqs, futs):
        assert f.done()                   # nobody is left hanging
        try:
            ids, d, _ = f.result()
        except QueueClosed:
            closed += 1
            continue
        served += 1
        ref_ids, ref_d, _ = eng.knn(rows, 3)
        np.testing.assert_array_equal(ids, ref_ids)
        np.testing.assert_array_equal(d, ref_d)
    assert served >= 1                    # the in-flight batch completed
    assert closed >= 1                    # the queued tail was failed fast
    with pytest.raises(QueueClosed):
        q.submit(reqs[0])


def test_queue_close_drains_admitted_requests():
    """Default close(): everything admitted before the close is flushed —
    no request is dropped, none sees QueueClosed."""
    eng = _SlowFake(0.05)
    rng = np.random.default_rng(59)
    reqs = [rng.random((1, 2)).astype(np.float32) for _ in range(4)]
    q = ServeQueue([eng], "knn", k=3, max_batch=1, depth=1)
    futs = [q.submit(r) for r in reqs]
    q.close()
    for rows, f in zip(reqs, futs):
        ids, d, _ = f.result(timeout=0)   # already resolved by close()
        ref_ids, ref_d, _ = eng.knn(rows, 3)
        np.testing.assert_array_equal(ids, ref_ids)
        np.testing.assert_array_equal(d, ref_d)
    with pytest.raises(QueueClosed):
        q.submit(reqs[0])


def _check_schedule_invisible(shards, sizes, seed, interleave):
    """Core property: whatever the request schedule (sizes, submission
    order, concurrent vs sequential arrival), every response is bit-exact
    with the direct per-request SpatialShards call — coalescing must be
    observationally invisible."""
    rng = np.random.default_rng(seed)
    reqs = [rng.random((m, 2)).astype(np.float32) for m in sizes]
    with ServeQueue(shards, "knn", k=3, max_batch=8,
                    max_delay_s=0.002) as q:
        if interleave:
            futs = [q.submit(r) for r in reqs]      # all in flight at once
            res = [f.result() for f in futs]
        else:
            res = [q.query(r) for r in reqs]        # strictly sequential
    for rows, (ids, d, _) in zip(reqs, res):
        ref_ids, ref_d, _ = shards.knn(rows, 3)
        np.testing.assert_array_equal(ids, ref_ids)
        np.testing.assert_array_equal(d, ref_d)


@pytest.mark.parametrize("sizes,seed,interleave", [
    ([1], 0, True),                       # lone request, own bucket
    ([8, 8], 1, True),                    # exactly fills max_batch
    ([1, 1, 1, 1, 1, 1, 1, 1, 1], 2, True),   # many tiny, spills a batch
    ([6, 5, 4], 3, True),                 # forces carry-over past bucket
    ([3, 1, 2], 4, False),                # sequential: no coalescing at all
])
def test_queue_schedule_invisible(shard_cache, sizes, seed, interleave):
    _, shards = _queue_fleet(shard_cache)
    _check_schedule_invisible(shards, sizes, seed, interleave)


if HAVE_HYPOTHESIS:
    @settings(max_examples=8, deadline=None)
    @given(sizes=st.lists(st.integers(min_value=1, max_value=6),
                          min_size=1, max_size=10),
           seed=st.integers(min_value=0, max_value=2**16),
           interleave=st.booleans())
    def test_queue_coalescing_is_invisible(shard_cache, sizes, seed,
                                           interleave):
        _, shards = _queue_fleet(shard_cache)
        _check_schedule_invisible(shards, sizes, seed, interleave)


# ---------------------------------------------------------------------------
# Replica fan-out (data axis)
# ---------------------------------------------------------------------------

def test_replicate_parity_with_host_path(shard_cache):
    """Every replica engine answers bit-exactly like the host fleet; the
    replica count adapts to the visible device count (1 on the single-
    device tier-1 run, 2 on the CI multi-device step)."""
    import jax
    rects, shards = _queue_fleet(shard_cache)
    n_dev = len(jax.devices())
    r = 2 if n_dev >= 2 and n_dev % 2 == 0 else 1
    reps = shards.replicate(replicas=r)
    assert len(reps) == r
    rng = np.random.default_rng(43)
    pts = rng.random((8, 2)).astype(np.float32)
    hi, hd, _ = shards.knn(pts, 4)          # host-path reference
    for rep in reps:
        assert rep.mesh_enabled
        mi, md, _ = rep.knn(pts, 4)
        np.testing.assert_array_equal(hi, mi)
        np.testing.assert_array_equal(hd, md)


def test_queue_over_replicas_bitexact(shard_cache):
    """The queue round-robins dispatches across replica engines; responses
    stay bit-exact with the host fleet regardless of which replica served
    which coalesced batch."""
    import jax
    rects, shards = _queue_fleet(shard_cache)
    n_dev = len(jax.devices())
    r = 2 if n_dev >= 2 and n_dev % 2 == 0 else 1
    reps = shards.replicate(replicas=r)
    rng = np.random.default_rng(47)
    reqs = [rng.random((m, 2)).astype(np.float32) for m in (2, 3, 1, 4, 2)]
    with ServeQueue(reps, "knn", k=4, max_batch=4,
                    max_delay_s=0.001) as q:
        res = q.query_many(reqs)
        assert q.summary["replicas"] == r
        assert q.summary["failures"] == 0
    for rows, (ids, d, _) in zip(reqs, res):
        ref_ids, ref_d, _ = shards.knn(rows, 4)
        np.testing.assert_array_equal(ids, ref_ids)
        np.testing.assert_array_equal(d, ref_d)


# ---------------------------------------------------------------------------
# The host path's per-partition Counters, summed once per operator call
# ---------------------------------------------------------------------------

def _field_sums(ctrs):
    out = {}
    for c in ctrs:
        for name, v in c.asdict().items():
            out[name] = np.add(out.get(name, 0), v)
    return {name: v.tolist() if np.ndim(v) else int(v)
            for name, v in out.items()}


def _distance_rows(op, pts):
    """Query rows of a distance operator around ``pts``, and the columns
    its router measures."""
    if op == "knn":
        return pts, pts
    if op == "knn_join":
        rows = np.concatenate([pts - 0.002, pts + 0.002], axis=1)
        return rows, rows
    rows = np.concatenate([pts, pts - 0.2, pts + 0.2], axis=1)
    return rows, pts


@pytest.mark.parametrize("op", ["range_select", "join", "knn", "knn_join",
                                "knn_filtered"])
def test_host_counters_equal_the_sum_of_the_engine_calls(shard_cache,
                                                         monkeypatch, op):
    """``last_counters`` after a host-path operator call is, field by field
    and bit for bit, the sum of the Counters its engine calls returned,
    and stays on the device; each distance operator makes phase-2 calls
    here, so the sum spans both phases."""
    rects, shards = _queue_fleet(shard_cache)
    rng = np.random.default_rng(53)
    m = shards.router_mbrs
    # points on the partitions' right edges: their neighbours straddle it
    edges = np.stack([m[:, 2], (m[:, 1] + m[:, 3]) / 2], axis=1)
    pts = np.concatenate([rng.random((8, 2)), edges]).astype(np.float32)
    seen = []

    def record(fn):
        def call(*a, **kw):
            out = fn(*a, **kw)
            seen.append(out[-1])
            return out
        return call

    if op == "join":
        build = traversal.build
        monkeypatch.setattr(traversal, "build",
                            lambda *a, **kw: record(build(*a, **kw)))
        shards.join(np.concatenate([pts, pts + 0.01], axis=1))
    else:
        engine_for = shards.engine_for
        monkeypatch.setattr(shards, "engine_for",
                            lambda *a, **kw: record(engine_for(*a, **kw)))
        if op == "range_select":
            shards.range_select(np.concatenate([pts, pts + 0.3], axis=1))
        else:
            rows, route = _distance_rows(op, pts)
            getattr(shards, op)(rows, 4)
            dist = mindist_rect_matrix_np if op == "knn_join" \
                else mindist_matrix_np
            primaries = np.unique(np.argmin(
                dist(route, shards.router_mbrs), axis=1))
            assert len(seen) > len(primaries)
    assert len(seen) >= 2
    assert shards.last_counters.asdict() == _field_sums(seen)
    assert all(isinstance(v, jax.Array)
               for v in shards.last_counters.tree_flatten()[0])


def test_select_row_over_the_result_cap_fails_its_request_alone(
        shard_cache):
    """A window that holds more points than ``result_cap`` fails its
    request with ``ResultOverflow`` and bumps
    ``repro.fleet.overflowed_rows``; a sibling request of the same batch
    still resolves exactly."""
    from repro.launch.queue import ResultOverflow
    from repro.runtime import trace
    rects, shards = _queue_fleet(shard_cache)
    cap = 64
    big = np.array([[0.0, 0.0, 1.0, 1.0]], np.float32)
    small = np.array([[0.40, 0.40, 0.45, 0.45]], np.float32)
    assert 0 < len(brute_select(rects, small[0])) <= cap

    direct = shards.range_select(np.concatenate([big, small]),
                                 result_cap=cap)
    np.testing.assert_array_equal(direct.overflowed, [0])
    np.testing.assert_array_equal(direct[1], brute_select(rects, small[0]))

    before = trace.snapshot()["counters"].get(
        "repro.fleet.overflowed_rows", 0)
    with ServeQueue(shards, "select", result_cap=cap, max_batch=2,
                    max_delay_s=1.0) as q:
        f_big, f_small = q.submit(big), q.submit(small)
        with pytest.raises(ResultOverflow):
            f_big.result(timeout=60)
        got = f_small.result(timeout=60)
        summary = q.summary
    after = trace.snapshot()["counters"]["repro.fleet.overflowed_rows"]
    assert summary["batches"] == 1 and summary["requests"] == 2
    assert summary["overflowed_requests"] == 1
    assert after - before == 1
    assert len(got) == 1
    np.testing.assert_array_equal(got[0], brute_select(rects, small[0]))


# ---------------------------------------------------------------------------
# the host path's phase 1: every primary call launched before any read-back
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tied_fleet():
    """Points left and right of x = 0.5 and 40 copies of (0.5, 0.5), which
    the STR split at the median puts into tiles on both sides of a tile
    boundary: their distances tie across partitions."""
    rng = np.random.default_rng(57)
    left = rng.random((2000, 2)) * [0.49, 1.0]
    right = rng.random((2000, 2)) * [0.49, 1.0] + [0.51, 0.0]
    pts = np.concatenate([left, np.full((40, 2), 0.5), right])
    rects = np.concatenate([pts, pts], axis=1).astype(np.float32)
    shards = SpatialShards.build(rects, n_partitions=4, fanout=16)
    tied = [pi for pi, part in enumerate(shards.partitions)
            if np.isin(np.arange(2000, 2040), part.ids).any()]
    assert len(tied) >= 2
    return rects, shards


def _one_call_at_a_time(shards, op, rows, route, k):
    """Two-phase routing with each partition call read back before the
    next is launched: the host path as it was before phase 1 shared one
    read-back.  → (global ids, float64 sq-dists)."""
    import jax.numpy as jnp
    dist = mindist_rect_matrix_np if op == "knn_join" else mindist_matrix_np
    dmat = dist(route, shards.router_mbrs)
    primary = np.argmin(dmat, axis=1)
    ids = np.full((len(rows), k), -1, np.int64)
    d = np.full((len(rows), k), np.inf)

    def call(pi, sel):
        got_i, got_d, ctr = shards.engine_for(op, pi, k=k)(
            jnp.asarray(shards._bucket(rows[sel])))
        got_i = np.asarray(got_i)[:len(sel)]
        got_d = np.asarray(got_d, np.float64)[:len(sel)]
        assert not bool(ctr.overflow)
        part_ids = shards.partitions[pi].ids
        return np.where(got_i >= 0, part_ids[np.maximum(got_i, 0)], -1), got_d

    for pi in range(len(shards.partitions)):
        sel = np.nonzero(primary == pi)[0]
        if len(sel):
            ids[sel], d[sel] = call(pi, sel)
    tau = d[:, k - 1].copy()
    for pi in range(len(shards.partitions)):
        sel = np.nonzero((primary != pi)
                         & (dmat[:, pi] <= tau * (1.0 + 1e-5) + 1e-30))[0]
        if len(sel) == 0:
            continue
        got_i, got_d = call(pi, sel)
        md = np.concatenate([d[sel], got_d], axis=1)
        mi = np.concatenate([ids[sel], got_i], axis=1)
        order = np.lexsort((mi, md))[:, :k]
        d[sel] = np.take_along_axis(md, order, axis=1)
        ids[sel] = np.take_along_axis(mi, order, axis=1)
        tau[sel] = d[sel, k - 1]
    return ids, d


def _recording(shards, monkeypatch, seen):
    """Wrap ``shards.engine_for`` so every engine call appends
    ``(partition, query bytes, Counters)`` to ``seen``."""
    engine_for = shards.engine_for

    def get(op, pi, **kw):
        fn = engine_for(op, pi, **kw)

        def call(q):
            out = fn(q)
            seen.append((pi, np.asarray(q).tobytes(), out[-1]))
            return out
        return call
    monkeypatch.setattr(shards, "engine_for", get)


def _counted(before, after, name):
    return after["counters"].get(name, 0) - before["counters"].get(name, 0)


@pytest.mark.parametrize("data", ["edges", "ties"])
@pytest.mark.parametrize("op", ["knn", "knn_join", "knn_filtered"])
def test_pipelined_phase1_matches_one_call_at_a_time(shard_cache, tied_fleet,
                                                     monkeypatch, op, data):
    """The host path launches every phase-1 call before reading any back.
    Its ids and float32 distances are bit for bit those of reading each
    call back before the next, and it makes the same partition calls with
    the same rows, in the same order; phase 2 runs here (points on tile
    edges, or distance ties across a tile boundary)."""
    from repro.runtime import trace
    rng = np.random.default_rng(61)
    if data == "edges":
        _, shards = _queue_fleet(shard_cache)
        m = shards.router_mbrs
        edges = np.stack([m[:, 2], (m[:, 1] + m[:, 3]) / 2], axis=1)
        pts = np.concatenate([rng.random((8, 2)), edges])
    else:
        _, shards = tied_fleet
        pts = np.concatenate([np.full((3, 2), 0.5), rng.random((6, 2)),
                              0.5 + 0.003 * rng.standard_normal((6, 2))])
    rows, route = _distance_rows(op, pts.astype(np.float32))
    seen = []
    _recording(shards, monkeypatch, seen)
    before = trace.snapshot()
    ids, d, ovf = getattr(shards, op)(rows, 4)
    after = trace.snapshot()
    got = [(pi, q) for pi, q, _ in seen]
    seen.clear()
    want_ids, want_d = _one_call_at_a_time(shards, op, rows, route, 4)
    assert got == [(pi, q) for pi, q, _ in seen]
    assert _counted(before, after, "repro.fleet.partition_calls") == len(got)
    dist = mindist_rect_matrix_np if op == "knn_join" else mindist_matrix_np
    primaries = len(np.unique(np.argmin(dist(route, shards.router_mbrs),
                                        axis=1)))
    assert primaries >= 2 and len(got) > primaries
    assert _counted(before, after, "repro.fleet.pipelined_calls") \
        == primaries
    assert not ovf
    np.testing.assert_array_equal(ids, want_ids)
    np.testing.assert_array_equal(d, want_d)
    np.testing.assert_array_equal(d.astype(np.float32),
                                  want_d.astype(np.float32))


def test_tight_tier_overflow_through_pipelined_phase1(monkeypatch):
    """Tight kNN tiers of one frontier slot a level overflow; each engine
    re-runs its full tier inside its own call, so the pipelined phase 1
    still returns the full tier's answer (the unpatched fleet's, whose
    tight tier holds), and a wrapper on ``engine_for`` sees exactly
    ``partition_calls`` calls, each with its overflow flag."""
    from repro.core import knn_vector
    from repro.runtime import trace
    rng = np.random.default_rng(59)
    rects = uniform_rects(rng, 3000)
    pts = rng.random((24, 2)).astype(np.float32)
    want = SpatialShards.build(rects, n_partitions=4, fanout=16).knn(pts, 1)

    real = knn_vector.knn_frontier_caps

    def one_slot(tree, k, *a, policy="static", **kw):
        full = real(tree, k, *a, **kw)
        return (1,) * len(full) if policy == "adaptive" else full
    monkeypatch.setattr(knn_vector, "knn_frontier_caps", one_slot)
    shards = SpatialShards.build(rects, n_partitions=4, fanout=16)
    seen = []
    _recording(shards, monkeypatch, seen)
    before = trace.snapshot()
    ids, d, ovf = shards.knn(pts, 1)
    after = trace.snapshot()
    assert len(seen) == _counted(before, after,
                                 "repro.fleet.partition_calls")
    primaries = len(np.unique(np.argmin(
        mindist_matrix_np(pts, shards.router_mbrs), axis=1)))
    assert primaries >= 2
    assert _counted(before, after, "repro.fleet.pipelined_calls") \
        == primaries
    assert [bool(c.overflow) for _, _, c in seen] == [False] * len(seen)
    assert all(int(c.escalations) == 1 for _, _, c in seen[:primaries])
    assert not ovf and not want[2]
    np.testing.assert_array_equal(ids, want[0])
    np.testing.assert_array_equal(d, want[1])
