"""The service's spans and counters (runtime/trace.py): nesting, self time,
threads, the bounded ring, and the spans of a queued fleet run."""
import collections
import os
import sys
import threading
import time

import numpy as np
import pytest

from repro.core import counters
from repro.core.geometry import mindist_matrix_np
from repro.distributed.spatial_shard import SpatialShards
from repro.launch.queue import ServeQueue
from repro.runtime import trace
from repro.runtime.straggler import ShardPool

from conftest import uniform_rects


def _by_name(records):
    out = collections.defaultdict(list)
    for r in records:
        out[r.name].append(r)
    return out


def test_nesting_parents_and_self_time():
    t = trace.Tracer()
    with t.span("repro.a"):
        time.sleep(0.01)
        with t.span("repro.b"):
            time.sleep(0.02)
            with t.span("repro.c"):
                time.sleep(0.01)
        with t.span("repro.b"):
            time.sleep(0.01)
    recs = _by_name(t.records())
    (a,), (c,) = recs["repro.a"], recs["repro.c"]
    b1, b2 = recs["repro.b"]
    assert a.parent is None
    assert b1.parent == b2.parent == a.span
    assert c.parent == b1.span
    assert list(t.records()) == [c, b1, b2, a]        # in closing order
    snap = t.snapshot()["spans"]
    assert snap["repro.b"]["count"] == 2
    # self = duration less the time its children cover
    assert snap["repro.a"]["self_s"] == pytest.approx(
        (a.end - a.start) - (b1.end - b1.start) - (b2.end - b2.start))
    assert snap["repro.b"]["self_s"] == pytest.approx(
        snap["repro.b"]["total_s"] - (c.end - c.start))
    assert snap["repro.c"]["self_s"] == snap["repro.c"]["total_s"]
    for s in snap.values():
        assert 0 <= s["self_s"] <= s["total_s"]


def test_counters_times_ids_and_snapshot_is_a_copy():
    t = trace.Tracer()
    t.add("repro.x")
    t.add("repro.x", 4)
    t.add_time("repro.w", 0.25)
    t.add_time("repro.w", 0.5)
    with trace.tag(batch=7):
        with t.span("repro.s", request=3):
            pass
        with t.span("repro.s"):
            pass
    with t.span("repro.s"):
        pass
    snap = t.snapshot()
    assert snap["counters"] == {"repro.x": 5}
    assert snap["times"] == {"repro.w": {"count": 2, "total_s": 0.75}}
    assert [r.ids for r in t.records()] == [{"batch": 7, "request": 3},
                                            {"batch": 7}, {}]
    snap["counters"]["repro.x"] = 0
    snap["spans"]["repro.s"]["count"] = 0
    assert t.snapshot()["counters"]["repro.x"] == 5
    assert t.snapshot()["spans"]["repro.s"]["count"] == 3
    assert t.new_id() != t.new_id()


def test_spanned_decorator_and_exception_still_records():
    t = trace.Tracer()

    @t.spanned("repro.f")
    def f(x):
        if x < 0:
            raise ValueError(x)
        return x + 1

    assert f(1) == 2
    with pytest.raises(ValueError):
        f(-1)
    assert t.snapshot()["spans"]["repro.f"]["count"] == 2
    with t.span("repro.after"):
        pass
    assert t.records()[-1].parent is None     # the failed span was closed


def test_ring_is_bounded_and_counts_dropped():
    t = trace.Tracer(capacity=4)
    for i in range(10):
        with t.span(f"repro.s{i % 2}"):
            pass
    recs = t.records()
    assert len(recs) == 4
    assert [r.name for r in recs] == ["repro.s0", "repro.s1"] * 2
    snap = t.snapshot()
    assert snap["dropped"] == 6
    assert snap["spans"]["repro.s0"]["count"] == 5    # aggregates keep all


@pytest.mark.parametrize("workers", [2, os.cpu_count() + 2],
                         ids=["two", "more_than_cores"])
def test_threads_keep_their_own_parents_and_lose_no_update(workers):
    t = trace.Tracer()
    n = 400
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work(tag):
            with trace.tag(worker=tag):
                for _ in range(n):
                    with t.span("repro.outer"):
                        with t.span("repro.inner"):
                            t.add("repro.calls")
                        t.add_time("repro.wait", 1e-3)
        threads = [threading.Thread(target=work, args=(i,))
                   for i in range(workers)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=60)
        assert not any(th.is_alive() for th in threads)
    finally:
        sys.setswitchinterval(old)
    snap = t.snapshot()
    assert snap["counters"]["repro.calls"] == workers * n
    assert snap["times"]["repro.wait"]["count"] == workers * n
    assert snap["spans"]["repro.outer"]["count"] == workers * n
    assert snap["spans"]["repro.inner"]["count"] == workers * n
    assert snap["dropped"] == 0
    recs = t.records()
    by_id = {r.span: r for r in recs}
    for r in recs:
        if r.name == "repro.inner":
            parent = by_id[r.parent]
            assert parent.name == "repro.outer"
            assert parent.thread == r.thread
            assert parent.ids == r.ids
            assert parent.start <= r.start <= r.end <= parent.end
        else:
            assert r.parent is None


def test_context_follows_work_into_the_shard_pool():
    t = trace.Tracer()

    def engine(payload):
        with t.span("repro.engine"):
            return payload * 2

    with ShardPool([engine], deadline_s=5.0) as pool:
        with trace.tag(batch=11):
            with t.span("repro.caller"):
                assert pool.query(0, 21) == 42
    eng, caller = t.records()
    assert eng.ids == caller.ids == {"batch": 11}
    assert eng.parent == caller.span
    assert eng.thread != caller.thread
    # a child in another thread that closes inside its parent counts
    # against the parent's self time
    snap = t.snapshot()["spans"]
    assert snap["repro.caller"]["self_s"] == pytest.approx(
        (caller.end - caller.start) - (eng.end - eng.start))
    with t.span("repro.untagged"):            # the tag ended with its block
        pass
    assert t.records()[-1].ids == {}


# ---------------------------------------------------------------------------
# a tiny fleet behind the serving queue
# ---------------------------------------------------------------------------

K = 1
FLEET_SPANS = {"repro.fleet.knn", "repro.fleet.route", "repro.fleet.phase1",
               "repro.fleet.phase2", "repro.fleet.enqueue",
               "repro.fleet.readback"}


@pytest.fixture(scope="module")
def fleet():
    rng = np.random.default_rng(41)
    rects = uniform_rects(rng, 4000)
    shards = SpatialShards.build(rects, n_partitions=4, fanout=16)
    shards.warm("knn", 16, k=K)
    return rects, shards


def _implied_calls(rects, shards, rows):
    """(phase, partition) engine calls that two-phase routing implies for
    ``rows``: each primary partition once, then each other partition whose
    MBR lies within some row's running k-th distance, from brute force."""
    dmat = mindist_matrix_np(rows, shards.router_mbrs)
    primary = np.argmin(dmat, axis=1)
    pts = rects[:, :2].astype(np.float64)

    def best(pi, sel):
        d = ((rows[sel, None, :].astype(np.float64)
              - pts[None, shards.partitions[pi].ids]) ** 2).sum(-1)
        return np.sort(d, axis=1)[:, K - 1]

    tau = np.empty(len(rows))
    calls = set()
    for pi in np.unique(primary):
        sel = primary == pi
        tau[sel] = best(pi, sel)
        calls.add((1, int(pi)))
    for pi in range(len(shards.partitions)):
        sel = (primary != pi) & (dmat[:, pi] <= tau * (1 + 1e-5) + 1e-30)
        if sel.any():
            tau[sel] = np.minimum(tau[sel], best(pi, sel))
            calls.add((2, pi))
    return calls


def test_queued_batch_spans_share_its_id_and_count_partition_calls(fleet):
    rects, shards = fleet
    rng = np.random.default_rng(43)
    reqs = [rng.random((m, 2)).astype(np.float32) for m in (5, 16, 3, 9)]
    before = trace.snapshot()
    first = trace.new_id()
    with ServeQueue(shards, "knn", k=K, max_batch=16,
                    max_delay_s=0.001) as q:
        for rows in reqs:                     # one request per batch
            q.query(rows)
        assert q.summary["batches"] == len(reqs)
    after = trace.snapshot()
    recs = [r for r in trace.records()
            if r.ids.get("batch", 0) > first and r.name.startswith("repro.")]
    batches = collections.defaultdict(list)
    for r in recs:
        batches[r.ids["batch"]].append(r)
    dispatched = [b for b, rs in sorted(batches.items())
                  if any(r.name == "repro.fleet.knn" for r in rs)]
    assert len(dispatched) == len(reqs)
    calls = pipelined = 0
    for bid, rows in zip(dispatched, reqs):
        names = collections.Counter(r.name for r in batches[bid])
        assert FLEET_SPANS | {"repro.queue.gather", "repro.queue.assemble",
                              "repro.queue.resolve"} <= set(names)
        want = _implied_calls(rects, shards, rows)
        phase1 = sum(1 for phase, _ in want if phase == 1)
        assert names["repro.fleet.enqueue"] == len(want)
        # one read-back for all phase-1 calls, then one per phase-2 call
        assert names["repro.fleet.readback"] \
            == (phase1 > 0) + len(want) - phase1
        calls += len(want)
        pipelined += phase1 if phase1 >= 2 else 0
        by_id = {r.span: r for r in batches[bid]}
        for r in batches[bid]:
            if r.parent is not None:
                # a batch's spans nest inside spans of the same batch
                parent = by_id[r.parent]
                assert parent.start <= r.start <= r.end <= parent.end
                kids = [c for c in batches[bid] if c.parent == r.parent]
                assert sum(c.end - c.start for c in kids) \
                    <= parent.end - parent.start
    counted = after["counters"]["repro.fleet.partition_calls"] \
        - before["counters"].get("repro.fleet.partition_calls", 0)
    assert counted == calls and pipelined > 0
    assert after["counters"].get("repro.fleet.pipelined_calls", 0) \
        - before["counters"].get("repro.fleet.pipelined_calls", 0) \
        == pipelined
    waits = after["times"]["repro.queue.wait"]
    assert waits["count"] - before["times"].get(
        "repro.queue.wait", {"count": 0})["count"] == len(reqs)
    for name, s in after["spans"].items():
        assert 0 <= s["self_s"] <= s["total_s"] + 1e-12, name


def test_direct_fleet_calls_count_partition_calls(fleet):
    rects, shards = fleet
    rows = np.random.default_rng(44).random((16, 2)).astype(np.float32)
    before = trace.snapshot()
    shards.knn(rows, K)
    after = trace.snapshot()
    got = after["counters"]["repro.fleet.partition_calls"] \
        - before["counters"].get("repro.fleet.partition_calls", 0)
    assert got == len(_implied_calls(rects, shards, rows))
    assert after["spans"]["repro.fleet.knn"]["count"] \
        == before["spans"].get("repro.fleet.knn", {"count": 0})["count"] + 1


def _deltas(before, after, name):
    return (after["counters"].get("repro.fleet.partition_calls", 0)
            - before["counters"].get("repro.fleet.partition_calls", 0),
            after["spans"][name]["count"]
            - before["spans"].get(name, {"count": 0})["count"])


def _one_partition_row(shards):
    """A query point at the middle of partition 0, whose nearest point
    lies in that partition alone."""
    m = shards.partitions[0].mbr
    return np.array([[(m[0] + m[2]) / 2, (m[1] + m[3]) / 2]], np.float32)


def test_counters_sum_once_per_host_fleet_call(fleet):
    """``repro.fleet.counters`` counts one sum per host-path operator
    call, whether the call touched one partition or all of them."""
    rects, shards = fleet
    p = len(shards.partitions)
    rows = np.random.default_rng(45).random((16, 2)).astype(np.float32)
    cases = [(lambda: shards.knn(_one_partition_row(shards), K), 1, 1),
             (lambda: shards.knn(rows, K), p, 2 * p),
             (lambda: shards.range_select(
                 np.array([[0, 0, 1, 1]], np.float32)), p, p)]
    for run, lo, hi in cases:
        before = trace.snapshot()
        run()
        calls, sums = _deltas(before, trace.snapshot(),
                              "repro.fleet.counters")
        assert lo <= calls <= hi
        assert sums == 1


def test_counters_sum_compiles_in_warm_and_never_again(fleet):
    """The sum is padded to a fixed number of terms, so ``warm`` compiles
    it once for the operator and fleet calls making 1 to P or more engine
    calls add no trace of it."""
    rects, shards = fleet
    counters._sum.clear_cache()
    shards.warm("knn", 16, k=K)
    assert counters._sum._cache_size() == 1
    rng = np.random.default_rng(46)
    made = set()
    for rows in (_one_partition_row(shards),
                 rng.random((16, 2)).astype(np.float32),
                 rng.random((5, 2)).astype(np.float32)):
        before = trace.snapshot()
        shards.knn(rows, K)
        made.add(_deltas(before, trace.snapshot(), "repro.fleet.knn")[0])
    assert min(made) == 1 and max(made) >= len(shards.partitions)
    assert counters._sum._cache_size() == 1


def test_select_result_path_spans_and_counters(fleet, monkeypatch):
    """On the host path ``repro.fleet.ids`` spans each partition call's
    mapping to global ids, and ``repro.fleet.result_ids`` is added once
    per fleet call, by the ids it returned."""
    rects, shards = fleet
    rng = np.random.default_rng(47)
    lo = rng.random((6, 2)).astype(np.float32) * 0.8
    rows = np.concatenate([lo, lo + np.float32(0.2)], axis=1)
    adds = []
    add = trace.add

    def recording_add(name, n=1):
        adds.append((name, n))
        add(name, n)
    monkeypatch.setattr(trace, "add", recording_add)
    before = trace.snapshot()
    got = [shards.range_select(rows), shards.range_select(rows[:1])]
    after = trace.snapshot()
    calls = [n for name, n in adds if name == "repro.fleet.partition_calls"]
    spans = after["spans"]["repro.fleet.ids"]["count"] \
        - before["spans"].get("repro.fleet.ids", {"count": 0})["count"]
    assert len(calls) == spans > 2
    ids = [n for name, n in adds if name == "repro.fleet.result_ids"]
    assert ids == [sum(len(r) for r in g) for g in got]
    assert ids[0] > 0
    assert after["counters"]["repro.fleet.result_ids"] \
        - before["counters"].get("repro.fleet.result_ids", 0) == sum(ids)
    assert not any(name == "repro.fleet.overflowed_rows" for name, _ in adds)
