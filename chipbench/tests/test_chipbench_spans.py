"""The program's spans read by the benchmark: device idle attributed to the
``repro.*`` spans open on the host threads, the breakdown of a tiny run,
and the engines' tiers as the log shows them."""
import lzma
import json
from pathlib import Path

import numpy as np
import pytest

import chipbench_tiny
from chipbench import breakdown, drive, span_idle, trace_reduce

WINDOW = {trace_reduce.WINDOW: [(0.0, 10.0)]}
OPS = {"/device:TPU:0": [("fusion", 1.0, 2.0), ("sort", 4.0, 5.0)]}
THREADS = {
    "/host:CPU/0:serve-queue-runner": [
        ("repro.queue.gather", 0.0, 3.0), ("repro.queue.slot_wait", 3.0, 6.0)],
    "/host:CPU/1:shard-pool": [
        ("repro.fleet.enqueue", 0.5, 1.5), ("repro.fleet.knn", 0.5, 7.0),
        ("repro.fleet.readback", 1.5, 3.5)],
}


def test_innermost_names_each_instant_after_the_latest_open_span():
    segs = span_idle.innermost(THREADS["/host:CPU/1:shard-pool"])
    assert segs == [("fleet.enqueue", 0.5, 1.5), ("fleet.readback", 1.5, 3.5),
                    ("fleet.knn", 3.5, 7.0)]
    assert span_idle.innermost([]) == []


def test_idle_is_labelled_by_the_spans_open_on_every_thread():
    red = span_idle.reduce_events(OPS, WINDOW, THREADS)
    # idle (0,1), (2,4), (5,10): 8 s, of which 7-10 has no span open
    assert red["idle_s"] == pytest.approx(8.0)
    assert red["idle_unattributed_s"] == pytest.approx(3.0)
    got = {k: pytest.approx(v) for k, v in red["idle_by_span"]}
    assert got == {"fleet.knn|queue.slot_wait": 1.5,
                   "fleet.readback|queue.gather": 1.0,
                   "fleet.knn": 1.0,
                   "queue.gather": 0.5,
                   "fleet.enqueue|queue.gather": 0.5,
                   "fleet.readback|queue.slot_wait": 0.5}
    times = [t for _, t in red["idle_by_span"]]
    assert times == sorted(times, reverse=True)
    assert sum(times) + red["idle_unattributed_s"] == \
        pytest.approx(red["idle_s"])
    # what trace_reduce reads of the same events is what it read before
    base = trace_reduce.reduce_events(OPS, WINDOW)
    assert base["busy_s"] == pytest.approx(2.0)
    assert red["idle_s"] == pytest.approx(base["window_s"] - base["busy_s"])


def test_one_label_per_span_name_however_many_threads_hold_it():
    threads = {"a": [("repro.fleet.readback", 0.0, 10.0)],
               "b": [("repro.fleet.readback", 0.0, 10.0)]}
    red = span_idle.reduce_events(OPS, WINDOW, threads)
    assert red["idle_by_span"] == [["fleet.readback", pytest.approx(8.0)]]
    assert red["idle_unattributed_s"] == 0.0


def test_no_program_spans_read_nothing():
    red = span_idle.reduce_events(OPS, WINDOW, {})
    assert red["idle_by_span"] == [] and red["idle_unattributed_s"] is None
    assert span_idle.reduce_events(OPS, {}, THREADS) is None
    assert span_idle.reduce_events({}, WINDOW, THREADS) is None


RECORDED = (Path(__file__).resolve().parent / "data"
            / "bulk_quarter_second.xplane.pb.xz")


def test_recorded_trace_has_no_program_span(tmp_path):
    """The trace recorded on the chip before the program had spans: the
    idle it attributes is all of the idle that trace_reduce reads."""
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(lzma.decompress(RECORDED.read_bytes()))
    ops, spans = trace_reduce.read_xplane(str(path))
    threads = span_idle.read_threads(str(path))
    assert threads == {}
    red = span_idle.reduce_events(ops, spans, threads)
    base = trace_reduce.reduce_xplane(str(path))
    assert base["busy_s"] == pytest.approx(0.03939496699999965)
    assert red["idle_s"] == pytest.approx(base["window_s"] - base["busy_s"])
    assert red["idle_unattributed_s"] is None


def test_breakdown_of_a_tiny_run(tmp_path, capsys):
    bd = chipbench_tiny.tiny_repo(tmp_path)
    rc = breakdown.main(["--workload", "revgeo-10m.bulk",
                         "--seed", str(2 ** 31 + 9), "--seconds", "1"],
                        repo=tmp_path, bench_dir=bd, platforms=("cpu",))
    assert rc == 0
    out = capsys.readouterr()
    res = json.loads(out.out.strip().splitlines()[-1])
    assert res["device"]["platform"] == "cpu"
    assert res["failed"] == 0 and res["batches"] > 0
    per = res["per_batch"]
    # two tiles, each some query's primary in nearly every batch
    assert 1 <= per["partition_calls"] <= 4
    assert per["enqueue_ms"] > 0 and per["readback_ms"] > 0
    assert per["queue_wait_ms"] > 0
    assert per["enqueue_ms"] + per["readback_ms"] <= per["fleet_ms"]
    spans = res["spans"]
    assert spans["repro.fleet.knn"]["count"] == res["batches"]
    assert res["counters"]["repro.fleet.partition_calls"] \
        == spans["repro.fleet.enqueue"]["count"]
    assert "repro.fleet.knn" in out.err
    assert res["trace"] is None         # no device plane in a CPU trace


def test_pinned_engine_shows_as_pinned_in_the_log():
    from repro.core import caps, rtree, select_vector, traversal
    rng = np.random.default_rng(5)
    pts = rng.random((2000, 2)).astype(np.float32)
    tree = rtree.build_rtree(np.concatenate([pts, pts], axis=1), fanout=16)
    full = caps.select_frontier_caps(tree, 4096)
    esc = traversal.make_escalating_engine(
        lambda c: select_vector.make_select_bfs(tree, caps=c,
                                                result_cap=4096),
        (1,) * len(full), full, stick_after=1)
    overflows = drive.Overflows()
    get = overflows.wrap(lambda op, pi, **kw: esc)
    q = np.array([[0.2, 0.2, 0.6, 0.6]], np.float32)
    for _ in range(3):
        get("select", 0)(q)
    assert overflows.tiers() == "p0:1S"
