"""The traffic generator and the load loops: open-loop schedules from a
seed, latency from due time with failures counted as missing, and the
closed loop's row count."""
import concurrent.futures as cf
import threading
import time

import numpy as np
import pytest

from chipbench import drive, gen, registry, run

VIEWPORTS = registry.mix("viewports")
BULK = registry.mix("bulk")
SEED = 2 ** 31 + 11


def test_open_schedule_is_a_function_of_the_seed():
    due_a, rows_a = gen.open_schedule(VIEWPORTS, 5.0, SEED)
    due_b, rows_b = gen.open_schedule(VIEWPORTS, 5.0, SEED)
    np.testing.assert_array_equal(due_a, due_b)
    np.testing.assert_array_equal(rows_a, rows_b)
    due_c, rows_c = gen.open_schedule(VIEWPORTS, 5.0, SEED + 1)
    assert not np.array_equal(rows_a, rows_c)


def test_open_schedule_shape_and_span():
    due, rows = gen.open_schedule(VIEWPORTS, 4.0, SEED)
    n = round(VIEWPORTS["rate_per_s"] * 4.0)
    assert due.shape == (n,)
    assert rows.shape == (n, gen.rows_per_request(VIEWPORTS), 4)
    assert rows.dtype == np.float32
    assert np.all(np.diff(due) > 0) and due[0] > 0
    assert due[-1] == pytest.approx(4.0)


def test_seeds_share_gaps_and_tile_sizes_in_another_order():
    """Every seed sends the same work: the same gaps and tile sizes."""
    def parts(seed):
        due, rows = gen.open_schedule(VIEWPORTS, 4.0, seed)
        gaps = np.diff(np.concatenate([[0.0], due]))
        side = rows[:, 0, 2] - rows[:, 0, 0]
        return gaps, side
    ga, sa = parts(SEED)
    gb, sb = parts(7)
    assert not np.array_equal(ga, gb)
    np.testing.assert_allclose(np.sort(ga), np.sort(gb), rtol=1e-9)
    # tile sides read back from float32 corners: equal to an ulp of 1
    np.testing.assert_allclose(np.sort(sa), np.sort(sb), rtol=0, atol=1.2e-7)


def test_viewport_tiles_are_adjacent_and_in_range():
    mix = dict(VIEWPORTS, tiles=[4, 4], tile_selectivity_log10=[-6, -4])
    _, rows = gen.open_schedule(mix, 2.0, SEED)
    r = rows.reshape(len(rows), 4, 4, 4)        # (req, i, j, rect)
    np.testing.assert_array_equal(r[:, 1:, :, 0], r[:, :-1, :, 2])
    np.testing.assert_array_equal(r[:, :, 1:, 1], r[:, :, :-1, 3])
    side = r[:, 0, 0, 2] - r[:, 0, 0, 0]
    assert side.min() > 0.9e-3 and side.max() < 1.1e-2
    assert rows.min() >= 0 and rows.max() <= 1


def test_closed_requests_are_drawn_per_index():
    make = gen.closed_requests(BULK, SEED)
    a, b = make(3), make(3)
    np.testing.assert_array_equal(a, b)
    assert a.shape == (64, 2) and not np.array_equal(a, make(4))


def test_points_from_a_large_seed():
    cfg = {"distribution": "uniform", "n_points": 1000}
    p = gen.points(cfg, 2 ** 31 + 3)
    np.testing.assert_array_equal(p, gen.points(cfg, 2 ** 31 + 3))
    assert p.shape == (1000, 2) and p.dtype == np.float32


def _sent(latencies_ms, failed=()):
    out = []
    for i, ms in enumerate(latencies_ms):
        r = drive.Sent(rows=np.zeros((16, 4), np.float32), due=10.0 + i)
        r.sent = r.due
        if i in failed:
            r.done, r.error = r.due, RuntimeError("boom")
        else:
            r.done = r.due + ms * 1e-3
        out.append(r)
    return out


def test_latency_is_taken_from_due_time():
    e2e = run.end_to_end(VIEWPORTS, _sent(range(1, 101)), 0.0, 200.0, 5.0)
    assert e2e["p50_ms"] == pytest.approx(50.0)
    assert e2e["p99_ms"] == pytest.approx(99.0)
    assert e2e["setup_s"] == 5.0


def test_failed_requests_count_as_missing():
    """A failure is slower than any answer: it moves the percentiles up, and
    with two failures in 100 the p99 is missing."""
    e2e = run.end_to_end(VIEWPORTS, _sent(range(1, 101), failed={0}),
                         0.0, 200.0, 5.0)
    assert e2e["p50_ms"] == pytest.approx(51.0)
    assert e2e["p99_ms"] == pytest.approx(100.0)
    e2e = run.end_to_end(VIEWPORTS, _sent(range(1, 101), failed={0, 1}),
                         0.0, 200.0, 5.0)
    assert e2e["p99_ms"] is None


class FakeQueue:
    """Answers each request after ``delay`` seconds on a worker pool, and
    records the most requests it ever held at once."""

    def __init__(self, delay=0.002):
        self.pool = cf.ThreadPoolExecutor(4)
        self.delay, self.held, self.most = delay, 0, 0
        self.lock = threading.Lock()

    def submit(self, rows):
        with self.lock:
            self.held += 1
            self.most = max(self.most, self.held)

        def answer():
            time.sleep(self.delay)
            with self.lock:
                self.held -= 1
            return len(rows)
        return self.pool.submit(answer)


def test_closed_loop_keeps_one_request_per_client_and_counts_rows():
    q = FakeQueue()
    loop = drive.ClosedLoop(q, gen.closed_requests(BULK, SEED), clients=3)
    t0 = time.perf_counter()
    loop.start()
    time.sleep(0.3)
    loop.stop()
    t1 = time.perf_counter()
    drive.wait(loop.sent, time.perf_counter() + 5)
    q.pool.shutdown()
    assert q.most <= 3
    assert all(r.ok for r in loop.sent) and len(loop.sent) > 30
    e2e = run.end_to_end(BULK, loop.sent, t0, t1, 1.0)
    in_window = [r for r in loop.sent if t0 <= r.done <= t1]
    assert e2e["rows_per_s"] == pytest.approx(
        64 * len(in_window) / (t1 - t0))
    assert [r.answer for r in loop.sent] == [64] * len(loop.sent)


def test_closed_loop_with_a_limit_sends_exactly_that_many_requests():
    """The warm-up's closed loop: a fixed amount of work, whatever the
    system's speed, and each client stops once the limit is sent."""
    q = FakeQueue(delay=0.0005)
    loop = drive.ClosedLoop(q, gen.closed_requests(BULK, SEED), clients=3,
                            limit=40)
    loop.start()
    deadline = time.perf_counter() + 10
    while len(loop.sent) < 40 and time.perf_counter() < deadline:
        time.sleep(0.005)
    drive.wait(loop.sent, time.perf_counter() + 5)
    time.sleep(0.05)
    q.pool.shutdown()
    assert len(loop.sent) == 40 and all(r.ok for r in loop.sent)
    assert q.held == 0
