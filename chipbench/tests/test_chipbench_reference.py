"""The plain reference against brute force, and against the served path
at a tiny size on the CPU; its bfloat16 control comes out not correct."""
import numpy as np
import pytest

import chipbench_tiny
from chipbench import check, control, gen, registry
from chipbench.reference import Reference

SEED = 2 ** 31 + 21


@pytest.fixture(scope="module")
def pts():
    return gen.points({"distribution": "uniform", "n_points": 5000}, SEED)


def brute_select(pts, r):
    m = ((pts[:, 0] >= r[0]) & (pts[:, 0] <= r[2])
         & (pts[:, 1] >= r[1]) & (pts[:, 1] <= r[3]))
    return np.nonzero(m)[0]


def test_select_matches_brute_force(pts):
    ref = Reference(pts)
    _, rows = gen.open_schedule(dict(registry.mix("viewports"),
                                     tile_selectivity_log10=[-3, -1]),
                                1.0, SEED)
    for q in rows[:20]:
        for got, r in zip(ref.select(q), q):
            np.testing.assert_array_equal(got, brute_select(pts, r))
    # a window edge on a point's coordinate includes it (closed intervals)
    p = pts[7]
    got = ref.select(np.array([[p[0], p[1], p[0], p[1]]], np.float32))[0]
    assert 7 in got


def test_nearest_matches_brute_force_with_ties(pts):
    dup = np.concatenate([pts, pts[:50]])       # ids 5000.. tie ids 0..49
    ref = Reference(dup)
    q = np.concatenate([gen.rng(SEED, 9).random((200, 2), dtype=np.float32),
                        pts[:5] + np.float32(1e-4)])
    d, tied = ref.nearest(q)
    dx = np.abs(dup[None, :, 0] - q[:, None, 0])
    dy = np.abs(dup[None, :, 1] - q[:, None, 1])
    full = dx * dx + dy * dy
    np.testing.assert_array_equal(d, full.min(axis=1))
    for i in range(len(q)):
        np.testing.assert_array_equal(
            tied[i], np.nonzero(full[i] == full[i].min())[0])
    assert all(len(tied[-1 - i]) == 2 for i in range(5))


@pytest.mark.parametrize("op", ["select", "knn"])
def test_reference_agrees_with_the_served_path(pts, op):
    """The fleet the harness builds, called as the queue calls it."""
    from chipbench import run
    cfg = dict(registry.config(registry.benchmark(held=True),
                               "mapsearch-10m" if op == "select"
                               else "revgeo-10m"), **chipbench_tiny.CUT)
    shards = run.fleet(cfg, pts)
    method, kw = run.fleet_call(shards, cfg)
    mix = registry.mix("viewports" if op == "select" else "bulk")
    if op == "select":
        mix = dict(mix, tile_selectivity_log10=[-3, -2])
        reqs = list(gen.open_schedule(mix, 0.3, SEED)[1][:6])
    else:
        make = gen.closed_requests(mix, SEED)
        reqs = [make(i)[:8] for i in range(6)]
    answers = []
    for q in reqs:
        out = getattr(shards, method)(q, **kw)
        answers.append(out)
    bad, total = check.mismatched_rows(op, reqs, answers, Reference(pts))
    assert (bad, total) == (0, sum(len(q) for q in reqs))
    # one altered answer is caught
    if op == "select":
        answers[0][0] = np.append(answers[0][0], 4999)
    else:
        answers[0][0][0, 0] = (answers[0][0][0, 0] + 1) % len(pts)
    assert check.mismatched_rows(op, reqs, answers, Reference(pts))[0] == 1


@pytest.mark.parametrize("cell", ["mapsearch-10m", "revgeo-10m"])
def test_bfloat16_control_is_not_correct(cell):
    bench = registry.benchmark(held=True)
    cfg = dict(registry.config(bench, cell), n_points=20000)
    w = next(w for w in bench["workloads"] if w["config"] == cell)
    mix = dict(registry.mix(w["traffic"]))
    if mix["arrivals"] == "open":
        mix["rate_per_s"] = 20
    r = control.reading(cfg, mix, SEED, 1.0, 512)
    assert r["compared_rows"] > 0
    assert r["mismatched_rows"] > r["compared_rows"] // 4
    assert r["correct"] is False


def test_wider_knn_answer_is_refused(pts):
    """The nearest-point comparison reads k=1 answers only: a k=8 answer is
    refused rather than checked on its first column alone."""
    q = pts[:4]
    ids = np.zeros((4, 8), np.int64)
    with pytest.raises(ValueError, match="k=1"):
        check.mismatched_rows("knn", [q], [(ids, np.zeros((4, 8)), False)],
                              Reference(pts))
