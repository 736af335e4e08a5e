"""The trace reduction: interval arithmetic, the whole reduction on
made-up events, and on a short trace recorded on the chip."""
import lzma
from pathlib import Path

import pytest

from chipbench import trace_reduce as tr


def test_union_merges_overlaps_and_clips():
    got = tr.union([(0.5, 2.0), (1.0, 3.0), (4.0, 6.0), (-1.0, 0.2)],
                   0.0, 5.0)
    assert got == [(0.0, 0.2), (0.5, 3.0), (4.0, 5.0)]


def test_gaps_are_the_complement():
    busy = [(0.0, 0.2), (0.5, 3.0), (4.0, 5.0)]
    assert tr.gaps(busy, 0.0, 6.0) == [(0.2, 0.5), (3.0, 4.0), (5.0, 6.0)]
    assert tr.gaps([], 1.0, 2.0) == [(1.0, 2.0)]


def test_reduce_events_busy_idle_ops_and_gaps():
    ops = {"/device:TPU:0": [("fusion.1", 1.0, 2.0), ("fusion.1", 1.5, 2.5),
                             ("copy", 3.0, 3.5), ("outside", 20.0, 21.0)]}
    spans = {tr.WINDOW: [(0.0, 10.0)],
             "chipbench.dispatch": [(0.5, 3.6)],
             "chipbench.submit": [(5.0, 6.0)]}
    red = tr.reduce_events(ops, spans)
    assert red["window_s"] == 10.0
    assert red["busy_s"] == pytest.approx(2.0)
    assert red["idle_share"] == pytest.approx(0.8)
    assert red["device_ops"] == [["fusion.1", 2.0], ["copy", 0.5]]
    # gaps: (0,1) under dispatch, (2.5,3) under dispatch, (3.5,10) at 6.75
    assert red["idle_gaps"][0] == ["none", 6.5]
    assert sorted(g[0] for g in red["idle_gaps"]) == \
        ["dispatch", "dispatch", "none"]


def test_no_window_or_no_device_reads_nothing():
    ops = {"/device:TPU:0": [("a", 1.0, 2.0)]}
    assert tr.reduce_events(ops, {}) is None
    assert tr.reduce_events({}, {tr.WINDOW: [(0.0, 1.0)]}) is None


RECORDED = (Path(__file__).resolve().parent / "data"
            / "bulk_quarter_second.xplane.pb.xz")


def test_reduce_xplane_on_a_trace_recorded_on_the_chip(tmp_path):
    """A quarter second of ``revgeo-10m.bulk`` traced on one TPU v5e: the
    reduction finds the window, one device, the busy time inside it, the
    ops by their trace names and the idle gaps by host span."""
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(lzma.decompress(RECORDED.read_bytes()))
    red = tr.reduce_xplane(str(path))
    assert red is not None and red["devices"] == 1
    # what the traced run that recorded it printed as busy_s and window_s
    assert red["window_s"] == pytest.approx(0.25017289500000006)
    assert red["busy_s"] == pytest.approx(0.03939496699999965)
    assert red["idle_share"] == pytest.approx(
        1.0 - red["busy_s"] / red["window_s"])
    assert 0 < len(red["device_ops"]) <= tr.TOP
    assert all(t > 0 for _, t in red["device_ops"])
    gaps = [g for _, g in red["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True)
    assert sum(gaps) <= red["window_s"] - red["busy_s"] + 1e-9
    assert {n for n, _ in red["idle_gaps"]} <= {"dispatch", "submit", "none"}
