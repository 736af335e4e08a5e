"""The whole harness at a tiny size on the CPU, past the platform gate:
sound runs come out correct, and a run whose timed path is broken
underneath comes out not correct, once for each fault a cell can have."""
import numpy as np
import pytest

import chipbench_tiny
from repro.distributed.spatial_shard import SpatialShards

CELLS = ["mapsearch-10m.viewports", "revgeo-10m.bulk"]


def _alter_one(method):
    """An answer altered where it is produced: row 0 of every batch."""
    def broken(self, batch, *a, **kw):
        out = method(self, batch, *a, **kw)
        if isinstance(out, list):
            out[0] = np.append(out[0], 0) if len(out[0]) == 0 else out[0][1:]
            return out
        ids, d, ovf = out
        ids = ids.copy()
        ids[0, 0] = ids[0, 0] + 1
        return ids, d, ovf
    return broken


def _half_batch(method):
    """Half of the batch left out: rows past the first half get nothing."""
    def broken(self, batch, *a, **kw):
        half = (len(batch) + 1) // 2
        out = method(self, batch[:half], *a, **kw)
        rest = len(batch) - half
        if isinstance(out, list):
            return out + [np.empty((0,), np.int64)] * rest
        ids, d, ovf = out
        return (np.concatenate([ids, np.full((rest, ids.shape[1]), -1)]),
                np.concatenate([d, np.full((rest, d.shape[1]), np.inf)]),
                ovf)
    return broken


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell, tmp_path, capsys):
    res = chipbench_tiny.run_tiny(tmp_path, cell, capsys=capsys)
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    want = {"setup_s", "p50_ms", "p99_ms"} if "viewports" in cell \
        else {"setup_s", "rows_per_s"}
    assert set(res["metrics"]) == want
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["device"]["platform"] == "cpu"
    assert list(res)[-1] == "checks"
    assert res["checks"]["compared_rows"]["value"] > 0


def test_traced_run_reports_per_layer_metrics(tmp_path, capsys):
    res = chipbench_tiny.run_tiny(tmp_path, "revgeo-10m.bulk", trace=1,
                                  capsys=capsys)
    assert res["correct"] is True
    # no device plane in a CPU trace: the idle share is left out
    assert set(res["metrics"]) == {"rows_per_dispatch.bulk",
                                   "dispatch_ms.bulk", "lane_occupancy.bulk"}
    assert 0 < res["metrics"]["lane_occupancy.bulk"]["value"] <= 100


@pytest.mark.parametrize("fault", [_alter_one, _half_batch],
                         ids=["answer_altered", "half_batch_left_out"])
@pytest.mark.parametrize("cell", CELLS)
def test_broken_timed_path_is_not_correct(cell, fault, tmp_path, capsys,
                                          monkeypatch):
    name = "range_select" if "viewports" in cell else "knn"
    monkeypatch.setattr(SpatialShards, name,
                        fault(getattr(SpatialShards, name)))
    res = chipbench_tiny.run_tiny(tmp_path, cell, capsys=capsys)
    assert res["correct"] is False
    assert res["checks"]["mismatched_rows"]["value"] > 0


@pytest.mark.parametrize("cell", CELLS)
def test_overflowed_engine_call_is_not_correct(cell, tmp_path, capsys,
                                               monkeypatch):
    """An engine that flags its answer as cut short by a cap fails the run,
    whether or not the cut rows fall in the compared sample."""
    import dataclasses
    engine_for = SpatialShards.engine_for

    def flagged(self, *a, **kw):
        fn = engine_for(self, *a, **kw)

        def call(*args, **k):
            out = fn(*args, **k)
            return out[:-1] + (dataclasses.replace(out[-1], overflow=1),)
        return call
    monkeypatch.setattr(SpatialShards, "engine_for", flagged)
    res = chipbench_tiny.run_tiny(tmp_path, cell, capsys=capsys)
    assert res["correct"] is False
    assert res["checks"]["overflowed_calls"]["value"] > 0
