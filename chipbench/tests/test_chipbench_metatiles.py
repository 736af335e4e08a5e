"""The cell ``mapsearch-10m.metatiles`` through the whole harness at a tiny
size on the CPU: a sound run is correct and reports its metrics, and a run
whose timed path is broken underneath is not.  Also the reader of
``result_ms``, on made-up trace records."""
import dataclasses
import importlib.util

import numpy as np
import pytest

import chipbench_tiny
from chipbench import registry
from repro.distributed.spatial_shard import SpatialShards
from repro.runtime.trace import Record
from test_chipbench_run import _alter_one, _half_batch

CELL = "mapsearch-10m.metatiles"


def test_sound_run_is_correct(tmp_path, capsys):
    res = chipbench_tiny.run_tiny(tmp_path, CELL, capsys=capsys)
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"setup_s", "rows_per_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["checks"]["compared_rows"]["value"] > 0


def test_traced_run_reports_per_layer_metrics(tmp_path, capsys):
    res = chipbench_tiny.run_tiny(tmp_path, CELL, trace=1, capsys=capsys)
    assert res["correct"] is True, res["checks"]
    # no device plane in a CPU trace: the idle share is left out
    assert set(res["metrics"]) == {
        "rows_per_dispatch.metatiles", "dispatch_ms.metatiles",
        "lane_occupancy.metatiles", "result_ms.metatiles"}
    m = res["metrics"]
    assert 0 < m["lane_occupancy.metatiles"]["value"] <= 100
    assert 0 < m["result_ms.metatiles"]["value"] \
        < m["dispatch_ms.metatiles"]["value"]


@pytest.mark.parametrize("fault", [_alter_one, _half_batch],
                         ids=["answer_altered", "half_batch_left_out"])
def test_broken_timed_path_is_not_correct(fault, tmp_path, capsys,
                                          monkeypatch):
    monkeypatch.setattr(SpatialShards, "range_select",
                        fault(SpatialShards.range_select))
    res = chipbench_tiny.run_tiny(tmp_path, CELL, capsys=capsys)
    assert res["correct"] is False
    assert res["checks"]["mismatched_rows"]["value"] > 0


def test_overflowed_engine_call_is_not_correct(tmp_path, capsys,
                                               monkeypatch):
    engine_for = SpatialShards.engine_for

    def flagged(self, *a, **kw):
        fn = engine_for(self, *a, **kw)

        def call(*args, **k):
            out = fn(*args, **k)
            return out[:-1] + (dataclasses.replace(out[-1], overflow=1),)
        return call
    monkeypatch.setattr(SpatialShards, "engine_for", flagged)
    res = chipbench_tiny.run_tiny(tmp_path, CELL, capsys=capsys)
    assert res["correct"] is False
    assert res["checks"]["overflowed_calls"]["value"] > 0


# ---------------------------------------------------------------------------
# result_ms.read on made-up records
# ---------------------------------------------------------------------------

def _read():
    path = registry.BENCH_DIR / "metrics" / "result_ms.py"
    spec = importlib.util.spec_from_file_location("result_ms_test", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _rec(name, ms, batch=None, at=0.0):
    ids = {} if batch is None else {"batch": batch}
    return Record(name, at, at + ms * 1e-3, "t", 0, None, ids)


def _batch(bid, ids_ms, merge_ms):
    """One fleet call of batch ``bid``: an ids span per partition call, a
    merge span, and spans the reader leaves alone."""
    return ([_rec("repro.fleet.ids", m, bid) for m in ids_ms]
            + [_rec("repro.fleet.merge", merge_ms, bid),
               _rec("repro.fleet.readback", 50.0, bid),
               _rec("repro.queue.gather", 9.0, bid)])


def test_result_ms_reads_the_newest_window_batches():
    read = _read()
    warm = _batch(3, [100.0], 100.0)                  # older: not the window
    straight = [_rec("repro.fleet.ids", 70.0), _rec("repro.fleet.merge", 70.0)]
    window = _batch(10, [1.0, 2.0], 3.0) + _batch(12, [4.0], 2.0)
    idle = [_rec("repro.queue.gather", 1.0, 13)]      # a gather with no call
    records = warm + straight + window + idle + straight
    ctx = {"dispatch_s": [0.01, 0.02]}
    assert read(ctx, records) == pytest.approx((6.0 + 6.0) / 2)


def test_result_ms_uses_the_batches_left_in_the_ring():
    read = _read()
    # the window held four fleet calls; the ring kept the last two
    records = _batch(20, [1.0], 1.0) + _batch(21, [2.0], 2.0)
    assert read({"dispatch_s": [0.01] * 4}, records) == pytest.approx(3.0)


def test_result_ms_is_none_without_a_window_batch_or_an_ids_span():
    read = _read()
    straight = [_rec("repro.fleet.ids", 70.0), _rec("repro.fleet.merge", 70.0)]
    assert read({"dispatch_s": []}, _batch(5, [1.0], 1.0)) is None
    assert read({"dispatch_s": [0.01]}, straight) is None
    # a program with merge spans but no ids span of its own
    merge_only = [_rec("repro.fleet.merge", 4.0, 7)]
    assert read({"dispatch_s": [0.01]}, merge_only) is None
    assert read({"dispatch_s": [0.01]}, []) is None
    assert np.isfinite(read({"dispatch_s": [0.01]}, _batch(5, [1.0], 1.0)))
