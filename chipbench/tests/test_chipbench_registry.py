"""Cells, configurations, mixes and metric readers are found by name from
files; a new one is added by adding files and entries alone.  Also checks
``BENCHMARK.json`` against the shape its runner relies on."""
import json
import re
import shutil

import pytest

from chipbench import registry

BENCH = registry.benchmark()
WITH_HELD = registry.benchmark(held=True)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.mark.parametrize("cell",
                         [w["name"] for w in WITH_HELD["workloads"]])
def test_every_cell_finds_its_parts(cell):
    w = registry.cell(WITH_HELD, cell)
    cfg = registry.config(WITH_HELD, w["config"])
    mix = registry.mix(w["traffic"])
    assert cfg["name"] == w["config"]
    assert mix["arrivals"] in ("open", "closed")
    e2e = [m["name"]
           for m in registry.metrics_of(WITH_HELD, "end_to_end", cell)]
    assert "setup_s" in e2e and len(e2e) >= 2
    layer = registry.metrics_of(WITH_HELD, "per_layer", cell)
    assert layer
    for m in layer:
        assert callable(registry.reader(m["name"]))
        assert m["moves"] in e2e


def test_unknown_names_are_refused():
    with pytest.raises(KeyError):
        registry.cell(BENCH, "no-such-cell")
    with pytest.raises(KeyError):
        registry.config(BENCH, "no-such-config")
    with pytest.raises(KeyError):
        registry.reader("no_such_metric.open")


def test_a_new_config_mix_and_metric_need_only_files(tmp_path):
    """Add a deployment, a mix, a cell and a metric split by group without
    editing a file that is there."""
    bd = tmp_path / "chipbench"
    shutil.copytree(registry.BENCH_DIR / "metrics", bd / "metrics")
    shutil.copytree(registry.BENCH_DIR / "traffic", bd / "traffic")
    (bd / "configs").mkdir()
    cfg = registry.config(BENCH, "revgeo-10m")
    cfg.update(name="revgeo-20m", n_points=20_000_000)
    (bd / "configs" / "revgeo-20m.json").write_text(json.dumps(cfg))
    mix = dict(registry.mix("bulk"), clients=32)
    (bd / "traffic" / "bulk32.json").write_text(json.dumps(mix))
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append(dict(bench["configs"][-1], name="revgeo-20m",
                                 file="chipbench/configs/revgeo-20m.json"))
    bench["workloads"].append({"name": "revgeo-20m.bulk32",
                               "config": "revgeo-20m", "traffic": "bulk32",
                               "chips": 1, "why": "a test cell"})
    for m in bench["per_layer"]:
        if m["name"] == "rows_per_dispatch.bulk":
            m["workloads"].append("revgeo-20m.bulk32")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    got = registry.benchmark(tmp_path)
    w = registry.cell(got, "revgeo-20m.bulk32")
    assert registry.config(got, w["config"], tmp_path)["n_points"] == 20_000_000
    assert registry.mix(w["traffic"], bd)["clients"] == 32
    names = [m["name"] for m in
             registry.metrics_of(got, "per_layer", "revgeo-20m.bulk32")]
    assert names == ["rows_per_dispatch.bulk"]
    # a metric split by a new group reads with its quantity's reader
    read = registry.reader("rows_per_dispatch.burst", bd)
    assert read({"queue": {"rows": 30, "batches": 3}}) == 10


def test_benchmark_json_shape():
    keys = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
    assert set(BENCH) == keys
    assert BENCH["paths"] == ["chipbench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    names = set()
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("chipbench/")
        cfg = registry.config(BENCH, c["name"])
        assert set(c["reduced"]) <= set(cfg)
        assert set(c["reduced"]) == set(cfg["reduced"])
        assert any(w["config"] == c["name"] for w in BENCH["workloads"])
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    for kind in ("end_to_end", "per_layer"):
        for m in BENCH[kind]:
            assert NAME.match(m["name"]) and UNIT.match(m["unit"])
            assert m["better"] in ("lower", "higher")
            assert m["name"] not in names
            names.add(m["name"])
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for x in BENCH["configs"] + BENCH["workloads"]:
        assert NAME.match(x["name"])


def test_held_cells_join_only_on_request():
    """A held cell (``held.json``) is not in the benchmark the driver reads,
    and is found, with its configuration and metrics, when asked for."""
    held = json.loads((registry.BENCH_DIR / "held.json").read_text())
    names = [w["name"] for w in held["workloads"]]
    assert names and not set(names) & {w["name"] for w in BENCH["workloads"]}
    for name in names:
        w = registry.cell(WITH_HELD, name)
        registry.config(WITH_HELD, w["config"])
        assert registry.metrics_of(WITH_HELD, "per_layer", name)
