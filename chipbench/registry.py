"""Find a cell's parts by name.  ``BENCHMARK.json`` names the cells and
metrics; a configuration's file is the one its entry names; a traffic mix
is ``traffic/<mix>.json``; the reader of per-layer metric ``<name>`` is
``metrics/<name>.py`` or, for a metric split by cell group as
``<quantity>.<group>``, ``metrics/<quantity>.py``.  Adding a cell, mix,
configuration or metric adds files and entries and edits none.  A cell
that waits for a measurement on the chip sits in ``held.json``, in
``BENCHMARK.json``'s own form, and runs by name like any other."""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from typing import Callable, Dict, List

BENCH_DIR = Path(__file__).resolve().parent
REPO = BENCH_DIR.parent


def benchmark(repo: Path = REPO, held: bool = False,
              bench_dir: Path = BENCH_DIR) -> Dict:
    """``BENCHMARK.json``; with ``held``, plus the entries of
    ``<bench_dir>/held.json`` where there is one: cells defined and tested
    on the CPU that wait for a measurement on the chip before they join
    the benchmark."""
    with open(repo / "BENCHMARK.json") as f:
        bench = json.load(f)
    path = bench_dir / "held.json"
    if held and path.exists():
        with open(path) as f:
            for kind, entries in json.load(f).items():
                bench[kind] = bench[kind] + entries
    return bench


def _named(entries: List[Dict], name: str, what: str) -> Dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json "
                   f"(have: {', '.join(e['name'] for e in entries)})")


def cell(bench: Dict, name: str) -> Dict:
    return _named(bench["workloads"], name, "workload")


def config(bench: Dict, name: str, repo: Path = REPO) -> Dict:
    with open(repo / _named(bench["configs"], name, "config")["file"]) as f:
        return json.load(f)


def mix(name: str, bench_dir: Path = BENCH_DIR) -> Dict:
    with open(bench_dir / "traffic" / f"{name}.json") as f:
        return json.load(f)


def metrics_of(bench: Dict, kind: str, cell_name: str) -> List[Dict]:
    """The ``end_to_end`` or ``per_layer`` metrics that a cell reports: those
    whose ``workloads`` list names it, and those with no such list."""
    return [m for m in bench[kind]
            if cell_name in m.get("workloads", [cell_name])]


def reader(metric: str, bench_dir: Path = BENCH_DIR) -> Callable:
    """The ``read(ctx)`` function of a per-layer metric's reader."""
    d = bench_dir / "metrics"
    path = d / f"{metric}.py"
    if not path.exists():
        path = d / f"{metric.split('.', 1)[0]}.py"
    if not path.exists():
        raise KeyError(f"no reader for metric {metric!r} under {d}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{path.stem.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
