"""A tiny copy of the benchmark for CPU tests: the real BENCHMARK.json and
its held cells,
readers and mixes, with every configuration cut to a few thousand points,
two partitions and 8-row batches, and the mixes to light load."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

from chipbench import registry

CUT = {"n_points": 6000, "partitions": 2, "max_batch": 8, "result_cap": 256}
MIX_CUT = {"viewports": {"rate_per_s": 20},
           "bulk": {"clients": 2, "rows": 4}}


def tiny_repo(tmp: Path) -> Path:
    """Write the tiny benchmark under ``tmp``; returns its bench dir."""
    bench = registry.benchmark(held=True)
    bd = tmp / "chipbench"
    shutil.copytree(registry.BENCH_DIR / "metrics", bd / "metrics")
    shutil.copytree(registry.BENCH_DIR / "traffic", bd / "traffic")
    (bd / "configs").mkdir()
    for c in bench["configs"]:
        cfg = registry.config(bench, c["name"])
        cfg.update(CUT)
        (tmp / c["file"]).write_text(json.dumps(cfg))
    for name, cut in MIX_CUT.items():
        mix = registry.mix(name)
        mix.update(cut)
        (bd / "traffic" / f"{name}.json").write_text(json.dumps(mix))
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return bd


def run_tiny(tmp: Path, workload: str, trace: int = 0, seed: int = 2 ** 31 + 5,
             capsys=None) -> dict:
    """Run the harness on the CPU over the tiny benchmark; returns the
    parsed result line."""
    from chipbench import run
    bd = tiny_repo(tmp)
    rc = run.main(["--workload", workload, "--seed", str(seed),
                   "--seconds", "1", "--trace", str(trace)],
                  repo=tmp, bench_dir=bd, platforms=("cpu",))
    assert rc == 0
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])
