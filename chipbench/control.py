"""The control of ``correct``: the plain reference computed in bfloat16, the
precision below the configurations' float32, put in the program's place.
Its answers go through the same comparison as a run's (``check.py``), and
must come out as not correct: this shows the comparison can fail.

    python3 chipbench/control.py --workload <cell> --seeds 1 2 3 [--seconds 10]

For each seed: the cell's points, the requests a run of ``--seconds``
would send (an open loop's whole window; a closed loop's first requests),
the same seeded sample a run compares, and one JSON line with the
numbers compared.  It runs on the host; it needs no chip.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import check, gen, registry  # noqa: E402
from chipbench.reference import Reference  # noqa: E402


def answers(op: str, ref: Reference, rows: np.ndarray):
    """``ref``'s answer to one request, in the form the queue returns."""
    if op == "select":
        return ref.select(rows)
    d, tied = ref.nearest(rows[:, :2])
    return (np.array([[t[0]] for t in tied]), d[:, None].astype(np.float64),
            False)


def reading(cfg, mix, seed: int, seconds: float, compare_rows: int) -> dict:
    pts = gen.points(cfg, seed)
    per = gen.rows_per_request(mix)
    if mix["arrivals"] == "open":
        reqs = list(gen.open_schedule(mix, seconds, seed)[1])
    else:
        make = gen.closed_requests(mix, seed)
        reqs = [make(i) for i in range(max(compare_rows // per, 1))]
    pick = gen.rng(seed, gen.SAMPLE, 0).permutation(len(reqs))
    reqs = [reqs[i] for i in np.sort(pick[:max(compare_rows // per, 1)])]
    low = Reference(pts, "bfloat16")
    bad, total = check.mismatched_rows(
        cfg["op"], reqs, [answers(cfg["op"], low, r) for r in reqs],
        Reference(pts))
    return {"seed": seed, "mismatched_rows": bad, "compared_rows": total,
            "correct": check.passed(check.checks(
                {"mismatched_rows": bad, "compared_rows": total}))}


def main(argv=None) -> int:
    from chipbench import run
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    bench = registry.benchmark(held=True)
    cell = registry.cell(bench, args.workload)
    cfg = registry.config(bench, cell["config"])
    mix = registry.mix(cell["traffic"])
    for seed in args.seeds:
        print(json.dumps(reading(cfg, mix, seed, args.seconds,
                                 run.COMPARE_ROWS)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
