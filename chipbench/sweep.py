"""Find the knee of an open-loop cell on the chip: the highest offered rate
whose p99 latency stays within a limit with no growing backlog.

    python3 chipbench/sweep.py --workload mapsearch-10m.viewports \\
        --rates 25 50 100 200 400 --seconds 8 --limit-ms 100

One process builds and warms the cell's fleet once, then runs one window
per rate, in the order given (ascending), each on a seed of its own,
and stops at the first rate over the limit.  One line per
rate: p50 and p99 in ms, and the backlog ratio, the median latency of the
window's last quarter of requests over that of its first quarter (well
above 1 when the queue grows through the window).  The cell's own rate is
fixed in its traffic file; this script only reads it.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import gen, registry, run  # noqa: E402

BACKLOG = 2.0       # last-quarter over first-quarter median: a growing queue


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--limit-ms", type=float, default=100.0)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)
    bench = registry.benchmark(held=True)
    cell = registry.cell(bench, args.workload)
    cfg = registry.config(bench, cell["config"])
    mix = registry.mix(cell["traffic"])
    if mix["arrivals"] != "open":
        sys.exit("sweep: only an open-loop cell has a knee")
    sys.path.insert(0, str(registry.REPO / "src"))
    try:
        run.gate(cell["chips"])
    except run.Gate as exc:
        sys.exit(f"sweep: {exc}")
    _, _, _, _, queue = run.setup(cfg, mix, args.seed, run.WARMUP_S)
    knee = None
    for i, rate in enumerate(args.rates):
        m = dict(mix, rate_per_s=rate)
        w = run.Traffic(queue, m, args.seconds, args.seed + 1 + i,
                        gen.WINDOW)
        w.start(time.perf_counter())
        sent = w.finish()
        lat = np.array([r.done - r.due if r.ok else np.inf for r in sent])
        q = max(len(lat) // 4, 1)
        backlog = float(np.median(lat[-q:]) / np.median(lat[:q]))
        p50, p99 = (1e3 * run.nearest_rank(lat, p) for p in (0.5, 0.99))
        ok = p99 <= args.limit_ms and backlog < BACKLOG
        print(json.dumps({"rate_per_s": rate, "requests": len(sent),
                          "p50_ms": p50, "p99_ms": p99, "backlog": backlog,
                          "within_limit": ok}), flush=True)
        if not ok:
            break
        knee = rate
    queue.close()
    print(json.dumps({"knee_per_s": knee, "limit_ms": args.limit_ms}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
