"""Benchmark orchestrator: one module per paper table/figure + the
framework-level benches.  ``python -m benchmarks.run [--full] [--only X]``.

Default sizes are CPU-container-friendly (1M points select / 100k join);
``--full`` uses the paper's 10M.  Results echo as aligned rows and land in
``runs/bench/*.csv``.
"""
from __future__ import annotations

import argparse
import os
import time


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale sizes (10M points)")
    ap.add_argument("--quick", action="store_true",
                    help="tiny sizes (CI smoke)")
    ap.add_argument("--only", default=None,
                    help="comma list: select,sweeps,join,knn,knn-join,"
                         "fused,quant,caps,browse,service")
    ap.add_argument("--out-dir", default="runs/bench")
    args = ap.parse_args(argv)

    from repro.launch import compile_cache
    compile_cache.enable()
    n_sel = 10_000_000 if args.full else (100_000 if args.quick
                                          else 1_000_000)
    # join wall-clock on CPU is dominated by padded frontier compaction
    # once caps grow past the true pair count - 50k keeps the caps honest
    n_join = 1_000_000 if args.full else (20_000 if args.quick
                                          else 50_000)
    n_service = 2_000_000 if args.full else (50_000 if args.quick
                                             else 500_000)
    only = set(args.only.split(",")) if args.only else None
    os.makedirs(args.out_dir, exist_ok=True)
    all_rows = []

    def want(name):
        return only is None or name in only

    t0 = time.time()
    if want("select"):
        from . import bench_select
        print(f"[select / Fig7]  n={n_sel}")
        all_rows.append(bench_select.run(n=n_sel))
    if want("sweeps"):
        from . import bench_select_sweeps
        print(f"[select sweeps / Fig9,10a,10b]  n={n_sel}")
        fanouts = (16, 64, 256, 1024) if not args.full else \
            (16, 32, 64, 128, 256, 512, 1024)
        all_rows.append(bench_select_sweeps.run_fanout(n=n_sel,
                                                       fanouts=fanouts))
        all_rows.append(bench_select_sweeps.run_selectivity(n=n_sel))
    if want("join"):
        from . import bench_join
        print(f"[join / Fig11]  n={n_join}")
        all_rows.append(bench_join.run(n=n_join,
                                       scalar=not args.full))
        print("[join fanout / Fig10c,12]")
        all_rows.append(bench_join.run_fanout(
            n=n_join, fanouts=(16, 64, 256) if not args.full else
            (16, 32, 64, 128, 256, 512)))
    if want("knn"):
        from . import bench_knn
        print(f"[knn sweep]  n={n_sel}")
        all_rows.append(bench_knn.run(n=n_sel,
                                      ks=(1, 8) if args.quick else (1, 8, 64)))
    if want("knn-join"):
        from . import bench_knn_join
        print(f"[knn-join sweep]  n={n_sel}")
        all_rows.append(bench_knn_join.run(
            n=n_sel, ks=(1, 8) if args.quick else (1, 8, 64)))
    if want("fused"):
        from . import bench_fused
        n_fused = 20_000 if args.quick else (1_000_000 if args.full
                                             else 200_000)
        print(f"[fused vs unfused dispatches]  n={n_fused}")
        rows, _ = bench_fused.run(
            n=n_fused, out_json=os.path.join(args.out_dir,
                                             "BENCH_fused.json"))
        all_rows.append(rows)
    if want("quant"):
        from . import bench_quant
        n_quant = 20_000 if args.quick else (2_000_000 if args.full
                                             else 500_000)
        print(f"[quantized D3 layout: bytes/node + latency]  n={n_quant}")
        rows, _ = bench_quant.run(
            n=n_quant, capacity_mult=5 if args.full else 4,
            out_json=os.path.join(args.out_dir, "BENCH_quant.json"))
        all_rows.append(rows)
    if want("caps"):
        from . import bench_caps
        n_caps = 20_000 if args.quick else (2_000_000 if args.full
                                            else 500_000)
        print(f"[adaptive frontier caps: small-frontier latency + "
              f"occupancy]  n={n_caps}")
        rows, _ = bench_caps.run(
            n=n_caps, out_json=os.path.join(args.out_dir,
                                            "BENCH_caps.json"))
        all_rows.append(rows)
    if want("browse"):
        from . import bench_browse
        n_browse = 20_000 if args.quick else (1_000_000 if args.full
                                              else 200_000)
        print(f"[browse vs fixed-k restarts]  n={n_browse}")
        rows, _ = bench_browse.run(
            n=n_browse, out_json=os.path.join(args.out_dir,
                                              "BENCH_browse.json"))
        all_rows.append(rows)
    if want("service"):
        from . import bench_service
        print(f"[spatial service]  n={n_service}")
        all_rows.append(bench_service.run(n=n_service))
        print(f"[spatial service sharded: host fan-out vs mesh SPMD]  "
              f"n={n_service // 4}")
        all_rows.append(bench_service.run_sharded(n=n_service // 4))

    for rows in all_rows:
        path = os.path.join(args.out_dir, rows.name + ".csv")
        with open(path, "w") as f:
            f.write(rows.csv() + "\n")
        print(f"wrote {path}")
    print(f"total {time.time() - t0:.1f}s")


if __name__ == "__main__":
    main()
