"""Where a cell's time goes inside the program, from the service's own spans
and counters (``repro.runtime.trace``) and a profiler trace.

    python3 chipbench/breakdown.py --workload <cell> --seed <n> --seconds <s>

From the repo root, on the chip only (exit 3 elsewhere, as ``run.py``).
Set-up is ``run.py``'s; then ``--seconds`` of the cell's traffic, whose
first ``run.TRACE_S`` seconds are traced.  The program's aggregates are
differenced across the window, at the two points where the queue's summary
is read.  stderr shows each span's count and its total and self ms per
coalesced batch, then each counter per batch; stdout one JSON line:

  ``spans``, ``counters``, ``times``  the window's differences
  ``batches``, ``rows_per_s``        the queue's batches, rows resolved
  ``failed``                         requests of the window that failed
  ``per_batch``     partition calls, enqueue ms, readback ms and the
                    operator's fleet span ms per batch; the mean queue
                    wait per request in ms; the benchmark's own
                    ``chipbench.dispatch`` span in ms per call
  ``trace``         busy and idle seconds of the traced window,
                    ``idle_by_span`` and ``idle_unattributed_s``
                    (``span_idle.py``) and its share of the idle
                    seconds, and the ten longest idle gaps
"""
from __future__ import annotations

import argparse
import glob
import json
import sys
import tempfile
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from chipbench import gen, registry, run, span_idle, trace_reduce  # noqa: E402


def delta(after, before):
    """``after - before`` of two trace snapshots, leaf by leaf."""
    if isinstance(after, dict):
        return {k: delta(v, before.get(k, 0 if not isinstance(v, dict)
                                       else {}))
                for k, v in after.items()}
    return after - before


def table(diff, batches: int) -> None:
    run.log(f"{'span':32s} {'count':>8s} {'total ms':>10s} {'self ms':>10s}"
            f"   per batch of {batches}")
    for name, s in sorted(diff["spans"].items(),
                          key=lambda kv: -kv[1]["total_s"]):
        run.log(f"{name:32s} {s['count'] / batches:8.2f} "
                f"{1e3 * s['total_s'] / batches:10.3f} "
                f"{1e3 * s['self_s'] / batches:10.3f}")
    for name, n in sorted(diff["counters"].items()):
        run.log(f"{name:32s} {n / batches:8.2f}")
    for name, t in sorted(diff["times"].items()):
        if t["count"]:
            run.log(f"{name:32s} mean {1e3 * t['total_s'] / t['count']:.3f}"
                    f" ms over {t['count']}")


def main(argv=None, repo: Path = REPO, bench_dir: Path = registry.BENCH_DIR,
         platforms=("tpu",)) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    bench = registry.benchmark(repo, held=True, bench_dir=bench_dir)
    cell = registry.cell(bench, args.workload)
    cfg = registry.config(bench, cell["config"], repo)
    mix = registry.mix(cell["traffic"], bench_dir)
    src = str(REPO / "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    try:
        devs = run.gate(cell["chips"], platforms)
    except run.Gate as exc:
        run.log(f"chipbench: {exc}; this breakdown runs on the chip only")
        return 3

    import jax
    from repro.runtime import trace
    pts, shards, spans, overflows, queue = run.setup(
        cfg, mix, args.seed, min(run.WARMUP_S, args.seconds))
    window = run.Traffic(queue, mix, args.seconds, args.seed, gen.WINDOW)
    q0, p0 = queue.summary, trace.snapshot()
    tracedir = tempfile.TemporaryDirectory(prefix="chipbench-breakdown-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(tracedir.name, profiler_options=opts)
    t0 = time.perf_counter()
    window.start(t0)
    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
        time.sleep(min(run.TRACE_S, args.seconds))
    jax.profiler.stop_trace()
    sent = window.finish()
    q1, p1 = queue.summary, trace.snapshot()
    queue.close()

    diff = delta(p1, p0)
    batches = q1.get("batches", 0) - q0.get("batches", 0)
    method, _ = run.fleet_call(shards, cfg)
    out = {"device": {"platform": devs[0].platform,
                      "kind": devs[0].device_kind, "count": len(devs)},
           "batches": batches,
           "failed": sum(not r.ok for r in sent),
           "rows_per_s": run.end_to_end(mix, sent, t0, window.t1,
                                        0.0)["rows_per_s"],
           "spans": diff["spans"], "counters": diff["counters"],
           "times": diff["times"], "per_batch": {}, "trace": None}
    if batches:
        table(diff, batches)
        s, c = diff["spans"], diff["counters"]
        wait = diff["times"].get("repro.queue.wait", {"count": 0})
        calls = spans.durations(t0, window.t1)
        out["per_batch"] = {
            "partition_calls": c.get("repro.fleet.partition_calls", 0)
            / batches,
            "enqueue_ms": 1e3 * s.get("repro.fleet.enqueue",
                                      {"total_s": 0})["total_s"] / batches,
            "readback_ms": 1e3 * s.get("repro.fleet.readback",
                                       {"total_s": 0})["total_s"] / batches,
            "fleet_ms": 1e3 * s.get(f"repro.fleet.{method}",
                                    {"total_s": 0})["total_s"] / batches,
            "queue_wait_ms": 1e3 * wait["total_s"] / wait["count"]
            if wait["count"] else None,
            "dispatch_ms": 1e3 * sum(calls) / len(calls) if calls else None,
        }
    paths = glob.glob(f"{tracedir.name}/**/*.xplane.pb", recursive=True)
    if paths:
        device_ops, host_spans = trace_reduce.read_xplane(paths[0])
        red = trace_reduce.reduce_events(device_ops, host_spans)
        idle = span_idle.reduce_events(device_ops, host_spans,
                                       span_idle.read_threads(paths[0]))
        if red and idle:
            none = idle["idle_unattributed_s"]
            out["trace"] = {"busy_s": red["busy_s"],
                            "window_s": red["window_s"],
                            "idle_share": red["idle_share"],
                            "idle_gaps": red["idle_gaps"], **idle,
                            "idle_unattributed_share":
                            None if none is None or not idle["idle_s"]
                            else none / idle["idle_s"]}
    tracedir.cleanup()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
