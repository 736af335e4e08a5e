"""Run one benchmark cell on the chip and print its result line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the repo root.  Refuses any platform but a TPU (exit 3, no result).
Set-up (counted as ``setup_s``): the seeded points, the STR-packed fleet,
every pow2 bucket of the cell's operator compiled or read from the
persistent cache in ``<checkout>/.jax_cache``, the serving queue, the
cell's own traffic on another seed (a closed loop: ``WARMUP_BATCHES`` full
batches of requests; an open loop: ``WARMUP_S`` seconds), and every bucket
warmed once more.  The escalating engines pin themselves to their full tier
after three escalations in a row, and compile that tier lazily; the
traffic brings every engine to the tier it keeps in steady serving, and
the second warm-up compiles that tier at every bucket, so that nothing
compiles in the window and the window sees no engine change its tier.
Then ``--seconds`` of the cell's traffic, the answers compared with the
plain reference, and one JSON line on stdout.  With ``--trace 1`` the line holds the per-layer
metrics, read from a profiler trace of the window's first ``TRACE_S``
seconds and from a replay of the window's rows; otherwise the end-to-end
metrics.  Lines before it, on stderr, give the set-up phases, each
partition engine's tier, the compiles counted inside the window (there
should be none), the rows resolved in each second of the window, how late
the open-loop sender ran, and each number compared beside its limit.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

REPO = Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from chipbench import check, drive, gen, registry, trace_reduce  # noqa: E402
from chipbench.reference import Reference  # noqa: E402

WARMUP_S = 3.0          # an open loop's own traffic before the window
WARMUP_BATCHES = 160    # a closed loop's, in full batches: pins every
                        # escalating kNN engine of a 10M-point fleet
WARMUP_LIMIT_S = 240.0  # the longest a closed loop's warm-up may take
TRACE_S = 3.0           # traced seconds at the window's start
COMPARE_ROWS = 2048     # rows compared with the reference, at most
REPLAY_ROWS = 4096      # rows replayed for the lane counters


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class Gate(Exception):
    """The machine cannot run this cell."""


def gate(chips: int, platforms=("tpu",)):
    import jax
    devs = jax.devices()
    d0 = devs[0]
    log(f"device: platform={d0.platform} kind={d0.device_kind} "
        f"count={len(devs)}")
    if d0.platform not in platforms:
        raise Gate(f"no TPU: JAX platform is {d0.platform!r}")
    if len(devs) < chips:
        raise Gate(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return devs


class CompileWatch:
    """Host-clock end times of JAX's backend compiles (each executable
    obtained, whether compiled or read from the persistent cache), and of
    the persistent cache's hits."""

    def __init__(self):
        import jax
        self.ends, self.names, self.hits = [], [], []

        def on_duration(event, duration, fun_name="?", **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.ends.append(time.perf_counter())
                self.names.append(fun_name)

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits.append(time.perf_counter())
        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def count(self, t0: float, t1: float, hits: bool = False) -> int:
        return sum(t0 <= t <= t1 for t in list(self.hits if hits
                                                 else self.ends))

    def names_in(self, t0: float, t1: float):
        return sorted({n for t, n in zip(list(self.ends), list(self.names))
                       if t0 <= t <= t1})


def nearest_rank(values: np.ndarray, q: float) -> float:
    """The q-quantile by nearest rank: a value some request really had."""
    v = np.sort(values)
    return float(v[max(int(np.ceil(q * len(v))) - 1, 0)])


def fleet(cfg, pts):
    from repro.distributed.spatial_shard import SpatialShards
    rects = np.concatenate([pts, pts], axis=1)
    return SpatialShards.build(rects, cfg["partitions"],
                               fanout=cfg["fanout"], layout=cfg["layout"])


def fleet_call(shards, cfg):
    """The method the queue calls on the fleet, and its extra arguments."""
    if cfg["op"] == "select":
        return "range_select", {"result_cap": cfg["result_cap"]}
    return cfg["op"], {"k": cfg["k"]}


def serve_queue(shards, cfg):
    from repro.launch.queue import ServeQueue
    return ServeQueue(shards, cfg["op"], k=cfg.get("k"),
                      result_cap=cfg["result_cap"],
                      max_batch=cfg["max_batch"],
                      max_delay_s=cfg["max_delay_ms"] * 1e-3,
                      depth=cfg["depth"])


class Traffic:
    """The cell's load over one window: ``start(t0)`` then ``finish()``."""

    def __init__(self, queue, mix, seconds: float, seed: int, stream: int,
                 limit=None):
        self.queue, self.mix, self.seconds = queue, mix, seconds
        if mix["arrivals"] == "open":
            self.due, self.rows = gen.open_schedule(mix, seconds, seed,
                                                    stream)
        elif mix["arrivals"] == "closed":
            self.loop = drive.ClosedLoop(
                queue, gen.closed_requests(mix, seed, stream),
                mix["clients"], limit)
        else:
            raise ValueError(f"unknown arrivals {mix['arrivals']!r}")
        self.sent = []

    def start(self, t0: float):
        self.t0, self.t1 = t0, t0 + self.seconds
        if self.mix["arrivals"] == "open":
            def send():
                self.sent = drive.open_loop(self.queue, self.due, self.rows,
                                            t0)
            self._sender = threading.Thread(target=send, name="chipbench-open")
            self._sender.start()
        else:
            self.sent = self.loop.sent
            self.loop.start()

    def finish(self):
        """Close the window, then wait for the answers still due."""
        wait = self.t1 - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        if self.mix["arrivals"] == "open":
            self._sender.join()
        else:
            self.loop.stop()
        drive.wait(self.sent, time.perf_counter() + drive.WAIT_AFTER_S)
        return self.sent


def end_to_end(mix, sent, t0, t1, setup_s):
    out = {"setup_s": setup_s}
    if mix["arrivals"] == "open":
        lat = np.array([r.done - r.due if r.ok else np.inf for r in sent])
        for q, name in ((0.5, "p50_ms"), (0.99, "p99_ms")):
            v = nearest_rank(lat, q)
            out[name] = 1e3 * v if np.isfinite(v) else None
    done = [r for r in sent if r.ok and t0 <= r.done <= t1]
    out["rows_per_s"] = sum(len(r.rows) for r in done) / (t1 - t0)
    return out


def timeline(sent, t0: float, t1: float, step: float = 1.0) -> str:
    """Rows resolved in each ``step`` seconds of the window, for the log."""
    n = max(int(round((t1 - t0) / step)), 1)
    bins = np.zeros(n, np.int64)
    for r in sent:
        if r.ok and t0 <= r.done < t1:
            bins[min(int((r.done - t0) / step), n - 1)] += len(r.rows)
    return " ".join(str(int(b)) for b in bins)


def replay_lanes(shards, cfg, sent, seed):
    """(lanes_live, lanes_padded) summed over a seeded sample of the window's
    rows, replayed in ``max_batch`` groups straight through the fleet."""
    rows = np.concatenate([r.rows for r in sent])
    g = gen.rng(seed, gen.SAMPLE, 1)
    rows = rows[np.sort(g.permutation(len(rows))[:REPLAY_ROWS])]
    method, kw = fleet_call(shards, cfg)
    live = padded = esc = 0
    for i in range(0, len(rows), cfg["max_batch"]):
        getattr(shards, method)(rows[i:i + cfg["max_batch"]], **kw)
        c = shards.last_counters
        live += int(np.asarray(c.lanes_live).sum())
        padded += int(np.asarray(c.lanes_padded).sum())
        esc += int(np.asarray(c.escalations).sum())
    log(f"replay: {len(rows)} rows, escalations {esc}")
    return live, padded


def compare(cfg, pts, sent, seed, summary, overflowed):
    """The numbers that decide ``correct``, each beside its limit."""
    answered = [r for r in sent if r.ok]
    g = gen.rng(seed, gen.SAMPLE, 0)
    per = max(len(sent[0].rows), 1) if sent else 1
    pick = g.permutation(len(answered))[:max(COMPARE_ROWS // per, 1)]
    picked = [answered[i] for i in np.sort(pick)]
    ref = Reference(pts)
    bad, total = check.mismatched_rows(
        cfg["op"], [r.rows for r in picked], [r.answer for r in picked], ref)
    return check.checks({
        "failed_requests": len(sent) - len(answered),
        "overflowed_calls": overflowed,
        "degraded_dispatches": int(summary.get("degraded_dispatches", 0)),
        "mismatched_rows": bad,
        "compared_rows": total,
    })


def setup(cfg, mix, seed: int, warmup_s: float):
    """Everything before the window: (points, fleet, its spans, its
    engines' overflow flags, queue)."""
    import jax
    from repro.launch import compile_cache
    log(f"compile cache: {compile_cache.enable()}")
    # keep every program of the fleet, however quick its compile
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    t = time.perf_counter()
    pts = gen.points(cfg, seed)
    shards = fleet(cfg, pts)
    log(f"setup build: {len(shards.partitions)} partitions over "
        f"{len(pts)} points, {time.perf_counter() - t:.2f} s")
    t = time.perf_counter()
    shards.warm(cfg["op"], cfg["max_batch"], k=cfg.get("k"),
                result_cap=cfg["result_cap"])
    log(f"setup warm: {time.perf_counter() - t:.2f} s")
    spans, overflows = drive.Spans(), drive.Overflows()
    method, _ = fleet_call(shards, cfg)
    setattr(shards, method, spans.wrap(getattr(shards, method)))
    shards.engine_for = overflows.wrap(shards.engine_for)
    queue = serve_queue(shards, cfg)
    t = time.perf_counter()
    if mix["arrivals"] == "closed":
        n = -(-WARMUP_BATCHES * cfg["max_batch"] // gen.rows_per_request(mix))
        warm = Traffic(queue, mix, 0.0, seed, gen.WARMUP, limit=n)
        warm.start(time.perf_counter())
        give_up = t + WARMUP_LIMIT_S       # a client refused at the door
        while len(warm.sent) < n and time.perf_counter() < give_up:
            time.sleep(0.01)
    else:
        warm = Traffic(queue, mix, warmup_s, seed, gen.WARMUP)
        warm.start(time.perf_counter())
    warm.finish()
    log(f"setup traffic: {len(warm.sent)} requests, "
        f"{time.perf_counter() - t:.2f} s; engines {overflows.tiers()}")
    t = time.perf_counter()
    shards.warm(cfg["op"], cfg["max_batch"], k=cfg.get("k"),
                result_cap=cfg["result_cap"])
    log(f"setup warm again: {time.perf_counter() - t:.2f} s")
    return pts, shards, spans, overflows, queue


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, repo: Path = REPO, bench_dir: Path = registry.BENCH_DIR,
         platforms=("tpu",)) -> int:
    args = parse(argv)
    bench = registry.benchmark(repo, held=True, bench_dir=bench_dir)
    cell = registry.cell(bench, args.workload)
    cfg = registry.config(bench, cell["config"], repo)
    mix = registry.mix(cell["traffic"], bench_dir)
    src = str(REPO / "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    try:
        devs = gate(cell["chips"], platforms)
    except Gate as exc:
        log(f"chipbench: {exc}; this benchmark runs on the chip only")
        return 3

    import jax
    watch = CompileWatch()
    pts, shards, spans, overflows, queue = setup(cfg, mix, args.seed,
                                      min(WARMUP_S, args.seconds))
    log(f"setup programs: {watch.count(T_START, time.perf_counter())} "
        f"executables, {watch.count(T_START, time.perf_counter(), True)} "
        f"of them from the persistent cache")
    window = Traffic(queue, mix, args.seconds, args.seed, gen.WINDOW)
    before = queue.summary
    if args.trace:
        tracedir = tempfile.TemporaryDirectory(prefix="chipbench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(tracedir.name, profiler_options=opts)
    t0 = time.perf_counter()
    setup_s = t0 - T_START
    window.start(t0)
    if args.trace:
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
            time.sleep(min(TRACE_S, args.seconds))
        jax.profiler.stop_trace()
    sent = window.finish()
    t1 = window.t1
    last = max((r.done for r in sent if r.done == r.done), default=t1)
    after = queue.summary
    overflowed = overflows.count(t0, last)
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)

    log(f"window: {len(sent)} requests, compiles inside the window: "
        f"{watch.count(t0, last)} {watch.names_in(t0, last)}")
    log(f"engines after the window: {overflows.tiers()}")
    log(f"rows resolved per second of the window: {timeline(sent, t0, t1)}")
    span = np.array(spans.durations(t0, t1)) * 1e3
    if len(span):
        log(f"fleet calls in the window: {len(span)}, ms p10 "
            f"{np.percentile(span, 10):.2f} p50 {np.median(span):.2f} "
            f"p90 {np.percentile(span, 90):.2f} max {span.max():.2f}")
    if mix["arrivals"] == "open":
        late = np.array([r.sent - r.due for r in sent]) * 1e3
        log(f"open-loop sender lateness ms: p50 {np.median(late):.4f} "
            f"p99 {nearest_rank(late, 0.99):.4f} max {late.max():.4f}")
    queue_delta = {k: after.get(k, 0) - before.get(k, 0)
                   for k in ("batches", "rows", "padded_rows", "requests")}

    e2e = end_to_end(mix, sent, t0, t1, setup_s)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    breakdown = None
    if args.trace:
        paths = glob.glob(f"{tracedir.name}/**/*.xplane.pb", recursive=True)
        red = trace_reduce.reduce_xplane(paths[0]) if paths else None
        tracedir.cleanup()
        ctx = {"queue": queue_delta, "dispatch_s": spans.durations(t0, t1),
               "trace": red,
               "lanes": replay_lanes(shards, cfg, sent, args.seed)}
        metrics = {}
        for m in registry.metrics_of(bench, "per_layer", args.workload):
            v = registry.reader(m["name"], bench_dir)(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        if red:
            device["busy_s"] = red["busy_s"]
            device["window_s"] = red["window_s"]
            breakdown = {"device_ops": red["device_ops"],
                         "idle_gaps": red["idle_gaps"]}
    else:
        metrics = {}
        for m in registry.metrics_of(bench, "end_to_end", args.workload):
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}

    queue.close()
    del queue, shards, window
    gc.collect()
    t = time.perf_counter()
    chk = compare(cfg, pts, sent, args.seed, after, overflowed)
    log(f"reference: {time.perf_counter() - t:.2f} s")
    correct = check.passed(chk) and all(
        m["value"] is not None for m in metrics.values())
    for line in check.lines(chk):
        log(line)
    result = {"correct": correct, "attempted": len(sent),
              "failed": chk["failed_requests"]["value"],
              "metrics": metrics, "device": device}
    if breakdown:
        result["breakdown"] = breakdown
    result["window_compiles"] = watch.count(t0, last)
    result["checks"] = chk
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
