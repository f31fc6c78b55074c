#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print one JSON result line.

    python3 bench/run_cell.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

The cell, its configuration, its traffic and its metrics are found by
name through ``BENCHMARK.json`` (``bench/harness/spec.py``).  One run is
one process: it builds the stream from ``--seed``, opens a fused
``SimulatorEngine`` session on the configuration's topology, warms up
every shape the traffic uses, measures for ``--seconds`` in a closed or
an open loop, closes the session, and compares what the timed path
produced with the plain reference (``bench/harness/reference.py``).

With ``--trace 0`` the result carries the cell's end-to-end metrics; with
``--trace 1`` the program's spans are on, the profiler records the window
from its start through the first feed that closes a window, and the
result carries the per-layer metrics, the device's busy and window
seconds, and a breakdown.

It exits non-zero and prints no result when JAX finds no TPU, or fewer
chips than the cell asks for.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
for _p in (str(BENCH), str(ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import numpy as np  # noqa: E402

from harness import devtrace, loops, reference, spec, stream  # noqa: E402

COUNT_EDGE = "source->count"
MERGE_EDGE = "count->merge"


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def topology(config):
    from repro.topology import Edge, Stage, Topology, WindowOp, config_for

    def grouping(g):
        g = dict(g)
        return config_for(g.pop("scheme"), **g)

    op = WindowOp(agg="sum", value="payload", size=config["window"])
    return Topology(
        name=config["name"],
        stages=(Stage("count", config["workers"], operator=op),
                Stage("merge", config["merge_workers"])),
        edges=(Edge("source", "count", grouping(config["grouping"])),
               Edge("count", "merge", grouping(config["merge_grouping"]))))


def feed_sizes(feed: int, window: int):
    """The sizes of the feeds a run sends, in order: ``feed`` tuples at a
    time, cut where a window ends."""
    pos = 0
    while True:
        n = min(feed, window - pos % window)
        yield n
        pos += n


class Feeder:
    """The cell's seeded stream, handed out as RecordBatches of the sizes
    :func:`feed_sizes` gives, warm-up included; ``ends`` records where each
    feed ended (the program launches once per feed, and the reference
    replays launch by launch)."""

    def __init__(self, config, traffic, seed: int):
        self.stream = stream.ZFStream(config, seed)
        self.window = config["window"]
        self.warm = traffic["warmup_tuples"]
        self._sizes = feed_sizes(traffic["feed_tuples"], self.window)
        self.ends = []

    def next(self):
        """(RecordBatch of the next feed, whether it ends a window)."""
        from repro.topology import RecordBatch

        keys, vals, t = self.stream.take(next(self._sizes))
        self.ends.append(self.stream.pos)
        return (RecordBatch(keys, t, vals.astype(np.float64)),
                self.stream.pos % self.window == 0)

    def warm_up(self):
        while self.stream.pos < self.warm:
            yield self.next()

    def feeds(self):
        while True:
            yield self.next()


def require_devices(chips: int):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        log(f"needs a TPU; JAX found {devices[0].platform!r} "
            f"({len(devices)} device(s))")
        return None
    if len(devices) < chips:
        log(f"the cell needs {chips} chips; JAX found {len(devices)}")
        return None
    return devices[:chips]


def prewarm_merge_buckets(session, config) -> list:
    """Compile the merge edge's launch for the padded sizes the measured
    window may need and the warm-up did not.

    The program pads each launch to a power of two.  The merge edge gets
    one launch per closed window, as long as the window's partial entries
    are many; a count seen in the warm-up within 5 % of a power of two
    may fall on the other side of it in a later window of the same kind.
    Each such size is compiled in set-up by feeding one batch of exactly
    that many tuples, with the same key range, through a separate session
    whose only edge is a copy of the merge edge."""
    from repro.topology import (Edge, RecordBatch, SimulatorEngine, Stage,
                                Topology, config_for)

    parts = session._st[COUNT_EDGE].mgr.partials
    if not parts:
        return []
    per_window = {}
    kmax = 0
    for p in parts:
        per_window[p.window] = per_window.get(p.window, 0) + p.keys.shape[0]
        kmax = max(kmax, int(p.keys.max()))

    def pow2(x):
        return 1 << max(int(math.ceil(x)) - 1, 0).bit_length()

    seen = {pow2(c) for c in per_window.values()}
    need = sorted({pow2(c * f) for c in per_window.values()
                   for f in (0.95, 1.05)} - seen)
    g = dict(config["merge_grouping"])
    topo = Topology(name="prewarm",
                    stages=(Stage("merge", config["merge_workers"]),),
                    edges=(Edge("source", "merge",
                                config_for(g.pop("scheme"), **g)),))
    for n in need:
        s = SimulatorEngine(mode="fused").open(
            topo, arrival_rate=config["model_rate"])
        keys = np.arange(n, dtype=np.int64) * max(kmax // max(n, 1), 1)
        keys[-1] = kmax
        s.feed(RecordBatch(keys, np.arange(n) / config["model_rate"]))
        s.close()
    return need


def outputs(session, report, latencies) -> reference.Outputs:
    parts = session._st[COUNT_EDGE].mgr.partials
    sizes = np.array([p.keys.shape[0] for p in parts], dtype=np.int64)

    def col(attr):
        return np.repeat(np.array([getattr(p, attr) for p in parts],
                                  dtype=np.int64), sizes)

    def cat(attr):
        return (np.concatenate([getattr(p, attr) for p in parts])
                .astype(np.int64) if parts else np.zeros(0, np.int64))

    return reference.Outputs(
        merged=report.state["count"]["merged"],
        p_window=col("window"), p_worker=col("worker"), p_key=cat("keys"),
        p_value=cat("values"), p_count=cat("counts"),
        latency=(np.concatenate(latencies) if latencies
                 else np.zeros(0)),
        merge_tuples=report.edge(MERGE_EDGE).n_tuples)


class Profile:
    """Profiles from the window's start through the first feed that ends a
    window (its pane flush included), then stops; records the traced
    window on ``perf_counter``."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        self.window = None
        self.anchor = None
        self._t0 = 0.0

    def start(self):
        import jax

        jax.profiler.start_trace(self.dir)
        a = time.perf_counter()
        with jax.profiler.TraceAnnotation(devtrace.ANCHOR):
            pass
        self.anchor = (a, time.perf_counter())
        self._t0 = time.perf_counter()

    def after_feed(self, g) -> None:
        if self.window is not None or not g.cycle_end:
            return
        import jax

        self.window = (self._t0, time.perf_counter())
        jax.profiler.stop_trace()

    def reduce(self, spans, bundle) -> dict:
        ex = devtrace.extract(devtrace.find_xplane(self.dir))
        shutil.rmtree(self.dir, ignore_errors=True)
        if not ex["anchors"] or not ex["devices"]:
            raise RuntimeError("the trace holds no anchor or no device")
        an = ex["anchors"][0]
        offset = sum(self.anchor) / 2 - (an[0] + an[1]) / 2
        w0, w1 = (self.window[0] - offset, self.window[1] - offset)
        devs = list(ex["devices"].values())
        busy = [devtrace.busy_seconds(d["ops"], w0, w1) for d in devs]
        ops = [e for d in devs for e in d["ops"]]
        mods = [e for d in devs for e in d["modules"]]
        gaps = devtrace.idle_gaps(devs[0]["ops"], w0, w1)
        bundle.update(
            busy_s=sum(busy) / len(busy), window_s=w1 - w0,
            segment_device_s=devtrace.module_seconds(mods, "jit_seg", w0,
                                                     w1) / len(devs))
        return {"device_ops": devtrace.top_ops(ops, w0, w1),
                "idle_gaps": devtrace.label_gaps(
                    gaps, [(s[0], s[1], s[2]) for s in spans], offset)}


def main(argv=None, root: Path = ROOT, require_accelerator: bool = True
         ) -> int:
    args = parse(argv)
    cell = spec.load_cell(args.workload, root)
    cfg, traffic = cell.config, cell.traffic
    # the compile cache lives at a fixed path inside the checkout
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(Path(root) / ".jax_cache")
    from repro.compile_cache import configure

    cache_dir = configure()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    if require_accelerator:
        devices = require_devices(cell.chips)
        if devices is None:
            return 2
    else:
        devices = jax.devices()[:cell.chips]
    dev = devices[0]
    from harness.compile_clock import CompileClock
    from harness.peaks import peaks_for
    from repro.obs import Telemetry
    from repro.topology import SimulatorEngine

    peaks = peaks_for(dev.device_kind) if require_accelerator else None
    clock = CompileClock()
    clock.on = True
    trace = bool(args.trace)
    win = cfg["window"]
    feeder = Feeder(cfg, traffic, args.seed)
    tel = Telemetry(enabled=trace, label=cell.name)
    session = SimulatorEngine(mode="fused").open(
        topology(cfg), arrival_rate=cfg["model_rate"], telemetry=tel)
    lats = []

    def feed_fn(b):
        lats.append(session.feed(b).latencies)

    for b, _ in feeder.warm_up():
        feed_fn(b)
    n_warm = feeder.stream.pos
    extra = prewarm_merge_buckets(session, cfg)
    if extra:
        log(f"merge edge sizes compiled ahead: {extra}")
    setup_s = time.perf_counter() - T_PROCESS
    log(f"set-up {setup_s:.3f} s: {clock.compiles} backend compiles "
        f"({clock.seconds:.3f} s), {clock.hits} cache hits, cache "
        f"{cache_dir}")
    clock.reset()
    prof = Profile() if trace else None
    bench_spans = []
    sleep = time.sleep
    if trace:
        def sleep(s):
            a = time.perf_counter()
            time.sleep(s)
            bench_spans.append(("bench.wait_for_arrivals", a,
                                time.perf_counter(), None))
        prof.start()
    after = prof.after_feed if trace else None
    if traffic["loop"] == "closed":
        logs, t0 = loops.run_closed(feed_fn, feeder.feeds(),
                                    args.seconds, after_feed=after)
        tuples, secs, cycles = loops.closed_rate(logs, t0)
        e2e = {"tuples_per_s": tuples / secs}
        attempted = tuples
        log(f"window: {tuples} tuples in {cycles} whole window cycles, "
            f"{secs:.6f} s, {len(logs)} feeds")
    else:
        rate = traffic["rate_tuples_per_s"]
        logs, t0, t_end = loops.run_open(feed_fn, feeder.feeds(), rate,
                                         args.seconds, sleep=sleep,
                                         after_feed=after)
        lat = loops.open_latencies(logs, rate, t0, t_end)
        e2e = {"tuple_latency_p50_ms": float(np.percentile(lat, 50)) * 1e3,
               "tuple_latency_p99_ms": float(np.percentile(lat, 99)) * 1e3}
        attempted = int(lat.shape[0])
        lag = np.array([g.start - g.due for g in logs])
        log(f"window: {attempted} tuples due, {len(logs)} feeds sent, "
            f"feed start lag p50 {np.median(lag) * 1e3:.3f} ms max "
            f"{lag.max() * 1e3:.3f} ms, last return "
            f"{logs[-1].end - t_end:+.3f} s from the window's end")
    e2e["setup_s"] = setup_s
    window_compiles = clock.compiles
    clock.on = False
    log(f"window compiles: {window_compiles}")
    stats = [d.memory_stats() or {} for d in devices]
    peak = max(int(s.get("peak_bytes_in_use", 0)) for s in stats)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}

    breakdown = None
    bundle = {}
    if trace:
        if prof.window is None:
            raise RuntimeError("the window closed before the traced cycles")
        spans = [(s.name, s.t0, s.t1, s.args) for s in tel.tracer.spans]
        spans += bench_spans
        bundle = {"spans": spans, "window": prof.window, "config": cfg,
                  "traffic": traffic, "peaks": peaks,
                  "window_compiles": window_compiles}
        breakdown = prof.reduce(spans, bundle)
        device["busy_s"] = bundle["busy_s"]
        device["window_s"] = bundle["window_s"]

    fed = n_warm + sum(g.n for g in logs)
    report = session.close()
    out = outputs(session, report, lats)
    per_win = np.bincount(out.p_window // win)
    log(f"partial entries per window: {per_win.tolist()}")
    del session, report, lats
    gc.collect()
    t_ref = time.perf_counter()
    fed_data = {k: v[:fed] for k, v in feeder.stream.fed().items()}
    fed_data["feed_ends"] = np.array([e for e in feeder.ends if e <= fed],
                                     dtype=np.int64)
    nums = reference.compare(fed_data, out, cfg)
    log(f"reference over {fed} tuples took "
        f"{time.perf_counter() - t_ref:.3f} s")
    limits = cfg["limits"]
    checks = {k: {"value": v, "limit": limits[k]} for k, v in nums.items()}
    failed = sum(1 for c in checks.values() if not c["value"] <= c["limit"])

    if trace:
        metrics = {}
        for m in cell.per_layer:
            v = spec.metric_reader(m["name"], root)(bundle)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    result = {"correct": failed == 0, "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    for k, c in checks.items():
        log(f"check {k} = {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
