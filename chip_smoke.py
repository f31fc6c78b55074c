#!/usr/bin/env python3
"""Chip smoke test: the fused stream session at deployment state size.

For each grouping scheme (sg, fg, pkg, dc, wc, fish) this opens a
``SimulatorEngine(mode="fused")`` session on a two-stage topology, the
paper's split-key -> partial -> merge shape:

* ``count``: 128 workers behind the scheme under test, each folding a
  tumbling 65,536-tuple windowed sum of the payload;
* ``merge``: 32 workers behind an FG edge, fed the window partials.

The stream is 196,608 tuples of ``zipf_time_evolving`` over 10^6 keys
with seeded integer payloads, fed in 12 batches of 16,384 tuples, so the
per-key device tables hold 2^20+1 rows x 129 worker lanes.

Checks, per scheme, and any failure exits non-zero:

* the fused path ran: no batched-engine fallback warning, one device
  launch per feed on the ``count`` edge, and its tables on the TPU;
* the merged windows equal ``direct_aggregate`` on the raw stream;
* SG/FG/PKG reports equal the host ``mode="batched"`` session (latencies
  to float32 precision), DC/WC/FISH stay within the DESIGN.md §6 bands of
  it.

Run from the repo root, on a machine with one TPU chip::

    python chip_smoke.py

With no TPU it exits non-zero and prints no result.  Lines before the last
are smoke observations (host wall clock, compile seconds, peak device
memory), not benchmark numbers.  The last line is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
"""

from __future__ import annotations

import json
import statistics
import sys
import time
import warnings
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

SCHEMES = ("sg", "fg", "pkg", "dc", "wc", "fish")
N_TUPLES = 196_608
NUM_KEYS = 1_000_000
ZIPF_Z = 1.2
FLIP_HEAD = 10_000
FEED = 16_384
WINDOW = 65_536
WORKERS = 128
MERGE_WORKERS = 32
ARRIVAL_RATE = 1e5
SEED = 0

# float32 device FIFO vs the float64 host scan: a hot worker's busy-time
# accumulation drifts a few hundred ulps (DESIGN.md §11)
F32_REL = 1e-4

COUNT_EDGE = "source->count"
MERGE_EDGE = "count->merge"
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


class SmokeFailure(AssertionError):
    """A phase of the smoke test found a wrong or missing result."""


def require(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def close(a: float, b: float, rel: float) -> bool:
    return abs(a - b) <= rel * max(abs(a), abs(b))


def require_tpu():
    """The device phase: the TPU devices JAX finds, or exit non-zero."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found "
                 f"{devices[0].platform!r} ({len(devices)} device(s))")
    return devices


class CompileClock:
    """Seconds JAX spends in backend compiles (a persistent-cache read
    included) and persistent-cache hits, while :attr:`on` is set."""

    def __init__(self):
        import jax

        self.on = False
        self.seconds = 0.0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **kw):
        if self.on and event == _BACKEND_COMPILE:
            self.seconds += secs

    def _event(self, event, **kw):
        if self.on and event == _CACHE_HIT:
            self.hits += 1

    def reset(self) -> None:
        self.seconds = 0.0
        self.hits = 0


def make_stream(n_tuples: int, num_keys: int, seed: int):
    import numpy as np

    from repro.data.synthetic import zipf_time_evolving

    keys = zipf_time_evolving(n_tuples, num_keys=num_keys, z=ZIPF_Z,
                              flip_head=min(FLIP_HEAD, num_keys), seed=seed)
    values = np.random.default_rng(seed + 1).integers(
        1, 100, n_tuples).astype(np.int64)
    return keys, values


def topology(scheme: str, workers: int, merge_workers: int, window: int):
    from repro.topology import (Edge, Stage, Topology, WindowOp,
                                config_for)

    op = WindowOp(agg="sum", value="payload", size=window)
    return Topology(
        name=f"smoke-{scheme}",
        stages=(Stage("count", workers, operator=op),
                Stage("merge", merge_workers)),
        edges=(Edge("source", "count", config_for(scheme)),
               Edge("count", "merge", config_for("fg"))))


def _device_tables(runner):
    # the per-key tables, the open pane and the ring tables; the replica
    # sets are kept on the host
    names = ("trk", "m_k", "pane_tab", "pane_last", "_pts_dev",
             "_cands_dev", "_rank_dev")
    return {n: getattr(runner, n) for n in names
            if getattr(runner, n) is not None}


def run_fused(topo, keys, values, feed: int, platform: str):
    """One fused session; returns (report, per-feed seconds, pane-flush
    seconds).  Fails if any feed falls back to the batched engine or the
    count edge's per-key tables are not on ``platform``."""
    from repro.obs import Telemetry
    from repro.topology import SimulatorEngine, Source

    # the repo's own tracer times the pane flushes (fused.pane_flush)
    tel = Telemetry(enabled=True, label=topo.name)
    sess = SimulatorEngine(mode="fused").open(
        topo, arrival_rate=ARRIVAL_RATE, telemetry=tel)
    src = Source(keys, arrival_rate=ARRIVAL_RATE, values=values)
    feed_s = []
    with warnings.catch_warnings():
        warnings.filterwarnings("error", message="simulate_edge falling back",
                                category=UserWarning)
        for i, batch in enumerate(src.iter_batches(batch_size=feed)):
            t0 = time.perf_counter()
            sess.feed(batch)
            feed_s.append(time.perf_counter() - t0)
            if i == 0:
                runner = sess._st[COUNT_EDGE].state.device
                require(hasattr(runner, "pane_tab"),
                        f"{topo.name}: the count edge has no fused runner")
                tables = _device_tables(runner)
                require(tables, f"{topo.name}: the count edge holds no "
                        "device table after its first feed")
                for name, arr in tables.items():
                    where = {d.platform for d in arr.devices()}
                    require(where == {platform},
                            f"{topo.name}: device table {name} lives on "
                            f"{where}, not {platform}")
        rep = sess.close()
    flush_s = [s.t1 - s.t0 for s in tel.tracer.spans
               if s.name == "fused.pane_flush"]
    return rep, feed_s, flush_s


def run_batched(topo, keys, values, feed: int):
    from repro.topology import SimulatorEngine, Source

    sess = SimulatorEngine(mode="batched").open(topo,
                                                arrival_rate=ARRIVAL_RATE)
    src = Source(keys, arrival_rate=ARRIVAL_RATE, values=values)
    for batch in src.iter_batches(batch_size=feed):
        sess.feed(batch)
    return sess.close()


def check_reports(scheme: str, rf, rb, direct, n_feeds: int) -> None:
    """The oracle phase: fused report ``rf`` against the batched report
    ``rb`` and the direct aggregate of the raw stream."""
    from repro.analysis.contracts import BANDED_SCHEMES, EXACT_SCHEMES

    ef, eb = rf.edge(COUNT_EDGE), rb.edge(COUNT_EDGE)
    require(ef.dispatches == n_feeds,
            f"{scheme}: {ef.dispatches} count-edge launches for "
            f"{n_feeds} feeds")
    require(ef.n_tuples == eb.n_tuples,
            f"{scheme}: {ef.n_tuples} tuples routed, batched {eb.n_tuples}")
    require(rf.state["count"]["merged"] == direct,
            f"{scheme}: merged windows differ from direct_aggregate")
    require(rb.state["count"]["merged"] == direct,
            f"{scheme}: batched merged windows differ from direct_aggregate")
    if scheme in EXACT_SCHEMES:
        require(ef.memory_overhead == eb.memory_overhead,
                f"{scheme}: memory_overhead {ef.memory_overhead} != "
                f"{eb.memory_overhead}")
        require(ef.imbalance == eb.imbalance,
                f"{scheme}: imbalance {ef.imbalance} != {eb.imbalance}")
        for f in ("latency_p99", "latency_avg", "execution_time"):
            require(close(getattr(ef, f), getattr(eb, f), F32_REL),
                    f"{scheme}: {f} {getattr(ef, f)} vs batched "
                    f"{getattr(eb, f)} beyond rel {F32_REL}")
        require(rf.state["count"]["partials"] ==
                rb.state["count"]["partials"],
                f"{scheme}: window partials differ from batched")
        mf, mb = rf.edge(MERGE_EDGE), rb.edge(MERGE_EDGE)
        require(mf.n_tuples == mb.n_tuples,
                f"{scheme}: merge edge {mf.n_tuples} tuples, batched "
                f"{mb.n_tuples}")
        require(close(mf.latency_p99, mb.latency_p99, F32_REL),
                f"{scheme}: merge latency_p99 {mf.latency_p99} vs "
                f"{mb.latency_p99}")
    else:
        require(scheme in BANDED_SCHEMES, f"{scheme}: no exactness contract")
        require(close(ef.execution_time, eb.execution_time, 0.05),
                f"{scheme}: execution_time {ef.execution_time} vs "
                f"{eb.execution_time} beyond rel 0.05")
        require(close(ef.throughput, eb.throughput, 0.05),
                f"{scheme}: throughput {ef.throughput} vs {eb.throughput} "
                "beyond rel 0.05")
        require(close(ef.memory_overhead, eb.memory_overhead, 0.25),
                f"{scheme}: memory_overhead {ef.memory_overhead} vs "
                f"{eb.memory_overhead} beyond rel 0.25")
        require(ef.imbalance <= eb.imbalance + 0.05,
                f"{scheme}: imbalance {ef.imbalance} vs {eb.imbalance}")
        require(ef.latency_p99 <= max(eb.latency_p99 * 10.0, 0.05),
                f"{scheme}: latency_p99 {ef.latency_p99} vs "
                f"{eb.latency_p99}")


def run_scheme(scheme: str, keys, values, direct, *, workers: int,
               merge_workers: int, window: int, feed: int, platform: str,
               clock=None) -> dict:
    """Fused session, batched oracle and checks for one scheme; returns
    the smoke observations."""
    topo = topology(scheme, workers, merge_workers, window)
    if clock is not None:
        clock.reset()
        clock.on = True
    t0 = time.perf_counter()
    rf, feed_s, flush_s = run_fused(topo, keys, values, feed, platform)
    fused_s = time.perf_counter() - t0
    if clock is not None:
        clock.on = False
    t0 = time.perf_counter()
    rb = run_batched(topo, keys, values, feed)
    batched_s = time.perf_counter() - t0
    n_feeds = -(-int(keys.shape[0]) // feed)
    check_reports(scheme, rf, rb, direct, n_feeds)
    obs = {"scheme": scheme, "fused_session_s": fused_s,
           "feed_ms_first": feed_s[0] * 1e3,
           "feed_ms_steady_median": statistics.median(feed_s[1:] or feed_s)
           * 1e3,
           "pane_flush_s": flush_s, "batched_oracle_s": batched_s}
    if clock is not None:
        obs["compile_s"] = clock.seconds
        obs["compile_cache_hits"] = clock.hits
    return obs


def main() -> int:
    from repro.compile_cache import configure

    cache_dir = configure()
    devices = require_tpu()
    dev = devices[0]
    print(f"smoke observation: device {dev.platform} {dev.device_kind!r} "
          f"x{len(devices)}, compile cache {cache_dir}", flush=True)

    from repro.state import WindowOp, direct_aggregate

    keys, values = make_stream(N_TUPLES, NUM_KEYS, SEED)
    direct = direct_aggregate(
        keys, WindowOp(agg="sum", value="payload", size=WINDOW),
        values=values)
    clock = CompileClock()
    for scheme in SCHEMES:
        obs = run_scheme(scheme, keys, values, direct, workers=WORKERS,
                         merge_workers=MERGE_WORKERS, window=WINDOW,
                         feed=FEED, platform="tpu", clock=clock)
        print("smoke observation: " + json.dumps(obs), flush=True)
    stats = dev.memory_stats() or {}
    print(f"smoke observation: peak_bytes_in_use "
          f"{stats.get('peak_bytes_in_use', 'not reported')}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
