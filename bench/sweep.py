#!/usr/bin/env python3
"""Find an open-loop cell's knee: the highest rate whose backlog does not
grow.  Run once when a cell's rate is set, on the chip; not part of a
benchmark run.

    python3 bench/sweep.py --workload <cell> --seed <n> \
        --seconds <per rate> --fractions 0.6,0.8,0.9,1.0,1.1

In one process it warms the cell up as a run does, then measures the
closed-loop rate at the cell's own feed size over whole window cycles
for ``--seconds`` (the capacity estimate), then offers load in the open loop at each
fraction of that estimate for ``--seconds``.  For each rate it prints the
latency percentiles and the backlog trend: how late each window cycle's
least-late feed was sent.  A rate the system sustains gets back to
sending on time in every cycle; above it, the least lateness grows from
cycle to cycle.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import numpy as np  # noqa: E402

import run_cell  # noqa: E402
from harness import loops, spec  # noqa: E402


def cycle_min_lag(logs):
    """Least send lateness (seconds) in each whole window cycle."""
    out, cur = [], []
    for g in logs:
        cur.append(g.start - g.due)
        if g.cycle_end:
            out.append(min(cur))
            cur = []
    return out


def main(argv=None, root: Path = BENCH.parent,
         require_accelerator: bool = True) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--fractions", default="0.6,0.8,0.9,1.0,1.1")
    a = ap.parse_args(argv)
    fr = [float(x) for x in a.fractions.split(",")]
    cell = spec.load_cell(a.workload, root)
    cfg, traffic = cell.config, cell.traffic
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(Path(root) / ".jax_cache")
    if require_accelerator and run_cell.require_devices(cell.chips) is None:
        return 2
    from repro.topology import SimulatorEngine

    feed = traffic["feed_tuples"]
    feeder = run_cell.Feeder(cfg, traffic, a.seed)
    sess = SimulatorEngine(mode="fused").open(
        run_cell.topology(cfg), arrival_rate=cfg["model_rate"])
    for b, _ in feeder.warm_up():
        sess.feed(b)
    batches = feeder.feeds()
    logs, t0 = loops.run_closed(sess.feed, batches, a.seconds)
    tuples, secs, cycles = loops.closed_rate(logs, t0)
    cap = tuples / secs
    print(json.dumps({"closed_tuples_per_s": cap, "cycles": cycles,
                      "feed_tuples": feed}), flush=True)
    for f in fr:
        rate = cap * f
        logs, t0, t_end = loops.run_open(sess.feed, batches, rate,
                                         a.seconds)
        lat = loops.open_latencies(logs, rate, t0, t_end)
        print(json.dumps({
            "fraction": f, "rate": rate,
            "p50_ms": float(np.percentile(lat, 50)) * 1e3,
            "p99_ms": float(np.percentile(lat, 99)) * 1e3,
            "cycle_min_lag_ms": [x * 1e3 for x in cycle_min_lag(logs)],
            "last_lag_ms": (logs[-1].start - logs[-1].due) * 1e3,
            "feeds": len(logs)}), flush=True)
    sess.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
