#!/usr/bin/env python3
"""Record the small device trace the harness tests read.

    python3 bench/record_fixture.py

On a machine with one TPU chip, this opens a fused SG session at a small
size (4,000 keys, 8 workers, 2,048-tuple windows), warms it up, and
profiles four 512-tuple feeds, one of which flushes a pane.  It writes
``bench/fixtures/sg_small.xplane.pb`` and, beside it,
``sg_small.spans.json``: the program's spans on ``perf_counter``, the
anchor's two ``perf_counter`` reads and the traced window.  It prints
every plane and line of the trace with its event count and first event
names.  Not part of a benchmark run.
"""

from __future__ import annotations

import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import numpy as np  # noqa: E402

from harness import devtrace, stream  # noqa: E402


def main() -> int:
    import jax
    from jax.profiler import ProfileData

    if jax.devices()[0].platform != "tpu":
        sys.exit("record_fixture: needs a TPU")
    from repro.obs import Telemetry
    from repro.topology import (Edge, RecordBatch, SimulatorEngine, Stage,
                                Topology, WindowOp, config_for)

    n, feed = 8 * 2048, 512
    keys, vals, t = stream.ZFStream(
        {"num_keys": 4000, "zipf_z": 1.2, "flip_at": 0.8, "flip_head": 100,
         "flip_period": 8192, "payload_min": 1, "payload_max": 99,
         "model_rate": 100000}, 5).take(n)
    vals = vals.astype(np.float64)
    topo = Topology(
        name="fixture",
        stages=(Stage("count", 8, operator=WindowOp(
            agg="sum", value="payload", size=2048)), Stage("merge", 4)),
        edges=(Edge("source", "count", config_for("sg")),
               Edge("count", "merge", config_for("fg"))))
    tel = Telemetry(enabled=True)
    sess = SimulatorEngine(mode="fused").open(topo, arrival_rate=1e5,
                                              telemetry=tel)
    bl = [RecordBatch(keys[i:i + feed], t[i:i + feed], vals[i:i + feed])
          for i in range(0, n, feed)]
    for b in bl[:12]:
        sess.feed(b)
    d = tempfile.mkdtemp(prefix="fixture-trace-")
    jax.profiler.start_trace(d)
    a0 = time.perf_counter()
    with jax.profiler.TraceAnnotation(devtrace.ANCHOR):
        pass
    a1 = time.perf_counter()
    w0 = time.perf_counter()
    for b in bl[12:16]:
        sess.feed(b)
    w1 = time.perf_counter()
    jax.profiler.stop_trace()
    sess.close()
    out = BENCH / "fixtures"
    out.mkdir(exist_ok=True)
    src = devtrace.find_xplane(d)
    shutil.copy(src, out / "sg_small.xplane.pb")
    spans = [(s.name, s.t0, s.t1, s.args) for s in tel.tracer.spans
             if s.t0 >= w0 and s.t1 <= w1]
    (out / "sg_small.spans.json").write_text(json.dumps(
        {"anchor": [a0, a1], "window": [w0, w1], "spans": spans},
        default=str))
    pd = ProfileData.from_file(src)
    for plane in pd.planes:
        for line in plane.lines:
            evs = list(line.events)
            names = sorted({e.name for e in evs})
            print(f"{plane.name} | {line.name} | {len(evs)} events | "
                  f"{names[:12]}")
    shutil.rmtree(d, ignore_errors=True)
    print(json.dumps({"xplane_bytes": (out / "sg_small.xplane.pb")
                      .stat().st_size}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
