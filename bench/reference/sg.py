"""Reference routing for shuffle grouping (Storm's default).

Tuple ``i`` of the stream goes to worker ``i mod workers`` (round robin
over the live workers in id order, starting at worker 0), so the
reference knows every tuple's worker and checks the FIFO and the partial
stream exactly."""

from __future__ import annotations

import numpy as np

from harness.reference import fifo_finishes, service_time


def check(stream, out, config):
    w = config["workers"]
    keys = stream["keys"].astype(np.int64)
    t = stream["t"]
    n = keys.shape[0]
    workers = np.arange(n, dtype=np.int64) % w
    p = service_time(config)
    ref_lat = fifo_finishes(workers, t, p, w) - t
    gap = float(np.max(np.abs(out.latency[:n] - ref_lat)) / p) if n else 0.0
    if out.latency.shape[0] != n:
        gap = float("inf")
    # partial entries: (window, worker, key) with its sum and count
    win = config["window"]
    kspan = int(max(keys.max(initial=0), out.p_key.max(initial=0))) + 1
    code = ((np.arange(n) // win) * w + workers) * kspan + keys
    u, inv = np.unique(code, return_inverse=True)
    sums = np.zeros(u.shape[0], np.int64)
    np.add.at(sums, inv, stream["values"])
    cnts = np.bincount(inv).astype(np.int64)
    pc = ((out.p_window // win) * w + out.p_worker) * kspan + out.p_key
    o = np.argsort(pc, kind="stable")
    pc, pv, pn = pc[o], out.p_value[o], out.p_count[o]
    dup = int(np.count_nonzero(np.diff(pc) == 0))
    common, ip, ir = np.intersect1d(pc, u, return_indices=True)
    partial_bad = (pc.shape[0] + u.shape[0] - 2 * common.shape[0] + dup
                   + int(np.count_nonzero((pv[ip] != sums[ir])
                                          | (pn[ip] != cnts[ir]))))
    return {"fifo_latency_gap": gap,
            "partial_mismatch": partial_bad,
            "merge_tuples_gap": abs(int(out.merge_tuples) - int(u.shape[0]))}
