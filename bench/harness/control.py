"""The control: the reference put in the program's place, one precision
down, to show that the comparison fails it.

The configuration states int32 key ids and int32 window sums on the
device, and float32 model-clock offsets and FISH wait estimates.  The
control keeps key ids and sums in int16 (which wraps) and computes the
FIFO in bfloat16.  Its routing is the reference's own: round robin for
shuffle grouping; for FISH the replay of ``bench/reference/fish.py`` over
the wrapped keys, with its wait estimates in bfloat16.  It is run by ``bench/control.py``, never by a benchmark run.
"""

from __future__ import annotations

import ml_dtypes
import numpy as np

from harness.reference import (Outputs, fifo_finishes, scheme_module,
                               service_time)

__all__ = ["control_outputs"]


def _bf16(x: float, _t=ml_dtypes.bfloat16) -> float:
    return float(_t(x))


def control_outputs(data, config) -> Outputs:
    w, win = config["workers"], config["window"]
    keys16 = data["keys"].astype(np.int16)
    vals16 = data["values"].astype(np.int16)
    n = keys16.shape[0]
    if config["grouping"]["scheme"] == "sg":
        workers = np.arange(n, dtype=np.int64) % w
    else:
        wrapped = dict(data, keys=keys16.astype(np.uint16))
        workers = scheme_module("fish").Replay(wrapped, config,
                                               rnd=_bf16).workers
    bf = ml_dtypes.bfloat16
    fin = fifo_finishes(workers, data["t"], service_time(config), w, dtype=bf)
    latency = (fin - data["t"].astype(bf)).astype(np.float64)
    rows = {"w": [], "k": [], "s": [], "v": [], "c": []}
    merged = {}
    for s in range(0, n, win):
        k = keys16[s:s + win].astype(np.int64)
        code = workers[s:s + win] * 65536 + (k + 32768)
        u, inv = np.unique(code, return_inverse=True)
        sums = np.zeros(u.shape[0], np.int16)
        np.add.at(sums, inv, vals16[s:s + win])
        rows["s"].append(np.full(u.shape[0], s, np.int64))
        rows["w"].append(u // 65536)
        rows["k"].append(u % 65536 - 32768)
        rows["v"].append(sums.astype(np.int64))
        rows["c"].append(np.bincount(inv).astype(np.int64))
        mk, minv = np.unique(rows["k"][-1], return_inverse=True)
        ms = np.zeros(mk.shape[0], np.int16)
        np.add.at(ms, minv, sums)
        merged[s] = dict(zip(mk.tolist(), ms.astype(np.int64).tolist()))
    cat = {k: np.concatenate(v) for k, v in rows.items()}
    return Outputs(merged=merged, p_window=cat["s"], p_worker=cat["w"],
                   p_key=cat["k"], p_value=cat["v"], p_count=cat["c"],
                   latency=latency, merge_tuples=int(cat["k"].shape[0]))
