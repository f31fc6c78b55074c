"""The control (the reference one precision down in the program's place)
fails the comparison that decides ``correct``, for every cell, and the
reference in full precision passes it.  Small size on the CPU; the
readings at the cells' own size are in PERF.md."""

from __future__ import annotations

import json

import pytest

import control
from harness import reference, spec
from harness.reference import Outputs

CELLS = [w["name"] for w in spec.load_benchmark()["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("seed", [7, 2 ** 32 + 5])
def test_control_fails(tiny_root, cell, seed):
    res = control.run(cell, seed, 20000, root=tiny_root)
    assert res["fails"], res["checks"]


def test_control_fails_every_number_where_keys_overflow_int16(tiny_root):
    # at 10^5 keys the int16 control also wraps keys and sums
    for f in (tiny_root / "bench" / "configs").glob("*.json"):
        c = json.loads(f.read_text())
        c["num_keys"] = 100_000
        c["flip_head"] = 1000
        f.write_text(json.dumps(c))
    res = control.run("zf-sg-128w.sat16k", 11, 40960, root=tiny_root)
    assert all(c["value"] > c["limit"] for c in res["checks"].values()), \
        res["checks"]


def test_reference_in_full_precision_passes_sg(tiny_root):
    cfg = spec.load_cell("zf-sg-128w.sat16k", tiny_root).config
    import numpy as np

    from harness import stream

    s = stream.ZFStream(cfg, 3)
    s.take(20000)
    data = s.fed()
    data["feed_ends"] = np.arange(256, 20001, 256)
    n = data["keys"].shape[0]

    w = np.arange(n) % cfg["workers"]
    lat = reference.fifo_finishes(w, data["t"], reference.service_time(cfg),
                                  cfg["workers"]) - data["t"]
    truth = reference.window_truth(data["keys"], data["values"],
                                   cfg["window"])
    merged = {s: dict(zip(u.tolist(), v.tolist())) for s, u, v, _ in truth}
    rows = []
    for s in range(0, n, cfg["window"]):
        k = data["keys"][s:s + cfg["window"]].astype(np.int64)
        ww = w[s:s + cfg["window"]]
        code = ww * 10 ** 7 + k
        u, inv = np.unique(code, return_inverse=True)
        vs = np.zeros(u.shape[0], np.int64)
        np.add.at(vs, inv, data["values"][s:s + cfg["window"]])
        rows.append((np.full(u.shape[0], s), u // 10 ** 7, u % 10 ** 7, vs,
                     np.bincount(inv)))
    cols = [np.concatenate([r[i] for r in rows]).astype(np.int64)
            for i in range(5)]
    out = Outputs(merged, *cols, latency=lat, merge_tuples=cols[0].shape[0])
    nums = reference.compare(data, out, cfg)
    assert all(v <= cfg["limits"][k] for k, v in nums.items()), nums
