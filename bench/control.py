#!/usr/bin/env python3
"""Run the control of a cell: the numbers its check compares, with the
reference one precision down in the program's place.

    python3 bench/control.py --workload <cell> --seed <n> --tuples <N>

``--tuples`` is what a run of the cell feeds (warm-up included), cut
into the feeds a run sends.  Prints one JSON line: ``{"workload", "seed", "tuples",
"checks": {name: {"value", "limit"}}, "fails": bool}``.  A control has
to fail; ``bench/tests/test_control.py`` runs it at a small size.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import numpy as np  # noqa: E402

from harness import reference, spec, stream  # noqa: E402
from harness.control import control_outputs  # noqa: E402


def run(workload: str, seed: int, tuples: int, root=BENCH.parent) -> dict:
    import run_cell

    cell = spec.load_cell(workload, root)
    cfg, traffic = cell.config, cell.traffic
    ends, pos = [], 0
    for n in run_cell.feed_sizes(traffic["feed_tuples"], cfg["window"]):
        if pos >= tuples:
            break
        pos += n
        ends.append(pos)
    s = stream.ZFStream(cfg, seed)
    s.take(pos)
    data = {k: v[:tuples] for k, v in s.fed().items()}
    data["feed_ends"] = np.array(ends, dtype=np.int64)
    nums = reference.compare(data, control_outputs(data, cfg), cfg)
    checks = {k: {"value": v, "limit": cfg["limits"][k]}
              for k, v in nums.items()}
    return {"workload": workload, "seed": seed, "tuples": tuples,
            "checks": checks,
            "fails": any(not c["value"] <= c["limit"]
                         for c in checks.values())}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--tuples", type=int, required=True)
    a = ap.parse_args(argv)
    print(json.dumps(run(a.workload, a.seed, a.tuples)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
