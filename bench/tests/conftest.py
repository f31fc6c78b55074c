"""Shared fixtures: a benchmark root whose cells are the real ones cut to
a size the CPU runs in seconds (same files, smaller numbers)."""

from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY = {"num_keys": 4000, "flip_head": 100, "flip_period": 8192,
        "workers": 8, "merge_workers": 4, "window": 2048}


def make_tiny_root(dst: Path) -> Path:
    """A copy of BENCHMARK.json and bench/ with every configuration and
    traffic mix cut down."""
    shutil.copy(ROOT / "BENCHMARK.json", dst / "BENCHMARK.json")
    shutil.copytree(BENCH, dst / "bench", dirs_exist_ok=True,
                    ignore=shutil.ignore_patterns("tests", "__pycache__",
                                                  "fixtures"))
    for f in (dst / "bench" / "configs").glob("*.json"):
        c = json.loads(f.read_text())
        c.update(TINY)
        f.write_text(json.dumps(c))
    for f in (dst / "bench" / "traffic").glob("*.json"):
        t = json.loads(f.read_text())
        t["feed_tuples"] = 512
        t["warmup_tuples"] = 4 * TINY["window"]
        f.write_text(json.dumps(t))
    return dst


@pytest.fixture
def tiny_root(tmp_path):
    return make_tiny_root(tmp_path)
