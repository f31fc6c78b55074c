"""Finds a cell and everything it needs by name.

``BENCHMARK.json`` at the checkout root lists configurations, cells and
metrics.  A configuration's sizes sit in the file its entry names, a
traffic mix in ``bench/traffic/<traffic>.json``, and a per-layer metric's
reader in ``bench/metrics/<base>.py``, where ``<base>`` is the metric's
name up to its first dot (``pane_flush_share.sat`` and
``pane_flush_share.open`` share one reader).  Adding a configuration, a
mix or a metric therefore adds files and edits none.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from typing import Callable, Dict, List

__all__ = ["ROOT", "Cell", "load_benchmark", "load_cell", "metric_reader",
           "cell_metrics"]

ROOT = Path(__file__).resolve().parents[2]


class Cell:
    """One workload entry with its configuration and traffic loaded."""

    def __init__(self, entry: Dict, config: Dict, traffic: Dict,
                 end_to_end: List[Dict], per_layer: List[Dict]):
        self.name = entry["name"]
        self.chips = int(entry["chips"])
        self.entry = entry
        self.config = config
        self.traffic = traffic
        self.end_to_end = end_to_end
        self.per_layer = per_layer


def load_benchmark(root: Path = ROOT) -> Dict:
    with open(Path(root) / "BENCHMARK.json") as f:
        return json.load(f)


def _reports(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell_metrics(bm: Dict, cell: str):
    """(end-to-end metrics, per-layer metrics) that ``cell`` reports.  A
    per-layer metric without a ``workloads`` key goes wherever the
    end-to-end metric it moves is reported."""
    e2e = [m for m in bm["end_to_end"] if _reports(m, cell)]
    names = {m["name"] for m in e2e}
    layer = [m for m in bm["per_layer"]
             if (cell in m["workloads"] if "workloads" in m
                 else m["moves"] in names)]
    return e2e, layer


def load_cell(name: str, root: Path = ROOT) -> Cell:
    root = Path(root)
    bm = load_benchmark(root)
    entries = {w["name"]: w for w in bm["workloads"]}
    if name not in entries:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    entry = entries[name]
    cfgs = {c["name"]: c for c in bm["configs"]}
    with open(root / cfgs[entry["config"]]["file"]) as f:
        config = json.load(f)
    with open(root / "bench" / "traffic" / f"{entry['traffic']}.json") as f:
        traffic = json.load(f)
    e2e, layer = cell_metrics(bm, name)
    return Cell(entry, config, traffic, e2e, layer)


def metric_reader(name: str, root: Path = ROOT) -> Callable:
    """The ``read(bundle)`` function of the per-layer metric ``name``."""
    base = name.split(".", 1)[0]
    path = Path(root) / "bench" / "metrics" / f"{base}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{base}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
