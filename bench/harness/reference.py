"""The plain reference and the comparison that decides ``correct``.

Written from the semantics the configuration states, and importing
nothing of the program:

* every tuple lands exactly once in its tumbling window of ``window``
  stream positions, and the window's per-key sum is an exact integer;
* each worker of the ``count`` stage serves its tuples first in, first
  out, one service time ``0.9 * workers / model_rate`` seconds each
  (the DSPE model clock), starting idle;
* each closed window leaves one partial entry per (worker, key) it
  touched, and the merge edge carries one tuple per entry.

What the program produced is handed in as plain arrays (:class:`Outputs`);
the routing checks that depend on the grouping live in
``bench/reference/<scheme>.py`` and are found by the scheme's name.
"""

from __future__ import annotations

import dataclasses
import importlib.util
from pathlib import Path
from typing import Dict, Optional

import numpy as np

__all__ = ["Outputs", "window_truth", "merged_mismatch",
           "key_count_mismatch", "fifo_finishes", "service_time",
           "fifo_chain_violations", "scheme_module", "route_checks",
           "compare"]


@dataclasses.dataclass
class Outputs:
    """What one run's timed path produced, as plain arrays.

    ``merged``: {window start: {key: sum}} after the merge of partials;
    ``p_*``: one row per partial entry (window start, worker, key, sum,
    tuple count); ``latency``: per fed tuple, its service latency on the
    ``count`` edge (model clock, seconds); ``merge_tuples``: tuples the
    merge edge carried."""

    merged: Dict
    p_window: np.ndarray
    p_worker: np.ndarray
    p_key: np.ndarray
    p_value: np.ndarray
    p_count: np.ndarray
    latency: np.ndarray
    merge_tuples: int


def window_truth(keys: np.ndarray, values: np.ndarray, window: int):
    """[(window start, sorted keys, int64 sums, int64 counts)] straight from
    the stream; the last window may be partial."""
    out = []
    keys = keys.astype(np.int64)
    for s in range(0, keys.shape[0], window):
        u, inv = np.unique(keys[s:s + window], return_inverse=True)
        sums = np.zeros(u.shape[0], np.int64)
        np.add.at(sums, inv, values[s:s + window])
        out.append((s, u, sums, np.bincount(inv).astype(np.int64)))
    return out


def _diff(pk, pv, rk, rv) -> int:
    """Entries that differ between two sorted key columns with values:
    keys on one side only, plus shared keys whose values differ."""
    common, ip, ir = np.intersect1d(pk, rk, assume_unique=True,
                                    return_indices=True)
    only = pk.shape[0] + rk.shape[0] - 2 * common.shape[0]
    return int(only + np.count_nonzero(pv[ip] != rv[ir]))


def merged_mismatch(merged: Dict, truth) -> int:
    """(window, key) results that are missing, extra or wrong."""
    bad = 0
    seen = set()
    for s, u, sums, _ in truth:
        seen.add(s)
        got = merged.get(s, {})
        pk = np.fromiter(got.keys(), np.int64, len(got))
        pv = np.fromiter(got.values(), np.int64, len(got))
        o = np.argsort(pk)
        bad += _diff(pk[o], pv[o], u, sums)
    bad += sum(len(v) for w, v in merged.items() if w not in seen)
    return bad


def key_count_mismatch(out: Outputs, truth) -> int:
    """(window, key) pairs whose tuples, summed over the workers' partial
    entries, are not exactly the stream's count: a tuple lost, counted
    twice or put in the wrong window."""
    bad = 0
    seen = set()
    for s, u, _, cnts in truth:
        seen.add(s)
        m = out.p_window == s
        pk, inv = np.unique(out.p_key[m], return_inverse=True)
        pc = np.zeros(pk.shape[0], np.int64)
        np.add.at(pc, inv, out.p_count[m])
        bad += _diff(pk, pc, u, cnts)
    bad += int(np.count_nonzero(~np.isin(out.p_window, list(seen))))
    return bad


def service_time(config: Dict) -> float:
    return 0.9 * config["workers"] / config["model_rate"]


def fifo_finishes(workers: np.ndarray, t: np.ndarray, p: float,
                  n_workers: int, dtype=np.float64) -> np.ndarray:
    """First-in-first-out finish times of tuples arriving at ``t`` on
    ``workers``, each served for ``p`` seconds by an initially idle
    worker: ``f = max(previous finish on the worker, t) + p``, solved per
    worker as ``f_j = (j+1) p + max(0, cummax_k(t_k - k p))``."""
    t = t.astype(dtype)
    p = dtype(p)
    fin = np.empty(t.shape[0], dtype)
    for w in range(n_workers):
        idx = np.flatnonzero(workers == w)
        if not idx.shape[0]:
            continue
        j = np.arange(idx.shape[0]).astype(dtype)
        g = np.maximum.accumulate(t[idx] - j * p)
        fin[idx] = (j + dtype(1)) * p + np.maximum(g, dtype(0))
    return fin


def scheme_module(scheme: str):
    """``bench/reference/<scheme>.py``, the reference's routing checks for
    one grouping scheme."""
    path = Path(__file__).resolve().parents[1] / "reference" / f"{scheme}.py"
    spec = importlib.util.spec_from_file_location(f"bench_ref_{scheme}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def route_checks(scheme: str, stream: Dict, out: Outputs,
                 config: Dict) -> Dict[str, float]:
    """The routing-dependent checks of ``scheme``."""
    return scheme_module(scheme).check(stream, out, config)


def compare(stream: Dict, out: Outputs, config: Dict,
            truth: Optional[list] = None) -> Dict[str, float]:
    """Every number the check compares, by name.  ``stream`` holds the
    ``keys``, ``values`` and model-clock arrival ``t`` of the tuples fed."""
    if truth is None:
        truth = window_truth(stream["keys"], stream["values"],
                             config["window"])
    nums = {"window_sum_mismatch": merged_mismatch(out.merged, truth),
            "key_count_mismatch": key_count_mismatch(out, truth)}
    nums.update(route_checks(config["grouping"]["scheme"], stream, out,
                             config))
    return nums


def fifo_chain_violations(t: np.ndarray, latency: np.ndarray, p: float,
                          rel_tol: float = 1e-3) -> int:
    """FIFO service checked without knowing which worker took which tuple.

    With one service time ``p`` per tuple, a tuple either found its worker
    idle and left after exactly ``p``, or it waited, and then it started
    the moment an earlier tuple of the same worker finished: its finish
    minus ``p`` is some other tuple's finish, and no finish is followed
    twice.  Counted violations: latencies below ``p``, plus waiting tuples
    in excess of the finishes they could have followed.  Times that lie
    within ``rel_tol * p`` of each other count as one (the program keeps
    model-clock offsets in float32)."""
    tol = rel_tol * p
    fin = t + latency
    short = int(np.count_nonzero(latency < p - tol))
    waited = latency > p + tol
    vals = np.concatenate([fin, fin[waited] - p])
    is_target = np.concatenate([np.zeros(fin.shape[0], np.int64),
                                np.ones(int(waited.sum()), np.int64)])
    order = np.argsort(vals, kind="stable")
    v = vals[order]
    cluster = np.concatenate([[0], np.cumsum(np.diff(v) > tol)])
    targets = np.bincount(cluster, weights=is_target[order])
    finishes = np.bincount(cluster, weights=1 - is_target[order])
    excess = np.maximum(targets - finishes, 0.0)
    return short + int(excess.sum())
