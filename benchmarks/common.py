"""Shared helpers for the paper-figure benchmarks."""

from __future__ import annotations

import csv
import io
import os
import time
from typing import Callable, Dict, List

import numpy as np

from repro.core import simulate_edge
from repro.data.synthetic import piecewise_zipf, zipf_time_evolving
from repro.topology import (Edge, SimulatorEngine, Source, Stage, Topology,
                            build_grouper, config_for)

ARTIFACT_DIR = os.path.join(os.path.dirname(__file__), "artifacts")

# CPU-friendly scale: the batched engine is O(tuples) NumPy work; the paper's
# 50M-tuple runs use identical code at scale=1 (see data/synthetic.py Table-2
# proxies).
N_TUPLES = 30_000
N_KEYS = 3_000
WORKERS = (16, 32, 64, 128)
SCHEMES = ("fg", "pkg", "sg", "dc", "wc", "fish")


def run_scheme(scheme, keys, workers: int, capacities=None,
               arrival_rate: float = 20_000.0, simulator: str = "batched",
               **kw):
    """Route ``keys`` through one grouped edge of ``scheme`` (a scheme name
    or a typed :class:`~repro.topology.SchemeConfig`); ``simulator`` picks
    the batched engine (default — ISSUE 1) or the per-tuple ``"reference"``
    oracle.  Returns ``(grouper, StreamMetrics)``."""
    if simulator not in ("batched", "reference"):
        raise ValueError(f"unknown simulator {simulator!r}")
    if capacities is None:
        capacities = np.full(workers, 0.9 * workers / arrival_rate)
    # no oracle capacities for the grouper: capacity-aware schemes discover
    # P_w through the sampling hook
    g = build_grouper(scheme, workers)
    res = simulate_edge(g, keys, mode=simulator, capacities=capacities,
                        arrival_rate=arrival_rate, **kw)
    return g, res.metrics


def run_edge(scheme, keys, workers: int,
             arrival_rate: float = 20_000.0, simulator: str = "batched"):
    """One grouped edge through the unified engine protocol (ISSUE 3):
    builds a single-edge :class:`Topology` and runs it on
    :class:`SimulatorEngine`.  Returns the :class:`EdgeReport`."""
    spec = scheme if not isinstance(scheme, str) else config_for(scheme)
    topo = Topology(name=f"edge-{spec.scheme}",
                    stages=(Stage("worker", parallelism=workers),),
                    edges=(Edge("source", "worker", spec),))
    rep = SimulatorEngine(mode=simulator).run(
        topo, Source(np.asarray(keys), arrival_rate=arrival_rate))
    return rep.edge("worker")


def am_proxy_keys(seed=0):
    return piecewise_zipf(N_TUPLES, N_KEYS, z=1.2, phases=6, seed=seed)


def mt_proxy_keys(seed=1):
    return piecewise_zipf(N_TUPLES, N_KEYS, z=1.1, phases=8, seed=seed)


def zf_keys(z: float, seed=2):
    return zipf_time_evolving(N_TUPLES, num_keys=N_KEYS, z=z,
                              flip_head=N_KEYS // 3, seed=seed)


class Reporter:
    """Collects ``name,us_per_call,derived`` rows (benchmarks/run.py CSV).

    Failures are recorded separately from measurements: an erroring module
    must never contribute a zero-valued row to the CSV that downstream
    artifact parsing would read as a measurement.  ``csv()`` emits
    measurements only; ``failure_summary()`` renders the failures (run.py
    prints it to stderr and sets the exit code).
    """

    def __init__(self):
        self.rows: List[Dict] = []
        self.failures: List[Dict] = []
        self._trace = None  # TraceWriter for the module now running, if any

    def timeit(self, name: str, fn: Callable, derived_fn=None):
        t0 = time.time()
        out = fn()
        us = (time.time() - t0) * 1e6
        derived = derived_fn(out) if derived_fn else out
        self.rows.append({"name": name, "us_per_call": round(us, 1),
                          "derived": derived})
        return out

    def add(self, name: str, us: float, derived):
        self.rows.append({"name": name, "us_per_call": round(us, 1),
                          "derived": derived})

    def attach_trace(self, writer) -> None:
        """Bind the currently-recording :class:`~repro.obs.export.TraceWriter`
        so a failing module's trace gets sealed instead of truncated."""
        self._trace = writer

    def add_failure(self, name: str, error: BaseException):
        self.failures.append({"name": name,
                              "error": f"{type(error).__name__}: {error}"})
        # ISSUE 9 bugfix: a module that dies mid-run must flush its partial
        # trace as *valid* JSON — whatever the bundle collected before the
        # crash is written out, then abort() seals the event array and
        # renames the tmp file into place with an ``aborted`` stamp
        if self._trace is not None and not self._trace.closed:
            try:
                from repro.obs import telemetry
                tel = telemetry.get_telemetry()
                if tel.enabled:
                    self._trace.write_telemetry(tel)
            except Exception:
                pass  # the seal below must happen even if the flush can't
            self._trace.abort(f"{name}: {type(error).__name__}: {error}")
        self._trace = None

    def csv(self) -> str:
        buf = io.StringIO()
        w = csv.DictWriter(buf, fieldnames=["name", "us_per_call", "derived"])
        w.writeheader()
        for r in self.rows:
            w.writerow(r)
        return buf.getvalue()

    def failure_summary(self) -> str:
        return "\n".join(f"FAILED {f['name']}: {f['error']}"
                         for f in self.failures)
