"""From a profiler trace to device busy time, idle gaps and kernel time.

The JAX profiler writes ``<dir>/plugins/profile/<run>/<host>.xplane.pb``.
:func:`extract` reads it with ``jax.profiler.ProfileData`` into plain
event lists; everything after that is arithmetic on intervals, which the
tests check on a trace recorded on the chip (``bench/fixtures``).

Clocks: device and host events of one trace share the profiler's clock
(nanoseconds).  The program's spans are on ``time.perf_counter``.  The
harness enters one ``jax.profiler.TraceAnnotation`` named
:data:`ANCHOR` between two ``perf_counter`` reads, which gives the offset
between the two clocks to within the annotation's few microseconds.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Sequence, Tuple

import numpy as np

__all__ = ["ANCHOR", "find_xplane", "extract", "union", "busy_seconds",
           "idle_gaps", "label_gaps", "top_ops", "module_seconds"]

ANCHOR = "bench.anchor"
OPS_LINE = "XLA Ops"
_DEVICE = re.compile(r"^/device:TPU:\d+$")
MODULES_LINE = "XLA Modules"


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def extract(path: str) -> Dict:
    """{"devices": {plane: {"ops": [...], "modules": [...]}},
    "anchors": [(t0, t1)]}, every event as (name, start s, end s)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices: Dict[str, Dict[str, List]] = {}
    anchors = []
    for plane in pd.planes:
        if _DEVICE.match(plane.name):
            d = devices.setdefault(plane.name, {"ops": [], "modules": []})
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(
                    line.name)
                if key is None:
                    continue
                d[key].extend((short_name(ev.name), ev.start_ns * 1e-9,
                               (ev.start_ns + ev.duration_ns) * 1e-9)
                              for ev in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == ANCHOR:
                        anchors.append((ev.start_ns * 1e-9,
                                        (ev.start_ns + ev.duration_ns)
                                        * 1e-9))
    return {"devices": devices, "anchors": anchors}


def short_name(name: str) -> str:
    """An operation's name without its HLO text: ``%fusion.3 = f32[..]
    fusion(..)`` becomes ``fusion.3``; a module keeps its name up to the
    fingerprint, ``jit_seg(123)`` becoming ``jit_seg``."""
    head = name.split(" = ", 1)[0].lstrip("%")
    return head.split("(", 1)[0]


def union(intervals: Sequence[Tuple[float, float]]) -> np.ndarray:
    """Merged, sorted (k, 2) array of the intervals' union."""
    if not len(intervals):
        return np.zeros((0, 2))
    a = np.asarray(intervals, dtype=np.float64)
    a = a[np.argsort(a[:, 0], kind="stable")]
    ends = np.maximum.accumulate(a[:, 1])
    new = np.concatenate([[True], a[1:, 0] > ends[:-1]])
    starts = a[new, 0]
    idx = np.flatnonzero(new)
    stops = ends[np.concatenate([idx[1:] - 1, [a.shape[0] - 1]])]
    return np.stack([starts, stops], axis=1)


def _clip(u: np.ndarray, w0: float, w1: float) -> np.ndarray:
    if not u.shape[0]:
        return u
    c = np.stack([np.maximum(u[:, 0], w0), np.minimum(u[:, 1], w1)], 1)
    return c[c[:, 1] > c[:, 0]]


def busy_seconds(events, w0: float, w1: float) -> float:
    """Seconds of [w0, w1] in which some device operation ran."""
    u = _clip(union([(e[1], e[2]) for e in events]), w0, w1)
    return float((u[:, 1] - u[:, 0]).sum())


def idle_gaps(events, w0: float, w1: float) -> List[Tuple[float, float]]:
    """The stretches of [w0, w1] in which no device operation ran."""
    u = _clip(union([(e[1], e[2]) for e in events]), w0, w1)
    edges = np.concatenate([[w0], u.ravel(), [w1]]).reshape(-1, 2)
    return [(float(a), float(b)) for a, b in edges if b > a]


def label_gaps(gaps, spans, offset: float, k: int = 10):
    """Idle seconds by what the host was doing: each gap is named by the
    innermost host span (``(name, t0, t1)`` on ``perf_counter``, properly
    nested) covering its midpoint, and the ``k`` names with the most idle
    seconds are returned.  ``offset`` maps profiler seconds to
    ``perf_counter`` seconds."""
    order = sorted(spans, key=lambda s: (s[1], -s[2]))
    mids = sorted(((a + b) / 2 + offset, b - a) for a, b in gaps)
    tot: Dict[str, float] = {}
    stack: List = []
    i = 0
    for mid, dur in mids:
        while i < len(order) and order[i][1] <= mid:
            while stack and stack[-1][2] < order[i][1]:
                stack.pop()
            stack.append(order[i])
            i += 1
        while stack and stack[-1][2] < mid:
            stack.pop()
        name = stack[-1][0] if stack else "no span"
        tot[name] = tot.get(name, 0.0) + dur
    return [[n, s] for n, s in sorted(tot.items(), key=lambda x: -x[1])[:k]]


def top_ops(events, w0: float, w1: float, k: int = 10):
    """The ``k`` operation names with the most device seconds in
    [w0, w1] (operations nested in a loop count under their own name)."""
    tot: Dict[str, float] = {}
    for name, a, b in events:
        d = min(b, w1) - max(a, w0)
        if d > 0:
            tot[name] = tot.get(name, 0.0) + d
    return [[n, s] for n, s in sorted(tot.items(), key=lambda x: -x[1])[:k]]


def module_seconds(modules, prefix: str, w0: float, w1: float) -> float:
    """Device seconds of the programs whose name starts with ``prefix``."""
    return float(sum(min(b, w1) - max(a, w0) for n, a, b in modules
                     if n.startswith(prefix) and min(b, w1) > max(a, w0)))
