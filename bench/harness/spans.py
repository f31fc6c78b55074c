"""Span arithmetic for the per-layer readers.

A span is ``(name, t0, t1, args)`` on ``time.perf_counter``: the
program's own tracer spans (``session.*``, ``fused.*``) and the
benchmark's (``bench.*``)."""

from __future__ import annotations

from typing import Iterable, List, Tuple

__all__ = ["inside", "named", "total", "covered"]


def inside(spans: Iterable, window: Tuple[float, float]) -> List:
    """Spans that start inside the window."""
    w0, w1 = window
    return [s for s in spans if w0 <= s[1] < w1]


def named(spans: Iterable, *names: str) -> List:
    return [s for s in spans if s[0] in names]


def total(spans: Iterable) -> float:
    return sum(s[2] - s[1] for s in spans)


def covered(parent, children: Iterable) -> float:
    """Seconds of ``parent`` covered by the union of ``children``."""
    iv = sorted((max(c[1], parent[1]), min(c[2], parent[2]))
                for c in children if c[2] > parent[1] and c[1] < parent[2])
    out, end = 0.0, parent[1]
    for a, b in iv:
        a = max(a, end)
        if b > a:
            out += b - a
            end = b
    return out
