"""Span tracer: wall-clock intervals + instant events, Perfetto-shaped.

Spans are recorded against a monotonic clock (``time.perf_counter``)
anchored to one wall-clock instant at tracer construction, so exported
Chrome-trace timestamps are drift-free within a run and still carry an
absolute ``trace_start_wall`` in metadata.  Each enabled span also holds a
``jax.profiler.TraceAnnotation`` of its name from open to ``done()``, so a
profile taken around the session shows the spans in its host plane, on
the device events' clock.  The disabled path is a pair of
shared singletons (:data:`NULL_TRACER` handing out :data:`NULL_SPAN`):
no allocation, no clock read, no list append — the overhead contract in
DESIGN.md §14.
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional

__all__ = ["Span", "Tracer", "NullTracer", "NULL_TRACER", "NULL_SPAN"]


class Span:
    """One traced interval.  Used as a context manager; ``set(**kw)``
    attaches args visible in the Perfetto detail pane.

    The span owns its profiler annotation: ``done()`` closes it, and a
    span dropped without ``done()`` closes it as it is freed, so no
    annotation outlives its span."""

    __slots__ = ("name", "cat", "t0", "t1", "args", "_tracer", "_ann")

    def __init__(self, tracer: "Tracer", name: str, cat: str,
                 args: Optional[Dict]):
        self._tracer = tracer
        self.name = name
        self.cat = cat
        self.args = args
        self._ann = tracer.annotation(name)
        self._ann.__enter__()
        self.t0 = time.perf_counter()
        self.t1 = -1.0

    def set(self, **kw) -> "Span":
        if self.args is None:
            self.args = kw
        else:
            self.args.update(kw)
        return self

    def done(self) -> None:
        if self.t1 < 0.0:
            self.t1 = time.perf_counter()
            self._ann.__exit__(None, None, None)
            self._ann = None
            self._tracer.spans.append(self)

    def __enter__(self) -> "Span":
        return self

    def __exit__(self, *exc) -> bool:
        self.done()
        return False


class Tracer:
    """Collects :class:`Span`s and instant events in memory."""

    def __init__(self) -> None:
        # imported here: only an enabled tracer touches the profiler
        from jax.profiler import TraceAnnotation

        self.annotation = TraceAnnotation
        self.t0 = time.perf_counter()
        self.wall0 = time.time()
        self.spans: List[Span] = []
        self.instants: List[tuple] = []  # (t, name, cat, args)

    @property
    def enabled(self) -> bool:
        return True

    def span(self, name: str, cat: str = "run", **args) -> Span:
        return Span(self, name, cat, args or None)

    def instant(self, name: str, cat: str = "run", **args) -> None:
        self.instants.append((time.perf_counter(), name, cat, args or None))

    def rel_us(self, t: float) -> float:
        """Monotonic instant → microseconds since trace start."""
        return (t - self.t0) * 1e6


class NullTracer:
    """Disabled tracer: every call is a constant-return no-op."""

    __slots__ = ()
    spans: List = []      # shared, always empty: never appended to
    instants: List = []
    t0 = 0.0
    wall0 = 0.0

    @property
    def enabled(self) -> bool:
        return False

    def span(self, name: str, cat: str = "run", **args) -> "_NullSpan":
        return NULL_SPAN

    def instant(self, name: str, cat: str = "run", **args) -> None:
        return None

    def rel_us(self, t: float) -> float:
        return 0.0


class _NullSpan:
    """Shared no-op span — ``span()`` on the null tracer allocates nothing."""

    __slots__ = ()

    def set(self, **kw) -> "_NullSpan":
        return self

    def done(self) -> None:
        return None

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False


NULL_SPAN = _NullSpan()
NULL_TRACER = NullTracer()
