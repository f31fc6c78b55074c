"""Trace/transfer auditor for the fused feed path (ISSUE 7, layer 3).

:class:`EdgeAuditor` wraps one :class:`~repro.kernels.feed_fused.FusedEdgeRunner`
instance and records every jit-boundary crossing:

- each ``run_segment`` launch, with the *recomputed static signature* the
  launch dispatches under (mirroring the tuple ``run_segment`` builds for
  ``_SEG_CACHE``) and the ``TRACE_COUNT`` delta it caused;
- each ``flush_pane`` / ``host_sync`` / ``refresh_membership`` — the
  device→host sync points — tagged with where in the feed they happened.

From that log it asserts the two budgets DESIGN.md §11 documents:

- **retrace budget** — traces observed ≤ distinct static signatures
  observed (every trace is explained by a new signature; nothing retraces
  on a signature already compiled);
- **sync budget** — device→host transfers happen only at pane-stride
  boundaries, at declared events, or at close
  (:data:`~repro.analysis.contracts.HOST_SYNC_POINTS`).

``jax.transfer_guard`` does not fire on the CPU backend (transfers are
zero-copy views there), so the auditor instruments the runner's methods —
the only code paths that materialize device state — instead of relying on
the guard.  On TPU the same audit holds with real transfers underneath.

Use as a context manager::

    runner = ...  # EdgeState.device after a fused open/feed
    with EdgeAuditor(runner, pane_stride=pane) as aud:
        session.feed(batch)
        ...
    aud.assert_retrace_budget()
    aud.assert_sync_budget(closed=True)
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import List, Optional, Set, Tuple

from . import contracts

__all__ = ["AuditEvent", "EdgeAuditor", "TraceBudget"]


def _trace_count() -> int:
    from ..kernels import feed_fused

    return feed_fused.TRACE_COUNT


@dataclasses.dataclass
class AuditEvent:
    kind: str                 # begin_feed | segment | flush_pane |
                              # host_sync | refresh_membership
    tuples: int = 0           # segment length / feed length
    offset: int = 0           # cumulative tuples fed when this happened
    signature: Optional[tuple] = None  # segment launches only
    traces: int = 0           # TRACE_COUNT delta caused by this call
    context: str = "feed"     # feed | event | close (expect() tag)


class EdgeAuditor:
    """Instrument a live FusedEdgeRunner; restore on exit."""

    _METHODS = ("begin_feed", "run_segment", "flush_pane", "host_sync",
                "refresh_membership")

    def __init__(self, runner, pane_stride: Optional[int] = None) -> None:
        self.runner = runner
        self.pane_stride = pane_stride
        self.events: List[AuditEvent] = []
        self.signatures: Set[tuple] = set()
        self.traces = 0
        self._offset = 0          # tuples fed since the audit started
        self._context = "feed"
        self._orig = {}

    # -- lifecycle ---------------------------------------------------------

    def __enter__(self) -> "EdgeAuditor":
        r = self.runner
        for name in self._METHODS:
            self._orig[name] = getattr(r, name)
        r.begin_feed = self._begin_feed
        r.run_segment = self._run_segment
        r.flush_pane = self._flush_pane
        r.host_sync = self._host_sync
        r.refresh_membership = self._refresh_membership
        return self

    def __exit__(self, *exc) -> None:
        self.restore()

    def restore(self) -> None:
        for name, fn in self._orig.items():
            setattr(self.runner, name, fn)
        self._orig.clear()

    @contextlib.contextmanager
    def expect(self, context: str):
        """Declare a sanctioned sync context ('event' or 'close') around
        engine calls that legitimately cross the device→host boundary off
        the pane grid."""
        if context not in contracts.HOST_SYNC_POINTS:
            raise ValueError(f"unknown sync context {context!r}; one of "
                             f"{contracts.HOST_SYNC_POINTS}")
        prev, self._context = self._context, context
        try:
            yield self
        finally:
            self._context = prev

    # -- instrumented methods ----------------------------------------------

    def _begin_feed(self, grouper, state, keys_arr, values, times, sink):
        t0 = _trace_count()
        out = self._orig["begin_feed"](grouper, state, keys_arr, values,
                                       times, sink)
        self._log("begin_feed", tuples=int(keys_arr.shape[0]),
                  traces=_trace_count() - t0)
        return out

    def _run_segment(self, grouper, state, lo: int, hi: int):
        r = self.runner
        sig = self._signature(lo, hi)
        t0 = _trace_count()
        out = self._orig["run_segment"](grouper, state, lo, hi)
        self._offset += hi - lo
        ev = self._log("segment", tuples=hi - lo,
                       traces=_trace_count() - t0)
        ev.signature = sig
        self.signatures.add(sig)
        return out

    def _flush_pane(self, sink):
        t0 = _trace_count()
        out = self._orig["flush_pane"](sink)
        self._log("flush_pane", traces=_trace_count() - t0)
        return out

    def _host_sync(self, grouper):
        t0 = _trace_count()
        out = self._orig["host_sync"](grouper)
        self._log("host_sync", traces=_trace_count() - t0)
        return out

    def _refresh_membership(self, grouper, state):
        t0 = _trace_count()
        out = self._orig["refresh_membership"](grouper, state)
        self._log("refresh_membership", traces=_trace_count() - t0)
        return out

    def _log(self, kind: str, tuples: int = 0, traces: int = 0
             ) -> AuditEvent:
        ev = AuditEvent(kind=kind, tuples=tuples, offset=self._offset,
                        traces=traces, context=self._context)
        self.traces += traces
        self.events.append(ev)
        return ev

    def _signature(self, lo: int, hi: int) -> tuple:
        """Mirror of the static-signature tuple ``run_segment`` keys
        ``_SEG_CACHE`` with — recomputed from runner state *before* the
        launch, so the audit is independent of the cache internals."""
        from ..kernels.feed_fused import _bucket

        r = self.runner
        n_pad = _bucket(hi - lo)
        if r.scheme == "sg":
            r_n, dmax = 0, 0
        else:
            r_n = r._pts.shape[0]
            dmax = r._cands.shape[1]
        reset = r.has_pane and r.pane_tab is None
        return (r.scheme, n_pad, r._w1, r._kcap + 1, r_n, dmax,
                r.has_pane, reset, r.fifo_impl,
                r._slots + 1 if r.has_pane else 0)

    # -- budget assertions -------------------------------------------------

    @property
    def dispatches(self) -> int:
        return sum(1 for e in self.events if e.kind == "segment")

    def assert_retrace_budget(self) -> None:
        """Traces ≤ distinct static signatures: nothing recompiled on a
        signature that was already compiled during this audit."""
        if self.traces > len(self.signatures):
            lines = [f"  {e.kind} @offset={e.offset} sig={e.signature} "
                     f"traces=+{e.traces}"
                     for e in self.events if e.traces]
            raise AssertionError(
                f"retrace budget exceeded: {self.traces} traces for "
                f"{len(self.signatures)} distinct signatures\n"
                + "\n".join(lines))

    def assert_sync_budget(self, closed: bool = False) -> None:
        """Every flush_pane/host_sync sits on a sanctioned sync point:
        a pane-stride boundary, a declared expect('event') /
        expect('close') context, or — when ``closed`` — the trailing
        close-time flush+sync pair."""
        syncs = [e for e in self.events
                 if e.kind in ("flush_pane", "host_sync")]
        tail: List[AuditEvent] = []
        if closed:
            while syncs and syncs[-1].offset == self._offset:
                tail.append(syncs.pop())
                if len(tail) == 2:
                    break
        bad = []
        for e in syncs:
            if e.context in ("event", "close"):
                continue
            if (self.pane_stride
                    and e.offset % self.pane_stride == 0):
                continue
            bad.append(e)
        if bad:
            raise AssertionError(
                "device→host sync off the sanctioned points "
                f"({', '.join(contracts.HOST_SYNC_POINTS)}): "
                + "; ".join(f"{e.kind} @offset={e.offset} "
                            f"context={e.context}" for e in bad))


class TraceBudget:
    """Assert TRACE_COUNT grows by at most ``budget`` inside the block::

        with TraceBudget(3):
            ...  # feeds across three distinct pow2 buckets
    """

    def __init__(self, budget: int, what: str = "block") -> None:
        self.budget = budget
        self.what = what
        self.traces = 0

    def __enter__(self) -> "TraceBudget":
        self._t0 = _trace_count()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.traces = _trace_count() - self._t0
        if exc_type is None and self.traces > self.budget:
            raise AssertionError(
                f"{self.what}: {self.traces} traces > budget "
                f"{self.budget}")
