"""Counts backend compiles while armed.

Copied from ``chip_smoke.CompileClock`` (the ``jax.monitoring`` listener
for backend-compile events), so the count stays the benchmark's own.
"""

from __future__ import annotations

__all__ = ["CompileClock"]

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


class CompileClock:
    """Backend compiles (a persistent-cache read included), their seconds,
    and persistent-cache hits, counted while :attr:`on` is set."""

    def __init__(self):
        import jax

        self.on = False
        self.compiles = 0
        self.seconds = 0.0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **kw):
        if self.on and event == _BACKEND_COMPILE:
            self.compiles += 1
            self.seconds += secs

    def _event(self, event, **kw):
        if self.on and event == _CACHE_HIT:
            self.hits += 1

    def reset(self) -> None:
        self.compiles = 0
        self.seconds = 0.0
        self.hits = 0
