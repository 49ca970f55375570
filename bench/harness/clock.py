"""Compilations counted from JAX's own monitoring events."""
from __future__ import annotations

import jax

_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


class CompileCounter:
    """Counts backend compiles and persistent-cache loads (either means a
    program was made in this process) and the seconds spent compiling."""

    def __init__(self):
        self.compiles = 0
        self.cache_loads = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == _COMPILE:
            self.compiles += 1
            self.seconds += duration

    def _event(self, event, **_):
        if event == _CACHE_HIT:
            self.cache_loads += 1

    @property
    def programs(self) -> int:
        return self.compiles + self.cache_loads
