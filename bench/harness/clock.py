"""Seconds JAX spends compiling or loading programs from the persistent
cache, and how many it compiled or loaded (its ``backend_compile_duration``
event) and how many the cache held."""
from __future__ import annotations

import jax


class CompileClock:
    def __init__(self):
        self.seconds, self.programs, self.cache_hits = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.programs += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
