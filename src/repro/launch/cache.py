"""Where JAX keeps its persistent compilation cache, and what it compiled.

A cold start on the chip compiles every program; the persistent cache
lets a second process (or a second run) load them instead.  The cache
directory is part of the cache's key, so it must not move between runs:
a temporary, per-process or timestamped path never hits.
``CompileClock`` says, per program, which were compiled and which the
cache held.
"""
from __future__ import annotations

import collections
import os

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"

#: The checkout's own cache directory (git-ignored).
CHECKOUT_CACHE = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "..", "..",
    ".jax_cache")


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here.  Otherwise the cache is ``.jax_cache/`` at the
    root of the checkout.
    """
    if os.environ.get(ENV):
        return os.environ[ENV]
    path = os.path.normpath(CHECKOUT_CACHE)
    jax.config.update("jax_compilation_cache_dir", path)
    return path


#: JAX's event around each compile or persistent-cache load; it names the
#: program (``fun_name``).
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
#: JAX's event for a program found in the persistent cache (no name); it
#: fires inside the ``BACKEND_COMPILE`` interval of that program.
CACHE_HIT = "/jax/compilation_cache/cache_hits"


class CompileClock:
    """Seconds JAX spends compiling programs or loading them from the
    persistent cache, and per program name how many it compiled
    (``compiled``) and how many the cache held (``loaded``).

    It listens to JAX's monitoring events from construction until
    ``close()``; compiles are assumed to run one at a time.
    """

    def __init__(self):
        self.seconds = 0.0
        self.compiled: collections.Counter = collections.Counter()
        self.loaded: collections.Counter = collections.Counter()
        self._hit = False
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    @property
    def programs(self) -> int:
        return sum(self.compiled.values()) + sum(self.loaded.values())

    @property
    def cache_hits(self) -> int:
        return sum(self.loaded.values())

    def _event(self, event, **_):
        if event == CACHE_HIT:
            self._hit = True

    def _duration(self, event, duration, fun_name="?", **_):
        if event == BACKEND_COMPILE:
            self.seconds += duration
            (self.loaded if self._hit else self.compiled)[fun_name] += 1
            self._hit = False

    def snapshot(self) -> tuple:
        return self.seconds, self.compiled.copy(), self.loaded.copy()

    def since(self, snap: tuple) -> tuple:
        """(seconds, compiled, loaded) since ``snapshot()`` gave ``snap``."""
        seconds, compiled, loaded = snap
        return (self.seconds - seconds, self.compiled - compiled,
                self.loaded - loaded)

    def close(self) -> None:
        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)
