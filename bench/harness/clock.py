"""Backend compiles and persistent-cache hits, from JAX's own monitoring
events (copied from the program's ``chip_smoke.CompileClock``)."""
from __future__ import annotations

import jax

COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


class CompileClock:
    def __init__(self):
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == COMPILE:
            self.seconds += secs
            self.compiles += 1

    def _event(self, event, **_):
        if event == CACHE_HIT:
            self.cache_hits += 1
