"""Compile seconds, compile count and persistent-cache hits, as JAX's
monitoring events report them (copied from the repository's chip smoke
script). A cache hit's retrieval time counts as its compile time."""
from __future__ import annotations

import jax

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class CompileMeter:
    def __init__(self):
        self.seconds = 0.0
        self.count = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == _COMPILE_EVENT:
            self.seconds += duration
            self.count += 1

    def _event(self, event, **_):
        if event == _CACHE_HIT_EVENT:
            self.cache_hits += 1

    def snapshot(self):
        return self.seconds, self.count, self.cache_hits
