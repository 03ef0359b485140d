"""XLA compile seconds and persistent-cache hits, read from
``jax.monitoring`` events (as the repository's ``chip_smoke.py`` counts
them)."""
from __future__ import annotations


class CompileMeter:
    def __init__(self, jax):
        self.secs = 0.0
        self.compiles = 0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += duration
            self.compiles += 1

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> dict:
        return {"compile_s": self.secs, "compiles": self.compiles,
                "cache_hits": self.hits, "cache_misses": self.misses}
