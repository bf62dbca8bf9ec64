"""Seconds XLA spends compiling, from JAX's own monitoring events (a copy
of the repository's chip-smoke clock), plus the number of compiles and of
persistent-cache hits and misses."""

from __future__ import annotations


class CompileClock:
    def __init__(self):
        from jax import monitoring
        self.seconds = 0.0
        self.compiles = 0
        self.hits = 0
        self.misses = 0

        def on_duration(name, secs, **_):
            if name == "/jax/core/compile/backend_compile_duration":
                self.seconds += secs
                self.compiles += 1

        def on_event(name, **_):
            if name == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif name == "/jax/compilation_cache/cache_misses":
                self.misses += 1

        monitoring.register_event_duration_secs_listener(on_duration)
        monitoring.register_event_listener(on_event)

    def snapshot(self) -> tuple[float, int]:
        return self.seconds, self.compiles
