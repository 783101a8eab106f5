"""Compile requests and persistent-cache hits, counted from ``jax.monitoring``.

Every in-process jit miss emits one backend-compile event, whether XLA then
compiles the program or loads it from the persistent cache; a cache hit
emits one more event of its own.
"""
from __future__ import annotations


class CompileCounter:
    def __init__(self):
        import jax.monitoring as mon

        self.requests = 0
        self.cache_hits = 0
        self.names: list[str] = []       # the program of each request

        def on_duration(event, duration, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                self.requests += 1
                self.names.append(str(kw.get("fun_name")))

        def on_event(event, **kw):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        mon.register_event_duration_secs_listener(on_duration)
        mon.register_event_listener(on_event)

    @property
    def compiles(self) -> int:
        """Programs XLA compiled (requests the persistent cache missed)."""
        return self.requests - self.cache_hits
