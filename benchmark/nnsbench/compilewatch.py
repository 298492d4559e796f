"""Counts what JAX compiles, from ``jax.monitoring``: seconds tracing,
lowering and in the backend compiler, backend compile requests, and
persistent-cache hits and misses. ``setup_s``'s split and "nothing
compiles inside the window" are read from here."""
from __future__ import annotations

import threading


class CompileWatch:
    def __init__(self):
        self._lock = threading.Lock()
        self.seconds = {}          # event suffix -> summed seconds
        self.backend_compiles = 0  # persistent-cache hits count too
        self.cache_hits = 0
        self.cache_misses = 0

    def install(self):
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)
        return self

    def _on_duration(self, event, duration, **_):
        if not event.startswith("/jax/core/compile/"):
            return
        key = event.rsplit("/", 1)[-1]
        with self._lock:
            self.seconds[key] = self.seconds.get(key, 0.0) + duration
            if key == "backend_compile_duration":
                self.backend_compiles += 1

    def _on_event(self, event, **_):
        with self._lock:
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.cache_misses += 1

    def snapshot(self) -> dict:
        with self._lock:
            return {"seconds": dict(self.seconds),
                    "backend_compiles": self.backend_compiles,
                    "cache_hits": self.cache_hits,
                    "cache_misses": self.cache_misses}
