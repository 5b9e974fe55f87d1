"""Counts XLA compilations through ``jax.monitoring`` (copied from
``chip_smoke.py``'s ``CompileClock``)."""
from __future__ import annotations

import threading


class CompileClock:
    """Programs compiled, seconds spent, and how many came from the
    persistent cache, since the clock was made."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.programs = 0
        self.cache_hits = 0
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration_secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.seconds += duration_secs
                self.programs += 1

    def _on_event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            with self._lock:
                self.cache_hits += 1
