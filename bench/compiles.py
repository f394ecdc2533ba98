"""Count XLA backend compiles and persistent-cache hits in this process.

JAX reports each backend compile through ``jax.monitoring``; a program
loaded from the persistent compilation cache raises a cache-hit event
instead. The harness reads the count before and after the measured
window: anything compiled inside it fails the run.
"""
from __future__ import annotations


class CompileCounter:
    """Counts XLA backend compiles and persistent-cache hits."""

    def __init__(self):
        import jax.monitoring as mon

        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += secs

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self):
        return self.compiles, self.compile_s, self.cache_hits
