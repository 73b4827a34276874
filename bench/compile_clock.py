"""Compiles seen by JAX, for set-up and for the measured window.

Copied from the program's ``chip_smoke.CompileClock``: it counts, through
``jax.monitoring``, the seconds JAX spent tracing, lowering and compiling,
the programs it compiled, and the programs it found in the persistent
cache.
"""
from __future__ import annotations


class CompileClock:
    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_kw):
        if event in self.EVENTS:
            self.seconds += duration
            self.compiles += event == self.EVENTS[-1]

    def _event(self, event, **_kw):
        self.cache_hits += event == "/jax/compilation_cache/cache_hits"

    def mark(self):
        return self.seconds, self.compiles, self.cache_hits

    def since(self, mark) -> dict:
        s, c, h = mark
        return {"compile_s": self.seconds - s, "compiled": self.compiles - c,
                "from_cache": self.cache_hits - h}
