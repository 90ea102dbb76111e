"""Seconds spent tracing, lowering and compiling, and persistent-cache hits
and misses, from JAX's own monitoring events while the monitor is open.

Copied from the program's ``chip_smoke.CompileMonitor`` so that the yardstick
does not move when the program does.
"""
from __future__ import annotations


class CompileMonitor:
    DURATION_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                       "/jax/core/compile/jaxpr_to_mlir_module_duration",
                       "/jax/core/compile/backend_compile_duration")
    BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.compiles = 0        # backend compiles, cache hits or not
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event in self.DURATION_EVENTS:
            self.seconds += secs
        if event == self.BACKEND_COMPILE:
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def snapshot(self) -> dict:
        return {"compile_s": self.seconds, "compiles": self.compiles,
                "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses}
