"""Compile accounting from jax's own monitoring events.

A copy of ``chip_smoke.CompileMeter`` (PERF.md, Open questions, lists the
original): the benchmark keeps its own so that no later change to the
program can move what ``setup_compile_s`` and the in-window compile count
mean.
"""

from __future__ import annotations


class CompileMeter:
    """Sums trace, lowering and backend-compile (or cache-retrieval)
    seconds and counts persistent-cache hits and misses."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.compiles = 0       # backend compiles or cache retrievals
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **_):
        if event in self.EVENTS:
            self.seconds += seconds
            self.compiles += event == self.EVENTS[-1]

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> dict:
        return {"seconds": self.seconds, "compiles": self.compiles,
                "hits": self.hits, "misses": self.misses}
