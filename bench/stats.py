"""Percentiles and JAX's compile clock, kept with the benchmark."""
from __future__ import annotations

import math


def percentile(xs, q: float) -> float:
    """Nearest rank: the smallest element whose cumulative rank reaches
    q% (rank = ceil(q/100 * n), at least 1)."""
    n = len(xs)
    if n == 0:
        raise ValueError("percentile of no samples")
    s = sorted(xs)
    return float(s[min(max(int(math.ceil(q / 100.0 * n)), 1), n) - 1])


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling (the compile span
    includes persistent-cache retrieval), backend compiles, and
    persistent-cache hits, from ``jax.monitoring`` events."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.secs = dict.fromkeys(self.EVENTS, 0.0)
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration_secs, **_kw):
        if event in self.secs:
            self.secs[event] += duration_secs
            self.compiles += event == self.EVENTS[2]

    def _event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def total(self) -> float:
        return sum(self.secs.values())
