"""Compile accounting read from ``jax.monitoring`` (after ``chip_smoke.py``'s
``CompileMeter``), and a clock that waits for the device.

JAX reports ``backend_compile_duration`` both for a real XLA compile and
for an executable loaded from the persistent compilation cache, and
``cache_hits`` for the latter alone, so XLA compiles are the difference.
"""

from __future__ import annotations

import jax

_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


class CompileMeter:
    """Running totals of traces, lowerings, backend compiles and persistent
    cache hits in this process.  A listener cannot be taken back, so make
    one meter per process."""

    def __init__(self):
        self.seconds = 0.0
        self.traces = 0
        self.backend = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **_) -> None:
        if event in (_TRACE, _LOWER, _BACKEND):
            self.seconds += duration
            if event == _TRACE:
                self.traces += 1
            elif event == _BACKEND:
                self.backend += 1

    def _on_event(self, event: str, **_) -> None:
        if event == _CACHE_HIT:
            self.cache_hits += 1

    def snapshot(self) -> dict:
        return {"compile_s": self.seconds, "traces": self.traces,
                "xla_compiles": self.backend - self.cache_hits,
                "cache_loads": self.cache_hits}

    @staticmethod
    def delta(before: dict, after: dict) -> dict:
        return {k: after[k] - before[k] for k in before}
