"""Program spans and counters (DESIGN.md §21).

``span(name, **args)`` is a ``jax.profiler.TraceAnnotation`` named
``"repro." + name``: a TraceMe event on the profiler's host plane, on the
same clock as the device trace.  It is recorded only while a profiler
runs (``jax.profiler.trace``, ``start_trace`` or a capture of a running
process); otherwise it costs one inactive TraceMe, about a microsecond.

The counter table is process-wide and always on: ``count(name, n)``,
``counters()`` and ``reset_counters(*names)``.  ``xla.compiles`` is read
from ``jax.monitoring`` (backend compiles less persistent-cache loads);
its listener is registered on the table's first use, not at import.
``GET /stats`` of the what-if service serves the table.
"""

from __future__ import annotations

import threading
from typing import Dict

import jax

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"

_COUNTS: Dict[str, int] = {}
_LOCK = threading.Lock()
_LISTENING = False


def span(name: str, **args) -> jax.profiler.TraceAnnotation:
    """A host span ``repro.<name>`` carrying ``args`` as its stats."""
    return jax.profiler.TraceAnnotation("repro." + name, **args)


def _add(name: str, n: int) -> None:
    with _LOCK:
        _COUNTS[name] = _COUNTS.get(name, 0) + n


def _on_duration(event: str, duration: float, **_) -> None:
    if event == _BACKEND_COMPILE:
        _add("xla.compiles", 1)


def _on_event(event: str, **_) -> None:
    if event == _CACHE_HIT:
        _add("xla.compiles", -1)


def _listen() -> None:
    global _LISTENING
    with _LOCK:
        if _LISTENING:
            return
        _LISTENING = True
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    jax.monitoring.register_event_listener(_on_event)


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to counter ``name``."""
    _listen()
    _add(name, n)


def counters() -> Dict[str, int]:
    """A copy of the counter table."""
    _listen()
    with _LOCK:
        return dict(_COUNTS)


def reset_counters(*names: str) -> None:
    """Zero the named counters, or every counter when none is named."""
    _listen()
    with _LOCK:
        for name in names or list(_COUNTS):
            _COUNTS[name] = 0
