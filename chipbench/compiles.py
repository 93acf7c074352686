"""Backend-compile events, timestamped, from JAX's monitoring hook.

JAX reports every XLA backend compile as the duration event
``/jax/core/compile/backend_compile_duration``; a program loaded from the
persistent cache or found in a jit's in-memory cache reports none.
"""
from __future__ import annotations

import threading
import time

import jax

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileWatch:
    """Records ``(end time on perf_counter, seconds)`` of every compile."""

    def __init__(self):
        self._lock = threading.Lock()
        self.events = []
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, secs: float, **_) -> None:
        if event == COMPILE_EVENT:
            with self._lock:
                self.events.append((time.perf_counter(), float(secs)))

    def close(self) -> None:
        jax.monitoring.unregister_event_duration_listener(self._on_event)

    def between(self, t0: float, t1: float) -> list:
        """Compile seconds of the compiles that ended in ``[t0, t1]``."""
        with self._lock:
            return [s for t, s in self.events if t0 <= t <= t1]
