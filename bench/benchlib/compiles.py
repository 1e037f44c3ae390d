"""Count compiles by JAX's own monitoring events.

An XLA backend compile and a load from the persistent compilation cache
both mean the program met a shape it had not run in this process; either
inside the measured window is set-up work leaking into it."""

from __future__ import annotations

import threading

#: duration events, one per backend compile / persistent-cache load
EVENTS = ("/jax/core/compile/backend_compile_duration",
          "/jax/compilation_cache/cache_retrieval_time_sec")


class CompileCounter:
    """Counts :data:`EVENTS` from the moment :meth:`install` runs."""

    def __init__(self):
        self.count = 0
        self._lock = threading.Lock()

    def install(self) -> "CompileCounter":
        from jax import monitoring

        monitoring.register_event_duration_secs_listener(self._on_duration)
        return self

    def _on_duration(self, event: str, duration: float, **_kw) -> None:
        if event in EVENTS:
            with self._lock:
                self.count += 1
