"""Process-wide XLA compile accounting from ``jax.monitoring`` (copied
from ``chip_smoke.CompileWatch``): every compile request is either a
backend compile or a read of the persistent cache; both are counted,
with their seconds, and cache hits apart."""

import threading

_BACKEND = "/jax/core/compile/backend_compile_duration"
_HIT = "/jax/compilation_cache/cache_hits"


class CompileWatch:
    def __init__(self):
        import jax

        self._lock = threading.Lock()
        self._c = {"compiles": 0, "compile_s": 0.0, "cache_hits": 0}
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event == _BACKEND:
            with self._lock:
                self._c["compiles"] += 1
                self._c["compile_s"] += duration

    def _on_event(self, event, **_):
        if event == _HIT:
            with self._lock:
                self._c["cache_hits"] += 1

    def snapshot(self) -> dict:
        with self._lock:
            return dict(self._c)

    def since(self, before: dict) -> dict:
        now = self.snapshot()
        return {k: now[k] - before[k] for k in now}
