"""Program building seen through ``jax.monitoring`` (copied from ``chip_smoke.Clock``).

``BuildClock`` counts the XLA executables built, compiled or loaded from the
persistent cache while it is open (``cache_hits`` of them loaded), the
seconds spent building them (``compile_seconds``, cache loads included) and
the seconds spent tracing to a jaxpr and lowering to MLIR
(``trace_seconds``; nested traces overlap).
"""

from __future__ import annotations

import jax

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_TRACE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                 "/jax/core/compile/jaxpr_to_mlir_module_duration")
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class BuildClock:
    """Counts program building between ``start()`` and ``stop()``."""

    def __init__(self):
        self.compiles = self.cache_hits = 0
        self.compile_seconds = self.trace_seconds = 0.0
        self._on = False

    def _on_duration(self, event: str, secs: float, **_kw) -> None:
        if not self._on:
            return
        if event == _COMPILE_EVENT:
            self.compiles += 1
            self.compile_seconds += secs
        elif event in _TRACE_EVENTS:
            self.trace_seconds += secs

    def _on_event(self, event: str, **_kw) -> None:
        if self._on and event == _CACHE_HIT_EVENT:
            self.cache_hits += 1

    def start(self) -> "BuildClock":
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        self._on = True
        return self

    def stop(self) -> "BuildClock":
        self._on = False
        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)
        return self

    def record(self) -> dict:
        return {"compiles": self.compiles, "cache_hits": self.cache_hits,
                "compile_seconds": self.compile_seconds,
                "trace_seconds": self.trace_seconds}
