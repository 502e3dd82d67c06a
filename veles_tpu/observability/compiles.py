"""CompileMonitor: what JAX compiled, from JAX's own monitoring events.

Seconds spent tracing, lowering and compiling, and the persistent
cache's traffic, counted by listeners on ``jax.monitoring``.  Each
backend compile also leaves an instant ``veles.compile`` in the event
log's ring and, while a profile is being taken, on its timeline, so a
recompile inside a measured window is seen next to the step that
caused it.
"""

from ..logger import events


class CompileMonitor:
    """Seconds JAX spent tracing, lowering and compiling, and its
    persistent-cache traffic, from JAX's own monitoring events.  JAX
    keeps its listeners for the life of the process: make one."""

    _DURATIONS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        from jax import monitoring
        self.compile_seconds = 0.0
        self.cache_hits = self.cache_misses = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, name, seconds, **_):
        if name in self._DURATIONS:
            self.compile_seconds += seconds
        if name == self._DURATIONS[2]:
            events.instant("compile", seconds=round(seconds, 6))

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1
