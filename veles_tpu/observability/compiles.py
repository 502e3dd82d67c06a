"""CompileMonitor: what JAX compiled, filed as the program's own spans.

JAX reports the start and end of each of its compile phases through
``jax.monitoring``.  The process's one monitor (:func:`monitor`, which
``backends.apply_compilation_cache_config`` installs wherever the program
configures JAX) files each phase as a span of the event log
(``veles_tpu.logger``: the ring, the totals, the JSONL file) with its
real start and length, under the program span open on the thread that
compiled:

- ``compile.trace`` [``fun``]: one span for each outermost trace.  The
  traces nested in it (every jitted ``jnp`` function traced inside the
  outer one) are not filed: they are counted in its ``nested``;
- ``compile.lower`` [``module``]: a jaxpr lowered to an MLIR module.
  The traces made while it lowers (lowering rules written as ``jnp``
  functions, the PRNG's and a Pallas kernel's among them: thousands of
  some microseconds each in a decoder's set-up) are its time: they are
  counted in its ``traces``, not filed;
- ``compile.xla`` [``module``, ``cache``]: XLA compiled the module.
  ``cache`` is ``miss`` where JAX asked its persistent cache and missed,
  ``off`` where it had none to ask (no directory is configured) or where
  nothing compiled now is written (``backends.compiles_not_persisted``);
- ``compile.cache_load`` [``module``, ``cache="jax"``]: JAX's persistent
  cache served the module (``compilecache`` files the same name with
  ``cache="veles"`` for its own store).

While a profile is being taken, each span also leaves an instant on its
timeline, so a recompile inside a traced window is seen beside the step
that caused it.  The listeners run only while JAX compiles, in O(1) for
each of its events: a step that compiles nothing pays nothing.
"""

import math
import threading
import time

from ..logger import SPAN_PREFIX, events

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
#: JAX's phase -> (span name, what the span calls the program)
PHASES = {TRACE: ("compile.trace", "fun"), LOWER: ("compile.lower", "module"),
          BACKEND_COMPILE: ("compile.xla", "module")}
_ASKED = "/jax/compilation_cache/compile_requests_use_cache"
_HIT = "/jax/compilation_cache/cache_hits"
_WRITTEN = "/jax/compilation_cache/cache_misses"


class _Compiling(threading.local):
    """What JAX has said so far on one thread: the traces and lowerings
    it has opened and not closed, the traces closed inside an open trace
    (``nested``) or lowering (``lowered``), and whether the backend
    compile under way asked the persistent cache and was served."""

    def __init__(self):
        self.open = {TRACE: 0, LOWER: 0}
        self.nested = self.lowered = 0
        self.asked = self.hit = False


class CompileMonitor:
    """Files JAX's compile phases as spans and keeps their totals:
    ``compile_seconds`` (the filed spans' seconds), the persistent cache's
    ``cache_hits`` and ``cache_misses`` (entries written), and its own
    cost, ``jax_events`` received and ``listener_seconds`` spent on them.
    JAX keeps its listeners for the life of the process, so a second
    monitor would file every span twice: use :func:`monitor`."""

    def __init__(self):
        import jax
        from jax import monitoring
        self.compile_seconds = 0.0
        self.cache_hits = self.cache_misses = 0
        self.jax_events = 0
        self.listener_seconds = 0.0
        self._thread = _Compiling()
        self._config = jax.config
        self._annotation = jax.profiler.TraceAnnotation
        # JAX announces a phase's start as a scalar (its start time)
        monitoring.register_scalar_listener(self._opened)
        monitoring.register_event_time_span_listener(self._closed)
        monitoring.register_event_listener(self._event)

    def _opened(self, name, value, **_):
        t0 = time.perf_counter()
        if name in self._thread.open:
            self._thread.open[name] += 1
        self._spent(t0)

    def _event(self, name, **_):
        t0 = time.perf_counter()
        if name == _ASKED:
            self._thread.asked = True
        elif name == _HIT:
            self._thread.hit = True
            self.cache_hits += 1
        elif name == _WRITTEN:
            self.cache_misses += 1
        self._spent(t0)

    def _closed(self, name, start, end, fun_name="?", **_):
        t0 = time.perf_counter()
        if name in PHASES:
            self._file(name, start, end, str(fun_name))
        self._spent(t0)

    def _spent(self, t0):
        self.jax_events += 1
        self.listener_seconds += time.perf_counter() - t0

    def _file(self, phase, start, end, fun_name):
        thread = self._thread
        counts = None
        if phase in thread.open:
            thread.open[phase] = max(thread.open[phase] - 1, 0)
        if phase == TRACE:
            if thread.open[LOWER]:      # a lowering rule's trace
                thread.lowered += 1
                return
            if thread.open[TRACE]:      # inside another trace
                thread.nested += 1
                return
            counts, thread.nested = {"nested": thread.nested}, 0
        elif phase == LOWER:
            counts, thread.lowered = {"traces": thread.lowered}, 0
        name, key = PHASES[phase]
        info = {key: fun_name}
        if phase == BACKEND_COMPILE:
            if thread.hit:
                name, info["cache"] = "compile.cache_load", "jax"
            elif thread.asked and self._persisting():
                info["cache"] = "miss"
            else:
                info["cache"] = "off"
            thread.asked = thread.hit = False
        seconds = end - start
        self.compile_seconds += seconds
        events.span(name, seconds, start_ns=int(start * 1e9), counts=counts,
                    **info)
        with self._annotation(SPAN_PREFIX + name, **info):
            pass        # an instant, where a profile is being taken

    def _persisting(self):
        """JAX has a persistent cache to read and writes what it compiles
        now to it (a directory is set, and the floor on a compile's
        seconds is finite: ``backends.compiles_not_persisted`` makes it
        infinite).  Without a directory JAX still asks, and finds
        nothing."""
        config = self._config
        return bool(config.jax_compilation_cache_dir) and not math.isinf(
            config.jax_persistent_cache_min_compile_time_secs)


_monitor = None
_lock = threading.Lock()


def monitor():
    """The process's one :class:`CompileMonitor`, made on the first
    call."""
    global _monitor
    if _monitor is None:
        with _lock:
            if _monitor is None:
                _monitor = CompileMonitor()
    return _monitor
