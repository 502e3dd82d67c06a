"""Unified observability core.

Four pieces, one picture (the reference platform's shared event stream
plus web status server, rebuilt TPU-native):

- :mod:`~veles_tpu.observability.registry` — the process-global
  :class:`MetricsRegistry` (counters/gauges/histograms with labels) that
  training AND serving record into; exposed as Prometheus text at the
  status server's ``/metrics`` and merged into ``/status`` JSON.
- :mod:`~veles_tpu.observability.profiler` — :class:`StepProfiler`,
  which wraps a workflow's training step and splits each step into
  data-wait / host / device-compute time, counts jit recompiles, tracks
  examples/sec and device-memory watermarks.
- :mod:`~veles_tpu.observability.trace` — trace-context propagation so
  per-process ``events-*.jsonl`` files from a distributed run share one
  ``trace_id`` and merge into a single Perfetto timeline
  (``tools/merge_traces.py``).

- :mod:`~veles_tpu.observability.compiles` — :func:`compiles.monitor`,
  the process's one :class:`CompileMonitor`: JAX's compile phases from
  its own monitoring events, filed among the spans as
  ``veles.compile.trace`` / ``.lower`` / ``.xla`` / ``.cache_load``.

The spans themselves (``events.timed``: ring, totals, the profiler's
timeline) live in :mod:`veles_tpu.logger`.

``registry`` and ``trace`` are stdlib-only and import nothing from
veles_tpu (so ``logger``/``units`` can use them cycle-free); the
profiler — which needs the logger — loads lazily via attribute access.
"""

from .registry import (MetricsRegistry, REGISTRY, counter, gauge,  # noqa
                       histogram, render_prometheus)
from . import trace                                                # noqa


def __getattr__(name):
    # lazy: profiler imports logger, which imports observability.trace —
    # resolving it on demand keeps the package importable from logger.py
    if name == "StepProfiler":
        from .profiler import StepProfiler
        return StepProfiler
    if name == "profiler":
        from . import profiler
        return profiler
    if name in ("flight", "attribution"):
        import importlib
        return importlib.import_module("." + name, __name__)
    if name == "FlightRecorder":
        from .flight import FlightRecorder
        return FlightRecorder
    raise AttributeError(name)
