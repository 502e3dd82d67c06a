"""The program's compile spans in set-up, for the readers of the layer
``Caches``.

The program files each of JAX's compile phases as a span of its own
(``veles_tpu/observability/compiles.py``): ``veles.compile.trace`` (one
for each outermost trace), ``.lower``, ``.xla`` (XLA compiled a module)
and ``.cache_load`` (a persistent cache served one).  A phase's set-up
seconds are the wall time that the UNION of its spans covers from the
set-up's first instant (``run.t_start``) to the traced window's
(``run.reduced.t0``, laid on the ring's clock by
``program_spans.anchor``).  A span that closes later, such as those of
the comparison with the reference after the window, counts nowhere.

``None`` where the program files none of these spans, where the ring
cannot be laid on the trace's clock, and where the ring has dropped a
span of the phase (the totals count more of it than the ring holds).
"""

import time

PREFIX = "veles.compile."
#: reader's phase -> the span that files it
PHASES = {"trace": PREFIX + "trace", "lower": PREFIX + "lower",
          "xla_compile": PREFIX + "xla", "cache_load": PREFIX + "cache_load"}


def program_spans():
    """``benchmark/program_spans.py``, found by path like every file of
    the benchmark (one module for all the readers that use it)."""
    import importlib.util
    import os
    import sys
    name = "benchmark_program_spans_py"
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "program_spans.py"))
        sys.modules[name] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(sys.modules[name])
    return sys.modules[name]


def setup_start_ns(run):
    """The set-up's first instant on the wall clock (``run.t_start`` is
    on ``time.perf_counter``)."""
    return time.time_ns() - round((time.perf_counter() - run.t_start) * 1e9)


def setup_seconds(run, phase, records=None, totals=None):
    """Seconds of set-up covered by ``phase``'s spans (see above), from
    the program's ring and totals unless ``records`` and ``totals`` are
    given."""
    spans = program_spans()
    if records is None:
        log = spans.event_log()
        if log is None:
            return None
        records, totals = spans.ring(), log.totals()
    if not any(name in totals for name in PHASES.values()) \
            or run.reduced is None:
        return None
    name = PHASES[phase]
    kept = [r for r in records if r["name"] == name]
    if len(kept) < totals.get(name, {}).get("count", 0):
        return None
    found = spans.anchor(run.reduced.spans, records)
    if found is None or found[1] > spans.MAX_SPREAD_NS:
        return None
    begin, end = setup_start_ns(run), run.reduced.t0 + found[0]
    covered = run.tracing.union(
        [max(r["start_ns"], begin), r["start_ns"] + r["duration_ns"]]
        for r in kept if begin < r["start_ns"] + r["duration_ns"] <= end)
    return sum(hi - lo for lo, hi in covered) / 1e9
