"""Milliseconds an epoch that the chip which idles most waits while
the train step finishes a class: idle gaps of the traced window inside
``veles.step.run`` after its dispatch returned (``veles.step.
flush_metrics``, ``veles.step.sync_weights`` and the step's own time
between them).  ``None`` where the program keeps no spans or they cannot
be laid on the trace's clock (``benchmark/program_spans.py``)."""


def _program_spans():
    """``benchmark/program_spans.py``, found by path like every file of
    the benchmark (one module for all the readers that use it)."""
    import importlib.util
    import os
    import sys
    name = "benchmark_program_spans_py"
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "program_spans.py"))
        sys.modules[name] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(sys.modules[name])
    return sys.modules[name]


def read(run):
    idle = _program_spans().idle_ms_per_epoch(run)
    return None if idle is None else idle["step_finish"]
