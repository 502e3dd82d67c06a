"""Milliseconds an epoch that the chip which idles most waits outside
the train step: idle gaps of the traced window whose innermost covering
program span is not ``veles.step.run`` nor below it (the decision, the
epoch clock, the repeater, the engine's scheduling between units).
``None`` where the program keeps no spans or they cannot be laid on the
trace's clock (``benchmark/program_spans.py``)."""


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
    return None if idle is None else idle["engine"]
