"""Seconds of set-up inside ``Workflow.initialize``: the program's own
total of its ``veles.workflow.initialize`` spans (every unit's
``initialize``: the loader's data set, the step's parameters), which
needs no clock.  ``None`` where the program keeps no totals."""


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
    return _program_spans().initialize_seconds()
