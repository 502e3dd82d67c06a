"""Seconds of set-up in which XLA compiled a module that no persistent
cache served: the union of the program's ``veles.compile.xla`` spans
(their ``cache`` says ``miss`` or ``off``), from the set-up's first
instant to the traced window's (``benchmark/compile_spans.py``).
``None`` where the program files no compile spans."""


def _compile_spans():
    """``benchmark/compile_spans.py``, found by path like every file of
    the benchmark (one module for all the readers that use it)."""
    import importlib.util
    import os
    import sys
    name = "benchmark_compile_spans_py"
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "compile_spans.py"))
        sys.modules[name] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(sys.modules[name])
    return sys.modules[name]


def read(run):
    return _compile_spans().setup_seconds(run, "xla_compile")
