"""Seconds of set-up spent tracing, lowering and compiling: JAX's own
monitoring durations up to the window's first instant (a run that finds
every program in the persistent cache still traces and lowers)."""


def read(run):
    return run.counters.get("setup_compile_s")
