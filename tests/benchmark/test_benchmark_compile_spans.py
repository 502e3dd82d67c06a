"""The readers of set-up's compile phases (``benchmark/compile_spans.py``
and ``setup_{trace,lower,xla_compile,cache_load}_s``): exact arithmetic
on the hand-made trace and ring of ``test_benchmark_program_spans.py``
with compile spans added, and a traced rehearsal on the CPU."""

import pytest

from benchlib import load
from test_benchmark_program_spans import OFFSET, FakeLog, run  # noqa: F401

compile_spans = load("compile_spans.py")

#: the anchor lays every ``veles.step.run`` 1 ns after its bench span
SHIFT = OFFSET + 1
#: the set-up's first instant, on the trace's clock
SETUP_START = -1_000
PHASES = ("trace", "lower", "xla_compile", "cache_load")


def compile_ring():
    """Compile spans on the trace's clock, as the ring holds them."""
    spans = {"trace": [(-1_200, -900),     # began before set-up: clipped
                       (-800, -600), (-700, -650), (-650, -500),
                       (990, 1_005),       # closes inside the window
                       (2_500, 2_600)],    # the reference, after it
             "lower": [(-400, -300)],
             "cache_load": [(-200, -150), (-150, -100)]}
    return [{"name": compile_spans.PHASES[phase], "seq": 1_000 + i,
             "parent": None, "start_ns": SHIFT + start,
             "duration_ns": end - start}
            for phase, pairs in spans.items()
            for i, (start, end) in enumerate(pairs)]


def totals_of(records):
    totals = {}
    for r in records:
        entry = totals.setdefault(r["name"], {"count": 0})
        entry["count"] += 1
    return totals


@pytest.fixture
def started(monkeypatch):
    monkeypatch.setattr(compile_spans, "setup_start_ns",
                        lambda run: SHIFT + SETUP_START)


def test_a_phase_is_the_union_of_its_spans_in_set_up(run, started):
    records = run.ring + compile_ring()
    totals = totals_of(records)
    seconds = {phase: compile_spans.setup_seconds(run, phase, records,
                                                  totals)
               for phase in PHASES}
    # trace: [-1,000, -900) of the first, then [-800, -500) as one
    assert seconds == {"trace": 400 / 1e9, "lower": 100 / 1e9,
                       "xla_compile": 0.0, "cache_load": 100 / 1e9}
    assert all(isinstance(value, float) for value in seconds.values())


def test_nothing_to_read_gives_none(run, started):
    records = run.ring + compile_ring()
    totals = totals_of(records)
    # a program that files no compile spans
    assert compile_spans.setup_seconds(run, "trace", run.ring,
                                       totals_of(run.ring)) is None
    # the ring dropped a span the totals counted
    dropped = dict(totals, **{"veles.compile.lower": {"count": 2}})
    assert compile_spans.setup_seconds(run, "lower", records,
                                       dropped) is None
    assert compile_spans.setup_seconds(run, "trace", records,
                                       dropped) == 400 / 1e9
    # no clock to lay the ring on
    bare = [r for r in records if r["name"] != "veles.step.run"]
    assert compile_spans.setup_seconds(run, "trace", bare, totals) is None


@pytest.mark.parametrize("phase", PHASES)
def test_readers_read_the_program_s_log(run, started, monkeypatch, phase):
    reader = load("layer_metrics/setup_%s_s.py" % phase)
    helper = reader._compile_spans()
    monkeypatch.setattr(helper, "setup_start_ns",
                        lambda run: SHIFT + SETUP_START)
    records = run.ring + compile_ring()
    monkeypatch.setattr(helper.program_spans(), "event_log",
                        lambda: FakeLog(records, totals_of(records)))
    assert reader.read(run) == compile_spans.setup_seconds(
        run, phase, records, totals_of(records))
    monkeypatch.setattr(helper.program_spans(), "event_log", lambda: None)
    assert reader.read(run) is None


def test_a_traced_rehearsal_splits_the_set_up():
    """The command's path at tiny size on the CPU: the four phases are in
    the result line, each at most ``setup_compile_s`` (JAX's durations
    summed), together at most ``setup_s``, and the compiles of the
    comparison with the reference, after the window, are in none."""
    from veles_tpu.logger import events
    from test_benchmark_rehearsal import bench, tiny_run
    events.reset()      # this run's spans alone in the ring
    run = tiny_run("alexnet_scan", trace=1)
    run.monitor = bench.CompileMonitor()
    driver = bench.load_module(bench.os.path.join(
        bench.BENCH, "drivers", run.config["driver"] + ".py"))
    outcome = driver.run(run)
    line = bench.result_line(run, outcome)
    assert line["correct"] is True
    found = {phase: line["metrics"]["setup_%s_s" % phase]["value"]
             for phase in PHASES}
    assert found["trace"] > 0 and found["lower"] > 0
    assert found["xla_compile"] + found["cache_load"] > 0
    assert sum(found.values()) <= outcome["end_to_end"]["setup_s"]
    assert all(value <= run.counters["setup_compile_s"]
               for value in found.values())
    # the reference compiled after the window: its spans are not counted
    offset, _ = compile_spans.program_spans().anchor(
        run.reduced.spans, compile_spans.program_spans().ring())
    closed = run.reduced.t1 + offset
    late = [s for s in events.spans() if s.name.startswith(
        compile_spans.PREFIX) and s.start_ns > closed]
    assert late
    everything = sum(hi - lo for lo, hi in run.tracing.union(
        [s.start_ns, s.start_ns + s.duration_ns] for s in events.spans()
        if s.name == compile_spans.PHASES["trace"])) / 1e9
    assert everything > found["trace"]
