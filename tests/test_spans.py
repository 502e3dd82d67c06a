"""``events.timed``: the one way the program times a region.  The
primitive (ring, totals, nesting, the profiler's clock), the trainer's
span sites, the device's layer scopes, ``CompileMonitor`` and
``--profiler-port``.  All on the CPU."""

import glob
import json
import os
import socket
import subprocess
import sys
import threading

import pytest

from veles_tpu.backends import Device
from veles_tpu.config import root
from veles_tpu.logger import EventLog, SPAN_PREFIX, events
from veles_tpu.observability import trace as trace_context
from veles_tpu.prng import RandomGenerator
from veles_tpu.znicz.samples import alexnet, mnist

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def by_seq(spans):
    """Spans in call order (the ring holds them in closing order)."""
    return sorted(spans, key=lambda s: s.seq)


def named(spans, name):
    return [s for s in by_seq(spans) if s.name == SPAN_PREFIX + name]


def compiled(span):
    """A span the compile monitor filed (``compile.trace``, ``.lower``,
    ``.xla``, ``.cache_load``)."""
    return span.name.startswith(SPAN_PREFIX + "compile.")


# -- the primitive ------------------------------------------------------------

def test_nesting_gives_the_parent():
    log = EventLog()
    with log.timed("outer", kind="a") as outer:
        with log.timed("inner") as inner:
            with log.timed("innermost") as innermost:
                pass
        with log.timed("second") as second:
            pass
    with log.timed("alone") as alone:
        pass
    assert outer.parent is None and alone.parent is None
    assert inner.parent == outer.seq and second.parent == outer.seq
    assert innermost.parent == inner.seq
    assert [s.name for s in by_seq(log.spans())] == [
        "veles.outer", "veles.inner", "veles.innermost", "veles.second",
        "veles.alone"]
    assert outer.seq < inner.seq < innermost.seq < second.seq < alone.seq
    # a parent is closed after its children and lasts at least as long
    assert outer.duration_ns >= inner.duration_ns >= innermost.duration_ns
    assert outer.start_ns <= inner.start_ns
    assert outer.thread == threading.get_ident()
    assert outer.info == {"kind": "a"}


def test_each_thread_has_its_own_parents():
    log = EventLog()
    seen = {}

    def work():
        with log.timed("in_thread") as span:
            seen["span"] = span
    with log.timed("main_thread") as main:
        worker = threading.Thread(target=work)
        worker.start()
        worker.join(timeout=10)
    assert not worker.is_alive()
    assert seen["span"].parent is None
    assert seen["span"].thread != main.thread


def test_the_ring_is_bounded_and_drops_the_oldest():
    log = EventLog(capacity=4)
    for i in range(10):
        with log.timed("tick", i=i):
            pass
    assert [s.info["i"] for s in log.spans()] == [6, 7, 8, 9]
    # the totals forget nothing
    assert log.totals()["veles.tick"]["count"] == 10


def test_totals_count_and_sum():
    log = EventLog()
    for steps, images in ((32, 8192), (2, 512)):
        with log.timed("step.run") as span:
            span.count(steps=steps, images=images)
    log.span("step.run", 0.25, images=8)    # reported after the fact
    total = log.totals()["veles.step.run"]
    assert total["count"] == 3
    assert total["steps"] == 34 and total["images"] == 8704
    assert total["longest"] == 0.25
    assert total["seconds"] == pytest.approx(
        sum(s.seconds for s in log.spans()))
    # counts are in the span's info too
    assert log.spans()[0].info == {"steps": 32, "images": 8192}
    # totals() hands out a copy
    log.totals()["veles.step.run"]["count"] = 0
    assert log.totals()["veles.step.run"]["count"] == 3


def test_an_exception_closes_the_span_and_propagates():
    log = EventLog()
    with pytest.raises(KeyError):
        with log.timed("outer"):
            with log.timed("fails"):
                raise KeyError("boom")
    assert [s.name for s in log.spans()] == ["veles.fails", "veles.outer"]
    assert all(s.duration_ns is not None for s in log.spans())
    with log.timed("after") as after:       # the stack is clean again
        pass
    assert after.parent is None


def test_span_reported_after_the_fact_feeds_ring_and_totals():
    log = EventLog()
    with log.timed("outer") as outer:
        log.span("legacy", 0.5, model="m")
    legacy = log.spans()[0]
    assert legacy.name == "veles.legacy" and legacy.parent == outer.seq
    assert legacy.duration_ns == 500_000_000
    assert legacy.start_ns + legacy.duration_ns \
        <= outer.start_ns + outer.duration_ns + 1_000_000
    assert log.totals()["veles.legacy"]["seconds"] == 0.5
    # a site that knows when its region started, and counted inside it
    log.span("legacy", 0.25, start_ns=123_456, counts={"nested": 4})
    late = log.spans()[-1]
    assert (late.start_ns, late.duration_ns) == (123_456, 250_000_000)
    assert late.info == {"nested": 4} and late.parent is None
    assert log.totals()["veles.legacy"]["nested"] == 4


def test_instant_has_no_length():
    log = EventLog()
    log.instant("probe.mark", seconds=1.5)
    (span,) = log.spans()
    assert span.name == "veles.probe.mark" and span.duration_ns == 0
    assert span.info == {"seconds": 1.5}


def test_work_is_the_epoch_or_the_trace_id():
    log = EventLog()
    with log.timed("no_context") as bare:
        pass
    assert bare.work is None
    with trace_context.span_context() as ctx:
        with log.timed("request") as request:
            pass
        assert request.work == ctx.trace_id
        with log.timed("unit") as unit:
            log.set_work(7)         # said inside: the enclosing span has it
            with log.timed("step") as step:
                pass
    assert (unit.work, step.work) == (7, 7)
    log.set_work(None)


def test_sink_and_file_receive_what_they_received(tmp_path):
    path = str(tmp_path / "events.jsonl")
    log = EventLog(path)
    mirrored = []
    log.span_sink = lambda name, kind, duration, info: mirrored.append(
        (name, kind, duration, dict(info)))
    with log.timed("quiet", cls="A"):       # file tracing off: sink only
        pass
    assert [(m[0], m[1], m[3]) for m in mirrored] \
        == [("quiet", "span", {"cls": "A"})]
    assert mirrored[0][2] > 0 and not os.path.exists(path)
    root.common.trace.enabled = True
    try:
        log.set_work(3)
        with log.timed("outer", cls="A") as outer:
            with log.timed("inner"):
                pass
        log.span("legacy", 0.001, model="m")
        log.instant("mark")
    finally:
        root.common.trace.enabled = False
        log.set_work(None)
        log.close()
    records = {r["name"]: r for r in map(json.loads, open(path))}
    assert records["outer"]["ph"] == "X" and records["outer"]["dur"] >= 0
    assert records["outer"]["args"] == {"cls": "A", "seq": outer.seq,
                                        "work": 3}
    assert records["inner"]["args"]["parent_seq"] == outer.seq
    assert records["legacy"]["args"]["model"] == "m"
    assert records["mark"]["ph"] == "i"
    assert [m[0] for m in mirrored] == ["quiet", "inner", "outer", "legacy",
                                        "mark"]
    # a sink that raises takes nothing down
    log.span_sink = lambda *a: 1 / 0
    with log.timed("still_fine"):
        pass
    assert log.spans()[-1].name == "veles.still_fine"


def test_reset_clears_ring_and_totals():
    log = EventLog()
    with log.timed("x"):
        pass
    log.reset()
    assert log.spans() == [] and log.totals() == {}


def test_logger_imports_without_jax():
    code = ("import sys; import veles_tpu.logger as l; "
            "assert 'jax' not in sys.modules, 'jax was imported'; "
            "log = l.EventLog()\n"
            "with log.timed('x'): pass\n"
            "assert 'jax' not in sys.modules; print(len(log.spans()))")
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "1"


def test_spans_are_on_the_profiler_s_clock(tmp_path):
    """Under a real ``jax.profiler`` trace a ``veles.*`` span is in the
    ``.xplane.pb`` with its info, at a constant offset from its ring
    entry (the wall clock against the profile's own)."""
    import time
    import jax
    log = EventLog()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        for i in range(6):
            with log.timed("probe.region", i=i):
                time.sleep(0.002)
            time.sleep(0.01)
        log.instant("probe.instant")
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                            / "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    found = sorted(
        (e for plane in data.planes if plane.name.startswith("/host:")
         for line in plane.lines for e in line.events
         if e.name.startswith("veles.probe.")),
        key=lambda e: e.start_ns)
    assert [e.name for e in found] == ["veles.probe.region"] * 6 \
        + ["veles.probe.instant"]
    ring = named(log.spans(), "probe.region")
    # the first annotation of a session pays its set-up: leave it out
    offsets = [s.start_ns - int(e.start_ns)
               for s, e in zip(ring, found)][1:]
    assert max(offsets) - min(offsets) < 100_000, offsets   # 0.1 ms
    for span, event in zip(ring, found):
        assert dict(event.stats)["i"] == span.info["i"]
        assert abs(int(event.duration_ns) - span.duration_ns) < 500_000


def test_compile_monitor_leaves_an_instant_per_backend_compile(tmp_path):
    """Each phase of a compile is a span of the ring with its real start
    and length, under the span open on the thread, and, while a profile
    is being taken, an instant on its timeline."""
    import jax
    import numpy
    from veles_tpu.observability import compiles
    monitor = compiles.monitor()
    seen = max((s.seq for s in events.spans()), default=0)
    seconds = monitor.compile_seconds
    jax.profiler.start_trace(str(tmp_path))
    try:
        with events.timed("probe.compiling") as outer:
            jax.jit(lambda x: x * 3 + 1.25)(
                numpy.ones(7, numpy.float32)).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    filed = [s for s in by_seq(events.spans())
             if s.seq > seen and compiled(s)]
    assert [s.name for s in filed] == ["veles.compile.trace",
                                       "veles.compile.lower",
                                       "veles.compile.xla"]
    assert all(s.parent == outer.seq and s.duration_ns > 0 for s in filed)
    assert filed[0].info["fun"] == "<lambda>"
    assert filed[2].info["module"] == filed[1].info["module"]
    assert filed[2].info["cache"] in ("miss", "off")
    for before, after in zip(filed, filed[1:]):
        assert before.start_ns + before.duration_ns <= after.start_ns
    assert outer.start_ns <= filed[0].start_ns
    assert filed[-1].start_ns + filed[-1].duration_ns \
        <= outer.start_ns + outer.duration_ns
    assert monitor.compile_seconds - seconds == pytest.approx(
        sum(s.seconds for s in filed))
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                            / "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    marks = {e.name for plane in data.planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name.startswith("veles.compile.")}
    assert marks == {s.name for s in filed}


def test_one_compile_monitor_outside_the_benchmark():
    found = glob.glob(os.path.join(REPO, "*.py"))
    for top in ("veles_tpu", "tools", "benchmark"):
        for folder, _, files in os.walk(os.path.join(REPO, top)):
            found += [os.path.join(folder, name) for name in files
                      if name.endswith(".py")]
    defining = sorted(
        os.path.relpath(path, REPO) for path in found
        if any(line.startswith("class CompileMonitor")
               for line in open(path, errors="replace")))
    assert defining == ["benchmark/run.py",
                        "veles_tpu/observability/compiles.py"]


# -- the trainer's sites ------------------------------------------------------

def mnist_workflow(**kwargs):
    wf = mnist.create_workflow(
        loader={"minibatch_size": 50, "n_train": 200, "n_valid": 100,
                "prng": RandomGenerator().seed(3)},
        decision={"max_epochs": 2, "silent": True}, **kwargs)
    events.reset()
    wf.initialize(device=Device(backend="cpu"))
    return wf


def check_step_spans(wf, children):
    """``step.run`` spans with ``children`` in call order, below the
    step unit's span, filed under their epoch, counting what the loader
    served."""
    spans = events.spans()
    runs = named(spans, "step.run")
    assert runs
    unit_name = SPAN_PREFIX + "unit." + wf.fused_step.name
    by_number = {s.seq: s for s in spans}
    for run in runs:
        assert by_number[run.parent].name == unit_name
        assert by_number[run.parent].info["cls"] \
            == type(wf.fused_step).__name__
        assert run.work == run.info["epoch"]
        assert by_number[run.parent].work == run.work
        # (the compile monitor files a span wherever something compiled)
        below = [s.name[len(SPAN_PREFIX):] for s in by_seq(spans)
                 if s.parent == run.seq and not compiled(s)]
        assert below == children(run), (run.info, below)
        assert all(s.work == run.work for s in spans
                   if s.parent == run.seq)
    assert check_class_ends(spans, runs) == 4   # two classes, two epochs
    assert sorted({r.info["epoch"] for r in runs}) == [0, 1]
    assert {r.info["cls"] for r in runs} == {"train", "validation"}
    total = events.totals()[SPAN_PREFIX + "step.run"]
    assert total["count"] == len(runs)
    assert total["images"] == wf.loader.samples_served == 2 * 300
    assert total["steps"] == sum(r.info["steps"] for r in runs) == 2 * 6
    # every unit that ran left spans and the timers read the same clock
    for unit in (wf.repeater, wf.decision, wf.fused_step):
        mine = named(spans, "unit." + unit.name)
        assert len(mine) == unit.timers["runs"] > 0
        assert unit.timers["run"] == pytest.approx(
            sum(s.seconds for s in mine))
    return runs


def check_class_ends(spans, runs):
    """At a class end the host blocks last: the weight copies are
    enqueued and their span closed, the new accumulator is made, and only
    then the one blocking read opens, as the only child of
    ``step.flush_metrics``.  Returns the number of class ends."""
    ends = 0
    for run in runs:
        below = {s.name[len(SPAN_PREFIX):]: s for s in spans
                 if s.parent == run.seq}
        if "step.flush_metrics" not in below:
            continue
        ends += 1
        sync, flush = below["step.sync_weights"], below["step.flush_metrics"]
        (read,) = [s for s in spans if s.parent == flush.seq
                   and not compiled(s)]
        assert read.name == "veles.step.read_metrics"
        assert below["step.dispatch"].seq < sync.seq < flush.seq < read.seq
        assert sync.start_ns + sync.duration_ns <= flush.start_ns \
            <= read.start_ns
        assert read.start_ns + read.duration_ns \
            <= flush.start_ns + flush.duration_ns + 1_000_000
        assert read.work == run.work
    assert events.totals()["veles.step.read_metrics"]["count"] == ends
    return ends


def test_scan_workflow_leaves_its_spans():
    wf = mnist_workflow(epoch_scan=True)
    wf.run()
    both = ["step.index_matrix", "step.dispatch", "step.sync_weights",
            "step.flush_metrics"]

    def children(run):
        # a new epoch starts with the validation class: it shuffles
        new_epoch = run.info["epoch"] > 0 and run.info["cls"] == "validation"
        return ["step.shuffle"] * new_epoch + both
    runs = check_step_spans(wf, children)
    assert len(runs) == 4       # one dispatch a class and epoch
    assert [r.info["steps"] for r in runs] == [2, 4, 2, 4]


def test_fused_workflow_leaves_its_spans():
    wf = mnist_workflow()
    wf.run()

    def children(run):
        last = run.info["images"] and run is last_of_class[
            (run.info["epoch"], run.info["cls"])]
        return ["step.dispatch"] + (
            ["step.sync_weights", "step.flush_metrics"] if last else [])
    last_of_class = {(r.info["epoch"], r.info["cls"]): r
                     for r in named(events.spans(), "step.run")}
    runs = check_step_spans(wf, children)
    assert len(runs) == 12 and {r.info["steps"] for r in runs} == {1}
    # the loader is a unit of its own here
    assert len(named(events.spans(), "unit." + wf.loader.name)) == 12


def test_train_epochs_is_one_step_run():
    wf = mnist_workflow(epoch_scan=True)
    wf.fused_step.train_epochs(3)
    (run,) = named(events.spans(), "step.run")
    assert run.info["epochs"] == 3 and run.info["steps"] == 12
    assert run.info["images"] == wf.loader.samples_served == 600
    below = [s.name for s in by_seq(events.spans())
             if s.parent == run.seq and not compiled(s)]
    assert below.count("veles.step.index_matrix") == 3
    assert below.count("veles.step.shuffle") == 2
    assert below[-3:] == ["veles.step.dispatch", "veles.step.sync_weights",
                          "veles.step.flush_metrics"]
    assert check_class_ends(events.spans(), [run]) == 1


def test_workflow_initialize_leaves_one_span_a_unit():
    wf = mnist_workflow()
    spans = events.spans()
    (whole,) = named(spans, "workflow.initialize")
    assert whole.info == {"workflow": wf.name}
    below = [s for s in by_seq(spans) if s.parent == whole.seq]
    units = [u for u in wf._dependency_order() if u is not wf]
    assert [s.name for s in below] == [
        "veles.unit.%s.initialize" % u.name for u in units]
    assert [s.info["cls"] for s in below] \
        == [type(u).__name__ for u in units]
    total = events.totals()["veles.workflow.initialize"]
    assert total["count"] == 1 and total["seconds"] == whole.seconds
    assert whole.seconds >= sum(s.seconds for s in below)


def test_a_deferred_unit_gets_a_span_per_attempt():
    from veles_tpu.units import Unit
    from veles_tpu.workflow import Workflow

    class Late(Unit):
        attempts = 0

        def initialize(self, **kwargs):
            self.attempts += 1
            if self.attempts < 3:
                return True
            return super().initialize(**kwargs)
    wf = Workflow(None, name="deferring")
    late = Late(wf, name="late")
    late.link_from(wf.start_point)
    wf.end_point.link_from(late)
    events.reset()
    wf.initialize()
    assert len(named(events.spans(), "unit.late.initialize")) == 3


def test_the_command_line_driver_leaves_its_spans(tmp_path):
    from veles_tpu.__main__ import Main
    events.reset()
    main = Main([os.path.join(REPO, "veles_tpu", "znicz", "samples",
                              "mnist.py"),
                 "root.mnist.loader.n_train=100",
                 "root.mnist.loader.n_valid=50",
                 "root.mnist.decision.max_epochs=1", "-a", "cpu"])
    assert main.run() == 0
    spans = events.spans()
    (load,), (init,), (run,) = (named(spans, "main." + what)
                                for what in ("load", "initialize", "run"))
    assert load.seq < init.seq < run.seq
    (whole,) = named(spans, "workflow.initialize")
    assert whole.parent == init.seq
    below = {s.name for s in spans if s.parent == run.seq}
    assert {"veles.unit.Repeater", "veles.unit.MnistLoader",
            "veles.unit.FusedTrainStep", "veles.unit.DecisionGD"} <= below
    assert run.work == main.workflow.loader.epoch_number


def test_profiler_port_opens_the_operator_s_door():
    from veles_tpu.__main__ import make_parser
    from veles_tpu.launcher import Launcher
    assert make_parser().parse_args(["w.py"]).profiler_port is None
    assert make_parser().parse_args(
        ["w.py", "--profiler-port", "9012"]).profiler_port == 9012
    with socket.socket() as probe:
        probe.bind(("127.0.0.1", 0))
        port = probe.getsockname()[1]
    quiet = Launcher(backend="cpu", stealth=True)
    assert quiet.profiler_port is None
    launcher = Launcher(backend="cpu", stealth=True, profiler_port=port)
    launcher.add_workflow(mnist.create_workflow(
        loader={"minibatch_size": 50, "n_train": 100, "n_valid": 50,
                "prng": RandomGenerator().seed(3)},
        decision={"max_epochs": 1, "silent": True}))
    launcher.initialize()
    try:
        assert launcher._profiler_server is not None
        with socket.create_connection(("127.0.0.1", port), timeout=10):
            pass                    # somebody listens
    finally:
        launcher.stop()
    assert launcher._profiler_server is None


# -- the device's names -------------------------------------------------------

def test_scope_names_are_the_units_names_made_unique():
    from types import SimpleNamespace as unit
    from veles_tpu.znicz.fused import scope_names
    assert scope_names([unit(name="conv1"), unit(name="pool1"),
                        unit(name="fc8")]) == ["conv1", "pool1", "fc8"]
    assert scope_names([unit(name="Conv"), unit(name="Pool"),
                        unit(name="Conv")]) == ["Conv0", "Pool", "Conv2"]


ALEXNET_LAYERS = ["conv1", "lrn1", "pool1", "conv2", "lrn2", "pool2",
                  "conv3", "conv4", "conv5", "pool5", "fc6", "dropout6",
                  "fc7", "dropout7", "fc8"]


@pytest.fixture(scope="module")
def tiny_alexnet():
    wf = alexnet.create_workflow(
        epoch_scan=True,
        loader={"minibatch_size": 4, "n_train": 8, "n_valid": 4,
                "n_classes": 20, "side": 67,
                "prng": RandomGenerator().seed(7)},
        decision={"max_epochs": 1, "silent": True})
    wf.initialize(device=Device(backend="cpu"))
    return wf


def lowered_text(jitted, *args):
    return jitted.lower(*args).as_text(debug_info=True)


def check_scopes(text, gather):
    """A train program's text: JAX derives the passes' names from the
    forward scope, ``jvp(conv2)`` and ``transpose(jvp(conv2))``."""
    for layer in ALEXNET_LAYERS:
        assert '"jvp(%s)/' % layer in text \
            or "/jvp(%s)/" % layer in text, layer
        assert "transpose(jvp(%s))/" % layer in text, layer
        if not layer.startswith(("dropout", "pool", "lrn")):
            assert "update/%s/" % layer in text, layer
    assert "jvp(loss)/" in text and "metrics/" in text
    assert ("gather/" in text) == gather


def test_the_train_step_names_every_layer(tiny_alexnet):
    import numpy
    step = tiny_alexnet.fused_step
    assert [f.name for f in step.forwards] == ALEXNET_LAYERS
    x = numpy.zeros((4, 67, 67, 3), numpy.float32)
    y = numpy.zeros((4,), numpy.int32)
    text = lowered_text(step._train_step_, step._params_, step._opt_,
                        step._macc_, x, y, numpy.int32(4), numpy.int32(1),
                        numpy.float32(1.0))
    check_scopes(text, gather=False)


def test_the_train_scan_names_every_layer_and_the_gather(tiny_alexnet):
    import numpy
    step = tiny_alexnet.fused_step
    idx, sizes = step._class_index_matrix(2)
    text = lowered_text(step._train_scan_, step._data_dev_, step._y_dev_,
                        step._params_, step._opt_, step._macc_, idx, sizes,
                        numpy.ones(len(sizes), numpy.int32),
                        numpy.float32(1.0))
    check_scopes(text, gather=True)
    assert "while" in text
    evaluate = lowered_text(step._eval_scan_, step._data_dev_, step._y_dev_,
                            step._params_, step._macc_, idx, sizes)
    assert "gather/" in evaluate and '"conv1/' in evaluate
    assert "update/" not in evaluate and "jvp(" not in evaluate


def test_scopes_are_no_part_of_the_executable_store_s_key(tiny_alexnet):
    """``compilecache``'s key is the module text WITHOUT debug info: an
    executable stored before the scopes existed is found again (and is
    loaded as it was, without them)."""
    import numpy
    from veles_tpu.compilecache.keys import cache_key
    step = tiny_alexnet.fused_step
    x = numpy.zeros((4, 67, 67, 3), numpy.float32)
    y = numpy.zeros((4,), numpy.int32)
    lowered = step._eval_step_.lower(step._params_, step._macc_, x, y,
                                     numpy.int32(4))
    assert "conv1" in lowered.as_text(debug_info=True)
    assert "conv1" not in lowered.as_text()
    assert len(cache_key(lowered)) == 64


def test_the_gather_step_names_its_gather():
    import numpy
    wf = mnist_workflow()
    step = wf.fused_step
    if not getattr(step, "_use_gather_", False):
        pytest.skip("the sample's loader keeps no data set on the device")
    idx = numpy.zeros(50, wf.loader.INDEX_DTYPE)
    jitted = getattr(step._eval_step_g_, "_jitted", step._eval_step_g_)
    text = lowered_text(jitted, step._data_dev_, step._y_dev_,
                        step._params_, step._macc_, idx, numpy.int32(50))
    assert "gather/" in text
