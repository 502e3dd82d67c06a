"""The program-span helper and its four readers, on a hand-made recorded
trace plus a hand-made ring (exact arithmetic): two epochs of 1,000 ns,
each one dispatch of a train scan and one of a validation scan, the ring
on a wall clock that runs ``OFFSET`` ahead of the trace's."""

import types

import pytest

from benchlib import load

tracing = load("tracing.py")
spans = load("program_spans.py")

OFFSET = 1_790_000_000_000_000_000      # wall clock minus trace clock, ns
T0, T1 = 1_000, 3_000                   # the window, trace clock


def device_line(epoch_start):
    """Operations of one epoch, and what the host does in its idle gaps:
    [0, 40) the step prepares the train class; [600, 630) it finishes it
    (inside ``flush_metrics``); [900, 940) it finishes the validation
    class (``flush_metrics`` covers only part: the step's own time);
    [960, 980) the decision runs; [990, 998) no unit does."""
    return [["fusion.%d = f32[8] fusion" % i, epoch_start + start, length]
            for i, (start, length) in enumerate(
                [(40, 560), (630, 270), (940, 20), (980, 10), (998, 2)])]


def epoch_ring(epoch, epoch_start, seq):
    """The ring's records of one epoch (trace clock + OFFSET), numbered
    from ``seq``: unit.step > step.run > children, twice; unit.decision."""
    def rec(name, start, length, number, parent):
        return {"name": name, "seq": number, "parent": parent,
                "start_ns": OFFSET + epoch_start + start,
                "duration_ns": length}
    out = []
    for k, (start, dispatched, end) in enumerate(
            [(0, 40, 640), (640, 650, 950)]):
        unit, step = seq + 6 * k, seq + 6 * k + 1
        out += [
            rec("veles.unit.step", start, end - start + 2, unit, None),
            rec("veles.step.run", start + 1, end - start, step, unit),
            rec("veles.step.index_matrix", start + 2, 3, step + 1, step),
            rec("veles.step.dispatch", start + 5, dispatched - start - 5,
                step + 2, step),
            rec("veles.step.flush_metrics", end - 42, 34, step + 3, step),
            rec("veles.step.sync_weights", end - 7, 5, step + 4, step)]
    out.append(rec("veles.unit.decision", 955, 30, seq + 12, None))
    return out


def bench_spans(epoch_start):
    # the benchmark's wrapper opens 1 ns before the method's own span
    return [["bench.train.epoch_dispatch", epoch_start, 641],
            ["bench.train.epoch_dispatch", epoch_start + 640, 311],
            ["bench.train.decision", epoch_start + 954, 32]]


@pytest.fixture
def run():
    recorded = {"devices": {"/device:TPU:0":
                            device_line(1_000) + device_line(2_000)},
                "host": [["bench.window", T0, T1 - T0]]
                + bench_spans(1_000) + bench_spans(2_000)}
    ring = []
    # one warm epoch, longer than the window's (it compiles)
    for epoch, start in enumerate((-700, 1_000, 2_000)):
        ring += epoch_ring(epoch, start, 1 + 20 * epoch)
    return types.SimpleNamespace(
        tracing=tracing, reduced=tracing.Reduced(recorded, T0, T1),
        counters={"epochs": 2}, ring=ring)


def test_anchoring_recovers_the_offset(run):
    offset, spread = spans.anchor(run.reduced.spans, run.ring)
    # every veles.step.run starts 1 ns after its bench span
    assert (offset, spread) == (OFFSET + 1, 0)
    placed, spread = spans.on_trace_clock(run, run.ring)
    assert spread == 0
    # the warm epoch lies before the window and is gone
    assert min(s["start_ns"] for s in placed) >= T0 - 1
    assert {s["seq"] for s in placed} == set(range(21, 34)) \
        | set(range(41, 54))


def test_a_spread_over_the_limit_gives_none(run):
    shaken = [dict(r) for r in run.ring]
    for r in shaken:
        if r["seq"] % 20 >= 7:      # every validation class, 0.2 ms late
            r["start_ns"] += 2 * spans.MAX_SPREAD_NS
    assert spans.anchor(run.reduced.spans, shaken)[1] \
        > spans.MAX_SPREAD_NS
    assert spans.on_trace_clock(run, shaken) is None
    assert spans.idle_ms_per_epoch(run, shaken) is None
    assert spans.idle_ms_per_epoch_by_span(run, shaken) is None


def test_nothing_to_pair_gives_none(run):
    assert spans.anchor(run.reduced.spans, []) is None
    assert spans.anchor([], run.ring) is None
    assert spans.idle_ms_per_epoch(run, []) is None


def test_gap_layers_and_their_sum(run):
    idle = spans.idle_ms_per_epoch(run, run.ring)
    # the anchor puts each veles.step.run where its bench span starts, so
    # the ring lies 1 ns early.  [0, 40) is cut where spans start and end:
    # the step's own first nanosecond, the index matrix and the dispatch
    # (prepare: 39), then 1 ns of the step's own time after the dispatch
    # returned (finish)
    assert idle == {"step_prepare": 39 / 1e6, "step_finish": 71 / 1e6,
                    "engine": 28 / 1e6}
    share = run.reduced.idle_share()
    window_ms = run.reduced.window_s * 1e3
    assert sum(idle.values()) == pytest.approx(
        share * window_ms / run.counters["epochs"], rel=1e-9)


def test_a_gap_outside_every_unit_span_goes_to_the_engine(run):
    by_span = spans.idle_ms_per_epoch_by_span(run, run.ring)
    assert by_span[None] == [8 / 1e6, 2]            # [990, 998), twice
    assert by_span["veles.unit.decision"] == [20 / 1e6, 2]
    assert by_span["veles.step.index_matrix"] == [3 / 1e6, 2]
    assert by_span["veles.step.dispatch"] == [35 / 1e6, 2]
    # [600, 630) lies inside flush_metrics; [900, 940) starts in the
    # step's own time (after its dispatch: finish) and ends in the flush
    assert by_span["veles.step.flush_metrics"] == [63 / 1e6, 4]
    assert by_span["veles.step.run"] == [9 / 1e6, 6]
    labelled = spans.idle_gaps_labelled(run, run.ring)
    assert [layer for _, name, layer in labelled if name is None] \
        == ["engine", "engine"]
    assert sorted(layer for _, name, layer in labelled
                  if name == "veles.step.run") \
        == ["step_finish"] * 4 + ["step_prepare"] * 2
    assert list(by_span)[0] == "veles.step.flush_metrics"   # largest first


def test_the_step_s_own_time_is_split_at_the_dispatch_s_return():
    step = {"name": "veles.step.run", "seq": 2, "parent": 1}
    by_seq = {2: step, 1: {"name": "veles.unit.step", "seq": 1,
                           "parent": None}}
    assert spans.layer_of(step, 50, by_seq, {2: 100}) == "step_prepare"
    assert spans.layer_of(step, 150, by_seq, {2: 100}) == "step_finish"
    # below a child of the step (a compile inside the dispatch)
    below = {"name": "veles.compile", "seq": 4, "parent": 3}
    by_seq[3] = {"name": "veles.step.dispatch", "seq": 3, "parent": 2}
    assert spans.layer_of(below, 150, by_seq, {2: 100}) == "step_prepare"
    assert spans.layer_of(by_seq[1], 150, by_seq, {2: 100}) == "engine"
    assert spans.layer_of(None, 150, by_seq, {2: 100}) == "engine"


class FakeLog:
    def __init__(self, records, totals):
        self._records, self._totals = records, totals

    def spans(self):
        return [types.SimpleNamespace(**r) for r in self._records]

    def totals(self):
        return self._totals


@pytest.mark.parametrize("metric, layer", [
    ("idle_gap_ms_per_epoch.engine", "engine"),
    ("idle_gap_ms_per_epoch.step_prepare", "step_prepare"),
    ("idle_gap_ms_per_epoch.step_finish", "step_finish")])
def test_gap_readers_read_the_program_s_ring(run, monkeypatch, metric, layer):
    reader = load("layer_metrics/%s.py" % metric)
    helper = reader._program_spans()
    monkeypatch.setattr(helper, "event_log",
                        lambda: FakeLog(run.ring, {}))
    assert reader.read(run) == spans.idle_ms_per_epoch(run, run.ring)[layer]
    # a program without a ring: nothing to read, nothing raised
    del run._idle_gaps_labelled
    monkeypatch.setattr(helper, "event_log", lambda: None)
    assert reader.read(run) is None


def test_setup_initialize_reader_reads_the_totals(run, monkeypatch):
    reader = load("layer_metrics/setup_initialize_s.py")
    helper = reader._program_spans()
    monkeypatch.setattr(helper, "event_log", lambda: FakeLog([], {
        "veles.workflow.initialize": {"count": 1, "seconds": 3.5,
                                      "longest": 3.5}}))
    assert reader.read(run) == 3.5
    monkeypatch.setattr(helper, "event_log", lambda: FakeLog([], {}))
    assert reader.read(run) is None
    monkeypatch.setattr(helper, "event_log", lambda: None)
    assert reader.read(run) is None


def test_the_program_s_own_log_is_found():
    log = spans.event_log()
    assert log is not None and callable(log.totals)
    with log.timed("test.benchmark_program_spans"):
        pass
    assert any(r["name"] == "veles.test.benchmark_program_spans"
               for r in spans.ring())


def test_a_traced_rehearsal_reports_what_the_program_timed():
    """The command's own path at tiny size on the CPU: the result line
    carries ``setup_initialize_s`` from the program's totals; the CPU's
    trace has no device line, so the gap metrics find nothing to read
    and are left out, as they are for a program without spans."""
    from test_benchmark_rehearsal import bench, tiny_run
    run = tiny_run("alexnet_scan", trace=1)
    line = bench.execute(run)
    assert line["correct"] is True
    metric = line["metrics"]["setup_initialize_s"]
    assert metric["unit"] == "s" and 0 < metric["value"] < 600
    assert not any(name.startswith("idle_gap_ms_per_epoch")
                   for name in line["metrics"])
    # the ring holds the window's epochs under the benchmark's wrappers
    records = spans.ring()
    steps = [r for r in records if r["name"] == spans.STEP]
    assert len(steps) >= 2 * run.counters["epochs"]
    found = spans.anchor(run.reduced.spans, records)
    assert found is not None and found[1] < spans.MAX_SPREAD_NS, found
