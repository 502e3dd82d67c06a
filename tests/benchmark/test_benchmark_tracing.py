"""The trace reduction, on hand-made traces (exact arithmetic) and on
two small traces recorded on the chip (fixtures/recorded_traces.json:
the first 60 ms of an AlexNet epoch scan and, for its nested loops and
Pallas calls, 150 ms of the flagship decode server's steps; TPU v5 lite,
PR 23)."""

import pytest

from benchlib import load, load_json

tracing = load("tracing.py")


@pytest.fixture(scope="module")
def recorded():
    return load_json("tests", "benchmark", "fixtures",
                     "recorded_traces.json")


#: one device: a while [0, 100) enclosing a fusion [10, 30) and an
#: all-reduce [30, 50); a kernel [120, 160); a copy [160, 170); idle
#: [100, 120) and [170, 200)
HAND = [
    ["while.1 = (tuple) while", 0, 100],
    ["fusion.7 = f32[8,8] fusion", 10, 20],
    ["all-reduce.2 = f32[64] all-reduce", 30, 20],
    ["fn.4 = f32[8] custom-call tpu_custom_call", 120, 40],
    ["copy.9 = f32[8] copy", 160, 10],
]


def test_busy_is_the_union_not_the_sum():
    assert tracing.busy_ns(HAND) == 100 + 50
    assert tracing.union([(5, 9), (0, 3), (2, 6)]) == [[0, 9]]


def test_idle_gaps_cover_the_rest_of_the_window():
    gaps = tracing.idle_gaps(HAND, 0, 200)
    assert gaps == [[100, 120], [170, 200]]
    assert tracing.busy_ns(HAND) + sum(b - a for a, b in gaps) == 200


def test_self_time_takes_children_out():
    by_name = {n: ns for n, _, ns in tracing.self_times(HAND)}
    assert by_name["while.1 = (tuple) while"] == 60
    assert by_name["fusion.7 = f32[8,8] fusion"] == 20
    totals = tracing.time_by_name(HAND, tracing.op_family)
    assert list(totals)[0] == "while (tuple)"
    assert totals["fn f32[8]"] == 40


def test_exposed_allreduce_is_what_no_other_leaf_covers():
    assert tracing.exposed_ns(HAND, tracing.is_allreduce) == 20
    hidden = HAND + [["fusion.8 = f32[8] fusion", 35, 10]]
    # fusion.8 runs inside the all-reduce, which is then no leaf at all
    assert tracing.exposed_ns(hidden, tracing.is_allreduce) == 0
    beside = [["all-reduce-done.1 = f32[64] all-reduce-done", 0, 30],
              ["fusion.1 = f32[8] fusion", 20, 30]]
    assert tracing.exposed_ns(beside, tracing.is_allreduce) == 20


def test_gap_label_is_the_innermost_covering_span():
    spans = [["bench.window", 0, 200], ["bench.outer", 90, 60],
             ["bench.inner", 100, 20], ["bench.far", 180, 5]]
    assert tracing.label_gap([100, 120], spans) == "bench.inner"
    assert tracing.label_gap([125, 140], spans) == "bench.outer"
    assert tracing.label_gap([170, 176], spans) == "no bench span"


def test_short_name_of_an_instruction():
    text = ("%copy.166 = f32[10241,64,8,32]{0,3,2,1:T(8,128)} copy("
            "f32[10241,64,8,32]{3,2,1,0:T(8,128)} %fusion.13)")
    assert tracing.short_name(text) == "copy.166 = f32[10241,64,8,32] copy"
    kernel = ('%fn.4 = f32[256,8,32]{2,1,0:T(8,128)} custom-call(s32[256,40]'
              '{1,0:T(8,128)S(1)} %copy-done.49), custom_call_target='
              '"tpu_custom_call", operand_layout_constraints={}')
    name = tracing.short_name(kernel)
    assert name == "fn.4 = f32[256,8,32] custom-call tpu_custom_call"
    assert tracing.opcode(name) == "custom-call"
    loop = ("%while.7 = (s32[]{:T(128)}, f32[256,256]{1,0:T(8,128)S(1)}) "
            "while((s32[]{:T(128)}, f32[256,256]{1,0}) %tuple.1), "
            "condition=%cond, body=%body")
    assert tracing.short_name(loop) == "while.7 = (tuple) while"
    reduce = ("%all-reduce-start.3 = f32[4096,1000]{1,0} all-reduce-start("
              "f32[4096,1000]{1,0} %p), replica_groups={{0,1,2,3}}")
    assert tracing.is_allreduce(tracing.short_name(reduce))
    assert tracing.op_family(name) == "fn f32[256,8,32]"
    assert tracing.short_name("Steps 25") == "Steps 25"


def test_reduced_window_and_breakdown():
    recorded_ = {"devices": {"/device:TPU:0": HAND,
                             "/device:TPU:1": HAND[:1]},
                 "host": [["bench.window", 0, 200],
                          ["bench.train.decision", 100, 18]]}
    reduced = tracing.Reduced(recorded_, 0, 200)
    assert reduced.busy_s() == {"/device:TPU:0": 150e-9,
                                "/device:TPU:1": 100e-9}
    assert reduced.mean_busy_s() == pytest.approx(125e-9)
    # the chip that idles most decides
    assert reduced.idle_share() == pytest.approx(0.5)
    breakdown = reduced.breakdown(ops=3, gaps=2)
    assert [n for n, _ in breakdown["device_ops"]][0] == "while (tuple)"
    assert len(breakdown["device_ops"]) == 3
    # device 1 idles most; its one gap is [100, 200)
    assert breakdown["idle_gaps"][0] == ["bench.train.decision", 100e-9]
    one = tracing.Reduced(recorded_, 50, 150, devices=1)
    assert list(one.devices) == ["/device:TPU:0"]
    assert one.busy_s()["/device:TPU:0"] == pytest.approx(80e-9)


@pytest.mark.parametrize("tag,busy_ms,window_ms", [
    ("alexnet", 51.121852, 59.796133), ("flagship", 144.873872, 150.0)])
def test_recorded_trace_busy_time(recorded, tag, busy_ms, window_ms):
    trace = recorded[tag]
    window = trace["host"][0]
    reduced = tracing.Reduced(trace, window[1], window[1] + window[2])
    assert reduced.window_s * 1e3 == pytest.approx(window_ms)
    assert reduced.mean_busy_s() * 1e3 == pytest.approx(busy_ms)
    events = trace["devices"]["/device:TPU:0"]
    # self times of nested operations add up to the busy time exactly
    # where nothing overlaps but parents and children
    assert sum(ns for _, _, ns in tracing.self_times(events)) \
        == pytest.approx(tracing.busy_ns(events), rel=0.02)


def test_recorded_flagship_trace_is_mostly_pool_copies(recorded):
    events = recorded["flagship"]["devices"]["/device:TPU:0"]
    totals = tracing.time_by_name(events, tracing.op_family)
    assert list(totals)[:2] == ["copy f32[10241,64,8,32]",
                                "fn f32[256,8,32]"]
    # the Pallas kernels' share of the busy time
    assert totals["fn f32[256,8,32]"] / tracing.busy_ns(events) \
        == pytest.approx(0.1562, abs=1e-3)
    assert tracing.exposed_ns(events, tracing.is_allreduce) == 0


def test_recorded_alexnet_trace_has_no_kernel_and_no_collective(recorded):
    events = recorded["alexnet"]["devices"]["/device:TPU:0"]
    assert not any(n.endswith("tpu_custom_call") for n, _, _ in events)
    assert not any(tracing.is_allreduce(n) for n, _, _ in events)
