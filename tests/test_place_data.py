"""Where the resident data set lies for the per-step programs
(``FusedTrainStep._place_data``): the gather train step is compiled with
its data argument's layout left to the compiler, and the set is moved,
once, to the layout the executable asks for.  On the CPU the compiler
asks for the layout the set already has, so nothing is placed; here the
answer is also forced to another layout, and the run that places has to
give the numbers of the run that does not, bit for bit, with no program
more per step.  What the v5e's compiler answers is in
``tests/test_chip_compile.py``.  All on the CPU."""

import os
import pickle
import subprocess
import sys
import time

import jax
import jax.experimental.layout as layout_api
import jax.monitoring
import numpy
import pytest
from jax.experimental.layout import Layout
from jax.sharding import SingleDeviceSharding

from veles_tpu.backends import Device
from veles_tpu.loader.fullbatch import FullBatchLoaderMSE
from veles_tpu.logger import events
from veles_tpu.memory import Array
from veles_tpu.observability.compiles import BACKEND_COMPILE
from veles_tpu.prng import RandomGenerator
from veles_tpu.znicz import transformer  # noqa: F401 — registers the units
from veles_tpu.znicz.standard_workflow import StandardWorkflow

from test_class_end import assert_trees_equal, executables, watch
from test_spans import named
from test_standard_workflow import build


# -- three resident sets -------------------------------------------------------

def images():
    """float32 ``[400, 32, 32, 3]`` through a convolution."""
    from veles_tpu.znicz.samples import cifar
    rate = {"learning_rate": 0.02, "gradient_moment": 0.9}
    wf = cifar.create_workflow(
        loader={"minibatch_size": 50, "n_train": 300, "n_valid": 100,
                "normalization_type": "range_linear",
                "prng": RandomGenerator().seed(7)},
        layers=[
            {"type": "conv_str", "<-": rate,
             "->": {"n_kernels": 8, "kx": 5, "ky": 5, "padding": 2}},
            {"type": "max_pooling", "->": {"kx": 2, "ky": 2}},
            {"type": "softmax", "->": {"output_sample_shape": 10},
             "<-": rate}],
        decision={"max_epochs": 2, "silent": True})
    wf.initialize(device=Device(backend="cpu"))
    return wf


class TokenLoader(FullBatchLoaderMSE):
    """Twelve sequences of eight int32 token ids; the labels are the
    next token."""

    def __init__(self, workflow, **kwargs):
        kwargs["dtype"] = "int32"
        super().__init__(workflow, **kwargs)

    def load_data(self):
        ids = numpy.random.RandomState(3).randint(
            0, 32, (12, 9)).astype(numpy.int32)
        self.original_data.mem = ids[:, :-1]
        self.original_targets.mem = ids[:, 1:]
        self.class_lengths[:] = [0, 4, 8]

    def analyze_dataset(self):
        pass        # ids are served as they are


def tokens():
    """int32 ``[12, 8]`` through an embedding and the token loss."""
    def unit(kind, name, **forward):
        forward.update(hidden_size=16, name=name)
        return {"type": kind, "->": forward, "<-": {"learning_rate": 0.05}}
    wf = StandardWorkflow(
        None, name="tokens", loader_factory=TokenLoader,
        loader={"minibatch_size": 4, "normalization_type": "none",
                "prng": RandomGenerator().seed(5)},
        layers=[unit("token_embedding", "embed", vocab_size=32),
                unit("gated_mlp_block", "mlp", intermediate_size=24),
                unit("lm_head", "head", vocab_size=32)],
        loss_function="token", decision={"max_epochs": 2, "silent": True},
        fused=True)
    wf.initialize(device=Device(backend="cpu"))
    return wf


def blobs():
    """float32 ``[200, 8]`` through two dense layers."""
    return build(fused=True, minibatch=40, max_epochs=2)


#: case: (builder, a layout the CPU's compiler would not choose)
SETS = {"images": (images, (0, 3, 1, 2)),
        "tokens": (tokens, (1, 0)),
        "blobs": (blobs, (1, 0))}


# -- the compiler's answer, forced ---------------------------------------------

@pytest.fixture()
def asking_for(monkeypatch):
    """``asking_for(major_to_minor)``: from then on a program lowered
    with a layout left to the compiler (``Layout.AUTO``) is compiled for
    that layout instead, as if the compiler had chosen it: the executable
    then asks for it, which is all ``_place_data`` goes by."""
    real = layout_api.Format

    def ask(major_to_minor):
        def answered(layout, sharding=None):
            if layout is Layout.AUTO:
                return real(Layout(major_to_minor=major_to_minor),
                            sharding or SingleDeviceSharding(
                                jax.devices()[0]))
            return real(layout, sharding)
        monkeypatch.setattr(layout_api, "Format", answered)
    return ask


@pytest.fixture()
def compiles():
    """[(instant, jitted function's name)] of the backend's compiles (the
    event the compile monitor files), appended for the life of the
    process: JAX keeps its listeners."""
    seen = []

    def listener(name, seconds, fun_name="?", **_):
        if name == BACKEND_COMPILE:
            seen.append((time.time_ns(), fun_name))
    jax.monitoring.register_event_duration_secs_listener(listener)
    return seen


def trained(case):
    """Build ``case``'s workflow and run its two epochs: (workflow, the
    ``step.place_data`` span, every step's loss, what a consumer sees at
    every class end)."""
    import veles_tpu.prng.random_generator as rg
    rg._generators.clear()      # the same weights in every build
    rg.get(0).seed(77)
    events.reset()
    wf = SETS[case][0]()
    step = wf.fused_step
    assert type(step).__name__ == "FusedTrainStep" and step._use_gather_
    (span,) = named(events.spans(), "step.place_data")
    ends, losses, minibatch = watch(step), [], step._run_minibatch

    def run_minibatch(size, train):
        minibatch(size, train)
        losses.append(numpy.asarray(step.loss))
    step._run_minibatch = run_minibatch
    wf.run()
    return wf, span, losses, ends


@pytest.mark.parametrize("case", sorted(SETS))
def test_a_placed_set_trains_to_the_same_bits(case, asking_for, compiles):
    """Losses, ``n_err`` and the other class-end numbers, parameters:
    those of the run that left the set where it lay.  The set is placed
    once, keeps its values, pickles, and costs not one program a step
    more: one executable each for training and evaluation, nothing
    compiled once the first epoch is over."""
    _, span_left, losses_left, ends_left = trained(case)
    assert span_left.info["placed"] is False
    asking_for(SETS[case][1])
    del compiles[:]
    wf, span, losses, ends = trained(case)
    step, data = wf.fused_step, wf.fused_step._data_dev_
    assert span.info["placed"] is True
    assert data.format.layout.major_to_minor == SETS[case][1]
    assert data is wf.loader.original_data.devmem
    assert len(losses) == len(losses_left) > 4 and len(ends) == 4
    for got, want in zip(losses, losses_left):
        assert got.dtype == want.dtype and numpy.array_equal(got, want)
    for got, want in zip(ends, ends_left):
        assert got["n_err"] == want["n_err"]
        assert got["max_err"] == want["max_err"]
        assert numpy.array_equal(got["mse"], want["mse"])
        assert (got["confusion"] is None) == (want["confusion"] is None)
        assert numpy.array_equal(got["confusion"], want["confusion"])
        assert_trees_equal(got["params"], want["params"])
    # no program more
    assert executables(step) == [1, 1]
    steps = [name for _, name in compiles if "step_g" in name]
    assert sorted(steps) == ["jit(eval_step_g)", "jit(train_step_g)"]
    second_epoch = min(r.start_ns for r in named(events.spans(), "step.run")
                       if r.info["epoch"] == 1)
    assert [name for at, name in compiles if at > second_epoch] == []
    # the loader's Array still reads as the values it held, a snapshot
    # of the workflow is taken as before, and an Array that does pickle
    # its payload pickles a placed one
    host = wf.loader.original_data.map_read()
    assert numpy.array_equal(host, numpy.asarray(data))
    del step._run_minibatch, step._finish_class     # this test's hooks
    assert pickle.loads(pickle.dumps(wf)).loader.class_lengths \
        == wf.loader.class_lengths
    held = Array()
    held.devmem = data
    assert numpy.array_equal(pickle.loads(pickle.dumps(held)).mem, host)


def test_a_set_that_lies_as_asked_is_left_alone(compiles):
    """The CPU's compiler asks for the layout ``device_put`` gave: no
    placement, the array the loader made, and the two programs a step has
    always had, compiled once each (the train step in ``initialize``, by
    ``_place_data``; its jit never compiles)."""
    events.reset()
    del compiles[:]
    wf = blobs()
    step, loader = wf.fused_step, wf.loader
    (span,) = named(events.spans(), "step.place_data")
    (inside,) = named(events.spans(), "unit.FusedTrainStep.initialize")
    assert span.parent == inside.seq
    info = span.info
    assert set(info) >= {"layout", "placed", "bytes_before", "bytes_after"}
    assert info["placed"] is False
    assert info["bytes_before"] == info["bytes_after"] == 200 * 8 * 4
    assert info["layout"] == str(step._data_dev_.format.layout)
    assert step._data_dev_ is loader.original_data.devmem
    assert not step._data_dev_.committed
    assert [name for _, name in compiles if "step_g" in name] \
        == ["jit(train_step_g)"]
    wf.run()
    assert executables(step) == [1, 1]
    assert step._train_step_g_._jitted._cache_size() == 0
    steps = [name for _, name in compiles if "step_g" in name]
    assert sorted(steps) == ["jit(eval_step_g)", "jit(train_step_g)"]


def test_a_set_with_no_room_for_two_copies_stays_and_says_so(
        asking_for, monkeypatch, caplog):
    """Where the device cannot hold the old and the new placement at
    once, the set stays, the log and the span say so, and the step runs
    today's program on today's placement to the same numbers."""
    from veles_tpu.znicz.fused import FusedTrainStep
    _, _, losses_left, ends_left = trained("blobs")
    asking_for((1, 0))
    monkeypatch.setattr(FusedTrainStep, "_room_to_place",
                        staticmethod(lambda data, step: False))
    with caplog.at_level("WARNING"):
        wf, span, losses, ends = trained("blobs")
    assert span.info["placed"] is False
    assert span.info["layout"] != str(wf.fused_step._data_dev_.format.layout)
    assert any("no room for two copies" in r.getMessage()
               for r in caplog.records)
    assert executables(wf.fused_step) == [1, 1]
    for got, want in zip(losses, losses_left):
        assert numpy.array_equal(got, want)
    assert_trees_equal(ends[-1]["params"], ends_left[-1]["params"])


def test_room_is_counted_from_the_devices_own_statistics():
    """Room for a second placement: what the device holds now and the
    compiled step's arguments, against the device's limit."""
    from veles_tpu.znicz.fused import FusedTrainStep

    class Device_:
        def __init__(self, stats):
            self.stats = stats

        def memory_stats(self):
            return self.stats

    class Data:
        def __init__(self, stats):
            self.device = Device_(stats)

        def devices(self):
            return {self.device}

    class Step:
        @staticmethod
        def memory_analysis():
            class Sizes:
                argument_size_in_bytes = 600
            return Sizes
    room = FusedTrainStep._room_to_place
    assert room(Data(None), Step)                       # the CPU
    assert room(Data({"bytes_in_use": 400, "bytes_limit": 1000}), Step)
    assert not room(Data({"bytes_in_use": 401, "bytes_limit": 1000}), Step)


# -- JAX's persistent cache ----------------------------------------------------

PLACE_TWICE = r"""
import sys
sys.path.insert(0, %(repo)r)
sys.path.insert(0, %(tests)r)
import jax
import jax.experimental.layout as layout_api
from jax.experimental.layout import Layout
from jax.sharding import SingleDeviceSharding
real = layout_api.Format
layout_api.Format = lambda layout, sharding=None: real(
    Layout(major_to_minor=(1, 0)) if layout is Layout.AUTO else layout,
    sharding or SingleDeviceSharding(jax.devices()[0]))
from veles_tpu.logger import events
from test_standard_workflow import build
wf = build(fused=True, minibatch=40, max_epochs=1)
span, = (s for s in events.spans() if s.name.endswith("step.place_data"))
print("PLACED", span.info["placed"],
      wf.fused_step._data_dev_.format.layout.major_to_minor)
"""


def test_a_second_process_places_the_set_again(tmp_path):
    """The relayout program stays out of JAX's persistent cache, which
    hands such an executable back producing the default layout: with
    every compile persisted (as ``engine.compilation_cache_dir`` sets it)
    the second process still places the set."""
    tests = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="-1")
    script = PLACE_TWICE % {"repo": os.path.dirname(tests), "tests": tests}
    for _ in range(2):
        done = subprocess.run([sys.executable, "-c", script], env=env,
                              capture_output=True, text=True, timeout=300)
        assert done.returncode == 0, done.stderr[-2000:]
        assert "PLACED True (1, 0)" in done.stdout, done.stdout[-500:]
    assert any(name.startswith("jit_train_step_g")
               for name in os.listdir(tmp_path))
