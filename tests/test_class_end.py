"""The class-end epilogue (``FusedTrainStep._finish_class``): what the
device still has to do is enqueued behind the running dispatch and the
host blocks on the class's scalars last.  Nothing more is compiled for
it, and every number is the one the old order gave (block first, enqueue
afterwards), which is kept here as the reference.  All on the CPU."""

import time

import jax
import jax.monitoring
import numpy
import pytest

from veles_tpu.logger import events
from veles_tpu.observability import compiles
from veles_tpu.parallel.mesh import make_mesh

from test_spans import named
from test_standard_workflow import build, build_mse


def data4():
    """Four of the eight virtual devices, as ``--mesh data=4`` takes
    four chips."""
    return make_mesh({"data": 4}, devices=jax.devices()[:4])


# -- nothing more is compiled -------------------------------------------------

@pytest.fixture(scope="module")
def backend_compiles():
    """The wall-clock instants at which JAX's backend compiled something
    (the event the compile monitor files as ``compile.xla`` or
    ``compile.cache_load``).  JAX keeps a listener for the life of the
    process, so this one only appends to its list."""
    instants = []

    def listener(name, seconds, **_):
        if name == compiles.BACKEND_COMPILE:
            instants.append(time.time_ns())
    jax.monitoring.register_event_duration_secs_listener(listener)
    return instants


def executables(step):
    """How many executables each of the two programs that the step's
    ``run()`` dispatches holds (the gather steps are ``AotStep``s: the
    one compiled ahead counts, their jits never compile)."""
    if hasattr(step, "_train_scan_"):
        found = (step._train_scan_, step._eval_scan_)
    elif step._use_gather_:
        found = (step._train_step_g_, step._eval_step_g_)
    else:
        found = (step._train_step_, step._eval_step_)
    return [program._cache_size() for program in found]


@pytest.mark.parametrize("case", ["scan", "scan_data4", "per_step",
                                  "per_step_data4"])
def test_nothing_compiles_after_the_first_epoch(case, backend_compiles):
    """One executable a program, and no backend compile once the first
    epoch is over: the epilogue brings no program of its own and hands
    the next dispatch arguments of the avals, weak types and placement
    the first one had."""
    scan, mesh = case.startswith("scan"), case.endswith("data4")
    wf = build(fused=True, minibatch=40, epoch_scan=scan,
               max_epochs=3 if scan else 2, mesh=data4() if mesh else None)
    step = wf.fused_step
    assert type(step).__name__ == ("ScanEpochStep" if scan
                                   else "FusedTrainStep")
    assert (step.mesh is not None) == mesh
    events.reset()
    del backend_compiles[:]
    wf.run()
    runs = named(events.spans(), "step.run")
    assert {r.info["epoch"] for r in runs} == set(range(3 if scan else 2))
    second_epoch = min(r.start_ns for r in runs if r.info["epoch"] == 1)
    assert [t for t in backend_compiles if t < second_epoch], \
        "the listener saw the first epoch compile"
    late = [t for t in backend_compiles if t > second_epoch]
    assert late == [], "%d backend compile(s) after the first epoch" \
        % len(late)
    assert executables(step) == [1, 1]


# -- the same numbers in the new order ----------------------------------------

def block_first(step):
    """The class end as it was before PR 27, from the step's own pieces:
    wait for the accumulator and file its scalars, THEN make the new
    accumulator and copy the weights."""
    def finish():
        jax.block_until_ready(step._macc_)
        step._pull_metrics(step._macc_)
        step._macc_ = step._macc_init()
        step.sync_weights()
    step._finish_class = finish


def watch(step):
    """Record what a consumer sees after every class end, and hold the
    forward units' device arrays over the next dispatch."""
    ends, finish = [], step._finish_class

    def units_arrays():
        return [fwd.params for fwd in step.forwards]

    def host(tree):
        return jax.tree.map(numpy.array, tree)

    def watched():
        if ends:
            # the dispatch just made donated the buffers the last class
            # end copied FROM: the copies must have outlived it
            last = ends[-1]
            assert not any(leaf.is_deleted() for leaf in
                           jax.tree.leaves(last["held"]))
            assert_trees_equal(host(last["held"]), last["params"])
        finish()
        cm = step.confusion_matrix
        ends.append({
            "n_err": int(step.n_err[0]),
            "max_err": float(step.max_err_output_sum[0]),
            "confusion": numpy.array(cm.map_read()) if cm else None,
            "mse": numpy.array(step.metrics.map_read()),
            "params": host(step._params_),
            "units": host(units_arrays()),
            "held": units_arrays()})
    step._finish_class = watched
    return ends


def assert_trees_equal(got, want):
    got, want = jax.tree.leaves(got), jax.tree.leaves(want)
    assert len(got) == len(want) > 0
    for a, b in zip(got, want):
        assert a.dtype == b.dtype and numpy.array_equal(a, b)


def drive(case, reference):
    loss, how = case.split("-")
    scan = how != "per_step"
    make = build if loss == "softmax" else build_mse
    wf = make(fused=True, minibatch=40, epoch_scan=scan, max_epochs=3,
              mesh=data4() if how == "scan_data4" else None)
    step = wf.fused_step
    if reference:
        block_first(step)
    ends = watch(step)
    if how == "train_epochs":
        step.train_epochs(2)
        step.train_epochs(1)
    else:
        wf.run()
    return ends


@pytest.mark.parametrize("case", [
    "softmax-scan", "softmax-per_step", "softmax-train_epochs",
    "softmax-scan_data4", "mse-scan", "mse-per_step", "mse-train_epochs"])
def test_class_ends_read_as_in_the_old_order(case):
    """After every class end the forward units hold ``_params_`` bit for
    bit in buffers of their own, and ``n_err``, the largest error sum,
    the confusion matrix, the mse triple and the weights are those of a
    run that blocks first."""
    new, old = drive(case, reference=False), drive(case, reference=True)
    how = case.split("-")[1]
    assert len(new) == len(old) == (2 if how == "train_epochs" else 6)
    for end in new:
        assert_trees_equal(end["units"], end["params"])
    softmax = case.startswith("softmax")
    for got, want in zip(new, old):
        assert got["n_err"] == want["n_err"]
        assert got["max_err"] == want["max_err"]
        assert numpy.array_equal(got["mse"], want["mse"])
        if softmax:
            assert numpy.array_equal(got["confusion"], want["confusion"])
        assert_trees_equal(got["params"], want["params"])
    if softmax:
        assert new[-1]["confusion"].sum() > 0 and new[-1]["max_err"] > 0
        assert any(end["n_err"] > 0 for end in new)
    else:
        assert 0 < new[-1]["mse"][2] <= new[-1]["mse"][1] < numpy.inf


def test_sync_weights_alone_leaves_its_span_and_fresh_copies():
    """``sync_weights()`` stays callable on its own (snapshot, rollback,
    the workflow's end): one span, no read, buffers that are not the
    step's."""
    wf = build(fused=True, minibatch=40, epoch_scan=True, max_epochs=1)
    wf.run()
    step = wf.fused_step
    events.reset()
    step.sync_weights()
    assert [s.name for s in events.spans()] == ["veles.step.sync_weights"]
    for fwd, layer in zip(step.forwards, step._params_):
        for name, value in fwd.params.items():
            assert value is not layer[name]
            assert value.unsafe_buffer_pointer() \
                != layer[name].unsafe_buffer_pointer()
            assert numpy.array_equal(numpy.asarray(value),
                                     numpy.asarray(layer[name]))
