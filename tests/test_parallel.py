"""Multi-device tests on the conftest-provisioned virtual 8-CPU platform:
the mesh data/tensor-parallel path must produce the same training result as
the single-device fused step (SURVEY.md §2.4: DP via sharded all-reduce is
the required first-class equivalent of the reference's master-slave star).
"""

import numpy
import pytest

import jax

from veles_tpu.backends import Device
from veles_tpu.parallel.mesh import (
    make_mesh, batch_sharding, tensor_parallel_sharding)
from veles_tpu.prng import RandomGenerator
from veles_tpu.znicz.standard_workflow import StandardWorkflow

from test_standard_workflow import BlobLoader, LAYERS


def build(mesh=None, model_axis=None, max_epochs=3, minibatch=40, seed=21,
          **extra):
    import veles_tpu.prng.random_generator as rg
    rg._generators.clear()
    rg.get(0).seed(seed)
    wf = StandardWorkflow(
        None, name="par",
        loader_factory=BlobLoader,
        loader={"minibatch_size": minibatch,
                "prng": RandomGenerator().seed(5)},
        layers=LAYERS, loss_function="softmax",
        decision={"max_epochs": max_epochs, "silent": True},
        fused=True, mesh=mesh, model_axis=model_axis, **extra)
    wf.initialize(device=Device(backend="cpu"))
    return wf


def test_mesh_has_8_devices():
    assert len(jax.devices()) >= 8, (
        "conftest must provision 8 virtual CPU devices")


def test_dp_equals_single_device():
    """Pure data parallelism over {"data": 8} must train the same weights
    as the unsharded fused step — the sharding annotations change the
    execution layout (gradient psum over the mesh), not the math."""
    wf_s = build()
    wf_d = build(mesh=make_mesh({"data": 8}))
    wf_s.run()
    wf_d.run()
    for fs, fd in zip(wf_s.forwards, wf_d.forwards):
        assert numpy.allclose(fs.weights.map_read(), fd.weights.map_read(),
                              atol=2e-5), type(fs).__name__
        assert numpy.allclose(fs.bias.map_read(), fd.bias.map_read(),
                              atol=2e-5)
    assert wf_s.decision.best_n_err_pt == pytest.approx(
        wf_d.decision.best_n_err_pt, abs=1e-9)
    assert wf_s.decision.best_epoch == wf_d.decision.best_epoch


def test_tp_equals_dp():
    """data x model tensor parallelism must match pure DP: the column-split
    weights + activation gathers are a layout change only."""
    wf_d = build(mesh=make_mesh({"data": 8}))
    wf_t = build(mesh=make_mesh({"data": 4, "model": 2}),
                 model_axis="model")
    wf_d.run()
    wf_t.run()
    for fd, ft in zip(wf_d.forwards, wf_t.forwards):
        assert numpy.allclose(fd.weights.map_read(), ft.weights.map_read(),
                              atol=2e-5), type(fd).__name__
    assert wf_d.decision.best_n_err_pt == pytest.approx(
        wf_t.decision.best_n_err_pt, abs=1e-9)


def test_dp_tail_batch():
    """Class lengths that don't divide the minibatch leave a padded tail
    batch; the sharded step must mask the padding identically to the
    single-device step (and not recompile per tail size — size is traced)."""
    wf_s = build(minibatch=32)        # 150 train -> tail of 22; 50 val -> 18
    wf_d = build(minibatch=32, mesh=make_mesh({"data": 8}))
    wf_s.run()
    wf_d.run()
    for fs, fd in zip(wf_s.forwards, wf_d.forwards):
        assert numpy.allclose(fs.weights.map_read(), fd.weights.map_read(),
                              atol=2e-5), type(fs).__name__
    assert wf_s.decision.best_n_err_pt == pytest.approx(
        wf_d.decision.best_n_err_pt, abs=1e-9)


def test_dp_no_tail_recompile():
    """The sharded train step must compile at most twice (train + eval
    signatures), not once per distinct tail-batch size."""
    wf = build(minibatch=32, mesh=make_mesh({"data": 8}), max_epochs=2)
    step = wf.fused_step
    wf.run()
    # _cache_size() counts distinct compiled signatures for this callable;
    # python-int weak types may add one variant, but per-size entries would
    # show up as one per distinct tail size
    assert step._train_step_._cache_size() <= 2, \
        "train step recompiled for tail batches: %d signatures" % \
        step._train_step_._cache_size()
    assert step._eval_step_._cache_size() <= 2, \
        "eval step recompiled for tail batches: %d signatures" % \
        step._eval_step_._cache_size()


class ImageBlobLoader(BlobLoader):
    """The blob problem reshaped to 16x16x3 images (conv TP parity)."""

    def load_data(self):
        super().load_data()
        rng = numpy.random.RandomState(7)
        n = len(self.original_data.mem)
        proj = rng.uniform(-0.4, 0.4, (8, 16 * 16 * 3)).astype(
            numpy.float32)
        self.original_data.mem = (
            self.original_data.mem @ proj).reshape(n, 16, 16, 3)


CONV_LAYERS = [
    {"type": "conv_str", "->": {"n_kernels": 8, "kx": 3, "ky": 3,
                                "padding": 1},
     "<-": {"learning_rate": 0.05, "gradient_moment": 0.9}},
    {"type": "max_pooling", "->": {"kx": 2, "ky": 2, "sliding": (2, 2)}},
    {"type": "conv_str", "->": {"n_kernels": 16, "kx": 3, "ky": 3,
                                "padding": 1},
     "<-": {"learning_rate": 0.05, "gradient_moment": 0.9}},
    {"type": "avg_pooling", "->": {"kx": 2, "ky": 2, "sliding": (2, 2)}},
    {"type": "softmax", "->": {"output_sample_shape": 4},
     "<-": {"learning_rate": 0.05, "gradient_moment": 0.9}},
]


def build_conv(mesh=None, model_axis=None, max_epochs=2, minibatch=40,
               seed=23, **extra):
    import veles_tpu.prng.random_generator as rg
    rg._generators.clear()
    rg.get(0).seed(seed)
    wf = StandardWorkflow(
        None, name="par-conv",
        loader_factory=ImageBlobLoader,
        loader={"minibatch_size": minibatch,
                "prng": RandomGenerator().seed(5)},
        layers=CONV_LAYERS, loss_function="softmax",
        decision={"max_epochs": max_epochs, "silent": True},
        fused=True, mesh=mesh, model_axis=model_axis, **extra)
    wf.initialize(device=Device(backend="cpu"))
    return wf


def test_tp_conv_equals_dp():
    """Tensor parallelism on a CONV stack (4-D kernels split on their
    output-channel dim over ``model``) must match pure DP — the north
    star (AlexNet) is a conv model, so "model parallelism" has to mean
    more than sharding the classifier."""
    wf_d = build_conv(mesh=make_mesh({"data": 8}))
    wf_t = build_conv(mesh=make_mesh({"data": 4, "model": 2}),
                      model_axis="model")
    wf_d.run()
    wf_t.run()
    for fd, ft in zip(wf_d.forwards, wf_t.forwards):
        if not fd.params:
            continue
        assert numpy.allclose(fd.weights.map_read(), ft.weights.map_read(),
                              atol=2e-5), type(fd).__name__
    assert wf_d.decision.best_n_err_pt == pytest.approx(
        wf_t.decision.best_n_err_pt, abs=1e-9)


def test_mesh_epoch_scan_equals_single_scan():
    """epoch_scan over a mesh (ScanEpochStep, mesh=): one scan dispatch
    per class, batch split over data, params replicated — must train
    the same weights as the single-device scan AND the per-step mesh."""
    wf_s = build(epoch_scan=True)
    wf_m = build(mesh=make_mesh({"data": 8}), epoch_scan=True)
    wf_s.run()
    wf_m.run()
    for fs, fm in zip(wf_s.forwards, wf_m.forwards):
        assert numpy.allclose(fs.weights.map_read(), fm.weights.map_read(),
                              atol=2e-5), type(fs).__name__
    assert wf_s.decision.best_n_err_pt == pytest.approx(
        wf_m.decision.best_n_err_pt, abs=1e-9)


def test_mesh_epoch_scan_with_tp():
    """dp x tp sharded scan trains to the same result as DP scan."""
    wf_d = build(mesh=make_mesh({"data": 8}), epoch_scan=True)
    wf_t = build(mesh=make_mesh({"data": 4, "model": 2}),
                 model_axis="model", epoch_scan=True)
    wf_d.run()
    wf_t.run()
    for fd, ft in zip(wf_d.forwards, wf_t.forwards):
        assert numpy.allclose(fd.weights.map_read(), ft.weights.map_read(),
                              atol=2e-5), type(fd).__name__


def test_megatron_tp_equals_dp():
    """Megatron col/row alternation is a layout change only: training
    must match pure DP exactly (within f32 reduction noise)."""
    wf_d = build(mesh=make_mesh({"data": 8}))
    wf_m = build(mesh=make_mesh({"data": 4, "model": 2}),
                 model_axis="model", tp_mode="megatron")
    wf_d.run()
    wf_m.run()
    for fd, fm in zip(wf_d.forwards, wf_m.forwards):
        assert numpy.allclose(fd.weights.map_read(), fm.weights.map_read(),
                              atol=2e-5), type(fd).__name__
    assert wf_d.decision.best_n_err_pt == pytest.approx(
        wf_m.decision.best_n_err_pt, abs=1e-9)


def test_megatron_sharding_alternates():
    """Consecutive divisible FC weights pair column then row; the row
    layer's bias replicates (it adds to the reduced activation)."""
    mesh = make_mesh({"data": 4, "model": 2})
    params = [{"weights": numpy.zeros((8, 6)), "bias": numpy.zeros(6)},
              {"weights": numpy.zeros((6, 4)), "bias": numpy.zeros(4)},
              {"weights": numpy.zeros((4, 4)), "bias": numpy.zeros(4)}]
    shard = tensor_parallel_sharding(mesh, params, "model",
                                     mode="megatron")
    assert tuple(shard[0]["weights"].spec) == (None, "model")   # col
    assert tuple(shard[0]["bias"].spec) == ("model",)
    assert tuple(shard[1]["weights"].spec) == ("model", None)   # row
    assert tuple(shard[1]["bias"].spec) == ()                   # psum'd
    assert tuple(shard[2]["weights"].spec) == (None, "model")   # col again
    # a non-FC layer breaks the pairing: the FC after it is column-split
    params_mix = [
        {"weights": numpy.zeros((8, 6)), "bias": numpy.zeros(6)},
        {"weights": numpy.zeros((3, 3, 6, 6)), "bias": numpy.zeros(6)},
        {"weights": numpy.zeros((6, 4)), "bias": numpy.zeros(4)}]
    shard = tensor_parallel_sharding(mesh, params_mix, "model",
                                     mode="megatron")
    assert tuple(shard[2]["weights"].spec) == (None, "model")
    with pytest.raises(ValueError, match="tp mode"):
        tensor_parallel_sharding(mesh, {"weights": numpy.zeros((4, 4))},
                                 "model", mode="megatorn")


def test_mesh_epoch_scan_conv_stack():
    """The north-star model class (conv) through the mesh scan path:
    dp x tp sharded scan == single-device scan on the conv stack."""
    wf_s = build_conv(epoch_scan=True)
    wf_m = build_conv(mesh=make_mesh({"data": 4, "model": 2}),
                      model_axis="model", epoch_scan=True)
    wf_s.run()
    wf_m.run()
    for fs, fm in zip(wf_s.forwards, wf_m.forwards):
        if not fs.params:
            continue
        assert numpy.allclose(fs.weights.map_read(), fm.weights.map_read(),
                              atol=2e-5), type(fs).__name__
    assert wf_s.decision.best_n_err_pt == pytest.approx(
        wf_m.decision.best_n_err_pt, abs=1e-9)


def test_conv_kernel_sharding_spec():
    """4-D conv kernels split dim 3 (output channels) over ``model``;
    odd channel counts replicate."""
    mesh = make_mesh({"data": 4, "model": 2})
    params = [{"weights": numpy.zeros((3, 3, 3, 8)),
               "bias": numpy.zeros(8)},
              {"weights": numpy.zeros((3, 3, 8, 5)),
               "bias": numpy.zeros(5)}]
    shard = tensor_parallel_sharding(mesh, params, "model")
    assert tuple(shard[0]["weights"].spec) == (None, None, None, "model")
    assert tuple(shard[0]["bias"].spec) == ("model",)
    assert tuple(shard[1]["weights"].spec) == ()  # 5 % 2 != 0


def test_tensor_parallel_sharding_specs():
    """2-D weights split their output dim over the model axis; odd shapes
    replicate."""
    mesh = make_mesh({"data": 4, "model": 2})
    params = [{"weights": numpy.zeros((8, 6)), "bias": numpy.zeros(6)},
              {"weights": numpy.zeros((6, 5)), "bias": numpy.zeros(5)}]
    shard = tensor_parallel_sharding(mesh, params, "model")
    spec0 = shard[0]["weights"].spec
    assert tuple(spec0) == (None, "model")
    # 5 is not divisible by 2 -> replicated
    assert tuple(shard[1]["weights"].spec) == ()
    assert tuple(shard[0]["bias"].spec) == ("model",)


def test_batch_sharding_places_shards():
    mesh = make_mesh({"data": 8})
    x = jax.device_put(numpy.zeros((32, 4), numpy.float32),
                       batch_sharding(mesh))
    assert len(x.sharding.device_set) == 8
