"""Conv/pooling/LRN/dropout stack tests: device-vs-numpy forward parity
(two independent algorithms for conv), vjp backward sanity, and a small
convnet training end-to-end."""

import numpy
import pytest

from veles_tpu.backends import Device
from veles_tpu.memory import Array
from veles_tpu.prng import RandomGenerator
from veles_tpu.workflow import Workflow
from veles_tpu.znicz import (
    Conv, ConvTanh, ConvStrictRELU, MaxPooling, AvgPooling, MaxAbsPooling,
    StochasticPooling, LRNormalizerForward, DropoutForward, Cutter,
    Depooling, Deconv, GradientDescentConv,
)


def make_unit(cls, input_shape=(4, 12, 12, 3), seed=13, **kwargs):
    wf = Workflow(name="w")
    u = cls(wf, prng=RandomGenerator().seed(seed), **kwargs)
    rng = numpy.random.RandomState(1)
    u.input = Array(rng.uniform(-1, 1, input_shape).astype(numpy.float32))
    return u


CONV_CASES = [
    (Conv, {"n_kernels": 8, "kx": 3, "ky": 3}),
    (ConvTanh, {"n_kernels": 4, "kx": 5, "ky": 5, "padding": 2}),
    (ConvStrictRELU, {"n_kernels": 6, "kx": 3, "ky": 3,
                      "sliding": (2, 2), "padding": 1}),
    # grouped conv (the AlexNet two-tower split, native on TPU)
    (Conv, {"n_kernels": 6, "kx": 3, "ky": 3, "grouping": 3}),
]


@pytest.mark.parametrize("cls,kwargs", CONV_CASES)
def test_conv_lax_vs_im2col(cls, kwargs):
    """lax.conv path must match the independent im2col twin."""
    dev = make_unit(cls, **kwargs)
    ref = make_unit(cls, **kwargs)
    dev.initialize(device=Device(backend="cpu"))
    ref.initialize(device=Device(backend="numpy"))
    dev.run()
    ref.run()
    assert dev.output.shape == ref.output.shape == \
        dev.output_shape_for(dev.input.shape)
    assert numpy.allclose(dev.output.map_read(), ref.output.map_read(),
                          atol=1e-4)


@pytest.mark.parametrize("cls", [MaxPooling, AvgPooling, MaxAbsPooling])
def test_pooling_parity(cls):
    kwargs = {"kx": 3, "ky": 3, "sliding": (2, 2)}
    dev = make_unit(cls, **kwargs)
    ref = make_unit(cls, **kwargs)
    dev.initialize(device=Device(backend="cpu"))
    ref.initialize(device=Device(backend="numpy"))
    dev.run()
    ref.run()
    assert numpy.allclose(dev.output.map_read(), ref.output.map_read(),
                          atol=1e-5)


def test_maxabs_keeps_sign():
    u = make_unit(MaxAbsPooling, kx=2, ky=2)
    u.initialize(device=Device(backend="cpu"))
    x = numpy.zeros((1, 2, 2, 1), numpy.float32)
    x[0, :, :, 0] = [[-5, 1], [2, 3]]
    u.input = Array(x)
    u.run()
    assert u.output.map_read()[0, 0, 0, 0] == -5


def test_stochastic_pooling_eval_is_expectation():
    u = make_unit(StochasticPooling, kx=2, ky=2)
    u.initialize(device=Device(backend="cpu"))
    x = numpy.abs(numpy.random.RandomState(0).uniform(
        0.1, 1, (2, 4, 4, 2))).astype(numpy.float32)
    u.input = Array(x)
    u.run()
    out = u.output.map_read()
    win = x[:, :2, :2, :].reshape(2, 4, 2)
    expect = (win * (win / win.sum(1, keepdims=True))).sum(1)
    assert numpy.allclose(out[:, 0, 0, :], expect, atol=1e-5)


def test_stochastic_pooling_train_samples_window_elements():
    import jax
    u = make_unit(StochasticPooling, kx=2, ky=2)
    u.initialize(device=Device(backend="cpu"))
    x = u.input.map_read()
    out = numpy.asarray(u.apply_train({}, x, jax.random.PRNGKey(0)))
    # every output element must be one of its window's elements
    win = x[:, 0:2, 0:2, :].reshape(x.shape[0], 4, x.shape[3])
    for b in range(x.shape[0]):
        for c in range(x.shape[3]):
            assert out[b, 0, 0, c] in win[b, :, c]


def test_lrn_parity_and_shape():
    u = make_unit(LRNormalizerForward)
    r = make_unit(LRNormalizerForward)
    u.initialize(device=Device(backend="cpu"))
    r.initialize(device=Device(backend="numpy"))
    u.run()
    r.run()
    assert numpy.allclose(u.output.map_read(), r.output.map_read(),
                          atol=1e-5)
    # normalization shrinks magnitudes
    assert numpy.abs(u.output.map_read()).max() <= \
        numpy.abs(u.input.map_read()).max()


def test_dropout_eval_identity_train_masks():
    import jax
    u = make_unit(DropoutForward, dropout_ratio=0.5)
    u.initialize(device=Device(backend="cpu"))
    u.run()
    assert numpy.allclose(u.output.map_read(), u.input.map_read())
    x = u.input.map_read()
    masked = numpy.asarray(u.apply_train({}, x, jax.random.PRNGKey(1)))
    zeros = (masked == 0).mean()
    assert 0.3 < zeros < 0.7
    kept = masked != 0
    assert numpy.allclose(masked[kept], x[kept] * 2, atol=1e-5)


def test_cutter_and_depooling_shapes():
    c = make_unit(Cutter, top=1, left=2, crop_h=8, crop_w=6)
    c.initialize(device=Device(backend="cpu"))
    c.run()
    assert c.output.shape == (4, 8, 6, 3)
    assert numpy.allclose(c.output.map_read(),
                          c.input.map_read()[:, 1:9, 2:8, :])
    d = make_unit(Depooling, kx=2, ky=2)
    d.initialize(device=Device(backend="cpu"))
    d.run()
    assert d.output.shape == (4, 24, 24, 3)


def test_deconv_inverts_conv_shape():
    u = make_unit(Deconv, n_kernels=5, kx=4, ky=4, sliding=(2, 2),
                  padding=1)
    u.initialize(device=Device(backend="cpu"))
    u.run()
    assert u.output.shape == u.output_shape_for(u.input.shape)


def test_conv_backward_matches_autodiff():
    import jax
    import jax.numpy as jnp
    fwd = make_unit(Conv, n_kernels=4, kx=3, ky=3)
    fwd.initialize(device=Device(backend="cpu"))
    fwd.run()
    gd = GradientDescentConv(fwd.workflow, learning_rate=0.0)
    gd.link_forward(fwd)
    rng = numpy.random.RandomState(2)
    err = rng.uniform(-1, 1, fwd.output.shape).astype(numpy.float32)
    params = {k: jnp.asarray(v) for k, v in fwd.params.items()}
    x = jnp.asarray(fwd.input.map_read())

    def loss(p, xx):
        return (fwd.apply(p, xx) * jnp.asarray(err)).sum() / x.shape[0]

    auto = jax.grad(loss)(params, x)
    err_in, grads = gd.backward(params, x, None, jnp.asarray(err))
    for k in grads:
        assert numpy.allclose(numpy.asarray(grads[k]),
                              numpy.asarray(auto[k]), atol=1e-4), k


def test_small_convnet_trains():
    """Mini CIFAR-style convnet end-to-end on synthetic images."""
    from veles_tpu.znicz.samples import cifar
    wf = cifar.create_workflow(
        loader={"minibatch_size": 50, "n_train": 300, "n_valid": 100,
                "normalization_type": "range_linear",
                "prng": RandomGenerator().seed(7)},
        layers=[
            {"type": "conv_str", "->": {"n_kernels": 8, "kx": 5, "ky": 5,
                                        "padding": 2},
             "<-": {"learning_rate": 0.02, "gradient_moment": 0.9}},
            {"type": "max_pooling", "->": {"kx": 2, "ky": 2}},
            {"type": "all2all_str", "->": {"output_sample_shape": 32},
             "<-": {"learning_rate": 0.02, "gradient_moment": 0.9}},
            {"type": "softmax", "->": {"output_sample_shape": 10},
             "<-": {"learning_rate": 0.02, "gradient_moment": 0.9}},
        ],
        decision={"max_epochs": 8, "silent": True})
    wf.initialize(device=Device(backend="cpu"))
    wf.run()
    assert wf.is_finished
    assert wf.decision.best_n_err_pt < 25.0, wf.decision.best_n_err_pt


def test_alexnet_builds_and_steps():
    """Full AlexNet topology compiles and takes one fused train step on
    tiny synthetic data (shape check for the headline model)."""
    from veles_tpu.znicz.samples import alexnet
    from veles_tpu import loader as loader_mod
    wf = alexnet.create_workflow(
        loader={"minibatch_size": 4, "n_train": 8, "n_valid": 4,
                "n_classes": 20, "side": 67,
                "prng": RandomGenerator().seed(7)},
        decision={"max_epochs": 1, "silent": True})
    wf.initialize(device=Device(backend="cpu"))
    while True:
        wf.loader.run()
        if wf.loader.minibatch_class == loader_mod.TRAIN:
            break
    wf.fused_step.run()
    loss = float(wf.fused_step.loss)
    assert loss == loss and loss > 0


def test_pallas_lrn_matches_reference_and_grads():
    """The Pallas LRN kernel pair (fwd + analytic custom-vjp bwd) matches
    the plain jnp formula and the numpy twin, values AND gradients."""
    import jax
    import jax.numpy as jnp
    import numpy
    from veles_tpu.workflow import Workflow
    from veles_tpu.znicz.lrn import LRNormalizerForward

    wf = Workflow(None)
    unit_p = LRNormalizerForward(wf, n=5, alpha=1e-4, beta=0.75, k=2.0,
                                 use_pallas=True)
    unit_j = LRNormalizerForward(wf, n=5, alpha=1e-4, beta=0.75, k=2.0,
                                 use_pallas=False)
    x = numpy.random.RandomState(3).randn(4, 5, 5, 16).astype(
        numpy.float32)
    y_p = numpy.asarray(unit_p.apply({}, jnp.asarray(x)))
    y_j = numpy.asarray(unit_j.apply({}, jnp.asarray(x)))
    y_np = unit_p.apply_numpy({}, x)
    assert numpy.abs(y_p - y_j).max() < 1e-5
    assert numpy.abs(y_p - y_np).max() < 1e-5

    def loss_p(v):
        return (unit_p.apply({}, v) ** 2).sum()

    def loss_j(v):
        return (unit_j.apply({}, v) ** 2).sum()
    g_p = numpy.asarray(jax.grad(loss_p)(jnp.asarray(x)))
    g_j = numpy.asarray(jax.grad(loss_j)(jnp.asarray(x)))
    assert numpy.abs(g_p - g_j).max() < 1e-4, numpy.abs(g_p - g_j).max()
    # even-n (asymmetric) windows must also agree across paths
    for n in (2, 4):
        up = LRNormalizerForward(wf, n=n, alpha=0.5, use_pallas=True)
        uj = LRNormalizerForward(wf, n=n, alpha=0.5, use_pallas=False)
        yp = numpy.asarray(up.apply({}, jnp.asarray(x)))
        yj = numpy.asarray(uj.apply({}, jnp.asarray(x)))
        assert numpy.abs(yp - yj).max() < 1e-5, (n, numpy.abs(yp - yj).max())
        assert numpy.abs(yp - up.apply_numpy({}, x)).max() < 1e-5
        # asymmetric windows need the TRANSPOSED window in the VJP
        gp = numpy.asarray(jax.grad(
            lambda v: (up.apply({}, v) ** 2).sum())(jnp.asarray(x)))
        gj = numpy.asarray(jax.grad(
            lambda v: (uj.apply({}, v) ** 2).sum())(jnp.asarray(x)))
        assert numpy.abs(gp - gj).max() < 1e-4, \
            (n, numpy.abs(gp - gj).max())
