"""The plain AlexNet reference's update against numbers worked out by
hand in numpy on a network small enough to write down, and the pieces of
the comparison that decide ``correct``."""

import numpy
import pytest

from benchlib import config, load

reference = load("configs/alexnet/reference.py")
driver = load("drivers/train.py")

#: a softmax layer alone: 3 inputs, 2 classes
LAYERS = [{"type": "softmax", "neurons": 2}]
SOLVER = {"learning_rate": 0.1, "momentum": 0.5, "weight_decay": 0.01}
X = numpy.array([[[[1.0, 2.0, -1.0]]], [[[0.5, -0.5, 2.0]]]], numpy.float32)
Y = numpy.array([0, 1], numpy.int32)
W = numpy.array([[0.1, -0.2], [0.3, 0.4], [-0.5, 0.2]], numpy.float32)
B = numpy.array([0.05, -0.05], numpy.float32)


def hand_gradient(w, b):
    x = X.reshape(2, 3).astype(numpy.float64)
    z = x @ w + b
    p = numpy.exp(z - z.max(axis=1, keepdims=True))
    p /= p.sum(axis=1, keepdims=True)
    p[numpy.arange(2), Y] -= 1.0
    return x.T @ p / 2, p.sum(axis=0) / 2


def test_momentum_steps_by_hand():
    w, b = W.astype(numpy.float64), B.astype(numpy.float64)
    vw, vb = numpy.zeros_like(w), numpy.zeros_like(b)
    for _ in range(2):
        gw, gb = hand_gradient(w, b)
        vw = 0.5 * vw - 0.1 * (gw + 0.01 * w)   # decay on the matrix
        vb = 0.5 * vb - 0.1 * gb                # and not on the bias
        w, b = w + vw, b + vb
    weights, velocity = reference.momentum_steps(
        LAYERS, SOLVER, [(W, B)], X, Y, 2)
    assert numpy.allclose(weights[0][0], w, atol=1e-6)
    assert numpy.allclose(weights[0][1], b, atol=1e-6)
    assert numpy.allclose(velocity[0][0], vw, atol=1e-7)
    assert numpy.allclose(velocity[0][1], vb, atol=1e-7)


@pytest.mark.parametrize("key,value", [("momentum", 0.4),
                                       ("weight_decay", 0.1),
                                       ("learning_rate", 0.11)])
def test_momentum_steps_sees_every_solver_setting(key, value):
    base = reference.momentum_steps(LAYERS, SOLVER, [(W, B)], X, Y, 2)
    other = reference.momentum_steps(LAYERS, dict(SOLVER, **{key: value}),
                                     [(W, B)], X, Y, 2)
    assert driver.relative_rms(other[1][0][0], base[1][0][0]) > 1e-3


def test_reference_precisions_agree_where_float32_is_float32():
    """On the CPU both precisions are the same arithmetic; on a TPU
    ``default`` is one bf16 pass, which no CPU test can show."""
    cfg = config("alexnet")
    rng = numpy.random.default_rng(0)
    layers = [dict(cfg["layers"][0], kernels=4), cfg["layers"][1],
              cfg["layers"][2], {"type": "softmax", "neurons": 3}]
    weights = [(rng.normal(0, 0.1, (11, 11, 3, 4)).astype(numpy.float32),
                numpy.zeros(4, numpy.float32)),
               (rng.normal(0, 0.1, (4 * 4 * 4, 3)).astype(numpy.float32),
                numpy.zeros(3, numpy.float32))]
    x = rng.uniform(-0.5, 0.5, (2, 47, 47, 3)).astype(numpy.float32)
    a = reference.forward(layers, weights, x, "highest")
    b = reference.forward(layers, weights, x, "default")
    assert a.shape == (2, 3)
    assert numpy.array_equal(numpy.asarray(a), numpy.asarray(b))


def test_relative_rms():
    want = numpy.array([3.0, -4.0])
    assert driver.relative_rms(want * 1.01, want) == pytest.approx(0.01)
    assert driver.relative_rms([0.0], [0.0]) == 0.0


def test_tolerances_on_each_side_of_one_bf16_rounding():
    """The comparison with the mathematics has to cover one rounding of
    every operand to bfloat16 (2^-9); the one in the stated arithmetic and
    the update at ``highest`` precision must not, or bfloat16 activations
    would pass them."""
    assert driver.STATED_LOGIT_TOLERANCE < 2 ** -8 < driver.LOGIT_TOLERANCE
    # a momentum of 0.8 for 0.9 moves the second velocity by 0.1 / 1.9
    assert driver.UPDATE_TOLERANCE < 0.1 / 1.9
    assert driver.UPDATE_STEPS >= 2     # the second step has a velocity
