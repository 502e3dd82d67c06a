"""Plain reference of the ``alexnet`` configuration: the forward pass,
the loss, its gradient and the momentum update in straightforward
``jax.numpy`` float32, no kernels, no fusion tricks, dropout the
identity.  It follows Krizhevsky et al. 2012 in the single-tower form of
``config.json``; it reads the layer list and the solver from that file
and imports nothing from the program.

    logits = forward(layers, weights, x)        # x: [B, H, W, C]
    loss   = softmax_loss(logits, labels)       # mean cross-entropy
    weights, velocity = momentum_steps(layers, solver, weights, x, labels, n)

``weights`` is the list of ``(kernel_or_matrix, bias)`` pairs of the
layers that have parameters, in order; convolution kernels are HWIO and
fully-connected matrices ``[inputs, outputs]`` over the NHWC activation
flattened row-major.

``precision`` is how float32 products are multiplied: ``"highest"`` (the
default: the mathematics, six bf16 passes on a TPU) or ``"default"``, the
arithmetic ``config.json`` states for the system (``precision_level`` 0:
operands rounded to bfloat16 once, products summed in float32, everything
between two products float32).  Against the first a tolerance has to
cover that rounding; against the second it need not, so a system that
ALSO keeps its activations in bfloat16 stands out.
"""

import jax
import jax.numpy as jnp
from jax import lax


def _lrn(x, n, alpha, beta, k):
    """Local response normalisation across channels (section 3.3 of the
    paper, with alpha divided by the window as Caffe and Znicz do):
    ``x / (k + alpha / n * sum_{window n} x^2) ** beta``."""
    half = n // 2
    sq = jnp.pad(x * x, ((0, 0), (0, 0), (0, 0), (half, n - 1 - half)))
    c = x.shape[-1]
    acc = sum(sq[..., i:i + c] for i in range(n))
    return x / (k + alpha / n * acc) ** beta


def forward(layers, weights, x, precision="highest"):
    """Evaluation-mode logits ``[B, classes]`` of the network that
    ``layers`` (the ``layers`` list of ``config.json``) describes."""
    weights = iter(weights)
    h = jnp.asarray(x, jnp.float32)
    with jax.default_matmul_precision(precision):
        for layer in layers:
            kind = layer["type"]
            if kind == "conv":
                w, b = next(weights)
                pad = layer["padding"]
                h = lax.conv_general_dilated(
                    h, w, (layer["stride"],) * 2, ((pad, pad), (pad, pad)),
                    dimension_numbers=("NHWC", "HWIO", "NHWC")) + b
                h = jnp.maximum(h, 0.0)
            elif kind == "lrn":
                h = _lrn(h, layer["n"], layer["alpha"], layer["beta"],
                         layer["k"])
            elif kind == "max_pool":
                h = lax.reduce_window(
                    h, -jnp.inf, lax.max, (1, layer["ky"], layer["kx"], 1),
                    (1, layer["stride"], layer["stride"], 1), "VALID")
            elif kind == "fc":
                w, b = next(weights)
                h = jnp.maximum(h.reshape(h.shape[0], -1) @ w + b, 0.0)
            elif kind == "dropout":
                pass                    # identity outside training
            elif kind == "softmax":
                w, b = next(weights)
                h = h.reshape(h.shape[0], -1) @ w + b
            else:
                raise ValueError("unknown layer type %r" % kind)
    return h


def softmax_loss(logits, labels):
    """Mean cross-entropy of ``labels`` under ``softmax(logits)``."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))


def momentum_steps(layers, solver, weights, x, labels, steps):
    """``steps`` steps of heavy-ball SGD on the one minibatch ``(x,
    labels)`` from ``weights`` and zero velocity, as section 5 of the paper
    has it: ``v = momentum * v - learning_rate * (dL/dw + weight_decay *
    w)``, ``w = w + v``, with the decay on the kernels and matrices and
    not on the biases (Znicz's and Caffe's convention), at ``highest``
    precision.  Returns the weights and the velocities after the last
    step."""
    def loss(w):
        return softmax_loss(forward(layers, w, x), labels)

    rate, moment = solver["learning_rate"], solver["momentum"]
    decay = (solver["weight_decay"], 0.0)       # (kernel, bias)
    weights = [tuple(pair) for pair in weights]
    velocity = [tuple(jnp.zeros_like(p) for p in pair) for pair in weights]
    for _ in range(steps):
        grads = jax.grad(loss)(weights)
        velocity = [tuple(moment * v - rate * (g + d * p)
                          for v, g, d, p in zip(vs, gs, decay, ps))
                    for vs, gs, ps in zip(velocity, grads, weights)]
        weights = [tuple(p + v for p, v in zip(ps, vs))
                   for ps, vs in zip(weights, velocity)]
    return weights, velocity
