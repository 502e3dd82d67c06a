"""Gradient-descent solvers as pure update rules.

Reference: the Znicz GradientDescentBase solver knobs (SURVEY.md §2.9;
docs manualrst_veles_algorithms.rst:150-165 — momentum, AdaGrad, AdaDelta,
L1/L2 blending, ``factor_ortho``).  Each solver is a pair of pure functions
so the fused jitted train step can thread solver state through
``lax``-friendly pytrees:

- ``init(param) -> state``  (a pytree of arrays, may be empty tuple)
- ``update(grad, param, state, lr) -> (delta, new_state)`` where the caller
  applies ``param + delta``.

``xp`` selects the array namespace (jax.numpy on device, numpy for the
parity twin) so the exact same arithmetic runs on both paths.
"""

import numpy


def regularized_grad(grad, param, weights_decay, l1_vs_l2, xp=numpy,
                     factor_ortho=0.0):
    """Add the L1/L2-blended decay term (and optional soft-orthogonality
    push) to a raw gradient.

    reg = decay * ((1 - l1_vs_l2) * w + l1_vs_l2 * sign(w) / 2)
    following the Znicz blending convention; ortho term is the gradient of
    ``factor_ortho/4 * ||W^T W - I||^2`` for 2-D weights.
    """
    g = grad
    if weights_decay:
        g = g + weights_decay * ((1.0 - l1_vs_l2) * param +
                                 0.5 * l1_vs_l2 * xp.sign(param))
    if factor_ortho and param.ndim == 2:
        wtw = param.T @ param
        eye = xp.eye(wtw.shape[0], dtype=param.dtype)
        g = g + factor_ortho * (param @ (wtw - eye))
    return g


class Solver:
    name = None

    def __init__(self, **hyper):
        self.hyper = hyper

    def init(self, param, xp=numpy):
        return ()

    def update(self, grad, param, state, lr, xp=numpy):
        raise NotImplementedError


class SGD(Solver):
    name = "sgd"

    def update(self, grad, param, state, lr, xp=numpy):
        return -lr * grad, state


class Momentum(Solver):
    """Classic heavy-ball: v = mu*v - lr*g; w += v (Znicz
    ``gradient_moment``)."""

    name = "momentum"

    def init(self, param, xp=numpy):
        return (xp.zeros_like(param),)

    def update(self, grad, param, state, lr, xp=numpy):
        (v,) = state
        v = self.hyper.get("momentum", 0.9) * v - lr * grad
        return v, (v,)


class AdaGrad(Solver):
    name = "adagrad"

    def init(self, param, xp=numpy):
        return (xp.zeros_like(param),)

    def update(self, grad, param, state, lr, xp=numpy):
        (accum,) = state
        eps = self.hyper.get("epsilon", 1e-8)
        accum = accum + grad * grad
        return -lr * grad / (xp.sqrt(accum) + eps), (accum,)


class AdaDelta(Solver):
    name = "adadelta"

    def init(self, param, xp=numpy):
        return (xp.zeros_like(param), xp.zeros_like(param))

    def update(self, grad, param, state, lr, xp=numpy):
        accum_g, accum_dx = state
        rho = self.hyper.get("rho", 0.95)
        eps = self.hyper.get("epsilon", 1e-6)
        accum_g = rho * accum_g + (1 - rho) * grad * grad
        dx = -xp.sqrt(accum_dx + eps) / xp.sqrt(accum_g + eps) * grad
        accum_dx = rho * accum_dx + (1 - rho) * dx * dx
        return lr * dx, (accum_g, accum_dx)


class RProp(Solver):
    """Resilient propagation (RPropAll2All parity): per-weight step sizes
    grown/shrunk by gradient sign agreement."""

    name = "rprop"

    def init(self, param, xp=numpy):
        return (xp.full_like(param, self.hyper.get("step0", 1e-3)),
                xp.zeros_like(param))

    def update(self, grad, param, state, lr, xp=numpy):
        step, prev_g = state
        inc = self.hyper.get("eta_plus", 1.2)
        dec = self.hyper.get("eta_minus", 0.5)
        agree = grad * prev_g
        step = xp.where(agree > 0,
                        xp.minimum(step * inc,
                                   self.hyper.get("step_max", 50.0)),
                        xp.where(agree < 0,
                                 xp.maximum(step * dec,
                                            self.hyper.get("step_min",
                                                           1e-9)),
                                 step))
        return -xp.sign(grad) * step, (step, grad)


class AdamW(Solver):
    """Adam with decoupled weight decay (Loshchilov & Hutter 2019):
    ``m = b1 m + (1 - b1) g``, ``v = b2 v + (1 - b2) g^2``, ``delta =
    -lr (m_hat / (sqrt(v_hat) + eps) + weight_decay w)`` with the bias
    corrections ``m_hat = m / (1 - b1^t)``, ``v_hat = v / (1 - b2^t)``.
    The decay acts on matrices only (``param.ndim >= 2``): norms' weights
    and biases are left alone, the usual convention.  State: ``(m, v,
    t)``, moments in the parameter's dtype (float32 master weights give
    float32 moments)."""

    name = "adamw"

    def init(self, param, xp=numpy):
        return (xp.zeros_like(param), xp.zeros_like(param),
                xp.zeros((), xp.int32))

    def update(self, grad, param, state, lr, xp=numpy):
        m, v, t = state
        b1 = self.hyper.get("beta1", 0.9)
        b2 = self.hyper.get("beta2", 0.999)
        eps = self.hyper.get("epsilon", 1e-8)
        decay = self.hyper.get("weight_decay", 0.0) \
            if param.ndim >= 2 else 0.0
        t = t + 1
        m = b1 * m + (1 - b1) * grad
        v = b2 * v + (1 - b2) * grad * grad
        steps = t.astype(param.dtype)
        m_hat = m / (1 - b1 ** steps)
        v_hat = v / (1 - b2 ** steps)
        step = m_hat / (xp.sqrt(v_hat) + eps)
        if decay:
            step = step + decay * param
        return -lr * step, (m, v, t)


_SOLVERS = {c.name: c for c in (SGD, Momentum, AdaGrad, AdaDelta, RProp,
                                AdamW)}


def factory(name, **hyper):
    try:
        return _SOLVERS[name](**hyper)
    except KeyError:
        raise ValueError("unknown solver %r (have: %s)" %
                         (name, sorted(_SOLVERS)))
