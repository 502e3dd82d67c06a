"""Pooling forward units.

Re-creation of ``veles.znicz.pooling`` (absent; SURVEY.md §2.9):
MaxPooling, AvgPooling, MaxAbsPooling, StochasticPooling(±Abs, ±Depooling).

TPU-first: ``lax.reduce_window`` (XLA's native windowed reduction),
whose autodiff emits ``SelectAndScatter`` for the backward.  What was
tried against it on the chip and turned down (a window-offset max pool
with a hand-written VJP, a separable window, bfloat16 activations
through the window) is recorded in docs/PERF.md, rounds 4 and 5.

MaxAbsPooling keeps the *signed* value whose magnitude wins (the Znicz
semantic), built from two reductions.  Stochastic pooling samples a
window element with probability proportional to its magnitude (Zeiler &
Fergus), keyed by the unit's deterministic KeyTree so runs are
reproducible.
"""

import numpy

from ..prng.random_generator import KeyTree
from .nn_units import ParamlessForward
from .conv import _quad


class PoolingBase(ParamlessForward):
    hide_from_registry = True

    def __init__(self, workflow, **kwargs):
        super().__init__(workflow, **kwargs)
        self.kx = kwargs["kx"]
        self.ky = kwargs["ky"]
        self.sliding = tuple(kwargs.get("sliding", (self.ky, self.kx)))
        self.padding = _quad(kwargs.get("padding", 0))
        self.include_bias = False

    def output_shape_for(self, input_shape):
        b, h, w, c = input_shape
        pt, pb, pl, pr = self.padding
        oh = (h + pt + pb - self.ky) // self.sliding[0] + 1
        ow = (w + pl + pr - self.kx) // self.sliding[1] + 1
        return (b, oh, ow, c)

    def _window_dims(self):
        return (1, self.ky, self.kx, 1)

    def _window_strides(self):
        return (1,) + self.sliding + (1,)

    def _window_padding(self):
        pt, pb, pl, pr = self.padding
        return ((0, 0), (pt, pb), (pl, pr), (0, 0))

    def numpy_windows(self, x):
        """Iterate (i, j, window[b, ky, kx, c]) host-side (numpy twin)."""
        pt, pb, pl, pr = self.padding
        xp = numpy.pad(x, ((0, 0), (pt, pb), (pl, pr), (0, 0)),
                       constant_values=self.PAD_VALUE)
        oh, ow = self.output_shape_for(x.shape)[1:3]
        sy, sx = self.sliding
        for i in range(oh):
            for j in range(ow):
                yield i, j, xp[:, i * sy:i * sy + self.ky,
                               j * sx:j * sx + self.kx, :]

    PAD_VALUE = 0.0


    def export_params(self):
        return {"kx": int(self.kx), "ky": int(self.ky),
                "padding": list(self.padding),
                "sliding": list(self.sliding)}


class MaxPooling(PoolingBase):
    MAPPING = "max_pooling"
    PAD_VALUE = -numpy.inf

    def apply(self, params, x):
        from jax import lax
        return lax.reduce_window(
            x, -numpy.inf, lax.max, self._window_dims(),
            self._window_strides(), self._window_padding())

    def apply_numpy(self, params, x):
        out = numpy.empty(self.output_shape_for(x.shape), x.dtype)
        for i, j, win in self.numpy_windows(x):
            out[:, i, j, :] = win.max(axis=(1, 2))
        return out


class AvgPooling(PoolingBase):
    MAPPING = "avg_pooling"

    def apply(self, params, x):
        import jax.numpy as jnp
        from jax import lax
        s = lax.reduce_window(x, 0.0, lax.add, self._window_dims(),
                              self._window_strides(),
                              self._window_padding())
        # the in-bounds count per window is pure geometry — computing
        # it as reduce_window(ones) made XLA constant-fold a full-size
        # windowed reduction at COMPILE time (observed 45+ s of
        # slow_operation_alarm per stl10 compile); numpy at trace time
        # produces the same [1, oh, ow, 1] constant for free
        return s / jnp.asarray(self._window_counts(x.shape), x.dtype)

    def _window_counts(self, xshape):
        _, h, w, _ = xshape
        key = (h, w, self.ky, self.kx, self.sliding, self.padding)
        cached = getattr(self, "_counts_cache_", None)
        if cached is not None and cached[0] == key:
            return cached[1]
        ones = numpy.ones((1, h, w, 1), numpy.float32)
        counts = numpy.empty(
            (1,) + self.output_shape_for((1, h, w, 1))[1:3] + (1,),
            numpy.float32)
        for i, j, win in self.numpy_windows(ones):
            counts[:, i, j, :] = win.sum(axis=(1, 2))
        self._counts_cache_ = (key, counts)
        return counts

    def apply_numpy(self, params, x):
        """Divides by the count of in-bounds elements per window (matching
        the jax path's ones-reduction), not by the full window size."""
        out = numpy.empty(self.output_shape_for(x.shape), x.dtype)
        counts = numpy.empty_like(out)
        for i, j, win in self.numpy_windows(x):
            out[:, i, j, :] = win.sum(axis=(1, 2))
        for i, j, win in self.numpy_windows(numpy.ones_like(x)):
            counts[:, i, j, :] = win.sum(axis=(1, 2))
        return out / counts


class MaxAbsPooling(PoolingBase):
    """Keeps the signed value with the largest magnitude (Znicz
    semantics)."""

    MAPPING = "maxabs_pooling"

    def apply(self, params, x):
        from jax import lax
        hi = lax.reduce_window(x, -numpy.inf, lax.max,
                               self._window_dims(), self._window_strides(),
                               self._window_padding())
        lo = lax.reduce_window(x, numpy.inf, lax.min,
                               self._window_dims(), self._window_strides(),
                               self._window_padding())
        import jax.numpy as jnp
        return jnp.where(jnp.abs(hi) >= jnp.abs(lo), hi, lo)

    def apply_numpy(self, params, x):
        out = numpy.empty(self.output_shape_for(x.shape), x.dtype)
        for i, j, win in self.numpy_windows(x):
            flat = win.reshape(win.shape[0], -1, win.shape[-1])
            idx = numpy.abs(flat).argmax(axis=1)
            out[:, i, j, :] = numpy.take_along_axis(
                flat, idx[:, None, :], axis=1)[:, 0, :]
        return out


class StochasticPoolingBase(PoolingBase):
    """Samples a window element ∝ its (abs) value at train time (the key
    arrives as an argument so jit never freezes the randomness); at eval
    time outputs the probability-weighted average (Zeiler & Fergus)."""

    hide_from_registry = True
    stochastic = True

    def __init__(self, workflow, **kwargs):
        super().__init__(workflow, **kwargs)
        self.key_tree = kwargs.get("key_tree") or KeyTree(
            kwargs.get("seed", 42))

    def _patches(self, x):
        """(b, oh, ow, ky*kx, c) patch tensor via jnp slicing."""
        import jax.numpy as jnp
        pt, pb, pl, pr = self.padding
        xp = jnp.pad(x, ((0, 0), (pt, pb), (pl, pr), (0, 0)))
        oh, ow = self.output_shape_for(x.shape)[1:3]
        sy, sx = self.sliding
        rows = []
        for dy in range(self.ky):
            for dx in range(self.kx):
                rows.append(xp[:, dy:dy + oh * sy:sy,
                               dx:dx + ow * sx:sx, :])
        return jnp.stack(rows, axis=3)

    ABS = True

    def _probs(self, p):
        import jax.numpy as jnp
        mag = jnp.abs(p) if self.ABS else jnp.maximum(p, 0.0)
        total = mag.sum(axis=3, keepdims=True)
        return jnp.where(total > 0, mag / jnp.maximum(total, 1e-30),
                         1.0 / p.shape[3])

    def apply(self, params, x):
        """Eval mode: probability-weighted average over the window."""
        p = self._patches(x)
        return (p * self._probs(p)).sum(axis=3)

    def apply_train(self, params, x, key):
        import jax
        import jax.numpy as jnp
        p = self._patches(x)                     # (b, oh, ow, k, c)
        logits = jnp.log(self._probs(p) + 1e-30)
        choice = jax.random.categorical(
            key, logits.transpose(0, 1, 2, 4, 3))  # (b, oh, ow, c)
        return jnp.take_along_axis(
            p, choice[:, :, :, None, :], axis=3)[:, :, :, 0, :]

    def apply_numpy(self, params, x):
        # the eval path is deterministic; the twin replays it on CPU
        return numpy.asarray(self.apply(params, x))


class StochasticPooling(StochasticPoolingBase):
    MAPPING = "stochastic_pooling"
    ABS = False


class StochasticAbsPooling(StochasticPoolingBase):
    MAPPING = "stochastic_abs_pooling"
    ABS = True


class StochasticPoolingDepooling(StochasticPooling):
    """Pools stochastically and immediately depools into the original
    shape (used by the Znicz conv autoencoders)."""

    MAPPING = "stochastic_pool_depool"

    def output_shape_for(self, input_shape):
        return tuple(input_shape)

    def apply(self, params, x):
        """Eval: keep the expected value in place (prob-weighted mask)."""
        p = self._patches(x)
        return self._scatter_back(p * self._probs(p), x)

    def apply_train(self, params, x, key):
        import jax
        import jax.numpy as jnp
        p = self._patches(x)
        choice = jax.random.categorical(
            key, jnp.log(self._probs(p) + 1e-30).transpose(0, 1, 2, 4, 3))
        mask = jax.nn.one_hot(choice, p.shape[3], axis=3, dtype=x.dtype)
        return self._scatter_back(p * mask, x)

    def _scatter_back(self, kept, x):
        # scatter windows back (non-overlapping sliding == window)
        b, oh, ow, _, c = kept.shape
        kept = kept.reshape(b, oh, ow, self.ky, self.kx, c)
        kept = kept.transpose(0, 1, 3, 2, 4, 5).reshape(
            b, oh * self.ky, ow * self.kx, c)
        return kept[:, :x.shape[1], :x.shape[2], :]


class StochasticAbsPoolingDepooling(StochasticPoolingDepooling):
    MAPPING = "stochastic_abs_pool_depool"
    ABS = True
