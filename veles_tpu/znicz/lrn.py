"""Local response normalization (AlexNet LRN).

Re-creation of ``veles.znicz.normalization.LRNormalizerForward/Backward``
(absent; SURVEY.md §2.9).  Cross-channel LRN:

    y = x / (k + alpha/n * sum_{j in window} x_j^2) ** beta

Two device paths:

- the **default**: the channel-window sum as ONE banded [C, C] matmul
  (``_window_sum_mxu``) — LRN is memory-bound (round-3 ablation: ~19 %
  of an AlexNet f32 step as n shifted HBM passes), and the band form
  moves it onto the MXU for a few percent of extra (free) FLOPs.
  Round-4 on-chip A/B: the biggest single perf win of the round
  (docs/PERF.md).  Summation order differs from the numpy twin's
  shifted adds by float-reassociation noise only (parity tests use
  atol 1e-5 and pass).
- ``use_pallas=True``: a **Pallas kernel pair** (forward + analytic
  backward via ``jax.custom_vjp``) with the closed form
  ``dx = g·den^-β − 2β·(α/n)·x·W(g·x·den^-(β+1))`` (W = the same
  channel-window sum).  Since round 4 it is gridded (1024xC row tiles)
  and compiles in seconds — but it LOST end-to-end in rounds 4-5
  (0.76x, docs/PERF.md; on the present chip: not measured): the
  ``pallas_call`` boundary blocks XLA from fusing LRN into its
  neighbors, which the matmul form allows.  Kept
  as the measured hand-kernel reference point
  (``root.common.engine.use_pallas`` / per-layer ``use_pallas=True``);
  on non-TPU backends it runs in Pallas interpret mode.
"""

import functools

import jax
import numpy

from .. import backends
from .nn_units import ParamlessForward, GenericVJPBackward


def _window_sum(v, n, xp, transpose=False):
    """Channel-axis sliding-window sum via static shifted concats (the
    form that lowers cleanly inside Pallas — jnp.roll/pad do not).
    Offsets are ``-n//2 .. n-1-n//2`` — the exact (asymmetric for even
    n) window the jnp/numpy ``_den`` formula uses.  ``transpose=True``
    negates the offsets: the VJP of an asymmetric window sum is the
    window sum over the TRANSPOSED window (for odd n they coincide)."""
    C = v.shape[-1]
    half = n // 2
    offsets = range(-half, n - half)
    if transpose:
        offsets = [-o for o in offsets]
    acc = None
    for off in offsets:
        if off == 0:
            t = v
        elif off > 0:
            z = xp.zeros(v.shape[:-1] + (off,), v.dtype)
            t = xp.concatenate([v[..., off:], z], axis=-1)
        else:
            z = xp.zeros(v.shape[:-1] + (-off,), v.dtype)
            t = xp.concatenate([z, v[..., :C + off]], axis=-1)
        acc = t if acc is None else acc + t
    return acc


def _band_matrix(c, n, dtype, transpose=False):
    """The [C, C] 0/1 band whose matmul computes the channel-window sum:
    ``(v @ B)[..., i] = sum_{off} v[..., i + off]`` over the same
    asymmetric offsets as :func:`_window_sum`.  ``transpose=True`` gives
    the window-sum over the negated offsets (the VJP's window)."""
    half = n // 2
    j = numpy.arange(c)
    d = j[:, None] - j[None, :]        # B[j, i] = 1 iff j - i in window
    lo, hi = -half, n - 1 - half
    band = ((d >= lo) & (d <= hi)).astype(dtype)
    return band.T if transpose else band


def _window_sum_mxu(v, n, transpose=False):
    """The channel window sum as ONE banded matmul: LRN's window
    accumulation is the memory-bound 19 % of an AlexNet step when done
    as n shifted HBM passes (docs/PERF.md); as a [.., C] x [C, C]
    product it rides the MXU, reading and writing each activation
    exactly once for a few % extra (essentially free) FLOPs."""
    import jax.numpy as jnp
    c = v.shape[-1]
    band = jnp.asarray(_band_matrix(c, n, numpy.float32,
                                    transpose=transpose), v.dtype)
    return jnp.einsum("...c,cd->...d", v, band)


def _pallas_interpret():
    return not backends.on_tpu()


_LRN_BLOCK_ROWS = 1024


def lrn_mxu(x, n, alpha, beta, k):
    """The MXU-band LRN forward as a free function (the math of the
    default ``apply`` path) — the ``impl: "mxu"`` layout candidate of
    the ``lrn`` autotune site, and what a tuned record dispatches to
    when the band measured faster than the Pallas pair."""
    import jax.numpy as jnp
    from jax import lax
    acc = _window_sum_mxu(x * x, n)
    den = k + (alpha / n) * acc
    if beta == 0.75:
        # den^-3/4 = rsqrt(den) * sqrt(rsqrt(den)) — two cheap HW
        # ops instead of the exp/log pair a general pow lowers to
        # (AlexNet's default beta; the generic path stays below)
        r = lax.rsqrt(den)
        return x * (r * jnp.sqrt(r))
    return x / den ** beta


def _lrn_grid(x, block_rows=None):
    """Flatten [..., C] to [N, C] and tile N into VMEM-sized row blocks.

    The round-3 kernel mapped the WHOLE array into one kernel invocation
    — at production shapes (128x55x55x96 f32 = 148 MB) Mosaic ground for
    >20 min on the oversized block and the bench recorded a timeout
    every round.  A trivial gridded kernel compiles in a second or two,
    so the fix is simply a real grid:
    row tiles of ``block_rows`` (default 1024, ~0.4-1 MB VMEM; tunable
    via the ``lrn`` autotune site), rows independent because the
    LRN window runs along C only.  Block-padding rows beyond N is safe —
    padded rows produce garbage that is never written back."""
    import jax.numpy as jnp
    c = x.shape[-1]
    flat = x.reshape(-1, c)
    from jax.experimental import pallas as pl
    rows = int(block_rows or _LRN_BLOCK_ROWS)
    grid = (pl.cdiv(flat.shape[0], rows),)
    spec = pl.BlockSpec((rows, c), lambda i: (i, 0))
    return flat, grid, spec


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5))
def pallas_lrn(x, n, alpha, beta, k, block_rows=None):
    """Fused cross-channel LRN forward (Pallas, gridded row tiles)."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    def kernel(x_ref, o_ref):
        xv = x_ref[...]
        acc = _window_sum(xv * xv, n, jnp)
        o_ref[...] = xv / (k + (alpha / n) * acc) ** beta

    flat, grid, spec = _lrn_grid(x, block_rows)
    out = pl.pallas_call(
        kernel, grid=grid, in_specs=[spec], out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(flat.shape, flat.dtype),
        interpret=_pallas_interpret(), name="lrn_forward")(flat)
    return out.reshape(x.shape)


def _pallas_lrn_fwd(x, n, alpha, beta, k, block_rows=None):
    return pallas_lrn(x, n, alpha, beta, k, block_rows), x


def _pallas_lrn_bwd(n, alpha, beta, k, block_rows, x, g):
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    def kernel(x_ref, g_ref, o_ref):
        xv = x_ref[...]
        gv = g_ref[...]
        c = alpha / n
        den = k + c * _window_sum(xv * xv, n, jnp)
        inner = gv * xv * den ** (-beta - 1.0)
        o_ref[...] = (gv * den ** -beta -
                      2.0 * beta * c * xv *
                      _window_sum(inner, n, jnp, transpose=True))

    flat, grid, spec = _lrn_grid(x, block_rows)
    gflat = g.reshape(flat.shape)
    dx = pl.pallas_call(
        kernel, grid=grid, in_specs=[spec, spec], out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(flat.shape, flat.dtype),
        interpret=_pallas_interpret(), name="lrn_backward")(flat, gflat)
    return (dx.reshape(x.shape),)


pallas_lrn.defvjp(_pallas_lrn_fwd, _pallas_lrn_bwd)


class LRNormalizerForward(ParamlessForward):
    MAPPING = "norm"

    def __init__(self, workflow, **kwargs):
        super().__init__(workflow, **kwargs)
        self.alpha = float(kwargs.get("alpha", 1e-4))
        self.beta = float(kwargs.get("beta", 0.75))
        self.k = float(kwargs.get("k", 2.0))
        self.n = int(kwargs.get("n", 5))
        self.include_bias = False
        from ..config import root
        # tri-state like attention's knob (nn_units.resolve_use_pallas)
        # — but AUTO resolves False here: the Pallas pair measured a
        # LOSS vs the MXU-band XLA path (docs/PERF.md, ~0.68x)
        up = kwargs.get("use_pallas",
                        root.common.engine.get("use_pallas", None))
        self.use_pallas = up if up is None else bool(up)

    def _den(self, sq, xp):
        acc = _window_sum(sq, self.n, xp)
        return (self.k + (self.alpha / self.n) * acc) ** self.beta

    def apply(self, params, x):
        from .nn_units import resolve_use_pallas
        if resolve_use_pallas(self.use_pallas, self.device,
                              tpu_auto=False):
            # the pallas path is a TUNABLE SITE: with a tuning record
            # for this (C, n, device, versions) the measured winner
            # decides the row-tile size — or the mxu band LAYOUT, the
            # answer when the pallas_call fusion boundary loses on this
            # device class.  Tuner off = the exact hand-picked kernel.
            from ..autotune import dispatch as _autotune
            cfg, src = _autotune.resolve(
                "lrn", "c%d_n%d" % (x.shape[-1], self.n),
                default={"impl": "pallas",
                         "block_rows": _LRN_BLOCK_ROWS})
            self.config_source = src
            if cfg.get("impl") != "mxu":
                return pallas_lrn(x, self.n, self.alpha, self.beta,
                                  self.k, int(cfg["block_rows"]))
        else:
            self.config_source = "default"
        # MXU path: one banded matmul instead of n shifted HBM passes
        # (autodiff gives the transposed band for the backward)
        return lrn_mxu(x, self.n, self.alpha, self.beta, self.k)

    def apply_numpy(self, params, x):
        return x / self._den(x * x, numpy)

    def export_params(self):
        return {"alpha": self.alpha, "beta": self.beta, "k": self.k,
                "n": self.n}


class LRNormalizerBackward(GenericVJPBackward):
    MAPPING = "norm"
