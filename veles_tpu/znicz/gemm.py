"""Compensated blocked GEMM: the reference's PRECISION_LEVEL semantics,
as a Pallas TPU kernel.

Re-creation of /root/reference/ocl/matrix_multiplication_precise.cl
(:37-48 contract, :119-170 accumulators): the reference's GEMM offered
PRECISION_LEVEL 0 (plain summation), 1 (Kahan summation), 2 ("most
precise": 32 sorted partials) — trading ~2x speed for ~2 more correct
decimal digits on large common dims.

TPU redesign: scalar-loop Kahan cannot ride the MXU (the systolic array
owns the inner products), so compensation moves to the BLOCK level — the
K dimension is tiled, each tile's partial product comes out of the MXU
in f32, and the running accumulation of tiles into the output block is
compensated in VMEM:

- level 0: plain ``acc += p`` (same blocking, uncompensated — the
  baseline the tests compare against);
- level 1: Kahan (one compensation term per output element);
- level 2: Kahan-Babuška-Neumaier second order (Klein's doubly
  compensated summation, two carry terms) — the 32-partial analog.

Intra-tile error (bk-length MXU chains) remains — that part of the
reference guarantee is hardware-owned on TPU (f32 MXU accumulation);
cross-tile cancellation, which dominates for large K, is what the
compensation recovers.  ``jax.config`` keeps XLA's algebraic rewrites
away from the compensation expressions (XLA does not reassociate floats
by default).

The jnp/XLA form of the same trade is
``backends.Device.PRECISION_LEVELS`` (the MXU pass-decomposition knob);
this kernel is the opt-in exact-summation path
(``root.common.engine.precise_gemm`` or ``All2All(precise_gemm=N)``).
"""

import functools

import jax
import jax.numpy as jnp

from .. import backends


def _interpret_default():
    return not backends.on_tpu()


def _accumulate_plain(p, acc_ref, _c1_ref, _c2_ref):
    acc_ref[:] = acc_ref[:] + p


def _accumulate_kahan(p, acc_ref, c1_ref, _c2_ref):
    # Kahan-Babuška-Neumaier: the rounding error of every (acc + p) is
    # carried in c1.  (Classic Kahan drops its compensation whenever a
    # summand exceeds the accumulator — exactly the cross-tile
    # cancellation case this kernel exists for — so the Neumaier form
    # is the honest "PRECISION_LEVEL 1".)
    s, e = _two_sum(acc_ref[:], p)
    acc_ref[:] = s
    c1_ref[:] = c1_ref[:] + e


def _two_sum(a, b):
    """Knuth's exact TwoSum: a + b = s + e with e the rounding error."""
    s = a + b
    v = s - a
    e = (a - (s - v)) + (b - v)
    return s, e


def _accumulate_klein(p, acc_ref, c1_ref, c2_ref):
    # Doubly compensated (Kahan-Babuška-Neumaier 2nd order): the error
    # of the main sum cascades into c1, c1's own error into c2
    s, e = _two_sum(acc_ref[:], p)
    c1, e2 = _two_sum(c1_ref[:], e)
    acc_ref[:] = s
    c1_ref[:] = c1
    c2_ref[:] = c2_ref[:] + e2


_ACCUMULATORS = {0: _accumulate_plain, 1: _accumulate_kahan,
                 2: _accumulate_klein}


#: hand-picked tile sizes — the `precise_gemm` autotune site's default
DEFAULT_BLOCK_M = 128
DEFAULT_BLOCK_N = 128
DEFAULT_BLOCK_K = 256


def _matmul_impl(a, b, level, interpret, block_m=None, block_n=None,
                 block_k=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if interpret is None:
        interpret = _interpret_default()
    a = jnp.asarray(a, jnp.float32)
    b = jnp.asarray(b, jnp.float32)
    m, k = a.shape
    k2, n = b.shape
    if k != k2:
        raise ValueError("shape mismatch %s @ %s" % (a.shape, b.shape))
    if block_m is None or block_n is None or block_k is None:
        # unpinned tiles resolve through the tuning store (clean miss /
        # tuner off = the hand-picked defaults, exactly) — forward and
        # backward matmuls each resolve for their OWN (m, k, n) class
        from ..autotune import dispatch as _autotune
        from ..autotune.space import site as _site
        ctx = {"m": m, "k": k, "n": n, "level": int(level)}
        cfg, _ = _autotune.resolve(
            "precise_gemm", _site("precise_gemm").shape_class(ctx),
            default={"block_m": DEFAULT_BLOCK_M,
                     "block_n": DEFAULT_BLOCK_N,
                     "block_k": DEFAULT_BLOCK_K})
        block_m = block_m if block_m is not None else int(cfg["block_m"])
        block_n = block_n if block_n is not None else int(cfg["block_n"])
        block_k = block_k if block_k is not None else int(cfg["block_k"])
    bm, bn, bk = min(block_m, m), min(block_n, n), min(block_k, k)
    pad_m, pad_n, pad_k = (-m) % bm, (-n) % bn, (-k) % bk
    if pad_m or pad_k:
        a = jnp.pad(a, ((0, pad_m), (0, pad_k)))
    if pad_k or pad_n:
        b = jnp.pad(b, ((0, pad_k), (0, pad_n)))
    grid = (a.shape[0] // bm, b.shape[1] // bn, a.shape[1] // bk)
    accumulate = _ACCUMULATORS[int(level)]
    k_steps = grid[2]

    def kernel(a_ref, b_ref, o_ref, acc_ref, c1_ref, c2_ref):
        kk = pl.program_id(2)

        @pl.when(kk == 0)
        def _():
            acc_ref[:] = jnp.zeros_like(acc_ref)
            c1_ref[:] = jnp.zeros_like(c1_ref)
            c2_ref[:] = jnp.zeros_like(c2_ref)

        # HIGHEST = exact-f32 tile products (6-pass bf16 decomposition
        # on the MXU, plain f32 in interpret mode).  The reference's
        # levels all multiplied exact floats and differed only in the
        # SUMMATION (matrix_multiplication_precise.cl:37-48); default
        # precision here would drown the compensation in bf16 product
        # noise
        p = jnp.dot(a_ref[:], b_ref[:],
                    preferred_element_type=jnp.float32,
                    precision=jax.lax.Precision.HIGHEST)
        accumulate(p, acc_ref, c1_ref, c2_ref)

        @pl.when(kk == k_steps - 1)
        def _():
            # fold the carries back in (zero for level 0)
            o_ref[:] = acc_ref[:] + (c1_ref[:] + c2_ref[:])

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
                  pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j))],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct(
            (a.shape[0], b.shape[1]), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)] * 3,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, b)
    if pad_m or pad_n:
        out = out[:m, :n]
    return out


# -- quantized weight GEMM (ISSUE 18) -----------------------------------------
#
# Serving-side counterpart of the compensated path above: the weights
# are static at serve time, so they quantize ONCE (symmetric, one f32
# scale per output channel) and the kernel streams int8/fp8 bytes from
# HBM, upcasting each tile in VMEM and folding the channel scales into
# the output tile after the K loop — scaled accumulation, exact up to
# the weight quantization itself because per-output-channel scales
# factor out of the K contraction.

#: largest-magnitude finite value of float8_e4m3fn (the fp8 flavor
#: jaxlib exposes for storage): per-channel scales target it the way
#: int8 targets 127
_FP8_E4M3_MAX = 448.0


def fp8_dtype():
    """The storage fp8 dtype of the weight path."""
    return jnp.float8_e4m3fn


def quantize_weight(w, dtype="int8"):
    """Symmetric per-output-channel quantization of a ``[K, N]`` weight.

    Returns ``(w_q, scales)``: ``w_q`` in ``dtype`` (``"int8"`` or
    ``"fp8"``), ``scales`` f32 ``[N]`` with ``scale[n] =
    max|w[:, n]| / qmax`` (1.0 for an all-zero column).  Because the
    scale is constant along K, ``x @ dequant(w_q)`` ==
    ``(x @ upcast(w_q)) * scales`` — which is what lets
    :func:`quantized_matmul` dequantize AFTER the accumulation.
    """
    w = jnp.asarray(w, jnp.float32)
    if w.ndim != 2:
        raise ValueError("quantize_weight wants [K, N], got %r"
                         % (w.shape,))
    amax = jnp.max(jnp.abs(w), axis=0)
    if dtype == "int8":
        scales = jnp.where(amax > 0, amax / 127.0, 1.0)
        q = jnp.clip(jnp.round(w / scales[None, :]), -127, 127)
        return q.astype(jnp.int8), scales.astype(jnp.float32)
    if dtype == "fp8":
        scales = jnp.where(amax > 0, amax / _FP8_E4M3_MAX, 1.0)
        return (w / scales[None, :]).astype(fp8_dtype()), \
            scales.astype(jnp.float32)
    raise ValueError("unknown weight dtype %r (want 'int8'|'fp8')"
                     % (dtype,))


def quantized_matmul(a, w_q, scales, block_m=None, block_n=None,
                     block_k=None, interpret=None):
    """``a @ dequant(w_q)`` with the dequant inside the kernel.

    ``a``: f32 [M, K]; ``w_q``: int8/fp8 [K, N] with f32 ``scales``
    [N] from :func:`quantize_weight`.  The weight tiles cross HBM in
    their quantized width; each tile upcasts to f32 in VMEM for the
    MXU, the accumulator runs plain f32, and the per-channel scales
    multiply the finished output tile once after the K loop.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if interpret is None:
        interpret = _interpret_default()
    a = jnp.asarray(a, jnp.float32)
    m, k = a.shape
    k2, n = w_q.shape
    if k != k2:
        raise ValueError("shape mismatch %s @ %s" % (a.shape, w_q.shape))
    if scales.shape != (n,):
        raise ValueError("scales shape %r != (N,) == (%d,)"
                         % (scales.shape, n))
    bm = min(block_m or DEFAULT_BLOCK_M, m)
    bn = min(block_n or DEFAULT_BLOCK_N, n)
    bk = min(block_k or DEFAULT_BLOCK_K, k)
    pad_m, pad_n, pad_k = (-m) % bm, (-n) % bn, (-k) % bk
    if pad_m or pad_k:
        a = jnp.pad(a, ((0, pad_m), (0, pad_k)))
    if pad_k or pad_n:
        w_q = jnp.pad(w_q, ((0, pad_k), (0, pad_n)))
    s2 = jnp.pad(scales.astype(jnp.float32),
                 (0, pad_n))[None, :]              # [1, N] for blocking
    grid = (a.shape[0] // bm, w_q.shape[1] // bn, a.shape[1] // bk)
    k_steps = grid[2]

    def kernel(a_ref, b_ref, s_ref, o_ref, acc_ref):
        kk = pl.program_id(2)

        @pl.when(kk == 0)
        def _():
            acc_ref[:] = jnp.zeros_like(acc_ref)

        p = jnp.dot(a_ref[:], b_ref[:].astype(jnp.float32),
                    preferred_element_type=jnp.float32,
                    precision=jax.lax.Precision.HIGHEST)
        acc_ref[:] = acc_ref[:] + p

        @pl.when(kk == k_steps - 1)
        def _():
            o_ref[:] = acc_ref[:] * s_ref[0][None, :]

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
                  pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
                  pl.BlockSpec((1, bn), lambda i, j, kk: (0, j))],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct(
            (a.shape[0], w_q.shape[1]), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, w_q, s2)
    if pad_m or pad_n:
        out = out[:m, :n]
    return out


def quantized_matmul_reference(a, w_q, scales, block_m=None,
                               block_n=None, block_k=None):
    """Pure-jnp oracle for :func:`quantized_matmul`, staged the way the
    kernel accumulates (K-tile-sequential partial products, scales
    folded after the loop) so parity tests can assert bitwise."""
    a = jnp.asarray(a, jnp.float32)
    m, k = a.shape
    bk = min(block_k or DEFAULT_BLOCK_K, k)
    pad_k = (-k) % bk
    if pad_k:
        a = jnp.pad(a, ((0, 0), (0, pad_k)))
        w_q = jnp.pad(w_q, ((0, pad_k), (0, 0)))
    acc = jnp.zeros((m, w_q.shape[1]), jnp.float32)
    for kk in range(a.shape[1] // bk):
        sl = slice(kk * bk, (kk + 1) * bk)
        acc = acc + jnp.dot(a[:, sl],
                            w_q[sl].astype(jnp.float32),
                            preferred_element_type=jnp.float32,
                            precision=jax.lax.Precision.HIGHEST)
    return acc * scales.astype(jnp.float32)[None, :]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def precise_matmul(a, b, level=1, interpret=None):
    """``a @ b`` with compensated cross-tile accumulation (see module
    docstring).  Differentiable: the backward matmuls run at the same
    precision level."""
    return _matmul_impl(a, b, level, interpret)


def _pm_fwd(a, b, level, interpret):
    return _matmul_impl(a, b, level, interpret), (a, b)


def _pm_bwd(level, interpret, res, g):
    a, b = res
    return (_matmul_impl(g, jnp.asarray(b, jnp.float32).T, level,
                         interpret),
            _matmul_impl(jnp.asarray(a, jnp.float32).T, g, level,
                         interpret))


precise_matmul.defvjp(_pm_fwd, _pm_bwd)


#: VMEM that one grouped-product kernel's tiles may take
#: (``grouped_matmul_tiles``); the rest of the v5e's 16 MiB scoped default
#: is Mosaic's own scratch, which some tilings estimated at 14 MiB overran
GROUPED_VMEM_BUDGET = 12 * 2 ** 20


def _tile_widths(x):
    """The tiles that split a dimension of ``x`` exactly: the multiples
    of 128 that divide it, else ``x`` whole."""
    return [t for t in range(x - x % 128, 0, -128) if x % t == 0] or [x]


def grouped_matmul_tiles(m, k, n, itemsize=2):
    """megablox's ``(tm, tk, tn)`` for a kernel over ``m`` rows that
    contracts ``k`` into ``n`` output columns.  ``tm`` is the largest of
    512, 256, ... that divides ``m``.  ``tk`` and ``tn`` split their
    dimensions exactly and minimise ``1 / tk + 1 / tn`` (the wider
    ``tn`` on a tie): the rows are read once per output-column tile and
    the float32 accumulator once per contraction tile, so each is
    overhead in proportion to one over its tile.  Both fit
    ``GROUPED_VMEM_BUDGET`` in ``gmm`` and in ``tgmm``, which receive
    the same triple: two buffers of each operand and of the output,
    ``itemsize`` bytes an element, and the accumulator of ``(tm, tn)``
    or ``(tk, tn)``."""
    tm = next(t for t in (512, 256, 128, 64, 32, 16, 8, 1) if m % t == 0)

    def vmem(tk, tn):
        return (2 * itemsize * (tm * tk + tk * tn + tm * tn)
                + 4 * tn * max(tm, tk))
    pairs = [(tk, tn) for tk in _tile_widths(k) for tn in _tile_widths(n)]
    fits = [p for p in pairs if vmem(*p) <= GROUPED_VMEM_BUDGET] \
        or [min(pairs, key=lambda p: vmem(*p))]
    tk, tn = min(fits, key=lambda p: (1 / p[0] + 1 / p[1], -p[1]))
    return tm, tk, tn


@functools.lru_cache(maxsize=None)
def _tiling(itemsize):
    """``grouped_matmul_tiles`` for operands of ``itemsize`` bytes, one
    function an itemsize: megablox calls it with each kernel's own
    triple, and keys its compiled kernels by it."""
    return functools.partial(grouped_matmul_tiles, itemsize=itemsize)


def grouped_matmul(lhs, rhs, group_sizes):
    """``out[r] = lhs[r] @ rhs[g(r)]`` for rows sorted by group: ``lhs``
    [M, K], ``rhs`` [G, K, N], ``group_sizes`` [G] int32 whose sum may be
    less than M (the rows past it are not computed and hold nothing).
    float32 sums, result in ``lhs``'s dtype.  The expert layer's product
    (``znicz/transformer.py``); differentiable in ``lhs`` and ``rhs``.

    JAX's megablox kernels (``gmm`` forward and for the rows' gradient,
    ``tgmm`` for the weights'): their grid covers the tiles that hold
    rows and no others, so the work follows the data's group sizes
    however uneven.  Against ``lax.ragged_dot`` at the expert layer's
    shapes on the v5e they were 2-8 % faster on the 2048 x 1536 product
    and 10-25 % on the 768 x 2048 one, forward and backward (PERF.md
    section 6), so they ship and nothing chooses.

    Each of the three kernels takes its tiles from its own shape
    (``grouped_matmul_tiles``; the rows' gradient contracts N into K).
    A tile that does not divide its dimension leaves a remainder tile
    that the kernel pads, masks and still multiplies, and every
    output-column tile reads all the rows again; one tuple for the three,
    chosen from the forward's shape, did both at the decoders' widths:
    Mellum2's six calls took 15.9 ms under it and 9.3 ms under the rule
    on one v5e (PERF.md section 6)."""
    from jax.experimental.pallas.ops.tpu.megablox import ops as megablox
    return megablox.gmm(lhs, rhs, group_sizes.astype(jnp.int32), lhs.dtype,
                        _tiling(jnp.result_type(lhs, rhs).itemsize),
                        interpret=_interpret_default())
