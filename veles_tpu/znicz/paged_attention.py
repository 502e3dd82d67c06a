"""Ragged paged decode attention as a Pallas TPU kernel.

The decode-serving counterpart of :mod:`.flash_attention` (PAPERS.md
"Ragged Paged Attention", arXiv 2604.15464): at decode time every
sequence contributes ONE query token, but its K/V history lives in
fixed-size blocks scattered across a preallocated device pool — the
page table (``[B, max_blocks]`` physical block ids) and the per-sequence
lengths are the only things that change shape-free from step to step,
so one compiled kernel serves ANY mix of sequence lengths with zero
recompilation.  That is what makes token-level continuous batching
(serving/decode.py) possible: admitting or retiring a sequence edits
the page table, never the executable.

Kernel structure — two sweeps over the inner block grid, page-table
indirection via scalar prefetch (the index map reads the prefetched
page table to pick which PHYSICAL pool block the next DMA fetches, the
canonical TPU paged-attention gather):

- sweep 1 streams the sequence's K blocks, scoring each against the
  query and materializing the per-sequence score row in VMEM scratch
  (decode scores are [1, T] — tiny, unlike the [T, T] training case);
- the boundary step normalizes: one max, one exp, one sum — a DENSE
  softmax over the scratch row, not an online rescale;
- sweep 2 streams the V blocks, accumulating the probability-weighted
  sum block by block.

K and V each cross HBM exactly once (same DMA bill as a fused single
sweep), and because the softmax is dense there is no online-softmax
rescale drift: in interpret mode on the CPU the kernel reproduces the
dense reference to the last place or close to it, which is what the
tier-1 parity tests assert.  Compiled for the TPU the contract is a
tolerance, not bit equality (``chip_smoke.py`` checks it there).  Blocks
past a sequence's length are skipped entirely: compute
AND DMA stay O(length), so a ragged batch costs its true token count,
not ``B * max_context``.

Padding rows (``length == 0``) return zeros; padding page-table entries
must point at physical block 0, which the serving pool reserves as the
trash block (never allocated to a live sequence).

Quantized pools (ISSUE 18): the same entry points accept int8 K/V
pools plus per-(block, head) f32 scale arrays (``k_scales``/``v_scales``,
``[num_blocks, H]`` — one symmetric scale per PHYSICAL pool block and
head).  The scales ride as two extra blocked operands, fetched through
the same page-table entry as their tile, and each K/V tile is
dequantized on the VMEM slab right after its DMA (``int8 -> f32 *
scale``), so HBM traffic on the hot loop is the int8 bytes; the
dense-softmax structure, trash-block handling and page-table
indirection are untouched, and the quantized dense reference stages the
same dequant elementwise.
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from .. import backends

__all__ = ["DEFAULT_BLOCK_SIZE", "paged_attention",
           "paged_attention_reference", "paged_prefill_attention",
           "paged_prefill_attention_reference", "paged_verify_attention",
           "paged_verify_attention_reference", "required_blocks",
           "quantize_pool", "dequantize_pool"]

_NEG_INF = float("-inf")

#: hand-picked KV page size (tokens per pool block).  The kernel reads
#: the actual size off the pool shape — this is the default the decode
#: scheduler builds pools with when nothing is pinned, and the
#: ``paged_attention`` autotune site's baseline candidate.
DEFAULT_BLOCK_SIZE = 8


def _interpret():
    return not backends.on_tpu()


def required_blocks(length, block_size):
    """Pool blocks a sequence of ``length`` tokens occupies."""
    return -(-int(length) // int(block_size))


def quantize_pool(pool):
    """Symmetric per-(block, head) int8 quantization of a
    ``[N, block_size, H, D]`` pool.

    Returns ``(q, scales)`` — ``q`` int8 with the pool's shape,
    ``scales`` f32 ``[N, H]`` with
    ``scale[i, h] = max|pool[i, :, h]| / 127`` (1.0 for an all-zero
    slice, so dequant never divides by zero).  One scale per head, not
    per block, because head projections differ in magnitude — sharing a
    scale across heads costs ~2x logit RMSE for zero bytes saved (the
    scale array is noise next to the pool either way).  The quantizer
    is deterministic (round-half-even), which is what lets prefix-chain
    keys commit to the quantized bytes: same content in, same int8
    bytes out.
    """
    if pool.ndim != 4:
        raise ValueError("expected a [N, block_size, H, D] pool, got "
                         "shape %r" % (pool.shape,))
    f = pool.astype(jnp.float32)
    amax = jnp.max(jnp.abs(f), axis=(1, 3))      # [N, H]
    scales = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(f / scales[:, None, :, None]), -127, 127)
    return q.astype(jnp.int8), scales.astype(jnp.float32)


def dequantize_pool(q, scales):
    """Inverse of :func:`quantize_pool`: ``int8 * scale`` per
    (block, head)."""
    return (q.astype(jnp.float32)
            * scales.astype(jnp.float32)[:, None, :, None])


def _decode_kernel(pt_ref, len_ref, q_ref, k_ref, v_ref, *rest,
                   block_size, n_blocks, scale, quantized):
    """One grid step = one K (sweep 1) or V (sweep 2) pool block of one
    sequence, ALL heads at once: the blocks are ``[block_size, H, D]``
    slabs, so their last two dims span the pool's and the TPU lowering
    accepts them at any page size (a per-head ``(1, D)`` slice of the
    ``(H, D)`` plane is refused: it neither tiles (8, 128) nor spans
    it).  Scores live as ``[n_blocks, block_size, H]`` with heads on
    lanes.  Over int8 pools the per-(block, head) scales arrive as two
    more blocked operands, ``[H, 1]`` columns picked by the same page
    table entry as their tile, and each tile is dequantized on the VMEM
    slab right after its DMA."""
    from jax.experimental import pallas as pl

    if quantized:
        ks_ref, vs_ref, o_ref, s_scr, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, s_scr, m_scr, l_scr, acc_scr = rest
        ks_ref = vs_ref = None
    b, j = pl.program_id(0), pl.program_id(1)
    length = len_ref[b]

    def tile(ref, scale_ref):
        x = ref[0].astype(jnp.float32)                    # [bs, H, D]
        return x if scale_ref is None else x * scale_ref[0][None]

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        s_scr[...] = jnp.full_like(s_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # -- sweep 1 (j < n_blocks): score K blocks into the scratch rows --------
    @pl.when(jnp.logical_and(j < n_blocks, j * block_size < length))
    def _score():
        q = q_ref[0].astype(jnp.float32) * scale          # [H, D]
        s = jnp.sum(q[None] * tile(k_ref, ks_ref), axis=-1)   # [bs, H]
        pos = j * block_size + lax.broadcasted_iota(
            jnp.int32, s.shape, 0)
        s = jnp.where(pos < length, s, _NEG_INF)
        s_scr[j] = s
        m_scr[...] = jnp.maximum(m_scr[...],
                                 jnp.max(s, axis=0, keepdims=True))

    # -- boundary: dense softmax over the whole scratch row ------------------
    @pl.when(j == n_blocks)
    def _normalize():
        m = m_scr[...]                                    # [1, H]
        safe_m = jnp.where(jnp.isneginf(m), 0.0, m)
        s = s_scr[...]
        p = jnp.where(jnp.isneginf(s), 0.0, jnp.exp(s - safe_m[None]))
        s_scr[...] = p
        l_scr[...] = jnp.sum(jnp.sum(p, axis=0), axis=0, keepdims=True)

    # -- sweep 2 (j >= n_blocks): weighted V accumulation --------------------
    jv = j - n_blocks

    @pl.when(jnp.logical_and(j >= n_blocks, jv * block_size < length))
    def _accumulate():
        p = s_scr[jv]                                     # [bs, H]
        acc_scr[...] = acc_scr[...] + jnp.sum(
            p[:, :, None] * tile(v_ref, vs_ref), axis=0)

    @pl.when(j == 2 * n_blocks - 1)
    def _finish():
        l = l_scr[0]                                      # [H]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[...] / safe_l[:, None]).astype(o_ref.dtype)


def _check_quant_args(k_pool, v_pool, k_scales, v_scales):
    """-> True when the pools are quantized (int8 + scales), False for
    the f32 path; raises on half-specified or mismatched operands."""
    quantized = k_pool.dtype == jnp.int8
    if quantized != (v_pool.dtype == jnp.int8):
        raise ValueError("k_pool/v_pool dtypes differ: %r vs %r"
                         % (k_pool.dtype, v_pool.dtype))
    if not quantized:
        if k_scales is not None or v_scales is not None:
            raise ValueError(
                "k_scales/v_scales are only valid with int8 pools "
                "(got %r pools)" % str(k_pool.dtype))
        return False
    if k_scales is None or v_scales is None:
        raise ValueError("int8 pools require k_scales and v_scales")
    n_pool, heads = k_pool.shape[0], k_pool.shape[2]
    for name, s in (("k_scales", k_scales), ("v_scales", v_scales)):
        if s.shape != (n_pool, heads):
            raise ValueError(
                "%s shape %r != (num_blocks, heads) == (%d, %d)"
                % (name, s.shape, n_pool, heads))
    return True


def paged_attention(q, k_pool, v_pool, page_table, lengths, scale=None,
                    k_scales=None, v_scales=None):
    """Ragged paged decode attention.

    ``q``: [B, H, D] — one query token per sequence;
    ``k_pool``/``v_pool``: [num_blocks, block_size, H, D] — the shared
    physical block pools;
    ``page_table``: int32 [B, max_blocks] — physical block id of each
    sequence's logical block, padded with 0 (the reserved trash block);
    ``lengths``: int32 [B] — valid tokens per sequence (0 = padding
    row, returns zeros);
    ``k_scales``/``v_scales``: f32 [num_blocks, H] — required iff the
    pools are int8 (per-(block, head) symmetric scales; the kernel
    dequantizes each tile in VMEM right after its DMA).

    Returns [B, H, D].  Compiled once per (B, H, D, block_size,
    max_blocks) — sequence lengths and table contents are runtime data.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, d = q.shape
    n_pool, bs, hp, dp = k_pool.shape
    if v_pool.shape != k_pool.shape:
        raise ValueError("k_pool and v_pool shapes differ: %r vs %r"
                         % (k_pool.shape, v_pool.shape))
    if (hp, dp) != (h, d):
        raise ValueError("pool head layout %r does not match q %r"
                         % ((hp, dp), (h, d)))
    quantized = _check_quant_args(k_pool, v_pool, k_scales, v_scales)
    nb = page_table.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    kernel = functools.partial(_decode_kernel, block_size=bs,
                               n_blocks=nb, scale=float(scale),
                               quantized=quantized)
    # index maps see the prefetched page table: sweep 1 follows it for
    # K, sweep 2 for V; the off-sweep operand pins to an already-mapped
    # block (clipped id) so no DMA reads out of range

    def k_index(b_, j, pt, ln):
        return pt[b_, jnp.minimum(j, nb - 1)], 0, 0, 0

    def v_index(b_, j, pt, ln):
        return pt[b_, jnp.clip(j - nb, 0, nb - 1)], 0, 0, 0

    def q_index(b_, j, pt, ln):
        return b_, 0, 0

    in_specs = [pl.BlockSpec((1, h, d), q_index),
                pl.BlockSpec((1, bs, h, d), k_index),
                pl.BlockSpec((1, bs, h, d), v_index)]
    operands = [q, k_pool, v_pool]
    if quantized:
        # the scales are blocked like their tiles, NOT scalar-prefetched:
        # prefetch puts the whole [num_blocks, H] array in SMEM, which a
        # real pool outgrows
        in_specs += [
            pl.BlockSpec((1, h, 1), lambda *a: k_index(*a)[:3]),
            pl.BlockSpec((1, h, 1), lambda *a: v_index(*a)[:3])]
        operands += [k_scales.astype(jnp.float32).reshape(n_pool, h, 1),
                     v_scales.astype(jnp.float32).reshape(n_pool, h, 1)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, 2 * nb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, h, d), q_index),
        scratch_shapes=[
            pltpu.VMEM((nb, bs, h), jnp.float32),   # score / prob rows
            pltpu.VMEM((1, h), jnp.float32),        # running max
            pltpu.VMEM((1, h), jnp.float32),        # softmax denominator
            pltpu.VMEM((h, d), jnp.float32),        # output accumulator
        ],
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        interpret=_interpret(),
    )(page_table.astype(jnp.int32), lengths.astype(jnp.int32), *operands)


def _prefill_table_lengths(block_row, start, length, chunk):
    """One sequence's chunk as a ragged "batch": every chunk token
    shares the sequence's block row, and causal masking IS the ragged
    length masking — query at absolute position ``p`` attends to
    ``p + 1`` cached tokens.  Positions past ``length`` are padding
    rows (length 0 → zeros, the kernel's existing convention)."""
    table = jnp.broadcast_to(block_row.astype(jnp.int32)[None, :],
                             (chunk, block_row.shape[0]))
    pos = start + jnp.arange(chunk, dtype=jnp.int32)
    lens = jnp.where(pos < length, pos + 1, 0).astype(jnp.int32)
    return table, lens


def paged_prefill_attention(q, k_pool, v_pool, block_row, start, length,
                            scale=None, k_scales=None, v_scales=None):
    """Chunked-prefill attention over a partially-resident page table.

    ``q``: [C, H, D] — one fixed-size chunk of prompt queries for ONE
    sequence, absolute positions ``start .. start + C - 1``;
    ``block_row``: int32 [max_blocks] — the sequence's page-table row
    (resident prefix blocks + freshly written chunk blocks, 0-padded);
    ``start``/``length``: scalars — chunk origin and total prompt
    length (positions past ``length`` are padding and return zeros).

    No new kernel: the chunk is dispatched through the decode kernel
    with the chunk axis as the batch axis and per-query causal lengths
    ``start + i + 1`` — which is exactly why ragged paged attention
    (arXiv 2604.15464) serves mixed prefill/decode from ONE executable.
    The resident prefix is read straight from the pool, so a prompt
    whose first blocks are already cached prefills only its suffix.
    """
    table, lens = _prefill_table_lengths(block_row, start, length,
                                         q.shape[0])
    return paged_attention(q, k_pool, v_pool, table, lens, scale=scale,
                           k_scales=k_scales, v_scales=v_scales)


def paged_prefill_attention_reference(q, k_pool, v_pool, block_row,
                                      start, length, scale=None,
                                      k_scales=None, v_scales=None):
    """Dense oracle for :func:`paged_prefill_attention` (same staging
    as :func:`paged_attention_reference`, so parity stays bitwise)."""
    table, lens = _prefill_table_lengths(block_row, start, length,
                                         q.shape[0])
    return paged_attention_reference(q, k_pool, v_pool, table, lens,
                                     scale=scale, k_scales=k_scales,
                                     v_scales=v_scales)


def _verify_table_lengths(page_table, lengths, span):
    """A speculative verify pass as a ragged "batch": the ``span`` query
    tokens of every sequence (the fed token plus its draft tail) each
    share the sequence's block row, and the per-query causal lengths are
    ``length + i + 1`` — query ``i`` attends to the history plus the
    ``i + 1`` tokens fed so far, never to the drafts after it.  Padding
    rows (``length == 0``) stay padding at every span position."""
    b, nb = page_table.shape
    table = jnp.repeat(page_table.astype(jnp.int32), span, axis=0)
    pos = jnp.arange(span, dtype=jnp.int32)[None, :]
    lens = jnp.where(lengths[:, None] > 0,
                     lengths[:, None].astype(jnp.int32) + pos + 1, 0)
    return table, lens.reshape(b * span)


def paged_verify_attention(q, k_pool, v_pool, page_table, lengths,
                           scale=None, k_scales=None, v_scales=None):
    """Multi-token (draft-and-verify) ragged paged attention.

    ``q``: [B, S, H, D] — ``S`` query tokens per sequence (speculative
    decoding's fed token + its ``S - 1`` draft tokens), whose K/V have
    already been written at positions ``length .. length + S - 1``;
    ``page_table``/``lengths``: as :func:`paged_attention` — ``lengths``
    counts the cached tokens BEFORE this verify span.

    Returns [B, S, H, D].  No new kernel (the same move as
    :func:`paged_prefill_attention`): the span is flattened into the
    batch axis of the decode kernel with per-query causal lengths
    ``length + i + 1``, so one warm executable verifies any mix of
    sequence lengths — the ragged batching of arXiv 2604.15464 serving
    the verify pass natively.  Rejected draft positions are "rolled
    back" simply by never advancing ``lengths`` past them: the kernel's
    length masking makes their K/V writes invisible until overwritten.
    """
    b, s, h, d = q.shape
    table, lens = _verify_table_lengths(page_table, lengths, s)
    o = paged_attention(q.reshape(b * s, h, d), k_pool, v_pool,
                        table, lens, scale=scale, k_scales=k_scales,
                        v_scales=v_scales)
    return o.reshape(b, s, h, d)


def paged_verify_attention_reference(q, k_pool, v_pool, page_table,
                                     lengths, scale=None, k_scales=None,
                                     v_scales=None):
    """Dense oracle for :func:`paged_verify_attention` (same staging as
    :func:`paged_attention_reference`, so parity stays bitwise)."""
    b, s, h, d = q.shape
    table, lens = _verify_table_lengths(page_table, lengths, s)
    o = paged_attention_reference(q.reshape(b * s, h, d), k_pool,
                                  v_pool, table, lens, scale=scale,
                                  k_scales=k_scales, v_scales=v_scales)
    return o.reshape(b, s, h, d)


def paged_attention_reference(q, k_pool, v_pool, page_table, lengths,
                              scale=None, k_scales=None, v_scales=None):
    """Pure-jnp dense oracle: gather every sequence's blocks into a
    dense [B, T_max, H, D] view, materialize the full score row, dense
    softmax, weighted sum.

    The reductions are staged the way the kernel streams (per-block
    partial sums, then a sequential accumulation over the block axis)
    so the parity tests can assert BITWISE equality, not just
    tolerance — float addition is non-associative, and XLA's fused
    reduce over the block axis associates differently than the
    kernel's block-sequential accumulator.
    """
    b, h, d = q.shape
    n_pool, bs, hp, dp = k_pool.shape
    quantized = _check_quant_args(k_pool, v_pool, k_scales, v_scales)
    nb = page_table.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    k = k_pool[page_table].astype(jnp.float32)  # [B, nb, bs, H, D]
    v = v_pool[page_table].astype(jnp.float32)
    if quantized:
        # dequantize elementwise with the gathered per-block scales —
        # the same ``int8 -> f32 * scale`` product the kernel computes
        # on the VMEM tile, so parity stays bitwise
        k = k * k_scales.astype(jnp.float32)[page_table][
            :, :, None, :, None]
        v = v * v_scales.astype(jnp.float32)[page_table][
            :, :, None, :, None]
    qf = q.astype(jnp.float32) * scale
    s = jnp.sum(k * qf[:, None, None], axis=-1)
    s = jnp.moveaxis(s, 3, 1)                   # [B, H, nb, bs]
    pos = (jnp.arange(nb)[:, None] * bs +
           jnp.arange(bs)[None, :])             # [nb, bs]
    valid = pos[None, None] < lengths[:, None, None, None]
    s = jnp.where(valid, s, _NEG_INF)
    m = jnp.max(s, axis=(2, 3), keepdims=True)
    safe_m = jnp.where(jnp.isneginf(m), 0.0, m)
    p = jnp.where(jnp.isneginf(s), 0.0, jnp.exp(s - safe_m))
    l = jnp.sum(p, axis=(2, 3))
    safe_l = jnp.where(l == 0.0, 1.0, l)
    vm = jnp.moveaxis(v.astype(jnp.float32), 3, 1)   # [B, H, nb, bs, D]
    pv = jnp.sum(p[..., None] * vm, axis=3)          # [B, H, nb, D]
    o = pv[:, :, 0]
    for j in range(1, nb):                      # block-sequential, like
        o = o + pv[:, :, j]                     # the kernel's sweep 2
    return (o / safe_l[..., None]).astype(q.dtype)
