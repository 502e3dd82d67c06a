"""Flash attention as a Pallas TPU kernel pair (forward + backward).

The hand-kernel capability case the framework was missing (VERDICT r4
item 4): LRN and GEMM hand kernels lost to XLA fusion because XLA
already fuses memory-bound elementwise chains well — attention is the
op where a hand kernel wins on TPU, because the win is ALGORITHMIC:
``attention_reference`` (znicz/attention.py, parallel/ring.py:27)
materializes the [B, H, T, T] score matrix through HBM, while this
kernel streams K/V blocks through VMEM with the online-softmax
recurrence and never materializes T x T anywhere.  HBM traffic drops
from O(T^2) to O(T * D), so the advantage GROWS with sequence length —
the regime the long-context/ring-attention story targets.

VMEM stays O(block): every kernel walks K (or Q) blocks via a third
grid dimension — Pallas pipelines the block DMAs while the online
recurrence lives in VMEM scratch across the innermost grid steps (the
canonical TPU flash structure).  Nothing is sized by T, so T=32k+
compiles in the same footprint as T=1k.

Same layout as the oracle: q/k/v [B, T, H, D] -> out [B, T, H, D];
numerics match to f32 tolerance (asserted in
tests/test_flash_attention.py).  The backward is the standard two-pass
flash backward (dq pass over Q tiles, dk/dv pass over K tiles) driven
by the forward's saved logsumexp — no [T, T] in the backward either.

Wiring: ``MultiHeadAttention(use_pallas=True)`` (or the global
``root.common.engine.use_pallas``) routes single-device attention here;
shapes the kernel cannot tile (T with no block-divisor >= 32) fall back
to the oracle with a logged warning, so the knob is always safe.
"""

import functools
import logging
import math
import typing

import jax
import jax.numpy as jnp
import numpy
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from .. import backends


def _interpret():
    return not backends.on_tpu()


DEFAULT_BLOCK_Q = 256
DEFAULT_BLOCK_K = 256
#: both blocks where a measurement chose them: bfloat16, causal, forward
#: + backward on the v5e, ms at both blocks 256 / 512 / 1,024.  [2, 8192,
#: 32 on 8, 64]: 123.3 / 58.8 / 44.2 (PERF.md section 6, PR 33).  [2,
#: 8192, 32 on 4, 128]: 116.6 / 57.6 / 44.4, and inside a window of 1,024
#: on the banded grids 34.4 / 22.2 / 21.8, with (512, 1024) at 21.9 and
#: every mixed pair behind (PERF.md section 6, PR 35)
#: and, on the exact bands that skip fetches and masks, (512, 512) at 19.7
#: against 20.0 at (1,024, 1,024), the pairs between them slower (PERF.md
#: section 6)
MEASURED_BLOCK = 1024
_MIN_BLOCK = 32         # >= f32 sublane tile; smallest worthwhile tile
_STAT_LANES = 128       # per-row stats (lse, delta) ride a full lane
                        # dim INSIDE the kernels: Mosaic requires block
                        # last-dims (8, 128) tileable, so a [BH, T] row
                        # vector can't be blocked (1, block_q).  The
                        # forward's lse OUTPUT does not pay the 128x
                        # broadcast in HBM though: when block_q divides
                        # into whole 128-lane rows the kernel emits a
                        # compact [BH, T//128, 128] block layout (the T
                        # axis folded into lanes, one f32 per row —
                        # 134 MB -> 1 MB at BH=8, T=32k) and only the
                        # backward's kernel-boundary broadcast
                        # materializes lanes, transiently.  Small-T
                        # fallback blocks (32/64) keep the broadcast
                        # layout.
_NEG_INF = float("-inf")
_warned_shapes = set()
#: what the forward rules call (``checkpoint_name``) the two results the
#: backward kernels read, the output and the row statistics: a
#: ``jax.checkpoint`` whose policy saves these names does not rerun the
#: forward kernel in the backward pass (the plain family, the latent one);
#: outside such a policy a name is an identity
SAVED_NAMES = ("flash_out", "flash_lse")
MLA_SAVED_NAMES = ("mla_flash_out", "mla_flash_lse")


def _blocks(t, block_q, block_k):
    """(bq, bk) dividing T, searching down from the requested sizes;
    None when no divisor >= _MIN_BLOCK exists."""
    def fit(want):
        cand = min(want, t)
        while cand >= _MIN_BLOCK:
            if t % cand == 0:
                return cand
            cand //= 2
        return None

    bq, bk = fit(block_q), fit(block_k)
    if bq is None or bk is None:
        return None
    return bq, bk


def default_blocks(d, window=None, itemsize=2):
    """(block_q, block_k) of head size ``d`` and operands of ``itemsize``
    bytes where no tuning record and no caller says otherwise: the
    measured winner for rows of 256 bytes and shorter (heads of 128 in
    bfloat16, of 64 in float32) without a window, and for heads of 128
    inside a window of a block or wider (a band of narrower windows is
    mostly masked at such blocks: not measured); the old pair
    elsewhere.  Float32 heads of 128 at the measured blocks pass the
    dk/dv kernel's 16 MiB of VMEM by 92 KB."""
    if d * itemsize <= 256 and (
            window is None or (d > 64 and window >= MEASURED_BLOCK)):
        return MEASURED_BLOCK, MEASURED_BLOCK
    return DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K


def flash_attention_supported(t, block_q=DEFAULT_BLOCK_Q,
                              block_k=DEFAULT_BLOCK_K):
    return _blocks(t, block_q, block_k) is not None


def _mask_causal(s, iq, jk, block_q, block_k, window=None):
    rows = iq * block_q + lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    cols = jk * block_k + lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    mask = cols > rows
    if window is not None:
        mask = jnp.logical_or(mask, cols <= rows - window)
    return jnp.where(mask, _NEG_INF, s)


# -- which blocks a grid step needs ------------------------------------------
#
# Every grid step pins one block and streams another.  From the static
# geometry a step is CLEAR (every (query, key) pair of the block is
# visible: no mask), EDGE (the causal diagonal or the window's edge
# crosses it: masked) or INVISIBLE (nothing computed).  The blocks a
# pinned block needs are one contiguous run, so an invisible step's
# index map re-references the nearest of them: a block index that does
# not change is not fetched again, and a skipped step fetches nothing.
# With a window the grids are BANDED: the streamed axis visits only as
# many blocks as the widest run, so compute and block DMA are O(T *
# window) instead of O(T^2); the streamed grid index j maps to a
# logical block from the run's start.


class _Scalar:
    """Arithmetic on traced block indices as single primitives, where
    jnp's adds promotion and, for ``//``, a sign fix: an index map is
    traced for every call and each operation costs its trace.  Every
    quotient taken here is of a non-negative number or clamped at 0
    after, where truncation and floor agree."""
    maximum = staticmethod(lax.max)
    minimum = staticmethod(lax.min)
    floor_divide = staticmethod(lax.div)


class _Grid(typing.NamedTuple):
    """The static geometry of one call.  Its methods take traced scalars
    in the kernels and index maps (``xp`` :class:`_Scalar`) and numpy
    arrays in :func:`block_census` (``xp`` numpy)."""
    t: int
    block_q: int
    block_k: int
    window: typing.Optional[int]
    causal: bool

    @property
    def n_q(self):
        return self.t // self.block_q

    @property
    def n_k(self):
        return self.t // self.block_k

    def k_first(self, iq, xp=_Scalar):
        """First key block query block ``iq`` sees: keys > its first
        query - window."""
        if self.window is None:
            return 0
        return xp.maximum(0, xp.floor_divide(
            iq * self.block_q + (1 - self.window), self.block_k))

    def k_last(self, iq, xp=_Scalar):
        """Last key block query block ``iq`` sees: the diagonal's."""
        if not self.causal:
            return self.n_k - 1
        return xp.floor_divide(iq * self.block_q + (self.block_q - 1),
                               self.block_k)

    def q_first(self, jk, xp=_Scalar):
        """First query block that sees key block ``jk``: queries >=
        keys."""
        if not self.causal:
            return 0
        return xp.floor_divide(jk * self.block_k, self.block_q)

    def q_last(self, jk, xp=_Scalar):
        """Last query block that sees key block ``jk``: queries < its
        last key + window."""
        if self.window is None:
            return self.n_q - 1
        return xp.minimum(self.n_q - 1, xp.floor_divide(
            jk * self.block_k + (self.block_k + self.window - 2),
            self.block_q))

    @property
    def k_inner(self):
        """The fwd / dq grids' streamed axis: the band where a window
        makes it narrower than the key blocks, else all of them."""
        if self.window is None:
            return self.n_k
        return min(self.n_k, _kband_size(self.t, self.block_q,
                                         self.block_k, self.window))

    @property
    def q_inner(self):
        """The dk/dv grid's streamed axis, a head's part of it."""
        if self.window is None:
            return self.n_q
        return min(self.n_q, _qband_size(self.t, self.block_q,
                                         self.block_k, self.window))

    def key(self, iq, j, xp=_Scalar):
        """Key block of the fwd / dq grids' step (``iq``, ``j``)."""
        return j if self.k_inner == self.n_k else self.k_first(iq, xp) + j

    def key_fetched(self, iq, j, xp=_Scalar):
        """The key block that step fetches: its own where query block
        ``iq`` needs it, else the nearest one that it needs; each map
        holds only the bounds it can cross."""
        if not self.causal:
            return j
        if self.k_inner < self.n_k:     # a band starts at the first needed
            jk = self.k_first(iq, xp) + j
        elif self.window is not None:
            jk = xp.maximum(j, self.k_first(iq, xp))
        else:
            jk = j
        return xp.minimum(jk, self.k_last(iq, xp))

    def query(self, jk, i, xp=_Scalar):
        """Query block of the dk/dv grid's step (``jk``, ``i``), ``i``
        counted within a head."""
        return i if self.q_inner == self.n_q else self.q_first(jk, xp) + i

    def query_fetched(self, jk, i, xp=_Scalar):
        """The query block that step fetches, as :meth:`key_fetched`."""
        if not self.causal:
            return i
        if self.q_inner < self.n_q:
            iq = self.q_first(jk, xp) + i
        else:
            iq = xp.maximum(i, self.q_first(jk, xp))
        if self.window is None:
            return iq
        return xp.minimum(iq, self.q_last(jk, xp))

    def step_class(self, iq, jk):
        """(needed, clear) of the step that pairs query block ``iq`` with
        key block ``jk``; a step past the last query block (a band's
        overshoot) is neither."""
        if not self.causal:
            in_range = iq < self.n_q
            return in_range, in_range
        # query row r sees key c iff 0 <= r - c < window; over the block
        # r - c runs from d - block_k + 1 to d + block_q - 1
        d = iq * self.block_q - jk * self.block_k
        needed = d > -self.block_q
        clear = d >= self.block_k - 1
        if self.window is not None:
            needed = needed & (d < self.window + self.block_k - 1)
            clear = clear & (d <= self.window - self.block_q)
        if self.q_inner < self.n_q:
            in_range = iq < self.n_q
            needed, clear = needed & in_range, clear & in_range
        return needed, clear

    @property
    def classes(self):
        """Whether any (query block, key block) pair is clear, and whether
        any is an edge.  A kernel holds a body only for a class that
        occurs (inside a window as wide as the blocks no block is clear):
        each body costs the program's lowering and compile time."""
        iq, jk = numpy.meshgrid(numpy.arange(self.n_q),
                                numpy.arange(self.n_k), indexing="ij")
        needed, clear = (numpy.broadcast_to(c, iq.shape)
                         for c in self.step_class(iq, jk))
        return bool(clear.any()), bool((needed & ~clear).any())


def _kband_size(t, block_q, block_k, window):
    """Key blocks the widest query block's run holds: the most any query
    block of the ``t`` rows needs."""
    grid = _Grid(t, block_q, block_k, window, True)
    iq = numpy.arange(grid.n_q)
    return int(numpy.max(grid.k_last(iq, numpy) - grid.k_first(iq, numpy)
                         + 1))


def _qband_size(t, block_q, block_k, window):
    """Query blocks the widest key block's run holds."""
    grid = _Grid(t, block_q, block_k, window, True)
    jk = numpy.arange(grid.n_k)
    return int(numpy.max(grid.q_last(jk, numpy) - grid.q_first(jk, numpy)
                         + 1))


def _run_step(step, grid, iq, jk):
    """``step(masked)`` on the blocks the step needs: unmasked on a clear
    block, masked on an edge block, not at all on an invisible one.  A
    call without causality runs every step under a traced ``when`` too:
    interpret mode inside a ``shard_map`` (the ring) cannot read the
    blocks outside one."""
    from jax.experimental import pallas as pl

    any_clear, any_edge = grid.classes
    needed, clear = grid.step_class(iq, jk)
    if any_clear:
        pl.when(clear)(lambda: step(False))
    if any_edge:
        pl.when(jnp.logical_and(needed, jnp.logical_not(clear)))(
            lambda: step(True))


def block_census(t, block_q, block_k, window=None, causal=True, group=1):
    """What the three grids of a call do for one key-value head and its
    ``group`` query heads: for ``fwd``, ``dq`` and ``dkv`` the grid's
    ``steps``, its ``clear`` and ``edge`` steps (the others compute
    nothing), the block ``fetches`` of its streamed operands (a step
    whose block index is its predecessor's fetches nothing) and the
    ``wasted`` ones, whose run of steps on that index holds no clear or
    edge step.  Pure: the kernels' own index maps and step classes,
    evaluated in numpy over the grid in its order."""
    if t % block_q or t % block_k:
        raise ValueError("blocks (%d, %d) do not tile %d rows"
                         % (block_q, block_k, t))
    grid = _Grid(t, block_q, block_k, window, causal)

    def census(iq, jk, head, block):
        needed, clear = (numpy.broadcast_to(c, iq.shape)
                         for c in grid.step_class(iq, jk))
        fetch = numpy.ones(iq.shape, bool)
        fetch[1:] = (head[1:] != head[:-1]) | (block[1:] != block[:-1])
        run = numpy.cumsum(fetch) - 1
        used = numpy.bincount(run, weights=needed) > 0
        return {"steps": iq.size, "clear": int(clear.sum()),
                "edge": int((needed & ~clear).sum()),
                "fetches": int(fetch.sum()), "wasted": int((~used).sum())}

    # fwd / dq: (query head, query block, streamed key step); the K and V
    # blocks of the one key-value head
    _, iq, j = (a.ravel() for a in numpy.meshgrid(
        numpy.arange(group), numpy.arange(grid.n_q),
        numpy.arange(grid.k_inner), indexing="ij"))
    rows = census(iq, grid.key(iq, j, numpy), numpy.zeros_like(iq),
                  grid.key_fetched(iq, j, numpy))
    # dk/dv: (key block, streamed query step of the heads in turn)
    jk, i = (a.ravel() for a in numpy.meshgrid(
        numpy.arange(grid.n_k), numpy.arange(group * grid.q_inner),
        indexing="ij"))
    i_head = i % grid.q_inner
    cols = census(grid.query(jk, i_head, numpy), jk, i // grid.q_inner,
                  grid.query_fetched(jk, i_head, numpy))
    return {"fwd": rows, "dq": dict(rows), "dkv": cols}


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr,
                acc_scr, *, scale, grid, compact_stats=False):
    from jax.experimental import pallas as pl

    iq, j = pl.program_id(1), pl.program_id(2)
    n_inner = pl.num_programs(2)
    jk = grid.key(iq, j)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def step(masked):
        q = q_ref[0].astype(jnp.float32) * scale       # [BQ, D]
        kb = k_ref[0].astype(jnp.float32)              # [BK, D]
        vb = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)        # [BQ, BK]
        if masked:
            s = _mask_causal(s, iq, jk, grid.block_q, grid.block_k,
                             grid.window)
        m = m_scr[...]
        new_m = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        # a fully-masked row keeps m at -inf: exp(-inf - -inf) must be
        # 0, not nan (same guard as parallel/ring.py:77); a row's first
        # block may be a clear one, so m is guarded on both paths
        safe_m = jnp.where(jnp.isneginf(new_m), 0.0, new_m)
        alpha = jnp.where(jnp.isneginf(m), 0.0, jnp.exp(m - safe_m))
        p = jnp.exp(s - safe_m)
        if masked:
            p = jnp.where(jnp.isneginf(s), 0.0, p)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1,
                                                  keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, vb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = new_m

    _run_step(step, grid, iq, jk)

    @pl.when(j == n_inner - 1)
    def _finish():
        m, l = m_scr[...], l_scr[...]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[...] / safe_l).astype(o_ref.dtype)
        lse = jnp.where(jnp.isneginf(m), 0.0, m) + jnp.log(safe_l)
        if compact_stats:
            # fold the [BQ, 1] column into whole 128-lane rows: one f32
            # per query row in HBM instead of a 128x lane broadcast (a
            # single in-VMEM relayout per Q block — negligible next to
            # the saved HBM write traffic)
            lse_ref[0, 0] = lse.reshape(grid.block_q // _STAT_LANES,
                                        _STAT_LANES)
        else:
            lse_ref[0] = jnp.broadcast_to(lse, (grid.block_q,
                                                _STAT_LANES))


def _call_name(kernel, window):
    """The ``pallas_call`` name, which is its HLO instruction's and so
    a device trace's: ``gqa_flash_<kernel>`` of a call without a window,
    ``gqa_window_flash_<kernel>`` of one with, so that a reader tells a
    window layer's events from a full layer's (neither name holds the
    other)."""
    return "gqa_%sflash_%s" % ("" if window is None else "window_", kernel)


def _struct(shape, dtype, vma):
    """ShapeDtypeStruct, with mesh-variance declared when the kernel
    runs inside a shard_map (ring flash attention) — check_vma requires
    pallas outputs to state their varying axes."""
    if vma is None:
        return jax.ShapeDtypeStruct(shape, dtype)
    return jax.ShapeDtypeStruct(shape, dtype, vma=frozenset(vma))


def _flash_fwd_bh(q, k, v, scale, causal, block_q, block_k, vma=None,
                  window=None):
    """Forward over ``q`` [B * H_q, T, D] and ``k``, ``v`` [B * H_kv, T,
    D] (``H_q`` a multiple of ``H_kv``: query head ``h`` reads key-value
    head ``h // (H_q / H_kv)`` through the K/V index map, so no copy of
    K or V per query head exists in HBM); returns (out, lse[B * H_q,
    T])."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, t, d = q.shape
    group = bh // k.shape[0]
    grid = _Grid(t, block_q, block_k, window, causal)
    # compact stats layout whenever each Q block covers whole 128-lane
    # rows (default 256/128 blocks do; the 32/64 fallbacks keep the
    # lane-broadcast layout) — see the _STAT_LANES note
    compact = block_q % _STAT_LANES == 0
    kernel = functools.partial(_fwd_kernel, scale=scale, grid=grid,
                               compact_stats=compact)
    qspec = pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0))
    kspec = pl.BlockSpec((1, block_k, d), lambda b, i, j: (
        b // group, grid.key_fetched(i, j), 0))
    if compact:
        # one [block_q // 128, 128] slab per Q block, as the LAST TWO
        # dims of a 4-D array: a block must either tile (8, 128) or
        # span its array's last two dims, and 256 // 128 = 2 rows do
        # neither inside a [BH, T // 128, 128] array
        rows = block_q // _STAT_LANES
        lse_spec = pl.BlockSpec((1, 1, rows, _STAT_LANES),
                                lambda b, i, j: (b, i, 0, 0))
        lse_shape = (bh, grid.n_q, rows, _STAT_LANES)
    else:
        lse_spec = pl.BlockSpec((1, block_q, _STAT_LANES),
                                lambda b, i, j: (b, i, 0))
        lse_shape = (bh, t, _STAT_LANES)
    out, lse = pl.pallas_call(
        kernel, grid=(bh, grid.n_q, grid.k_inner),
        in_specs=[qspec, kspec, kspec],
        out_specs=[qspec, lse_spec],
        out_shape=[_struct((bh, t, d), q.dtype, vma),
                   _struct(lse_shape, jnp.float32, vma)],
        scratch_shapes=[pltpu.VMEM((block_q, 1), jnp.float32),
                        pltpu.VMEM((block_q, 1), jnp.float32),
                        pltpu.VMEM((block_q, d), jnp.float32)],
        name=_call_name("fwd", window), interpret=_interpret())(q, k, v)
    # contiguous fold back to [BH, T] rows (free: a metadata reshape in
    # the compact layout, a lane slice otherwise)
    return out, (lse.reshape(bh, t) if compact else lse[:, :, 0])


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_scr, *, scale, grid):
    from jax.experimental import pallas as pl

    iq, j = pl.program_id(1), pl.program_id(2)
    n_inner = pl.num_programs(2)
    jk = grid.key(iq, j)

    @pl.when(j == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    def step(masked):
        q = q_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0, :, 0:1]
        delta = delta_ref[0, :, 0:1]
        kb = k_ref[0].astype(jnp.float32)
        vb = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if masked:
            s = _mask_causal(s, iq, jk, grid.block_q, grid.block_k,
                             grid.window)
        p = jnp.exp(s - lse)
        if masked:
            p = jnp.where(jnp.isneginf(s), 0.0, p)
        dov = jax.lax.dot_general(
            do, vb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)        # [BQ, BK]
        ds = p * (dov - delta)
        dq_scr[...] = dq_scr[...] + jax.lax.dot_general(
            ds, kb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    _run_step(step, grid, iq, jk)

    @pl.when(j == n_inner - 1)
    def _finish():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_scr, dv_scr, *, scale, grid):
    """The K block of one KEY-VALUE head is pinned; the Q blocks of the
    query heads that read it stream through the innermost grid axis
    (``grid.q_inner`` blocks a head, head after head), so dK and dV are
    summed over the group in VMEM and written once."""
    from jax.experimental import pallas as pl

    jk, j = pl.program_id(1), pl.program_id(2)
    n_inner = pl.num_programs(2)
    iq = grid.query(jk, j % grid.q_inner)

    @pl.when(j == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def step(masked):
        kb = k_ref[0].astype(jnp.float32)
        vb = v_ref[0].astype(jnp.float32)
        q = q_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0, :, 0:1]
        delta = delta_ref[0, :, 0:1]
        s = jax.lax.dot_general(
            q, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if masked:
            s = _mask_causal(s, iq, jk, grid.block_q, grid.block_k,
                             grid.window)
        p = jnp.exp(s - lse)
        if masked:
            p = jnp.where(jnp.isneginf(s), 0.0, p)
        dv_scr[...] = dv_scr[...] + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dov = jax.lax.dot_general(
            do, vb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dov - delta)
        dk_scr[...] = dk_scr[...] + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    _run_step(step, grid, iq, jk)

    @pl.when(j == n_inner - 1)
    def _finish():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _flash_bwd_bh(q, k, v, out, lse, do, scale, causal, block_q,
                  block_k, vma=None, delta=None, window=None):
    """``q``, ``out``, ``do`` [B * H_q, T, D]; ``k``, ``v`` [B * H_kv,
    T, D] -> (dq, dk, dv), dk and dv per KEY-VALUE head: the dk/dv
    kernel's streamed axis walks the query heads of a group, so their
    sum is taken in VMEM (nothing is written per query head, no
    reduction follows).  lse (and the optional precomputed delta) may
    arrive either as [BH, T] rows or already lane-broadcast [BH, T,
    _STAT_LANES] — the ring backward hoists the broadcast out of its
    per-hop loop."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, t, d = q.shape
    bh_kv = k.shape[0]
    group = bh // bh_kv
    grid = _Grid(t, block_q, block_k, window, causal)
    if delta is None:
        # delta_i = sum_d do*out — tiny elementwise reduce; XLA fuses it
        delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                        axis=-1)                       # [BH, T]
    # stats enter the kernels lane-broadcast (see _STAT_LANES)
    if delta.ndim == 2:
        delta = jnp.broadcast_to(delta[..., None],
                                 (bh, t, _STAT_LANES))
    if lse.ndim == 2:
        lse = jnp.broadcast_to(lse[..., None], (bh, t, _STAT_LANES))
    qspec = pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0))
    qrow = pl.BlockSpec((1, block_q, _STAT_LANES),
                        lambda b, i, j: (b, i, 0))
    kspec = pl.BlockSpec((1, block_k, d), lambda b, i, j: (
        b // group, grid.key_fetched(i, j), 0))
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, grid=grid),
        grid=(bh, grid.n_q, grid.k_inner),
        in_specs=[qspec, kspec, kspec, qspec, qrow, qrow],
        out_specs=qspec,
        out_shape=_struct((bh, t, d), q.dtype, vma),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        name=_call_name("dq", window), interpret=_interpret())(
            q, k, v, do, lse, delta)
    # dk/dv pass: K block pinned per middle-grid step; the inner axis
    # streams the Q blocks of the group's query heads, head after head
    nq_inner = grid.q_inner

    def dkv_q_index(b, j, i):
        return (b * group + i // nq_inner,
                grid.query_fetched(j, i % nq_inner), 0)
    kq_spec = pl.BlockSpec((1, block_q, d), dkv_q_index)
    kq_row = pl.BlockSpec((1, block_q, _STAT_LANES), dkv_q_index)
    kk_spec = pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, grid=grid),
        grid=(bh_kv, grid.n_k, group * nq_inner),
        in_specs=[kq_spec, kk_spec, kk_spec, kq_spec, kq_row, kq_row],
        out_specs=[kk_spec, kk_spec],
        out_shape=[_struct((bh_kv, t, d), k.dtype, vma),
                   _struct((bh_kv, t, d), v.dtype, vma)],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        name=_call_name("dkv", window), interpret=_interpret())(
            q, k, v, do, lse, delta)
    return dq, dk, dv


def _to_bh(x):
    b, t, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, t, d)


def _from_bh(x, b, h):
    bh, t, d = x.shape
    return x.reshape(b, h, t, d).transpose(0, 2, 1, 3)


def _warn_fallback(t):
    if t >= 256 and t not in _warned_shapes:
        _warned_shapes.add(t)
        logging.getLogger("flash_attention").warning(
            "T=%d has no block divisor >= %d: falling back to the XLA "
            "oracle, which materializes the [T, T] scores (pad T to a "
            "multiple of %d to engage the flash kernel)",
            t, _MIN_BLOCK, _MIN_BLOCK)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_attention(q, k, v, causal, scale, block_q, block_k,
                     window):
    out, _ = _flash_fwd(q, k, v, causal, scale, block_q, block_k,
                        window)
    return out


def flash_attention(q, k, v, causal=False, scale=None, block_q=None,
                    block_k=None, window=None):
    """Flash attention, ``q`` [B, T, H_q, D], ``k`` and ``v`` [B, T,
    H_kv, D] with ``H_q`` a multiple of ``H_kv`` — drop-in for
    ``attention_reference`` (falls back to it, with a logged warning,
    when T can't be tiled).  With fewer key-value heads than query heads
    (grouped-query attention) query head ``h`` reads key-value head ``h
    // (H_q / H_kv)``; one head count is the group of 1 of the same
    kernels.  ``window`` (requires ``causal``):
    sliding-window attention — position i sees keys in
    (i - window, i]; off-band blocks skip their MXU work entirely.

    ``block_q``/``block_k`` default to the measured winner for this
    (T, D, device, versions) when a tuning record exists (autotune
    sites ``flash_attention`` / ``window_attention``), else the
    hand-picked :func:`default_blocks` of the head size;
    explicit values always win.  Resolution happens at trace time
    (shapes are static), outside the custom-vjp boundary."""
    if q.shape[2] % k.shape[2] or k.shape != v.shape:
        raise ValueError("%d query heads cannot share %d key-value heads "
                         "(k %s, v %s)" % (q.shape[2], k.shape[2],
                                           k.shape, v.shape))
    if block_q is None or block_k is None:
        from ..autotune import dispatch as _autotune
        site = "window_attention" if window is not None \
            else "flash_attention"
        ctx = {"t": q.shape[1], "d": q.shape[3], "causal": causal}
        if window is not None:
            ctx["window"] = window
        from ..autotune.space import site as _site
        cfg, _ = _autotune.resolve(
            site, _site(site).shape_class(ctx),
            default=dict(zip(("block_q", "block_k"),
                             default_blocks(q.shape[3], window,
                                            q.dtype.itemsize))))
        block_q = block_q if block_q is not None else int(cfg["block_q"])
        block_k = block_k if block_k is not None else int(cfg["block_k"])
    return _flash_attention(q, k, v, causal, scale, block_q, block_k,
                            window)


def _flash_fwd(q, k, v, causal, scale, block_q, block_k, window=None):
    from ..parallel.ring import attention_reference
    if window is not None and not causal:
        raise ValueError("window requires causal=True")
    if window is not None and window < 1:
        raise ValueError("window must be >= 1, got %r" % (window,))
    b, t, h, d = q.shape
    blocks = _blocks(t, block_q, block_k)
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    if blocks is None:
        _warn_fallback(t)
        out = attention_reference(q, k, v, causal=causal, scale=scale,
                                  window=window)
        return out, (q, k, v, out, None)
    bq, bk = blocks
    out_bh, lse = _flash_fwd_bh(_to_bh(q), _to_bh(k), _to_bh(v),
                                scale, causal, bq, bk, window=window)
    out = checkpoint_name(_from_bh(out_bh, b, h), SAVED_NAMES[0])
    lse = checkpoint_name(lse, SAVED_NAMES[1])
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, scale, block_q, block_k, window, res, g):
    from ..parallel.ring import attention_reference
    q, k, v, out, lse = res
    b, t, h, d = q.shape
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    if lse is None:  # untileable shape took the oracle path forward
        _, vjp = jax.vjp(
            lambda q, k, v: attention_reference(q, k, v, causal=causal,
                                                scale=scale,
                                                window=window), q, k, v)
        return vjp(g)
    bq, bk = _blocks(t, block_q, block_k)
    dq, dk, dv = _flash_bwd_bh(
        _to_bh(q), _to_bh(k), _to_bh(v), _to_bh(out), lse, _to_bh(g),
        scale, causal, bq, bk, window=window)
    h_kv = k.shape[2]
    return (_from_bh(dq, b, h), _from_bh(dk, b, h_kv),
            _from_bh(dv, b, h_kv))


_flash_attention.defvjp(_flash_fwd, _flash_bwd)


# -- latent attention: query-key and value heads of different sizes ----------
#
# A latent-attention layer (DeepSeek-V2 and its descendants) scores each
# head with a per-head part (``nope``) plus a rotary part whose KEY is one
# vector a token, shared by every head, and its value heads are narrower
# than its query-key heads.  The kernels above read one ``D`` for all
# operands, so this is a second family: operands stay in their own dtype
# (bf16 on the training path: the MXU multiplies them at its native rate
# and sums in float32), the shared rope key is read through an index map
# that divides the head out (no H-fold copy of it exists in HBM), a
# causal grid re-references the last needed block instead of fetching
# blocks it will skip, and the mask is applied on diagonal blocks only.
# Layout is head-major, [B, H, T, D], which a projection's einsum writes
# directly (no transpose pass).  Each pallas_call carries a ``name`` so a
# device trace shows ``mla_flash_fwd`` / ``mla_flash_dq`` /
# ``mla_flash_dkv``.

#: the fastest of the pairs tried at [2, 32, 8192, .] bfloat16 on the v5e
#: (PERF.md section 6, PR 28); (1024, 2048) and (512, 2048) pass the
#: kernels' VMEM
MLA_BLOCK_Q = 1024
MLA_BLOCK_K = 1024


def _nt(a, b):
    """a [M, D] x b [N, D] -> [M, N], float32 sums."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _nn(a, b):
    """a [M, K] x b [K, N] -> [M, N], float32 sums."""
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _mla_fwd_kernel(qn_ref, qr_ref, kn_ref, kr_ref, v_ref, o_ref, lse_ref,
                    m_scr, l_scr, acc_scr, *, scale, causal, block_q,
                    block_k):
    from jax.experimental import pallas as pl

    iq, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def step(masked):
        s = (_nt(qn_ref[0], kn_ref[0]) + _nt(qr_ref[0], kr_ref[0])) * scale
        if masked:
            s = _mask_causal(s, iq, j, block_q, block_k)
        m = m_scr[...]
        # every row of a needed causal block sees at least key 0, so
        # after the first step m is finite; exp(-inf - m) is 0
        new_m = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - new_m)
        p = jnp.exp(s - new_m)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1,
                                                  keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + _nn(
            p.astype(v_ref.dtype), v_ref[0])
        m_scr[...] = new_m

    if causal:
        # block fully visible: its last key <= the block's first query
        clear = (j + 1) * block_k - 1 <= iq * block_q
        needed = j * block_k <= iq * block_q + block_q - 1
        pl.when(clear)(lambda: step(False))
        pl.when(jnp.logical_and(needed, jnp.logical_not(clear)))(
            lambda: step(True))
    else:
        step(False)

    @pl.when(j == pl.num_programs(2) - 1)
    def _finish():
        l = l_scr[...]
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)
        lse_ref[0] = m_scr[...] + jnp.log(l)


def _mla_dq_kernel(qn_ref, qr_ref, kn_ref, kr_ref, v_ref, do_ref, lse_ref,
                   delta_ref, dqn_ref, dqr_ref, dqn_scr, dqr_scr, *, scale,
                   causal, block_q, block_k):
    from jax.experimental import pallas as pl

    iq, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        dqn_scr[...] = jnp.zeros_like(dqn_scr)
        dqr_scr[...] = jnp.zeros_like(dqr_scr)

    def step(masked):
        kn, kr = kn_ref[0], kr_ref[0]
        s = (_nt(qn_ref[0], kn) + _nt(qr_ref[0], kr)) * scale
        if masked:
            s = _mask_causal(s, iq, j, block_q, block_k)
        p = jnp.exp(s - lse_ref[0])
        dov = _nt(do_ref[0], v_ref[0])
        ds = (p * (dov - delta_ref[0]) * scale).astype(kn.dtype)
        dqn_scr[...] = dqn_scr[...] + _nn(ds, kn)
        dqr_scr[...] = dqr_scr[...] + _nn(ds, kr)

    if causal:
        clear = (j + 1) * block_k - 1 <= iq * block_q
        needed = j * block_k <= iq * block_q + block_q - 1
        pl.when(clear)(lambda: step(False))
        pl.when(jnp.logical_and(needed, jnp.logical_not(clear)))(
            lambda: step(True))
    else:
        step(False)

    @pl.when(j == pl.num_programs(2) - 1)
    def _finish():
        dqn_ref[0] = dqn_scr[...].astype(dqn_ref.dtype)
        dqr_ref[0] = dqr_scr[...].astype(dqr_ref.dtype)


def _mla_dkv_kernel(qn_ref, qr_ref, kn_ref, kr_ref, v_ref, do_ref, lse_ref,
                    delta_ref, dkn_ref, dkr_ref, dv_ref, dkn_scr, dkr_scr,
                    dv_scr, *, scale, causal, block_q, block_k):
    """The K block is pinned, Q blocks stream; everything is computed
    TRANSPOSED ([keys, queries]) so the per-query statistics are rows
    and no operand is transposed on the way into the MXU."""
    from jax.experimental import pallas as pl

    jk, i = pl.program_id(1), pl.program_id(2)

    @pl.when(i == 0)
    def _init():
        dkn_scr[...] = jnp.zeros_like(dkn_scr)
        dkr_scr[...] = jnp.zeros_like(dkr_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def step(masked):
        qn, qr, do = qn_ref[0], qr_ref[0], do_ref[0]
        st = (_nt(kn_ref[0], qn) + _nt(kr_ref[0], qr)) * scale
        if masked:
            keys = jk * block_k + lax.broadcasted_iota(
                jnp.int32, (block_k, block_q), 0)
            queries = i * block_q + lax.broadcasted_iota(
                jnp.int32, (block_k, block_q), 1)
            st = jnp.where(keys > queries, _NEG_INF, st)
        pt = jnp.exp(st - lse_ref[0])                  # [BK, BQ]
        dv_scr[...] = dv_scr[...] + _nn(pt.astype(do.dtype), do)
        dovt = _nt(v_ref[0], do)
        dst = (pt * (dovt - delta_ref[0]) * scale).astype(qn.dtype)
        dkn_scr[...] = dkn_scr[...] + _nn(dst, qn)
        dkr_scr[...] = dkr_scr[...] + _nn(dst, qr)

    if causal:
        # fully visible: the block's first query >= its last key
        clear = i * block_q >= jk * block_k + block_k - 1
        needed = i * block_q + block_q - 1 >= jk * block_k
        pl.when(clear)(lambda: step(False))
        pl.when(jnp.logical_and(needed, jnp.logical_not(clear)))(
            lambda: step(True))
    else:
        step(False)

    @pl.when(i == pl.num_programs(2) - 1)
    def _finish():
        dkn_ref[0] = dkn_scr[...].astype(dkn_ref.dtype)
        dkr_ref[0] = dkr_scr[...].astype(dkr_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _mla_specs(heads_per_rope, block_q, block_k, dr, causal):
    """Block specs of the (q-pinned, k-streamed) grids: forward and dq."""
    from jax.experimental import pallas as pl

    def kj(i, j):
        # a causal grid re-references the last block it needs: a block
        # index that does not change is not fetched again
        return jnp.minimum(j, (i * block_q + block_q - 1) // block_k) \
            if causal else j

    def q_spec(d):
        return pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0))

    def k_spec(d):
        return pl.BlockSpec((1, block_k, d),
                            lambda b, i, j: (b, kj(i, j), 0))
    kr_spec = pl.BlockSpec(
        (1, block_k, dr),
        lambda b, i, j: (b // heads_per_rope, kj(i, j), 0))
    return q_spec, k_spec, kr_spec


def _mla_fwd_bh(qn, qr, kn, kr, v, scale, causal, block_q, block_k):
    """[BH, T, .] operands (``kr`` [BH / heads_per_rope, T, dr]) ->
    (out [BH, T, dv], lse [BH, T, 1])."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, t, dn = qn.shape
    dr, dv = qr.shape[-1], v.shape[-1]
    q_spec, k_spec, kr_spec = _mla_specs(
        bh // kr.shape[0], block_q, block_k, dr, causal)
    return pl.pallas_call(
        functools.partial(_mla_fwd_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k),
        grid=(bh, t // block_q, t // block_k),
        in_specs=[q_spec(dn), q_spec(dr), k_spec(dn), kr_spec, k_spec(dv)],
        out_specs=[q_spec(dv), q_spec(1)],
        out_shape=[jax.ShapeDtypeStruct((bh, t, dv), v.dtype),
                   jax.ShapeDtypeStruct((bh, t, 1), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((block_q, 1), jnp.float32),
                        pltpu.VMEM((block_q, 1), jnp.float32),
                        pltpu.VMEM((block_q, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="mla_flash_fwd", interpret=_interpret())(qn, qr, kn, kr, v)


def _mla_bwd_bh(qn, qr, kn, kr, v, out, lse, do, scale, causal, block_q,
                block_k):
    """-> (dqn, dqr, dkn, dkr [BH, T, dr] float32 per head, dv)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, t, dn = qn.shape
    dr, dv = qr.shape[-1], v.shape[-1]
    heads_per_rope = bh // kr.shape[0]
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)            # [BH, T, 1]
    q_spec, k_spec, kr_spec = _mla_specs(
        heads_per_rope, block_q, block_k, dr, causal)
    semantics = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))
    dqn, dqr = pl.pallas_call(
        functools.partial(_mla_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k),
        grid=(bh, t // block_q, t // block_k),
        in_specs=[q_spec(dn), q_spec(dr), k_spec(dn), kr_spec, k_spec(dv),
                  q_spec(dv), q_spec(1), q_spec(1)],
        out_specs=[q_spec(dn), q_spec(dr)],
        out_shape=[jax.ShapeDtypeStruct(qn.shape, qn.dtype),
                   jax.ShapeDtypeStruct(qr.shape, qr.dtype)],
        scratch_shapes=[pltpu.VMEM((block_q, dn), jnp.float32),
                        pltpu.VMEM((block_q, dr), jnp.float32)],
        compiler_params=semantics, name="mla_flash_dq",
        interpret=_interpret())(qn, qr, kn, kr, v, do, lse, delta)

    # dk/dv pass: per-query statistics as rows [BH, 1, T] (the same
    # bytes as the columns above)
    def qi(j, i):
        return jnp.maximum(i, (j * block_k) // block_q) if causal else i

    def qs_spec(d):
        return pl.BlockSpec((1, block_q, d),
                            lambda b, j, i: (b, qi(j, i), 0))

    def ks_spec(d):
        return pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0))
    row = pl.BlockSpec((1, 1, block_q), lambda b, j, i: (b, 0, qi(j, i)))
    dkn, dkr, dvv = pl.pallas_call(
        functools.partial(_mla_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k),
        grid=(bh, t // block_k, t // block_q),
        in_specs=[qs_spec(dn), qs_spec(dr), ks_spec(dn),
                  pl.BlockSpec((1, block_k, dr),
                               lambda b, j, i: (b // heads_per_rope, j, 0)),
                  ks_spec(dv), qs_spec(dv), row, row],
        out_specs=[ks_spec(dn), ks_spec(dr), ks_spec(dv)],
        out_shape=[jax.ShapeDtypeStruct(kn.shape, kn.dtype),
                   jax.ShapeDtypeStruct((bh, t, dr), jnp.float32),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, dn), jnp.float32),
                        pltpu.VMEM((block_k, dr), jnp.float32),
                        pltpu.VMEM((block_k, dv), jnp.float32)],
        compiler_params=semantics, name="mla_flash_dkv",
        interpret=_interpret())(qn, qr, kn, kr, v, do,
                                lse.reshape(bh, 1, t),
                                delta.reshape(bh, 1, t))
    return dqn, dqr, dkn, dkr, dvv


def mla_attention_reference(q_nope, q_rope, k_nope, k_rope, v, causal=True,
                            scale=None):
    """The same attention with explicit [T, T] scores, head-major
    [B, H, T, D] operands (``k_rope`` [B, T, dr], one a token, or
    [B, H, T, dr]): the oracle of the kernels and the path of a T they
    cannot tile."""
    scale = scale if scale is not None else 1.0 / math.sqrt(
        q_nope.shape[-1] + q_rope.shape[-1])
    rope = "btd" if k_rope.ndim == 3 else "bhtd"
    s = (jnp.einsum("bhqd,bhkd->bhqk", q_nope, k_nope,
                    preferred_element_type=jnp.float32)
         + jnp.einsum("bhqd,%s->bhqk" % rope.replace("t", "k"), q_rope,
                      k_rope, preferred_element_type=jnp.float32)) * scale
    if causal:
        t = s.shape[-1]
        s = jnp.where(jnp.arange(t)[None, :] > jnp.arange(t)[:, None],
                      _NEG_INF, s)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v,
                      preferred_element_type=jnp.float32).astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _mla_flash(q_nope, q_rope, k_nope, k_rope, v, causal, scale, block_q,
               block_k):
    return _mla_flash_fwd(q_nope, q_rope, k_nope, k_rope, v, causal, scale,
                          block_q, block_k)[0]


def _flat(x):
    return x.reshape((-1,) + x.shape[-2:])


def _mla_flash_fwd(q_nope, q_rope, k_nope, k_rope, v, causal, scale,
                   block_q, block_k):
    out, lse = _mla_fwd_bh(_flat(q_nope), _flat(q_rope), _flat(k_nope),
                           _flat(k_rope), _flat(v), scale, causal, block_q,
                           block_k)
    out = checkpoint_name(out.reshape(v.shape), MLA_SAVED_NAMES[0])
    # the statistics wait for the backward pass as [BH, T]: of [BH, T, 1],
    # as the kernels write and read them, the v5e pads the 1 to 128
    # lanes, 268 MB a block of 2 x 8,192 tokens where 2 MB are numbers
    # (PERF.md section 6, PR 31)
    lse = checkpoint_name(lse.reshape(lse.shape[:2]), MLA_SAVED_NAMES[1])
    return out, (q_nope, q_rope, k_nope, k_rope, v, out, lse)


def _mla_flash_bwd(causal, scale, block_q, block_k, res, g):
    q_nope, q_rope, k_nope, k_rope, v, out, lse = res
    dqn, dqr, dkn, dkr, dv = _mla_bwd_bh(
        _flat(q_nope), _flat(q_rope), _flat(k_nope), _flat(k_rope),
        _flat(v), _flat(out), lse.reshape(lse.shape + (1,)), _flat(g),
        scale, causal, block_q, block_k)
    dkr = dkr.reshape(q_rope.shape)
    if k_rope.ndim == 3:        # one key for all heads: their sum
        dkr = dkr.sum(axis=1)
    return (dqn.reshape(q_nope.shape), dqr.reshape(q_rope.shape),
            dkn.reshape(k_nope.shape), dkr.astype(k_rope.dtype),
            dv.reshape(v.shape))


_mla_flash.defvjp(_mla_flash_fwd, _mla_flash_bwd)


def mla_flash_attention(q_nope, q_rope, k_nope, k_rope, v, causal=True,
                        scale=None, block_q=MLA_BLOCK_Q,
                        block_k=MLA_BLOCK_K):
    """Latent attention, head-major: ``q_nope``/``k_nope`` [B, H, T, dn],
    ``q_rope`` [B, H, T, dr], ``k_rope`` [B, T, dr] (one rope key a
    token, read by every head) or [B, H, T, dr], ``v`` [B, H, T, dv] ->
    [B, H, T, dv].  Scores are ``(q_nope.k_nope + q_rope.k_rope) *
    scale`` (default ``1 / sqrt(dn + dr)``).  Falls back to
    :func:`mla_attention_reference` for a T that cannot be tiled."""
    scale = scale if scale is not None else 1.0 / math.sqrt(
        q_nope.shape[-1] + q_rope.shape[-1])
    blocks = _blocks(q_nope.shape[2], block_q, block_k)
    if blocks is None:
        _warn_fallback(q_nope.shape[2])
        return mla_attention_reference(q_nope, q_rope, k_nope, k_rope, v,
                                       causal, scale)
    return _mla_flash(q_nope, q_rope, k_nope, k_rope, v, causal, scale,
                      *blocks)
