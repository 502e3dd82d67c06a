"""Ring attention: sequence/context parallelism over the ``seq`` axis.

The reference predates attention models, but its capability surface —
"scale the model/sequence beyond one device" — maps on TPU to sequence
parallelism: shard the sequence over a mesh axis and rotate K/V blocks
around the ICI ring (`lax.ppermute`), accumulating attention with the
online-softmax (flash) recurrence so no device ever materializes the
full [T, T] score matrix or the full K/V.  This is the standard ring
attention construction (Liu et al. 2023; see PAPERS.md) expressed the
JAX-native way: `shard_map` over a Mesh axis + in-program collectives,
composable with the ``data`` axis for DP x SP meshes.

Numerics: block products in f32 (``preferred_element_type``), the
running max/denominator recurrence is exactly flash attention's, so the
result matches single-device softmax attention to f32 tolerance
(asserted in tests/test_ring_attention.py).
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax


def attention_reference(q, k, v, causal=False, scale=None,
                        window=None):
    """Plain single-device softmax attention, [B, T, H, D] layout —
    the parity oracle (and the small-model fallback).  ``k`` and ``v``
    may have fewer heads than ``q`` (grouped-query attention: query head
    ``h`` reads key-value head ``h // (H_q / H_kv)``).  ``window``
    (requires ``causal``): sliding-window attention — position i sees
    keys in (i - window, i], the Mistral-style band."""
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    if window is not None and not causal:
        raise ValueError("window requires causal=True")
    if window is not None and window < 1:
        raise ValueError("window must be >= 1, got %r" % (window,))
    # the query heads of a group as rows of their key-value head (one
    # head count: groups of 1)
    b, t, h, d = q.shape
    kv = k.shape[2]
    s = jnp.einsum("bqngd,bknd->bngqk", q.reshape(b, t, kv, h // kv, d),
                   k) * scale
    if causal:
        tq, tk = s.shape[-2], s.shape[-1]
        rows = jnp.arange(tq)[:, None]
        cols = jnp.arange(tk)[None, :]
        mask = cols > rows
        if window is not None:
            mask = mask | (cols <= rows - window)
        s = jnp.where(mask, -jnp.inf, s)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bngqk,bknd->bqngd", p, v).reshape(b, t, h, -1)


def _ring_attention_local(q, k, v, axis_name, causal, scale,
                          vary_axes=None):
    """Per-shard body: local Q stays put, K/V blocks ride the ring.

    q/k/v: [B, T_local, H, D] (this device's sequence chunk).  Also
    reused (inside a caller-owned shard_map binding more axes) by
    znicz.samples.flagship — keep the signature in sync with it."""
    n_dev = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)
    b, t_local, h, d = q.shape
    q32 = q.astype(jnp.float32)

    # flash accumulators: running max m, denominator l, output acc
    m0 = jnp.full((b, h, t_local), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, h, t_local), jnp.float32)
    acc0 = jnp.zeros((b, t_local, h, d), jnp.float32)
    # fresh zeros are unvarying over the mesh axis; the loop carry mixes
    # them with shard-varying data, so mark them varying up front (the
    # new shard_map type system requires carry in/out types to agree)
    m0, l0, acc0 = lax.pcast((m0, l0, acc0),
                             vary_axes or (axis_name,), to="varying")
    q_pos = my_idx * t_local + jnp.arange(t_local)

    def step(i, carry):
        k_blk, v_blk, m, l, acc = carry
        src = (my_idx - i) % n_dev  # which shard this K/V block came from
        s = jnp.einsum("bqhd,bkhd->bhqk", q32,
                       k_blk.astype(jnp.float32),
                       preferred_element_type=jnp.float32) * scale
        if causal:
            k_pos = src * t_local + jnp.arange(t_local)
            mask = k_pos[None, :] > q_pos[:, None]
            s = jnp.where(mask[None, None], -jnp.inf, s)
        blk_max = jnp.max(s, axis=-1)
        new_m = jnp.maximum(m, blk_max)
        # guard: a fully-masked block keeps m at -inf; exp(-inf - -inf)
        # must be 0, not nan
        safe_m = jnp.where(jnp.isneginf(new_m), 0.0, new_m)
        alpha = jnp.where(jnp.isneginf(m), 0.0, jnp.exp(m - safe_m))
        p = jnp.exp(s - safe_m[..., None])
        p = jnp.where(jnp.isneginf(s), 0.0, p)
        l = l * alpha + p.sum(axis=-1)
        acc = acc * alpha.transpose(0, 2, 1)[..., None] + jnp.einsum(
            "bhqk,bkhd->bqhd", p, v_blk.astype(jnp.float32),
            preferred_element_type=jnp.float32)
        m = new_m
        # rotate K/V one hop around the ring (ICI neighbor exchange)
        perm = [(j, (j + 1) % n_dev) for j in range(n_dev)]
        k_blk = lax.ppermute(k_blk, axis_name, perm)
        v_blk = lax.ppermute(v_blk, axis_name, perm)
        return k_blk, v_blk, m, l, acc

    _, _, m, l, acc = lax.fori_loop(0, n_dev, step,
                                    (k, v, m0, l0, acc0))
    l = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows output 0
    out = acc / l.transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def ring_attention(q, k, v, mesh, seq_axis="seq", data_axis=None,
                   causal=False, scale=None, use_pallas=False):
    """Sequence-parallel attention over ``mesh[seq_axis]``.

    q/k/v: [B, T, H, D] with T divisible by the seq-axis size (and B by
    the data axis when given).  Returns [B, T, H, D], numerically equal
    to :func:`attention_reference` on one device.

    ``use_pallas=True`` runs each hop's block math through the Pallas
    flash kernels (ring flash attention, :mod:`znicz.flash_attention`):
    the per-hop [B, H, T_local, T_local] score tensor this module's jnp
    recurrence materializes disappears, so per-device memory stays
    O(T_local * D) — the long-context composition.  Falls back to the
    jnp recurrence when the local chunk can't tile."""
    from jax.sharding import PartitionSpec as P
    shard_map = jax.shard_map

    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    local = _ring_attention_local
    if use_pallas:
        from ..znicz.flash_attention import flash_attention_supported
        t_local = q.shape[1] // mesh.shape[seq_axis]
        if flash_attention_supported(t_local):
            local = _ring_flash_local
    spec = P(data_axis, seq_axis, None, None)
    fn = shard_map(
        functools.partial(local, axis_name=seq_axis,
                          causal=causal, scale=scale,
                          vary_axes=(seq_axis,) + (
                              (data_axis,) if data_axis else ())),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    return fn(q, k, v)


# -- ring flash attention ----------------------------------------------------
#
# The ring recurrence above is already flash-attention math ACROSS hops;
# ring flash attention additionally makes each hop's block computation a
# Pallas flash kernel call, so nothing quadratic in T_local exists
# either.  Gradients cannot flow through raw pallas_call, so the WHOLE
# per-shard ring is one custom_vjp: the forward saves the global
# logsumexp, and the backward is a second ring pass — dk/dv accumulators
# rotate along with their K/V blocks and arrive home after n hops (no
# psum needed), exactly the published ring-flash construction (Liu et
# al. 2023), built from this repo's own flash kernel pair.


def _hop_mode(src, my_idx, causal):
    """0 = block fully visible, 1 = diagonal (causal mask), 2 = skip."""
    if not causal:
        return jnp.int32(0)
    return jnp.where(src < my_idx, 0, jnp.where(src == my_idx, 1, 2))


def _ring_flash_fwd_pass(q, k, v, axis_name, causal, scale,
                         vary_axes=None):
    from ..znicz.flash_attention import (DEFAULT_BLOCK_K,
                                         DEFAULT_BLOCK_Q, _NEG_INF,
                                         _blocks, _flash_fwd_bh,
                                         _from_bh, _to_bh)
    n_dev = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)
    b, t_local, h, d = q.shape
    bq, bk = _blocks(t_local, DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K)
    q_bh = _to_bh(q)

    vma = frozenset(vary_axes or (axis_name,))

    def attend(causal_flag):
        def run(k_blk, v_blk):
            out_bh, lse = _flash_fwd_bh(
                q_bh, _to_bh(k_blk), _to_bh(v_blk), scale, causal_flag,
                bq, bk, vma=vma)
            # f32 like the skip branch: lax.switch branches must agree
            return (_from_bh(out_bh, b, h).astype(jnp.float32),
                    lse.reshape(b, h, t_local))
        return run

    def skip(k_blk, v_blk):
        return lax.pcast(
            (jnp.zeros((b, t_local, h, d), jnp.float32),
             jnp.full((b, h, t_local), _NEG_INF, jnp.float32)),
            tuple(vma), to="varying")

    def step(i, carry):
        k_blk, v_blk, out, lse = carry
        src = (my_idx - i) % n_dev
        o_blk, lse_blk = lax.switch(
            _hop_mode(src, my_idx, causal),
            [attend(False), attend(True), skip], k_blk, v_blk)
        new_lse = jnp.logaddexp(lse, lse_blk)
        safe = jnp.where(jnp.isneginf(new_lse), 0.0, new_lse)
        wa = jnp.where(jnp.isneginf(lse), 0.0, jnp.exp(lse - safe))
        wb = jnp.where(jnp.isneginf(lse_blk), 0.0,
                       jnp.exp(lse_blk - safe))
        # weights are [B, H, Tl]; out is [B, Tl, H, D]
        out = (out * wa.transpose(0, 2, 1)[..., None] +
               o_blk * wb.transpose(0, 2, 1)[..., None])
        perm = [(j, (j + 1) % n_dev) for j in range(n_dev)]
        return (lax.ppermute(k_blk, axis_name, perm),
                lax.ppermute(v_blk, axis_name, perm), out, new_lse)

    out0 = jnp.zeros((b, t_local, h, d), jnp.float32)
    lse0 = jnp.full((b, h, t_local), _NEG_INF, jnp.float32)
    # fresh zeros are unvarying; the carry mixes them with shard-varying
    # data (same pcast dance as _ring_attention_local:59)
    out0, lse0 = lax.pcast((out0, lse0), vary_axes or (axis_name,),
                           to="varying")
    _, _, out, lse = lax.fori_loop(
        0, n_dev, step, (k, v, out0, lse0))
    return out.astype(q.dtype), lse



def _ring_flash_local(q, k, v, axis_name, causal, scale,
                      vary_axes=None):
    """Per-shard ring flash attention (signature-compatible with
    :func:`_ring_attention_local`)."""

    @functools.partial(jax.custom_vjp, nondiff_argnums=())
    def inner(q, k, v):
        out, _ = _ring_flash_fwd_pass(q, k, v, axis_name, causal, scale,
                                      vary_axes)
        return out

    def inner_fwd(q, k, v):
        out, lse = _ring_flash_fwd_pass(q, k, v, axis_name, causal,
                                        scale, vary_axes)
        return out, (q, k, v, out, lse)

    def inner_bwd(res, g):
        from ..znicz.flash_attention import (DEFAULT_BLOCK_K,
                                             DEFAULT_BLOCK_Q,
                                             _STAT_LANES, _blocks,
                                             _flash_bwd_bh, _from_bh,
                                             _to_bh)
        q, k, v, out, lse = res
        n_dev = lax.psum(1, axis_name)
        my_idx = lax.axis_index(axis_name)
        b, t_local, h, d = q.shape
        bq, bk = _blocks(t_local, DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K)
        q_bh, out_bh, g_bh = _to_bh(q), _to_bh(out), _to_bh(g)
        # lse/delta are hop-invariant: lane-broadcast them ONCE here,
        # not inside every hop's _flash_bwd_bh call
        lse_bh = jnp.broadcast_to(
            lse.reshape(b * h, t_local)[..., None],
            (b * h, t_local, _STAT_LANES))
        delta_bh = jnp.broadcast_to(
            jnp.sum(g_bh.astype(jnp.float32) *
                    out_bh.astype(jnp.float32), axis=-1)[..., None],
            (b * h, t_local, _STAT_LANES))

        vma = frozenset(vary_axes or (axis_name,))

        def bwd(causal_flag):
            def run(k_blk, v_blk):
                dq_bh, dk_bh, dv_bh = _flash_bwd_bh(
                    q_bh, _to_bh(k_blk), _to_bh(v_blk), out_bh, lse_bh,
                    g_bh, scale, causal_flag, bq, bk, vma=vma,
                    delta=delta_bh)
                return (_from_bh(dq_bh, b, h).astype(jnp.float32),
                        _from_bh(dk_bh, b, h).astype(jnp.float32),
                        _from_bh(dv_bh, b, h).astype(jnp.float32))
            return run

        def skip(k_blk, v_blk):
            z = jnp.zeros((b, t_local, h, d), jnp.float32)
            z = lax.pcast(z, tuple(vma), to="varying")
            return z, z, z

        def step(i, carry):
            k_blk, v_blk, dk_blk, dv_blk, dq = carry
            src = (my_idx - i) % n_dev
            dq_c, dk_c, dv_c = lax.switch(
                _hop_mode(src, my_idx, causal),
                [bwd(False), bwd(True), skip], k_blk, v_blk)
            perm = [(j, (j + 1) % n_dev) for j in range(n_dev)]
            # dk/dv accumulators RIDE THE RING with their blocks: after
            # n hops block b has visited every device and is home again
            return (lax.ppermute(k_blk, axis_name, perm),
                    lax.ppermute(v_blk, axis_name, perm),
                    lax.ppermute(dk_blk + dk_c, axis_name, perm),
                    lax.ppermute(dv_blk + dv_c, axis_name, perm),
                    dq + dq_c)

        z0 = jnp.zeros((b, t_local, h, d), jnp.float32)
        z0 = lax.pcast(z0, vary_axes or (axis_name,), to="varying")
        _, _, dk, dv, dq = lax.fori_loop(
            0, n_dev, step, (k, v, z0, z0, z0))
        return (dq.astype(q.dtype), dk.astype(k.dtype),
                dv.astype(v.dtype))

    inner.defvjp(inner_fwd, inner_bwd)
    return inner(q, k, v)
