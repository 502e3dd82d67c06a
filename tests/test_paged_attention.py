"""Ragged paged attention: kernel vs dense reference (ISSUE 6).

The contract under test (acceptance):
- the Pallas kernel (CPU interpret mode here; compiled on TPU) is
  BITWISE equal to the pure-jnp dense reference at equal lengths — the
  kernel's softmax is dense over a VMEM score row, not an online
  rescale, so there is no recurrence drift to tolerate;
- ragged batches (every row a different length, including block
  boundaries, single tokens and empty padding rows) match to numerical
  tolerance — and, with the reference's reductions staged like the
  kernel's sweeps, bitwise in practice;
- the reference itself is anchored against a float64 numpy softmax
  oracle, so kernel and reference can't be wrong together;
- the page-table indirection really is an indirection: permuting the
  physical placement of the same logical sequence never changes the
  result.

These run in the default tier-1 set so ``JAX_PLATFORMS=cpu`` exercises
the kernel (interpret mode) on every run.
"""

import math

import numpy
import pytest

import jax.numpy as jnp

from veles_tpu.znicz.paged_attention import (paged_attention,
                                             paged_attention_reference,
                                             required_blocks)

B, H, D = 4, 2, 8
BLOCK, NB, NPOOL = 4, 6, 32
T_MAX = BLOCK * NB


def _setup(seed=0, npool=NPOOL, nb=NB, permute=None):
    rng = numpy.random.RandomState(seed)
    q = jnp.asarray(rng.standard_normal((B, H, D)), jnp.float32)
    k_pool = jnp.asarray(rng.standard_normal((npool, BLOCK, H, D)),
                         jnp.float32)
    v_pool = jnp.asarray(rng.standard_normal((npool, BLOCK, H, D)),
                         jnp.float32)
    ids = numpy.arange(1, B * nb + 1)
    if permute is not None:
        ids = permute(ids)
    table = jnp.asarray(ids.reshape(B, nb), jnp.int32)
    return q, k_pool, v_pool, table


def _naive_f64(q, k_pool, v_pool, table, lengths):
    """Fully independent float64 numpy oracle."""
    q = numpy.asarray(q, numpy.float64)
    kp = numpy.asarray(k_pool, numpy.float64)
    vp = numpy.asarray(v_pool, numpy.float64)
    table = numpy.asarray(table)
    out = numpy.zeros_like(q)
    scale = 1.0 / math.sqrt(q.shape[-1])
    for b in range(q.shape[0]):
        n = int(lengths[b])
        if n == 0:
            continue
        k = kp[table[b]].reshape(-1, H, D)[:n]      # [n, H, D]
        v = vp[table[b]].reshape(-1, H, D)[:n]
        for h in range(H):
            s = (k[:, h] @ (q[b, h] * scale))
            p = numpy.exp(s - s.max())
            out[b, h] = (p[:, None] * v[:, h]).sum(0) / p.sum()
    return out


def test_equal_lengths_match_reference_to_the_last_places():
    """The kernel's contract with its dense reference is a tolerance
    (its docstring; ROADMAP D13): both stage their sums alike, but the
    compiler may still associate or fuse them differently, and under
    the present JAX the interpreted kernel differs from the reference
    by 8.9e-8 on outputs of 0.8, a unit or two in the last place.  The
    bound is the ragged test's: a few last places of O(1) outputs, far
    below what one wrong, skipped or doubled block would move."""
    q, kp, vp, table = _setup()
    lengths = jnp.full((B,), T_MAX, jnp.int32)
    out = paged_attention(q, kp, vp, table, lengths)
    ref = paged_attention_reference(q, kp, vp, table, lengths)
    assert numpy.allclose(numpy.asarray(out), numpy.asarray(ref),
                          atol=1e-6, rtol=1e-6)


@pytest.mark.parametrize("lengths", [
    (1, 2, 3, 5),                          # sub-block raggedness
    (BLOCK, 2 * BLOCK, 3 * BLOCK, T_MAX),  # exact block boundaries
    (BLOCK - 1, BLOCK + 1, T_MAX - 1, 1),  # boundary straddles
    (0, 1, T_MAX, 7),                      # empty padding row mixed in
])
def test_ragged_matches_reference(lengths):
    q, kp, vp, table = _setup(seed=3)
    lv = jnp.asarray(lengths, jnp.int32)
    out = numpy.asarray(paged_attention(q, kp, vp, table, lv))
    ref = numpy.asarray(paged_attention_reference(q, kp, vp, table, lv))
    assert numpy.allclose(out, ref, atol=1e-6, rtol=1e-6)
    # empty rows must be exactly zero (padding rows of the decode batch)
    for b, n in enumerate(lengths):
        if n == 0:
            assert numpy.array_equal(out[b], numpy.zeros_like(out[b]))


def test_reference_anchored_to_naive_f64():
    """The dense reference itself is right, not just kernel-consistent."""
    q, kp, vp, table = _setup(seed=5)
    lengths = (3, BLOCK, T_MAX, 11)
    lv = jnp.asarray(lengths, jnp.int32)
    ref = numpy.asarray(paged_attention_reference(q, kp, vp, table, lv))
    oracle = _naive_f64(q, kp, vp, table, lengths)
    assert numpy.allclose(ref, oracle, atol=1e-5)


def test_physical_placement_is_invisible():
    """The same logical sequences through two different physical
    layouts (fresh vs recycled/shuffled blocks) produce identical
    outputs — the paging indirection leaks nothing."""
    rng = numpy.random.RandomState(11)
    q, kp, vp, table = _setup(seed=7)
    lengths = jnp.asarray((5, 9, T_MAX, 2), jnp.int32)
    base = numpy.asarray(paged_attention(q, kp, vp, table, lengths))
    # permute physical blocks: move every sequence's data to new slots
    perm = numpy.concatenate([[0], 1 + rng.permutation(NPOOL - 1)])
    inv_kp = numpy.asarray(kp)[numpy.argsort(perm)]
    inv_vp = numpy.asarray(vp)[numpy.argsort(perm)]
    new_table = perm[numpy.asarray(table)]
    moved = numpy.asarray(paged_attention(
        q, jnp.asarray(inv_kp), jnp.asarray(inv_vp),
        jnp.asarray(new_table, numpy.int32), lengths))
    assert numpy.array_equal(base, moved)


def test_trash_block_contents_never_leak():
    """Padding table entries point at block 0; whatever garbage lives
    there must not reach any live row's output."""
    q, kp, vp, table = _setup(seed=9)
    lengths = jnp.asarray((3, 7, 12, 5), jnp.int32)
    out1 = numpy.asarray(paged_attention(q, kp, vp, table, lengths))
    kp2 = kp.at[0].set(1e9)            # poison the trash block
    vp2 = vp.at[0].set(-1e9)
    out2 = numpy.asarray(paged_attention(q, kp2, vp2, table, lengths))
    assert numpy.array_equal(out1, out2)


def test_single_block_and_single_token():
    """Smallest geometries: one block per sequence, one-token history."""
    rng = numpy.random.RandomState(13)
    q = jnp.asarray(rng.standard_normal((2, H, D)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((4, BLOCK, H, D)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((4, BLOCK, H, D)), jnp.float32)
    table = jnp.asarray([[1], [2]], jnp.int32)
    lengths = jnp.asarray([1, BLOCK], jnp.int32)
    out = numpy.asarray(paged_attention(q, kp, vp, table, lengths))
    ref = numpy.asarray(paged_attention_reference(q, kp, vp, table,
                                                  lengths))
    assert numpy.allclose(out, ref, atol=1e-6)
    # length-1: attention over one token is exactly that token's V
    assert numpy.allclose(out[0], numpy.asarray(vp)[1, 0], atol=1e-6)


def test_required_blocks():
    assert required_blocks(1, 4) == 1
    assert required_blocks(4, 4) == 1
    assert required_blocks(5, 4) == 2
    assert required_blocks(16, 4) == 4


def test_shape_validation():
    q, kp, vp, table = _setup()
    lengths = jnp.zeros((B,), jnp.int32)
    with pytest.raises(ValueError):
        paged_attention(q, kp[:, :, :1], vp[:, :, :1], table, lengths)
    with pytest.raises(ValueError):
        paged_attention(q, kp, vp[:4], table, lengths)
