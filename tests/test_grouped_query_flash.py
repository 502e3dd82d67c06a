"""``flash_attention`` with fewer key-value heads than query heads
(grouped-query attention): forward and gradients against
``attention_reference`` with each key-value head repeated for the query
heads that read it.  One head count is the group of 1 of the same
kernels.  Pallas interpret mode on the CPU; the real widths are compiled
for the described chip in ``tests/test_chip_compile.py``."""

import jax
import jax.numpy as jnp
import numpy
import pytest

from veles_tpu.parallel.ring import attention_reference
from veles_tpu.znicz import flash_attention as fa

H_Q, T, D = 8, 128, 16


def operands(h_kv, dtype=jnp.float32, seed=0, t=T):
    rng = numpy.random.RandomState(seed)

    def draw(h):
        return jnp.asarray(0.5 * rng.standard_normal((2, t, h, D)), dtype)
    return draw(H_Q), draw(h_kv), draw(h_kv)


def repeated(q, k, v, causal, window):
    group = q.shape[2] // k.shape[2]
    return attention_reference(q, jnp.repeat(k, group, axis=2),
                               jnp.repeat(v, group, axis=2),
                               causal=causal, window=window)


def weighed(attend):
    return lambda q, k, v: jnp.sum(jnp.sin(attend(q, k, v)) ** 2)


# group of 1 (one head count), of 4, of 8 = H_q (one key-value head)
@pytest.mark.parametrize("h_kv", [8, 2, 1])
@pytest.mark.parametrize("causal,window", [(False, None), (True, None),
                                           (True, 40)])
def test_forward_and_gradients_against_repeated_heads(h_kv, causal, window):
    q, k, v = operands(h_kv)

    def flash(q, k, v):
        return fa.flash_attention(q, k, v, causal, None, 64, 32, window)
    want = repeated(q, k, v, causal, window)
    numpy.testing.assert_allclose(flash(q, k, v), want, rtol=2e-5,
                                  atol=2e-5)
    got = jax.grad(weighed(flash), argnums=(0, 1, 2))(q, k, v)
    ref = jax.grad(weighed(lambda q, k, v: repeated(
        q, k, v, causal, window)), argnums=(0, 1, 2))(q, k, v)
    for g, w, name in zip(got, ref, "qkv"):
        assert g.shape == w.shape, name    # dk, dv per KEY-VALUE head
        numpy.testing.assert_allclose(g, w, rtol=5e-4, atol=5e-4,
                                      err_msg="d" + name)


# 16 query heads in groups of 1, of 8 and of 16 = H_q, inside a window
# that is no multiple of either block (and one narrower than a block):
# the K band of the forward and dq grids and the Q band that the dk/dv
# grid walks head after head within a group
@pytest.mark.parametrize("h_kv", [16, 2, 1], ids=["group1", "group8",
                                                  "groupHq"])
@pytest.mark.parametrize("window,bq,bk", [(40, 32, 32), (24, 64, 32),
                                          (72, 32, 64)])
def test_a_window_over_grouped_heads(h_kv, window, bq, bk):
    rng = numpy.random.RandomState(4)

    def draw(h):
        return jnp.asarray(0.5 * rng.standard_normal((1, 256, h, D)),
                           jnp.float32)
    q, k, v = draw(16), draw(h_kv), draw(h_kv)
    # the grids are banded at these sizes, in both directions
    assert fa._kband_size(256, bq, bk, window) < 256 // bk
    assert fa._qband_size(256, bq, bk, window) < 256 // bq

    def flash(q, k, v):
        return fa.flash_attention(q, k, v, True, None, bq, bk, window)
    want = repeated(q, k, v, True, window)
    numpy.testing.assert_allclose(flash(q, k, v), want, rtol=2e-5,
                                  atol=2e-5)
    got = jax.grad(weighed(flash), argnums=(0, 1, 2))(q, k, v)
    ref = jax.grad(weighed(lambda q, k, v: repeated(
        q, k, v, True, window)), argnums=(0, 1, 2))(q, k, v)
    for g, w, name in zip(got, ref, "qkv"):
        assert g.shape == w.shape, name    # dk, dv per KEY-VALUE head
        numpy.testing.assert_allclose(g, w, rtol=5e-4, atol=5e-4,
                                      err_msg="d" + name)


@pytest.mark.parametrize("h_kv", [2, 1])
def test_the_oracle_reads_grouped_heads_itself(h_kv):
    """``attention_reference`` given K and V at their own head count is
    itself given them repeated: the one oracle, and the kernels' path
    for a length they cannot tile."""
    q, k, v = operands(h_kv, seed=1)
    for causal, window in [(False, None), (True, None), (True, 40)]:
        numpy.testing.assert_allclose(
            attention_reference(q, k, v, causal=causal, window=window),
            repeated(q, k, v, causal, window), rtol=1e-6, atol=1e-6)
    got = jax.grad(weighed(lambda q, k, v: attention_reference(
        q, k, v, causal=True)), argnums=(0, 1, 2))(q, k, v)
    ref = jax.grad(weighed(lambda q, k, v: repeated(
        q, k, v, True, None)), argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, ref):
        assert g.shape == w.shape
        numpy.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-5)


def test_bfloat16_operands_stay_bfloat16():
    """The training path's dtype: bfloat16 in, bfloat16 out, gradients
    too."""
    q, k, v = operands(1, jnp.bfloat16, seed=2)
    got = fa.flash_attention(q, k, v, True, None, 64, 64)
    assert got.dtype == jnp.bfloat16
    want = repeated(*(x.astype(jnp.float32) for x in (q, k, v)), True, None)
    numpy.testing.assert_allclose(got.astype(jnp.float32), want, atol=2e-2)
    grads = jax.grad(lambda *a: fa.flash_attention(
        *a, True, None, 64, 64).astype(jnp.float32).sum(),
        argnums=(0, 1, 2))(q, k, v)
    assert [g.dtype for g in grads] == [jnp.bfloat16] * 3
    assert [g.shape for g in grads] == [q.shape, k.shape, v.shape]


def test_a_length_the_kernels_cannot_tile_takes_the_oracle():
    q, k, v = operands(2, seed=3, t=7)
    got = fa.flash_attention(q, k, v, True, None, 4, 4)
    numpy.testing.assert_allclose(got, repeated(q, k, v, True, None),
                                  rtol=1e-6, atol=1e-6)
    grads = jax.grad(weighed(lambda q, k, v: fa.flash_attention(
        q, k, v, True, None, 4, 4)), argnums=(1,))(q, k, v)
    assert grads[0].shape == k.shape


def test_heads_that_do_not_divide_are_refused():
    q, k, v = operands(3)
    with pytest.raises(ValueError, match="8 query heads cannot share 3"):
        fa.flash_attention(q, k, v, True)


def test_the_three_calls_carry_names_a_trace_can_find():
    q, k, v = operands(2)
    jaxpr = jax.make_jaxpr(jax.grad(weighed(lambda q, k, v:
                           fa.flash_attention(q, k, v, True, None, 64, 64)),
                           argnums=(0, 1, 2)))(q, k, v)
    from veles_tpu.znicz import fused
    names = sorted(eqn.params["name"]
                   for eqn in fused.jaxpr_equations(jaxpr.jaxpr)
                   if eqn.primitive.name == "pallas_call")
    assert names == ["gqa_flash_dkv", "gqa_flash_dq", "gqa_flash_fwd"]
    # neither family's reader matches the other's events
    assert not any("mla_flash" in n for n in names)
    # the same three kernels called with a window carry names of their
    # own, which hold none of the names above and which none of them hold
    jaxpr = jax.make_jaxpr(jax.grad(weighed(lambda q, k, v:
                           fa.flash_attention(q, k, v, True, None, 64, 64,
                                              40)),
                           argnums=(0, 1, 2)))(q, k, v)
    windowed = sorted(eqn.params["name"]
                      for eqn in fused.jaxpr_equations(jaxpr.jaxpr)
                      if eqn.primitive.name == "pallas_call")
    assert windowed == ["gqa_window_flash_dkv", "gqa_window_flash_dq",
                        "gqa_window_flash_fwd"]
    assert not any(a in b or b in a for a in names for b in windowed)


def test_narrow_heads_take_larger_blocks_by_default():
    """Where no caller and no tuning record says otherwise: the measured
    winner for heads of 128 and narrower, and for heads of 128 inside a
    window of 1,024 or wider; the old pair for wider heads and for the
    windows nobody measured."""
    old = (fa.DEFAULT_BLOCK_Q, fa.DEFAULT_BLOCK_K)
    assert fa.default_blocks(64) == (1024, 1024) == fa.default_blocks(16)
    assert fa.default_blocks(128) == (1024, 1024)
    assert fa.default_blocks(128, window=1024) == (1024, 1024) \
        == fa.default_blocks(128, window=4096)
    assert fa.default_blocks(256) == old == fa.default_blocks(256, 1024)
    # float32 heads of 128 do not fit the dk/dv kernel at such blocks
    assert fa.default_blocks(128, itemsize=4) == old \
        == fa.default_blocks(128, 1024, itemsize=4)
    assert fa.default_blocks(64, itemsize=4) == (1024, 1024)
    assert fa.default_blocks(128, window=512) == old
    assert fa.default_blocks(64, window=512) == old \
        == fa.default_blocks(64, window=1024)
    # a short sequence is one block
    q, k, v = operands(2)
    numpy.testing.assert_allclose(
        fa.flash_attention(q, k, v, causal=True),
        repeated(q, k, v, True, None), rtol=2e-5, atol=2e-5)
