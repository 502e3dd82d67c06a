"""The transformer units' pieces by hand and against oracles, on the
CPU: latent flash attention (query-key and value heads of different
sizes, a rope key shared by the heads) in interpret mode against
``attention_reference``; interleaved RoPE, RMSNorm and AdamW against
hand arithmetic; the grouped product and the dispatch / combine pair of
the expert layer; the head's blocked token loss."""

import jax
import jax.numpy as jnp
import numpy
import pytest

from veles_tpu.parallel.ring import attention_reference
from veles_tpu.znicz import gemm, solvers, transformer
from veles_tpu.znicz.flash_attention import (mla_attention_reference,
                                             mla_flash_attention)


def latent_operands(shared, t=128, b=2, h=4, dn=16, dr=8, dv=16):
    ks = jax.random.split(jax.random.key(0), 5)
    return (jax.random.normal(ks[0], (b, h, t, dn)),
            jax.random.normal(ks[1], (b, h, t, dr)),
            jax.random.normal(ks[2], (b, h, t, dn)),
            jax.random.normal(ks[3], (b, t, dr) if shared
                              else (b, h, t, dr)),
            jax.random.normal(ks[4], (b, h, t, dv)))


def oracle(q_nope, q_rope, k_nope, k_rope, v, causal):
    """``attention_reference`` on the concatenated 24-wide query-key
    heads and the 16-wide value heads, [B, T, H, D] layout."""
    if k_rope.ndim == 3:
        k_rope = jnp.broadcast_to(k_rope[:, None], q_rope.shape)
    q = jnp.concatenate([q_nope, q_rope], -1).transpose(0, 2, 1, 3)
    k = jnp.concatenate([k_nope, k_rope], -1).transpose(0, 2, 1, 3)
    out = attention_reference(q, k, v.transpose(0, 2, 1, 3), causal=causal)
    return out.transpose(0, 2, 1, 3)


@pytest.mark.parametrize("shared", [True, False])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("blocks", [(32, 32), (32, 64), (64, 32)])
def test_latent_flash_attention_against_the_oracle(shared, causal, blocks):
    operands = latent_operands(shared)
    weight = jnp.cos(jnp.arange(16.0))

    def flash(*a):
        return mla_flash_attention(*a, causal=causal, block_q=blocks[0],
                                   block_k=blocks[1])
    got, want = flash(*operands), oracle(*operands, causal)
    assert got.shape == (2, 4, 128, 16)
    assert float(jnp.abs(got - want).max()) < 2e-5
    assert float(jnp.abs(mla_attention_reference(
        *operands, causal=causal) - want).max()) < 2e-5
    grads = jax.grad(lambda *a: (flash(*a) * weight).sum(),
                     argnums=(0, 1, 2, 3, 4))(*operands)
    wants = jax.grad(lambda *a: (oracle(*a, causal) * weight).sum(),
                     argnums=(0, 1, 2, 3, 4))(*operands)
    for g, w in zip(grads, wants):
        assert g.shape == w.shape
        assert float(jnp.abs(g - w).max()) < 1e-4 * float(
            jnp.abs(w).max() + 1)


def test_latent_flash_attention_bfloat16_and_untileable_lengths():
    operands = [a.astype(jnp.bfloat16) for a in latent_operands(True)]
    got = mla_flash_attention(*operands, block_q=64, block_k=64)
    want = oracle(*[a.astype(jnp.float32) for a in operands], True)
    assert got.dtype == jnp.bfloat16
    assert float(jnp.abs(got.astype(jnp.float32) - want).max()) < 5e-2
    # T = 24 has no block of 32 or more: the explicit-score path
    short = latent_operands(True, t=24)
    assert float(jnp.abs(mla_flash_attention(*short)
                         - oracle(*short, True)).max()) < 2e-5


def test_interleaved_rope_by_hand():
    x = jnp.asarray([[1.0, 2.0, 3.0, 4.0], [5.0, 6.0, 7.0, 8.0],
                     [0.5, -1.0, 2.0, 0.0]])
    theta = 100.0
    got = numpy.asarray(transformer.rope_interleaved(x, theta))
    want = numpy.zeros((3, 4))
    for pos in range(3):
        for i in range(2):                  # the pair (2i, 2i + 1)
            angle = pos * theta ** (-2 * i / 4)
            a, b = float(x[pos, 2 * i]), float(x[pos, 2 * i + 1])
            want[pos, 2 * i] = a * numpy.cos(angle) - b * numpy.sin(angle)
            want[pos, 2 * i + 1] = a * numpy.sin(angle) \
                + b * numpy.cos(angle)
    assert numpy.allclose(got, want, atol=1e-5)
    assert numpy.allclose(got[0], x[0])     # position 0 does not rotate
    # a rotation: norms of the pairs are kept
    assert numpy.allclose((got ** 2).reshape(3, 2, 2).sum(-1),
                          (numpy.asarray(x) ** 2).reshape(3, 2, 2).sum(-1),
                          atol=1e-4)


def test_rms_norm_by_hand():
    x = jnp.asarray([[3.0, 4.0], [0.0, 2.0]])
    w = jnp.asarray([2.0, 0.5])
    got = numpy.asarray(transformer.rms_norm(x, w, 1e-6))
    want = numpy.asarray(x) / numpy.sqrt(
        (numpy.asarray(x) ** 2).mean(-1, keepdims=True) + 1e-6) * [2.0, 0.5]
    assert numpy.allclose(got, want, atol=1e-6)


@pytest.mark.parametrize("xp", [numpy, jnp], ids=["numpy", "jax"])
def test_adamw_by_hand(xp):
    solver = solvers.factory("adamw", beta1=0.9, beta2=0.95, epsilon=1e-8,
                             weight_decay=0.1)
    lr = 3e-4
    for w0 in (numpy.asarray([[1.0, -2.0], [0.5, 4.0]], numpy.float32),
               numpy.asarray([1.0, -2.0, 0.5], numpy.float32)):
        rng = numpy.random.default_rng(1)
        w, state = xp.asarray(w0), solver.init(xp.asarray(w0), xp)
        m = v = numpy.zeros_like(w0, numpy.float64)
        want = w0.astype(numpy.float64)
        for t in (1, 2, 3):
            g = rng.standard_normal(w0.shape).astype(numpy.float32)
            delta, state = solver.update(xp.asarray(g), w, state, lr, xp)
            w = w + delta
            m = 0.9 * m + 0.1 * g
            v = 0.95 * v + 0.05 * g.astype(numpy.float64) ** 2
            step = (m / (1 - 0.9 ** t)) / (numpy.sqrt(v / (1 - 0.95 ** t))
                                           + 1e-8)
            if w0.ndim >= 2:                # decay on matrices only
                step = step + 0.1 * want
            want = want - lr * step
            assert numpy.allclose(numpy.asarray(w), want, rtol=2e-5,
                                  atol=1e-7)
        assert int(state[2]) == 3
        assert numpy.allclose(numpy.asarray(state[0]), m, rtol=1e-5)
        assert numpy.allclose(numpy.asarray(state[1]), v, rtol=1e-5)
    # a zero gradient moves nothing that does not decay (a buffer)
    delta, _ = solver.update(xp.zeros(3), xp.ones(3), solver.init(
        xp.ones(3), xp), lr, xp)
    assert not numpy.asarray(delta).any()


@pytest.mark.parametrize("sizes, shape", [
    pytest.param([5, 0, 9, 2], (16, 24, 40), id="sizes0"),
    pytest.param([0, 0, 0, 0], (16, 24, 40), id="sizes1"),
    pytest.param([16, 0, 0, 0], (16, 24, 40), id="sizes2"),
    pytest.param([3, 3, 3, 3], (16, 24, 40), id="sizes3"),
    # wide enough that every kernel runs several tiles of K or N
    # (test_grouped_matmul_tiles_split_exactly), over two row tiles
    pytest.param([300, 0, 500, 100], (1024, 1024, 2048), id="tiled"),
    pytest.param([1024, 0, 0, 0], (1024, 1024, 2048), id="tiled-one")])
def test_grouped_matmul_against_a_loop(sizes, shape):
    m, k, n = shape
    lhs = jax.random.normal(jax.random.key(1), (m, k)) * (24 / k) ** 0.5
    rhs = jax.random.normal(jax.random.key(2), (len(sizes), k, n))
    sizes = jnp.asarray(sizes, jnp.int32)
    filled = (jnp.arange(m) < sizes.sum())[:, None]
    group = jnp.searchsorted(jnp.cumsum(sizes), jnp.arange(m),
                             side="right").clip(0, len(sizes) - 1)

    def loop(lhs, rhs):
        return sum(jnp.where(filled & (group == g)[:, None],
                             jnp.dot(lhs, rhs[g], precision="highest"), 0)
                   for g in range(len(sizes)))

    def ours(lhs, rhs):
        return jnp.where(filled, gemm.grouped_matmul(lhs, rhs, sizes), 0)
    if m > 512:
        for kk, nn in ((k, n), (n, k)):
            _, tk, tn = gemm.grouped_matmul_tiles(m, kk, nn, itemsize=4)
            assert tk < kk or tn < nn
    assert numpy.allclose(ours(lhs, rhs), loop(lhs, rhs), atol=1e-4)
    weight = jnp.sin(jnp.arange(m * n, dtype=jnp.float32)).reshape(m, n)
    got = jax.grad(lambda a, b: (ours(a, b) * weight).sum(),
                   argnums=(0, 1))(lhs, rhs)
    want = jax.grad(lambda a, b: (loop(a, b) * weight).sum(),
                    argnums=(0, 1))(lhs, rhs)
    for g, w in zip(got, want):
        g = jnp.where(filled, g, 0) if g.shape == lhs.shape else g
        assert numpy.allclose(g, w, atol=1e-4)


#: the decoder cells' grouped products: (rows in the row buffer, K, N)
#: and the tiles (tk, tn) of the forward ``gmm`` and ``tgmm``, and of the
#: rows' gradient, which contracts N into K
_GROUPED = {"kanana_gate_up": ((24576, 2048, 1536), (1024, 768), (768, 1024)),
            "kanana_down": ((24576, 768, 2048), (768, 1024), (1024, 768)),
            "lfm2_gate_up": ((16384, 2048, 3072), (1024, 1024), (1024, 1024)),
            "lfm2_down": ((16384, 1536, 2048), (768, 1024), (1024, 768)),
            "mellum_gate_up": ((65536, 2304, 1792), (1152, 896), (896, 1152)),
            "mellum_down": ((65536, 896, 2304), (896, 1152), (1152, 896))}


@pytest.mark.parametrize("kernel", ["gmm", "gmm_transposed", "tgmm"])
@pytest.mark.parametrize("product", list(_GROUPED))
def test_grouped_matmul_tiles_split_exactly(product, kernel):
    """The tiles megablox gets for each of a product's three kernels:
    the forward ``gmm`` and ``tgmm`` (the weights' gradient) see the
    product's (m, K, N), the rows' gradient (``gmm`` over the transposed
    weights) sees (m, N, K).  Every tile divides its dimension, so no
    kernel runs a masked remainder tile; it is a multiple of 128 or the
    whole dimension; the VMEM of both kernels that could receive the
    triple, double-buffered bfloat16 operands and output and the float32
    accumulator, is within the budget; and they are the tiles measured
    on the chip (PERF.md section 6)."""
    (m, k, n), forward, transposed = _GROUPED[product]
    if kernel == "gmm_transposed":
        k, n = n, k
    tm, tk, tn = gemm.grouped_matmul_tiles(m, k, n)
    for tile, dim in ((tm, m), (tk, k), (tn, n)):
        assert dim % tile == 0
        assert tile % 128 == 0 or tile == dim
    assert tm == transformer.ROW_TILE
    gmm = 2 * 2 * (tm * tk + tk * tn + tm * tn) + 4 * tm * tn
    tgmm = 2 * 2 * (tm * tk + tm * tn + tk * tn) + 4 * tk * tn
    assert max(gmm, tgmm) <= gemm.GROUPED_VMEM_BUDGET
    assert (tk, tn) == (transposed if kernel == "gmm_transposed"
                        else forward)


def a_sort(tokens=6, k=3, held=7):
    """(src, pos, here) of a sort of ``tokens * k`` choices of which the
    ``held`` first sorted rows are on a held expert."""
    src = jax.random.permutation(jax.random.key(4), tokens * k)
    pos = jnp.argsort(src).reshape(tokens, k)
    return src, pos, pos < held


@pytest.mark.parametrize("rows", [18, 12])
def test_dispatch_is_a_take_and_its_gradient_a_segment_sum(rows):
    src, pos, here = a_sort()
    x = jax.random.normal(jax.random.key(3), (6, 5))
    weight = jax.random.normal(jax.random.key(5), (rows, 5))
    # the grouped product hands back no cotangent for rows past the last
    # group: poison them
    poisoned = jnp.where((jnp.arange(rows) < 7)[:, None], weight, jnp.nan)
    assert numpy.array_equal(
        transformer._dispatch(x, src[:rows], pos, here),
        jnp.take(x, src[:rows] // 3, axis=0))
    got = jax.grad(lambda a: (transformer._dispatch(
        a, src[:rows], pos, here) * poisoned).sum())(x)
    want = jax.ops.segment_sum(weight[:7], src[:7] // 3, num_segments=6)
    assert numpy.allclose(got, want, atol=1e-5)
    # and against a plain take's gradient, every row held
    got = jax.grad(lambda a: (transformer._dispatch(
        a, src, pos, pos >= 0) * jnp.resize(weight, (18, 5))).sum())(x)
    want = jax.grad(lambda a: (jnp.take(a, src // 3, axis=0)
                               * jnp.resize(weight, (18, 5))).sum())(x)
    assert numpy.allclose(got, want, atol=1e-5)


@pytest.mark.parametrize("rows", [18, 12])
def test_combine_is_a_weighted_segment_sum_and_its_gradient_a_take(rows):
    src, pos, here = a_sort()
    buf = jax.random.normal(jax.random.key(3), (rows, 5))
    buf = jnp.where((jnp.arange(rows) < 7)[:, None], buf, jnp.nan)
    w = jax.random.uniform(jax.random.key(6), (6, 3))
    weight = jax.random.normal(jax.random.key(5), (6, 5))

    def plain(buf, w):
        scale = jnp.take(w.reshape(-1), src[:7])
        return jax.ops.segment_sum(buf[:7] * scale[:, None], src[:7] // 3,
                                   num_segments=6)
    got = transformer._combine(buf, w, src[:rows], pos, here)
    assert got.dtype == jnp.float32
    assert numpy.allclose(got, plain(buf, w), atol=1e-6)
    got = jax.grad(lambda *a: (transformer._combine(
        *a, src[:rows], pos, here) * weight).sum(), argnums=(0, 1))(buf, w)
    want = jax.grad(lambda *a: (plain(*a) * weight).sum(),
                    argnums=(0, 1))(jnp.nan_to_num(buf), w)
    assert numpy.allclose(got[0][:7], want[0][:7], atol=1e-5)
    assert numpy.allclose(got[1], want[1], atol=1e-5)
    assert numpy.array_equal(got[1] != 0, here)


def test_blocked_token_loss_is_the_plain_one():
    head = transformer.NormHead(None, hidden_size=16, vocab_size=50,
                                loss_block_tokens=8, name="head")
    params = {"norm": 1.0 + 0.1 * jax.random.normal(jax.random.key(6),
                                                    (16,)),
              "weights": jax.random.normal(jax.random.key(7), (16, 50))}
    x = jax.random.normal(jax.random.key(8), (3, 8, 16))
    labels = jax.random.randint(jax.random.key(9), (3, 8), 0, 50)
    mask = jnp.asarray([1.0, 1.0, 0.0])     # the last sequence is padding
    total, wrong, pred = head.token_loss(params, x, labels, mask)
    logits = head.apply(params, x)
    logp = jax.nn.log_softmax(logits, -1)
    nll = -jnp.take_along_axis(logp, labels[..., None], -1)[..., 0]
    assert abs(float(total) - float(nll[:2].sum())) < 1e-4
    assert numpy.array_equal(pred, logits.argmax(-1))
    assert int(wrong) == int((logits.argmax(-1) != labels)[:2].sum())
    got = jax.grad(lambda p: head.token_loss(p, x, labels, mask)[0])(params)
    want = jax.grad(lambda p: -(jnp.take_along_axis(jax.nn.log_softmax(
        head.apply(p, x), -1), labels[..., None], -1)[..., 0][:2]).sum())(
        params)
    for name in params:
        assert numpy.allclose(got[name], want[name], atol=1e-4), name
