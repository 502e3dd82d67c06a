"""Flash-attention kernel pair vs the jnp oracle (VERDICT r4 item 4).

Runs in Pallas interpret mode on the CPU suite; the on-chip A/B lives
in docs/PERF.md + tools/ab_flash_attention.py.
"""

import numpy
import pytest

import jax
import jax.numpy as jnp

from veles_tpu.parallel.ring import attention_reference
from veles_tpu.znicz.flash_attention import (
    flash_attention, flash_attention_supported)


def _mk(b, t, h, d, seed=0):
    rng = numpy.random.RandomState(seed)
    return tuple(
        jnp.asarray(rng.standard_normal((b, t, h, d)) * 0.5, jnp.float32)
        for _ in range(3))


@pytest.mark.parametrize("causal", [False, True])
def test_forward_matches_oracle(causal):
    q, k, v = _mk(2, 256, 2, 16)
    got = flash_attention(q, k, v, causal, None, 128, 64)
    want = attention_reference(q, k, v, causal=causal)
    numpy.testing.assert_allclose(numpy.asarray(got),
                                  numpy.asarray(want),
                                  rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_gradients_match_oracle(causal):
    q, k, v = _mk(1, 128, 2, 8, seed=1)

    def loss_flash(q, k, v):
        out = flash_attention(q, k, v, causal, None, 64, 64)
        return jnp.sum(jnp.sin(out) * out)

    def loss_ref(q, k, v):
        out = attention_reference(q, k, v, causal=causal)
        return jnp.sum(jnp.sin(out) * out)

    got = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for g, w, name in zip(got, want, "qkv"):
        numpy.testing.assert_allclose(
            numpy.asarray(g), numpy.asarray(w), rtol=5e-4, atol=5e-4,
            err_msg="d%s diverges" % name)


def test_untileable_t_falls_back_to_oracle():
    # T=6 can't tile into 256-blocks evenly after clamping (6 % 6 == 0
    # would tile; use T=7 which is prime and != block)
    q, k, v = _mk(1, 7, 1, 8, seed=2)
    assert not flash_attention_supported(7, 4, 4)
    got = flash_attention(q, k, v, True, None, 4, 4)
    want = attention_reference(q, k, v, causal=True)
    numpy.testing.assert_allclose(numpy.asarray(got),
                                  numpy.asarray(want),
                                  rtol=1e-5, atol=1e-5)
    g1 = jax.grad(lambda q: jnp.sum(
        flash_attention(q, k, v, True, None, 4, 4) ** 2))(q)
    g2 = jax.grad(lambda q: jnp.sum(
        attention_reference(q, k, v, causal=True) ** 2))(q)
    numpy.testing.assert_allclose(numpy.asarray(g1), numpy.asarray(g2),
                                  rtol=1e-4, atol=1e-4)


def test_mha_unit_use_pallas_knob():
    """MultiHeadAttention(use_pallas=True) routes through the kernel
    and matches the default path."""
    from veles_tpu.backends import Device
    from veles_tpu.memory import Array
    from veles_tpu.prng import RandomGenerator
    from veles_tpu.workflow import Workflow
    from veles_tpu.znicz.attention import MultiHeadAttention

    rng = numpy.random.RandomState(3)
    x = rng.standard_normal((2, 64, 16)).astype(numpy.float32)
    outs = {}
    for use_pallas in (False, True):
        wf = Workflow(name="mha-knob-%s" % use_pallas)
        unit = MultiHeadAttention(wf, heads=2, causal=True,
                                  use_pallas=use_pallas,
                                  prng=RandomGenerator().seed(7))
        unit.input = Array(x.copy())
        unit.initialize(device=Device(backend="cpu"))
        unit.run()
        outs[use_pallas] = numpy.asarray(unit.output.map_read())
    numpy.testing.assert_allclose(outs[True], outs[False],
                                  rtol=2e-5, atol=2e-5)


def test_use_pallas_auto_default():
    """Unset use_pallas is AUTO: oracle on CPU (interpret kernels are
    slow), flash on TPU — resolved at run time, not construction."""
    from veles_tpu.config import root
    from veles_tpu.workflow import Workflow
    from veles_tpu.znicz.attention import MultiHeadAttention
    assert root.common.engine.get("use_pallas", None) is None
    wf = Workflow(name="auto")
    unit = MultiHeadAttention(wf, heads=2)
    assert unit.use_pallas is None
    assert unit._resolved_use_pallas() is False  # suite runs on CPU
    unit_forced = MultiHeadAttention(wf, heads=2, use_pallas=True)
    assert unit_forced._resolved_use_pallas() is True


def test_resolve_use_pallas_semantics():
    """Shared tri-state knob: force wins, AUTO is per-unit measured
    best on the unit's OWN device (not the process default), and
    oracle_only (the export guard) overrides everything."""
    from veles_tpu.backends import Device
    from veles_tpu.znicz.nn_units import oracle_only, resolve_use_pallas

    cpu_dev = Device(backend="cpu")

    class FakeTPU:
        BACKEND = "tpu"

    assert resolve_use_pallas(True, cpu_dev, tpu_auto=True) is True
    assert resolve_use_pallas(False, FakeTPU(), tpu_auto=True) is False
    # AUTO keyed off the unit's device, not jax.default_backend()
    assert resolve_use_pallas(None, FakeTPU(), tpu_auto=True) is True
    assert resolve_use_pallas(None, cpu_dev, tpu_auto=True) is False
    # LRN-style units (measured loss) never auto-enable
    assert resolve_use_pallas(None, FakeTPU(), tpu_auto=False) is False
    # the export guard forces the pure-XLA path even when forced on
    with oracle_only():
        assert resolve_use_pallas(True, FakeTPU(), tpu_auto=True) is False
    assert resolve_use_pallas(True, FakeTPU(), tpu_auto=True) is True


@pytest.mark.parametrize("window", [1, 5, 64, 100, 256])
def test_window_forward_matches_bruteforce(window):
    """Sliding-window masking vs an explicit brute-force mask, at
    window sizes below / straddling / above the block size (the
    off-by-one-prone boundaries live at block edges)."""
    q, k, v = _mk(1, 256, 2, 16, seed=4)
    got = flash_attention(q, k, v, True, None, 64, 64, window)
    oracle = attention_reference(q, k, v, causal=True, window=window)
    # independent brute force: softmax over the explicit band
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(16.0)
    rows = jnp.arange(256)[:, None]
    cols = jnp.arange(256)[None, :]
    banned = (cols > rows) | (cols <= rows - window)
    p = jax.nn.softmax(jnp.where(banned, -jnp.inf, s), axis=-1)
    brute = jnp.einsum("bhqk,bkhd->bqhd", p, v)
    numpy.testing.assert_allclose(numpy.asarray(oracle),
                                  numpy.asarray(brute),
                                  rtol=1e-5, atol=1e-5)
    numpy.testing.assert_allclose(numpy.asarray(got),
                                  numpy.asarray(brute),
                                  rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("window", [5, 64, 100])
def test_window_gradients_match_oracle(window):
    q, k, v = _mk(1, 128, 2, 8, seed=5)

    def loss(attend):
        def f(q, k, v):
            return jnp.sum(jnp.sin(attend(q, k, v)) ** 2)
        return f

    got = jax.grad(loss(lambda q, k, v: flash_attention(
        q, k, v, True, None, 64, 32, window)), argnums=(0, 1, 2))(
        q, k, v)
    want = jax.grad(loss(lambda q, k, v: attention_reference(
        q, k, v, causal=True, window=window)), argnums=(0, 1, 2))(
        q, k, v)
    for g, w, name in zip(got, want, "qkv"):
        numpy.testing.assert_allclose(
            numpy.asarray(g), numpy.asarray(w), rtol=5e-4, atol=5e-4,
            err_msg="d%s diverges (window=%d)" % (name, window))


def test_window_requires_causal():
    q, k, v = _mk(1, 64, 1, 8)
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, v, False, None, 32, 32, 8)
    with pytest.raises(ValueError, match="causal"):
        attention_reference(q, k, v, window=8)


def test_window_unit_path():
    """MultiHeadAttention(window=...) through both engines; ring mesh
    with a window is a loud NotImplementedError."""
    from veles_tpu.backends import Device
    from veles_tpu.memory import Array
    from veles_tpu.parallel.mesh import make_mesh
    from veles_tpu.prng import RandomGenerator
    from veles_tpu.workflow import Workflow
    from veles_tpu.znicz.attention import MultiHeadAttention

    rng = numpy.random.RandomState(6)
    x = rng.standard_normal((2, 64, 16)).astype(numpy.float32)
    outs = {}
    for use_pallas in (False, True):
        wf = Workflow(name="mha-window-%s" % use_pallas)
        unit = MultiHeadAttention(wf, heads=2, causal=True, window=10,
                                  use_pallas=use_pallas,
                                  prng=RandomGenerator().seed(7))
        unit.input = Array(x.copy())
        unit.initialize(device=Device(backend="cpu"))
        unit.run()
        assert unit.export_params()["window"] == 10
        outs[use_pallas] = numpy.asarray(unit.output.map_read())
    numpy.testing.assert_allclose(outs[True], outs[False],
                                  rtol=2e-5, atol=2e-5)
    wf = Workflow(name="mha-window-mesh")
    with pytest.raises(ValueError, match="causal"):
        MultiHeadAttention(wf, heads=2, window=4)
    unit = MultiHeadAttention(wf, heads=2, causal=True, window=4,
                              mesh=make_mesh({"seq": 8}),
                              prng=RandomGenerator().seed(7))
    unit.input = Array(x.copy())
    with pytest.raises(NotImplementedError, match="window"):
        unit.initialize(device=Device(backend="cpu"))
        unit.run()


def test_window_banded_backward_geometry():
    """Gradients at a geometry where BOTH backward passes take the
    banded grid (band < n_blocks on each streamed axis): T=256,
    32x32 blocks, window=40 -> k-band 4 of 8, q-band 4 of 8."""
    from veles_tpu.znicz.flash_attention import (_kband_size,
                                                 _qband_size)
    assert _kband_size(256, 32, 32, 40) < 256 // 32
    assert _qband_size(256, 32, 32, 40) < 256 // 32
    q, k, v = _mk(1, 256, 2, 8, seed=8)

    got = jax.grad(lambda q, k, v: jnp.sum(jnp.sin(flash_attention(
        q, k, v, True, None, 32, 32, 40))), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(lambda q, k, v: jnp.sum(jnp.sin(
        attention_reference(q, k, v, causal=True, window=40))),
        argnums=(0, 1, 2))(q, k, v)
    for g, w, name in zip(got, want, "qkv"):
        numpy.testing.assert_allclose(
            numpy.asarray(g), numpy.asarray(w), rtol=5e-4, atol=5e-4,
            err_msg="d%s diverges" % name)


def test_window_rejects_nonpositive():
    from veles_tpu.workflow import Workflow
    from veles_tpu.znicz.attention import MultiHeadAttention
    q, k, v = _mk(1, 64, 1, 8)
    for w in (0, -3):
        with pytest.raises(ValueError, match=">= 1"):
            flash_attention(q, k, v, True, None, 32, 32, w)
        with pytest.raises(ValueError, match=">= 1"):
            attention_reference(q, k, v, causal=True, window=w)
        with pytest.raises(ValueError, match=">= 1"):
            MultiHeadAttention(Workflow(name="w"), heads=1,
                               causal=True, window=w)
