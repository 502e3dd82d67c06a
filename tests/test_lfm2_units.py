"""The gated short-convolution block and the grouped-query attention
block against their formulas written out by hand (numpy loops over
positions, taps and heads), values and gradients, on the CPU; the
half-split rotary pairing by hand; what the two units tell the trainer
(checkpoint, kept names, scopes) and the record a step of them files."""

import jax
import jax.numpy as jnp
import numpy
import pytest

from veles_tpu.backends import Device
from veles_tpu.logger import events
from veles_tpu.prng import RandomGenerator
from veles_tpu.workflow import Workflow
from veles_tpu.znicz import flash_attention, fused, transformer
from veles_tpu.znicz.standard_workflow import StandardWorkflow

from test_remat_saves import TokenLoader
from test_spans import named

HIDDEN, SEQ, EPS = 16, 12, 1e-5


def block(cls, **kwargs):
    unit = cls(Workflow(name="lfm2"), name="unit", seed=7,
               weights_stddev=0.5, rms_norm_eps=EPS, hidden_size=HIDDEN,
               **kwargs)
    unit.init_params()
    params = {k: numpy.asarray(v, numpy.float64)
              for k, v in unit.params.items()}
    # norms off one, so that a forgotten weight shows
    for name in params:
        if params[name].ndim == 1:
            params[name] = 1.0 + 0.3 * numpy.cos(
                numpy.arange(params[name].size) + len(name))
    return unit, params


def activations(t=SEQ):
    return numpy.asarray(jax.random.normal(jax.random.key(1),
                                           (2, t, HIDDEN)), numpy.float64)


def rms(x, weight):
    return x / numpy.sqrt((x * x).mean(-1, keepdims=True) + EPS) * weight


def as_jax(params):
    return {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}


# -- the gated short convolution ------------------------------------------------

def short_conv_by_hand(p, x, taps):
    """``x + (C * v) W_out``, ``v[t] = sum_j w[j] * u[t - (L-1) + j]``,
    one position and one tap at a time."""
    b, t, d = x.shape
    bcx = rms(x, p["norm"]) @ p["in_proj"]
    gate_b, gate_c, xt = bcx[..., :d], bcx[..., d:2 * d], bcx[..., 2 * d:]
    u = gate_b * xt
    v = numpy.zeros_like(u)
    for pos in range(t):
        for j in range(taps):
            src = pos - (taps - 1) + j
            if src >= 0:                    # u is zero before the start
                v[:, pos] += p["conv"][j] * u[:, src]
    return x + (gate_c * v) @ p["out_proj"]


@pytest.mark.parametrize("taps,t", [(3, SEQ), (4, SEQ), (3, 2), (3, 1)],
                         ids=["L3", "L4", "shorter-than-the-taps", "T1"])
def test_short_conv_block_is_its_formula(taps, t):
    unit, p = block(transformer.ShortConvBlock, conv_L_cache=taps)
    assert p["conv"].shape == (taps, HIDDEN)
    assert p["in_proj"].shape == (HIDDEN, 3 * HIDDEN)
    x = activations(t)
    got = unit.apply(as_jax(p), jnp.asarray(x, jnp.float32))
    want = short_conv_by_hand(p, x, taps)
    numpy.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    # the first L-1 positions see fewer taps: position 0 only the last
    gate_b, gate_c, xt = numpy.split(rms(x, p["norm"]) @ p["in_proj"], 3, -1)
    first = x + (gate_c * p["conv"][taps - 1] * gate_b * xt) @ p["out_proj"]
    numpy.testing.assert_allclose(numpy.asarray(got)[:, 0], first[:, 0],
                                  rtol=2e-5, atol=2e-5)


def test_short_conv_block_is_causal_and_has_no_bias():
    unit, p = block(transformer.ShortConvBlock, conv_L_cache=3)
    params = as_jax(p)
    x = jnp.asarray(activations(), jnp.float32)
    base = unit.apply(params, x)
    later = unit.apply(params, x.at[:, 7].add(1.0))
    changed = numpy.abs(numpy.asarray(later - base)).max(axis=(0, 2)) > 1e-6
    # position 7 reaches 7, 8 and 9 (three taps) and nothing before it
    assert changed.tolist() == [False] * 7 + [True] * 3 + [False] * 2
    assert numpy.array_equal(numpy.asarray(unit.apply(params, 0 * x)),
                             numpy.zeros_like(x))
    assert sorted(params) == ["conv", "in_proj", "norm", "out_proj"]


def test_short_conv_gradients_against_differences():
    """The VJP XLA derives from the shifted sums against central
    differences of the hand formula in float64, every tensor and the
    input."""
    unit, p = block(transformer.ShortConvBlock, conv_L_cache=3)
    x = activations(5)
    weight = numpy.cos(numpy.arange(2 * 5 * HIDDEN)).reshape(2, 5, HIDDEN)

    def scalar(p, x):
        return float((short_conv_by_hand(p, x, 3) * weight).sum())
    grads, dx = jax.grad(
        lambda p, x: (unit.apply(p, x) * weight).sum(), argnums=(0, 1))(
        as_jax(p), jnp.asarray(x, jnp.float32))
    rng = numpy.random.RandomState(0)
    for name in sorted(p) + ["x"]:
        target = x if name == "x" else p[name]
        got = numpy.asarray(dx if name == "x" else grads[name])
        for _ in range(4):
            at = tuple(rng.randint(0, n) for n in target.shape)
            keep, h = target[at], 1e-5
            target[at] = keep + h
            up = scalar(p, x)
            target[at] = keep - h
            down = scalar(p, x)
            target[at] = keep
            assert got[at] == pytest.approx((up - down) / (2 * h),
                                            rel=2e-3, abs=2e-4), (name, at)


def test_short_conv_in_bfloat16_sums_in_float32():
    """Operands bfloat16, the taps' sum float32: against the formula on
    bfloat16-rounded operands, far inside bfloat16's own step."""
    unit, p = block(transformer.ShortConvBlock, conv_L_cache=3)

    def bf(a):
        return numpy.asarray(jnp.asarray(a, jnp.bfloat16), numpy.float64)
    x = bf(activations())
    params = {k: v if k == "norm" else bf(v) for k, v in p.items()}
    got = unit.apply({k: jnp.asarray(v, jnp.float32 if k == "norm"
                                     else jnp.bfloat16)
                      for k, v in params.items()},
                     jnp.asarray(x, jnp.bfloat16))
    assert got.dtype == jnp.bfloat16
    want = short_conv_by_hand(params, x, 3)
    err = numpy.abs(numpy.asarray(got, numpy.float64) - want).max()
    assert err < 0.05 * numpy.abs(want).max()


# -- grouped-query attention ------------------------------------------------------

HEADS, KV_HEADS, DIM, THETA = 4, 2, 8, 1e4


def rope_by_hand(x, theta):
    """[T, D]: pair (i, i + D/2) turns by ``t * theta ** (-2i / D)``."""
    t, d = x.shape
    out = numpy.empty_like(x)
    for pos in range(t):
        for i in range(d // 2):
            angle = pos * theta ** (-2.0 * i / d)
            a, b = x[pos, i], x[pos, i + d // 2]
            out[pos, i] = a * numpy.cos(angle) - b * numpy.sin(angle)
            out[pos, i + d // 2] = a * numpy.sin(angle) + b * numpy.cos(angle)
    return out


def gqa_by_hand(p, x):
    b, t, d = x.shape
    h = rms(x, p["norm"])
    group = HEADS // KV_HEADS
    out = numpy.zeros((b, t, HEADS * DIM))
    for n in range(b):
        q = (h[n] @ p["wq"]).reshape(t, HEADS, DIM)
        k = (h[n] @ p["wk"]).reshape(t, KV_HEADS, DIM)
        v = (h[n] @ p["wv"]).reshape(t, KV_HEADS, DIM)
        for head in range(HEADS):
            kv = head // group              # the key-value head it reads
            qh = rope_by_hand(rms(q[:, head], p["q_norm"]), THETA)
            kh = rope_by_hand(rms(k[:, kv], p["k_norm"]), THETA)
            scores = qh @ kh.T / numpy.sqrt(DIM)
            scores[numpy.triu_indices(t, 1)] = -numpy.inf
            prob = numpy.exp(scores - scores.max(-1, keepdims=True))
            prob /= prob.sum(-1, keepdims=True)
            out[n, :, head * DIM:(head + 1) * DIM] = prob @ v[:, kv]
    return x + out @ p["wo"]


def gqa_block(**kwargs):
    return block(transformer.GQAAttentionBlock, num_attention_heads=HEADS,
                 num_key_value_heads=KV_HEADS, head_dim=DIM,
                 rope_theta=THETA, **kwargs)


def test_half_split_rope_by_hand():
    x = numpy.asarray(jax.random.normal(jax.random.key(2), (3, 6, DIM)),
                      numpy.float64)
    got = transformer.rope_half_split(jnp.asarray(x, jnp.float32), THETA)
    for head in range(3):
        numpy.testing.assert_allclose(got[head], rope_by_hand(x[head],
                                                              THETA),
                                      rtol=1e-5, atol=1e-5)
    # position 0 is not turned; the other pairing is another function
    numpy.testing.assert_allclose(got[:, 0], x[:, 0], rtol=1e-6)
    other = transformer.rope_interleaved(jnp.asarray(x, jnp.float32), THETA)
    assert not numpy.allclose(got, other, atol=1e-3)
    # both preserve each position's norm
    numpy.testing.assert_allclose(numpy.linalg.norm(got, axis=-1),
                                  numpy.linalg.norm(x, axis=-1), rtol=1e-5)


@pytest.mark.parametrize("use_pallas", [False, True],
                         ids=["scores", "kernels"])
def test_gqa_attention_block_is_its_formula(use_pallas):
    # a length the kernels can tile (32) where they are asked for
    unit, p = gqa_block(use_pallas=use_pallas)
    x = activations(32 if use_pallas else SEQ)
    assert {k: v.shape for k, v in p.items()} == {
        "norm": (HIDDEN,), "wq": (HIDDEN, HEADS * DIM),
        "wk": (HIDDEN, KV_HEADS * DIM), "wv": (HIDDEN, KV_HEADS * DIM),
        "q_norm": (DIM,), "k_norm": (DIM,), "wo": (HEADS * DIM, HIDDEN)}
    got = unit.apply(as_jax(p), jnp.asarray(x, jnp.float32))
    numpy.testing.assert_allclose(got, gqa_by_hand(p, x), rtol=5e-5,
                                  atol=5e-5)


def test_gqa_gradients_are_the_same_on_kernels_and_scores():
    kernels, p = gqa_block(use_pallas=True)
    scores, _ = gqa_block(use_pallas=False)
    x = jnp.asarray(activations(32), jnp.float32)
    weight = jnp.cos(jnp.arange(float(HIDDEN)))

    def grads(unit):
        return jax.grad(lambda p, x: (unit.apply(p, x) * weight).sum(),
                        argnums=(0, 1))(as_jax(p), x)
    for got, want in zip(jax.tree.leaves(grads(kernels)),
                         jax.tree.leaves(grads(scores))):
        numpy.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    # and against differences of the hand formula, a few entries
    ps, dx = grads(scores)
    rng = numpy.random.RandomState(1)
    x64 = numpy.asarray(x, numpy.float64)

    def scalar():
        return float((gqa_by_hand(p, x64) * numpy.asarray(weight)).sum())
    for name in ("wk", "k_norm", "wv", "wq"):
        for _ in range(2):
            at = tuple(rng.randint(0, n) for n in p[name].shape)
            keep, h = p[name][at], 1e-5
            p[name][at] = keep + h
            up = scalar()
            p[name][at] = keep - h
            down = scalar()
            p[name][at] = keep
            assert numpy.asarray(ps[name])[at] == pytest.approx(
                (up - down) / (2 * h), rel=5e-3, abs=5e-4), (name, at)


def test_one_head_count_is_the_group_of_one():
    unit, p = block(transformer.GQAAttentionBlock, num_attention_heads=4,
                    rope_theta=THETA)
    assert (unit.kv_heads, unit.head_dim) == (4, HIDDEN // 4)
    assert p["wk"].shape == p["wq"].shape
    with pytest.raises(ValueError, match="4 query heads cannot share 3"):
        block(transformer.GQAAttentionBlock, num_attention_heads=4,
              num_key_value_heads=3)


# -- what the units tell the trainer ----------------------------------------------

def test_what_the_units_declare():
    attn, conv = transformer.GQAAttentionBlock, transformer.ShortConvBlock
    assert attn.remat and attn.remat_saves == flash_attention.SAVED_NAMES
    assert conv.remat and conv.remat_saves == ()
    assert attn.FLOAT32_PARAMS == ("norm", "q_norm", "k_norm")
    assert conv.FLOAT32_PARAMS == ("norm",)
    assert (attn.MAPPING, transformer.GDGQAAttentionBlock.MAPPING) == (
        "gqa_attention_block",) * 2
    assert (conv.MAPPING, transformer.GDShortConvBlock.MAPPING) == (
        "short_conv_block",) * 2


def test_the_forward_kernel_runs_once_under_the_blocks_checkpoint():
    unit, p = gqa_block(use_pallas=True)
    x = jnp.asarray(activations(32), jnp.float32)

    def calls(fn):
        jaxpr = jax.make_jaxpr(jax.grad(lambda p, x: fn(p, x).sum()))(
            as_jax(p), x)
        return sorted(eqn.params["name"]
                      for eqn in fused.jaxpr_equations(jaxpr.jaxpr)
                      if eqn.primitive.name == "pallas_call")
    assert calls(fused.applier(unit)) == ["gqa_flash_dkv", "gqa_flash_dq",
                                          "gqa_flash_fwd"]
    assert calls(jax.checkpoint(unit.apply)).count("gqa_flash_fwd") == 2


@pytest.mark.parametrize("cls,kwargs,scopes", [
    (transformer.ShortConvBlock, {},
     ["conv/in_proj", "conv/mix", "conv/out_proj"]),
    (transformer.GQAAttentionBlock, {"num_attention_heads": 4},
     ["attn/qkv", "attn/core", "attn/out"])], ids=["conv", "attention"])
def test_the_blocks_name_their_parts(cls, kwargs, scopes):
    unit, p = block(cls, **kwargs)
    text = jax.jit(unit.apply).lower(
        as_jax(p), jnp.asarray(activations(), jnp.float32)).as_text(
        debug_info=True)
    for scope in scopes:
        assert scope in text, scope


def test_a_step_of_the_new_units_files_what_it_keeps():
    """conv + MLP, attention + experts through ``StandardWorkflow``: the
    ``step.remat`` record names the four checkpointed units and the
    attention block's kept output and row statistics."""
    def unit(kind, name, **forward):
        forward.update(hidden_size=32, name=name)
        return {"type": kind, "->": forward, "<-": {"learning_rate": 0.05}}
    events.reset()
    wf = StandardWorkflow(
        None, name="lfm2", loader_factory=TokenLoader,
        loader={"minibatch_size": 4, "normalization_type": "none",
                "prng": RandomGenerator().seed(5)},
        layers=[unit("token_embedding", "embed", vocab_size=32),
                unit("short_conv_block", "conv0", conv_L_cache=3),
                unit("gated_mlp_block", "mlp0", intermediate_size=24),
                unit("gqa_attention_block", "attn1", num_attention_heads=4,
                     num_key_value_heads=2, head_dim=8, use_pallas=True),
                unit("expert_block", "moe1", moe_intermediate_size=16,
                     n_routed_experts=4, num_experts_per_tok=2),
                unit("lm_head", "head", vocab_size=32)],
        loss_function="token", decision={"max_epochs": 1, "silent": True},
        fused=True, epoch_scan=True, trainer={"compute_dtype": "float32"})
    wf.initialize(device=Device(backend="cpu"))
    (span,) = named(events.spans(), "step.remat")
    assert span.info["units"] == "conv0,mlp0,attn1,moe1"
    assert span.info["saves"] == "attn1:flash_out+flash_lse"
    # the output [4, 4, 64, 8] float32 and the statistics [4 * 4, 64]
    assert span.info["bytes"] == 4 * 4 * 64 * 8 * 4 + 4 * 4 * 64 * 4
    wf.run()
    assert numpy.isfinite(float(wf.fused_step.loss))
    stats = wf.fused_step.unit_stats["train"]["moe1"]
    assert int(stats["moe_rows"]) == int(stats["moe_routed"]) == 8 * 64 * 2
