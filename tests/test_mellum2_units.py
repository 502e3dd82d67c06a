"""What a depth of window layers beside full ones asks of the units, on
the CPU: ``gqa_attention_block`` with a ``sliding_window`` and with
``rope_parameters`` of ``rope_type`` ``yarn`` against their formulas
written out by hand (numpy loops), values and gradients; the band where
YaRN blends its frequencies; the expert block's ``softmax`` router
against a direct transcription, the ``sigmoid`` one as it was, and the
shares of an expert-parallel layer adding up under both; the record a
step of two kinds of attention layer files."""

import math

import jax
import jax.numpy as jnp
import numpy
import pytest

from veles_tpu.backends import Device
from veles_tpu.logger import events
from veles_tpu.prng import RandomGenerator
from veles_tpu.workflow import Workflow
from veles_tpu.znicz import fused, transformer
from veles_tpu.znicz.standard_workflow import StandardWorkflow

from test_lfm2_units import (DIM, HEADS, HIDDEN, KV_HEADS, THETA,
                             activations, as_jax, block, rms)
from test_remat_saves import TokenLoader
from test_spans import named

#: the published group of Mellum 2's full layers
PUBLISHED_YARN = {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
                  "original_max_position_embeddings": 8192,
                  "beta_fast": 32, "beta_slow": 1,
                  "attention_factor": 1.2772588722239782}
#: the same kind of group for heads of 8 at short lengths: the band lies
#: inside the head's four pairs (1, 3)
SMALL_YARN = {"rope_type": "yarn", "rope_theta": THETA, "factor": 4.0,
              "original_max_position_embeddings": 64, "beta_fast": 1,
              "beta_slow": 0.05, "attention_factor": 1.3}


def yarn_by_hand(group, d):
    """(inv_freq of each pair, the factor on cos and sin): the formula of
    ISSUE 35, one pair at a time."""
    theta, factor = group["rope_theta"], group["factor"]

    def c(turns):
        return d * math.log(group["original_max_position_embeddings"]
                            / (2 * math.pi * turns)) / (2 * math.log(theta))
    low = max(math.floor(c(group["beta_fast"])), 0)
    high = min(math.ceil(c(group["beta_slow"])), d - 1)
    freq = []
    for i in range(d // 2):
        extrap = theta ** (-2.0 * i / d)
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        freq.append(extrap / factor * ramp + extrap * (1 - ramp))
    return numpy.asarray(freq), group["attention_factor"], (low, high)


def rope_by_hand(x, freq, gain=1.0):
    """[T, D]: pair (i, i + D/2) turns by ``t * freq[i]``, cos and sin
    times ``gain``."""
    t, d = x.shape
    out = numpy.empty_like(x)
    for pos in range(t):
        for i in range(d // 2):
            cos = gain * numpy.cos(pos * freq[i])
            sin = gain * numpy.sin(pos * freq[i])
            a, b = x[pos, i], x[pos, i + d // 2]
            out[pos, i] = a * cos - b * sin
            out[pos, i + d // 2] = a * sin + b * cos
    return out


def attention_by_hand(p, x, window=None, group=None):
    """``x + Attention(RMSNorm(x)) W_o``: position ``i`` sees the keys in
    ``(i - window, i]``, one score at a time."""
    b, t, _ = x.shape
    if group is None:
        freq = THETA ** (-2.0 * numpy.arange(DIM // 2) / DIM)
        gain = 1.0
    else:
        freq, gain, _ = yarn_by_hand(group, DIM)
    h = rms(x, p["norm"])
    out = numpy.zeros((b, t, HEADS * DIM))
    for n in range(b):
        q = (h[n] @ p["wq"]).reshape(t, HEADS, DIM)
        k = (h[n] @ p["wk"]).reshape(t, KV_HEADS, DIM)
        v = (h[n] @ p["wv"]).reshape(t, KV_HEADS, DIM)
        for head in range(HEADS):
            kv = head // (HEADS // KV_HEADS)
            qh = rope_by_hand(rms(q[:, head], p["q_norm"]), freq, gain)
            kh = rope_by_hand(rms(k[:, kv], p["k_norm"]), freq, gain)
            for i in range(t):
                first = 0 if window is None else max(0, i - window + 1)
                scores = kh[first:i + 1] @ qh[i] / numpy.sqrt(DIM)
                prob = numpy.exp(scores - scores.max())
                prob /= prob.sum()
                out[n, i, head * DIM:(head + 1) * DIM] = \
                    prob @ v[first:i + 1, kv]
    return x + out @ p["wo"]


def gqa_block(**kwargs):
    kwargs.setdefault("rope_theta", THETA)
    return block(transformer.GQAAttentionBlock, num_attention_heads=HEADS,
                 num_key_value_heads=KV_HEADS, head_dim=DIM, **kwargs)


# -- the window ------------------------------------------------------------------

@pytest.mark.parametrize("use_pallas", [False, True],
                         ids=["scores", "kernels"])
@pytest.mark.parametrize("window", [1, 5, 12])
def test_a_window_layer_is_its_formula(use_pallas, window):
    # a length the kernels can tile (32) where they are asked for
    unit, p = gqa_block(sliding_window=window, use_pallas=use_pallas)
    assert unit.sliding_window == window and unit.rope_type == "default"
    x = activations(32 if use_pallas else 12)
    got = unit.apply(as_jax(p), jnp.asarray(x, jnp.float32))
    numpy.testing.assert_allclose(got, attention_by_hand(p, x, window),
                                  rtol=5e-5, atol=5e-5)
    if window < x.shape[1]:
        assert not numpy.allclose(got, attention_by_hand(p, x), atol=1e-3)


@pytest.mark.parametrize("use_pallas", [False, True],
                         ids=["scores", "kernels"])
def test_a_window_wider_than_the_sequence_is_no_window(use_pallas):
    wide, p = gqa_block(sliding_window=64, use_pallas=use_pallas)
    full, _ = gqa_block(use_pallas=use_pallas)
    assert full.sliding_window is None
    x = jnp.asarray(activations(32), jnp.float32)
    numpy.testing.assert_allclose(wide.apply(as_jax(p), x),
                                  full.apply(as_jax(p), x), rtol=1e-6,
                                  atol=1e-6)


def test_a_window_layers_gradients():
    """Kernels against scores, every tensor and the input; and against
    central differences of the hand formula in float64."""
    kernels, p = gqa_block(sliding_window=5, use_pallas=True)
    scores, _ = gqa_block(sliding_window=5, use_pallas=False)
    x = jnp.asarray(activations(32), jnp.float32)
    weight = jnp.cos(jnp.arange(float(HIDDEN)))

    def grads(unit):
        return jax.grad(lambda p, x: (unit.apply(p, x) * weight).sum(),
                        argnums=(0, 1))(as_jax(p), x)
    for got, want in zip(jax.tree.leaves(grads(kernels)),
                         jax.tree.leaves(grads(scores))):
        numpy.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    ps, _ = grads(scores)
    rng = numpy.random.RandomState(1)
    x64 = numpy.asarray(x, numpy.float64)

    def scalar():
        return float((attention_by_hand(p, x64, 5)
                      * numpy.asarray(weight)).sum())
    for name in ("wk", "k_norm", "wv", "wq"):
        at = tuple(rng.randint(0, n) for n in p[name].shape)
        keep, h = p[name][at], 1e-5
        p[name][at] = keep + h
        up = scalar()
        p[name][at] = keep - h
        down = scalar()
        p[name][at] = keep
        assert numpy.asarray(ps[name])[at] == pytest.approx(
            (up - down) / (2 * h), rel=5e-3, abs=5e-4), (name, at)


def test_a_window_layer_runs_the_banded_calls_under_its_checkpoint():
    """The calls made with a window carry names of their own, and the
    unit's checkpoint keeps the forward kernel's results as a full
    layer's does."""
    unit, p = gqa_block(sliding_window=5, use_pallas=True)
    x = jnp.asarray(activations(32), jnp.float32)

    def calls(fn):
        jaxpr = jax.make_jaxpr(jax.grad(lambda p, x: fn(p, x).sum()))(
            as_jax(p), x)
        return sorted(eqn.params["name"]
                      for eqn in fused.jaxpr_equations(jaxpr.jaxpr)
                      if eqn.primitive.name == "pallas_call")
    names = ["gqa_window_flash_dkv", "gqa_window_flash_dq",
             "gqa_window_flash_fwd"]
    assert calls(fused.applier(unit)) == names
    assert calls(jax.checkpoint(unit.apply)).count(names[2]) == 2
    # no name holds a full layer's, nor a full layer's one of these
    full = ["gqa_flash_dkv", "gqa_flash_dq", "gqa_flash_fwd"]
    assert not any(a in b or b in a for a in names for b in full)


# -- YaRN ------------------------------------------------------------------------

def test_the_published_band():
    """At the published values pairs 0-18 turn as before and pairs 35-63
    sixteen times slower."""
    assert transformer.yarn_band(128, 500000, 8192, 32, 1) == (18, 35)
    freq, gain, band = yarn_by_hand(PUBLISHED_YARN, 128)
    assert band == (18, 35) and gain == pytest.approx(0.1 * math.log(16) + 1)
    plain = 500000 ** (-2.0 * numpy.arange(64) / 128)
    numpy.testing.assert_allclose(freq[:19], plain[:19], rtol=1e-12)
    numpy.testing.assert_allclose(freq[35:], plain[35:] / 16, rtol=1e-12)
    assert ((freq[19:35] < plain[19:35])
            & (freq[19:35] > plain[19:35] / 16)).all()
    # the unit's angles are the formula's, at the cell's length too
    cos, sin = transformer._rope_angles(8192, 128, 500000.0, PUBLISHED_YARN)
    at = numpy.asarray([0, 1, 1000, 8191])
    want = at[:, None] * freq[None, :]
    numpy.testing.assert_allclose(numpy.asarray(cos)[at],
                                  gain * numpy.cos(want), atol=2e-3)
    numpy.testing.assert_allclose(numpy.asarray(sin)[at],
                                  gain * numpy.sin(want), atol=2e-3)
    # without attention_factor in the group: 0.1 ln(factor) + 1
    less = {k: v for k, v in PUBLISHED_YARN.items()
            if k != "attention_factor"}
    numpy.testing.assert_allclose(
        transformer._rope_angles(16, 128, 500000.0, less)[0][0],
        0.1 * math.log(16) + 1, rtol=1e-6)


def test_factor_one_is_the_default_bit_for_bit():
    one = dict(PUBLISHED_YARN, factor=1)
    del one["attention_factor"]
    for got, want in zip(transformer._rope_angles(512, 128, 500000.0, one),
                         transformer._rope_angles(512, 128, 500000.0)):
        assert numpy.array_equal(numpy.asarray(got), numpy.asarray(want))
    x = jnp.asarray(activations(32)[..., :DIM], jnp.float32)
    assert numpy.array_equal(
        numpy.asarray(transformer.rope_half_split(
            x, THETA, scaling=dict(SMALL_YARN, factor=1.0,
                                   attention_factor=1.0))),
        numpy.asarray(transformer.rope_half_split(x, THETA)))


def test_yarn_rope_by_hand():
    freq, gain, band = yarn_by_hand(SMALL_YARN, DIM)
    assert band == (1, 3) and transformer.yarn_band(
        DIM, THETA, 64, 1, 0.05) == band
    x = numpy.asarray(jax.random.normal(jax.random.key(2), (3, 6, DIM)),
                      numpy.float64)
    got = transformer.rope_half_split(jnp.asarray(x, jnp.float32), THETA,
                                      scaling=SMALL_YARN)
    for head in range(3):
        numpy.testing.assert_allclose(
            got[head], rope_by_hand(x[head], freq, gain), rtol=1e-5,
            atol=1e-5)
    # every position's norm grows by attention_factor
    numpy.testing.assert_allclose(numpy.linalg.norm(got, axis=-1),
                                  1.3 * numpy.linalg.norm(x, axis=-1),
                                  rtol=1e-5)


@pytest.mark.parametrize("use_pallas", [False, True],
                         ids=["scores", "kernels"])
def test_a_yarn_layer_is_its_formula(use_pallas):
    unit, p = gqa_block(rope_parameters=SMALL_YARN, use_pallas=use_pallas)
    assert (unit.rope_type, unit.rope_theta, unit.sliding_window) == (
        "yarn", THETA, None)
    x = activations(32 if use_pallas else 12)
    got = unit.apply(as_jax(p), jnp.asarray(x, jnp.float32))
    numpy.testing.assert_allclose(
        got, attention_by_hand(p, x, group=SMALL_YARN), rtol=5e-5,
        atol=5e-5)
    assert not numpy.allclose(got, attention_by_hand(p, x), atol=1e-3)
    # a default group is a bare rope_theta
    plain, _ = gqa_block(rope_parameters={"rope_type": "default",
                                          "rope_theta": THETA},
                         rope_theta=1.0, use_pallas=use_pallas)
    numpy.testing.assert_allclose(
        plain.apply(as_jax(p), jnp.asarray(x, jnp.float32)),
        attention_by_hand(p, x), rtol=5e-5, atol=5e-5)
    with pytest.raises(ValueError, match="rope_type 'longrope'"):
        gqa_block(rope_parameters={"rope_type": "longrope"})


def test_a_yarn_layers_gradients_against_differences():
    unit, p = gqa_block(rope_parameters=SMALL_YARN, use_pallas=False)
    x = activations(12)
    weight = numpy.cos(numpy.arange(float(HIDDEN)))
    grads = jax.grad(lambda p, x: (unit.apply(p, x) * weight).sum())(
        as_jax(p), jnp.asarray(x, jnp.float32))
    rng = numpy.random.RandomState(3)

    def scalar():
        return float((attention_by_hand(p, x, group=SMALL_YARN)
                      * weight).sum())
    for name in ("wk", "q_norm", "wq"):
        for _ in range(2):
            at = tuple(rng.randint(0, n) for n in p[name].shape)
            keep, h = p[name][at], 1e-5
            p[name][at] = keep + h
            up = scalar()
            p[name][at] = keep - h
            down = scalar()
            p[name][at] = keep
            assert numpy.asarray(grads[name])[at] == pytest.approx(
                (up - down) / (2 * h), rel=5e-3, abs=5e-4), (name, at)


# -- the routers -------------------------------------------------------------------

EXPERTS, TOP_K, WIDTH = 8, 3, 6


def expert_block(**kwargs):
    kwargs.setdefault("n_routed_experts", EXPERTS)
    return block(transformer.ExpertBlock, moe_intermediate_size=WIDTH,
                 num_experts_per_tok=TOP_K, **kwargs)


def route_by_hand(p, h, scoring, eps, bias=True):
    """(chosen ids sorted, dense weights [T, E]) one token at a time."""
    chosen, dense = [], numpy.zeros((len(h), EXPERTS))
    for t, row in enumerate(h):
        logits = row @ p["router"]
        if scoring == "softmax":
            s = numpy.exp(logits - logits.max())
            s /= s.sum()
        else:
            s = 1.0 / (1.0 + numpy.exp(-logits))
        best = numpy.argsort(-(s + (p["router_bias"] if bias else 0)))[:TOP_K]
        chosen.append(sorted(best))
        dense[t, best] = s[best] / (s[best].sum() + eps)
    return chosen, dense


@pytest.mark.parametrize("scoring,eps", [("softmax", 0.0), ("sigmoid", 1e-20),
                                         ("sigmoid", 1e-6)])
def test_route_against_a_direct_transcription(scoring, eps):
    unit, p = expert_block(scoring_func=scoring, norm_topk_eps=eps)
    assert unit.scoring_func == scoring
    h = numpy.asarray(jax.random.normal(jax.random.key(6), (40, HIDDEN)),
                      numpy.float64)
    chosen, weights = unit.route(as_jax(p), jnp.asarray(h, jnp.float32))
    want_chosen, want = route_by_hand(p, h, scoring, eps)
    assert [sorted(row) for row in numpy.asarray(chosen).tolist()] \
        == want_chosen
    dense = numpy.zeros_like(want)
    numpy.put_along_axis(dense, numpy.asarray(chosen),
                         numpy.asarray(weights, numpy.float64), axis=1)
    numpy.testing.assert_allclose(dense, want, rtol=2e-5, atol=1e-7)
    numpy.testing.assert_allclose(dense.sum(1), 1.0, rtol=1e-5)
    # the bias enters the choice only: the weights are the scores'
    assert want_chosen != route_by_hand(p, h, scoring, eps, bias=False)[0]


def test_the_default_router_is_the_sigmoid_as_it_was():
    """No ``scoring_func``: the block of the two decoder cells, whose
    scores are each expert's own; a softmax's depend on the others."""
    default, p = expert_block()
    named_, _ = expert_block(scoring_func="sigmoid")
    soft, _ = expert_block(scoring_func="softmax")
    assert default.scoring_func == "sigmoid"
    h = jax.random.normal(jax.random.key(7), (40, HIDDEN))
    params = as_jax(p)
    for a, b in zip(default.route(params, h), named_.route(params, h)):
        assert numpy.array_equal(numpy.asarray(a), numpy.asarray(b))
    scores = jax.nn.sigmoid(jnp.dot(h, params["router"],
                                    precision="highest"))
    chosen, weights = default.route(params, h)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    numpy.testing.assert_allclose(
        weights, picked / (picked.sum(-1, keepdims=True) + 1e-20), rtol=1e-6)
    assert not numpy.allclose(soft.route(params, h)[1], weights, atol=1e-3)
    with pytest.raises(ValueError, match="no router scores by 'tanh'"):
        expert_block(scoring_func="tanh")


@pytest.mark.parametrize("scoring", ["sigmoid", "softmax"])
def test_four_shares_add_up_to_the_whole_layer(scoring):
    """4 chips hold 2 experts of 8 each (offsets 0, 2, 4, 6): their
    routed parts, with the residual every chip computes alike counted
    once, add up to the layer that holds all 8, and that to the layer
    written out by hand."""
    whole, p = expert_block(scoring_func=scoring, norm_topk_eps=0.0)
    x = activations(10)
    params = as_jax(p)
    xj = jnp.asarray(x, jnp.float32)
    h = rms(x, p["norm"]).reshape(-1, HIDDEN)
    _, dense = route_by_hand(p, h, scoring, 0.0)
    by_hand = numpy.zeros_like(h)
    for e in range(EXPERTS):
        gu = h @ p["experts_gate_up"][e]
        g, u = gu[:, :WIDTH], gu[:, WIDTH:]
        by_hand += dense[:, e:e + 1] * (
            (g / (1 + numpy.exp(-g)) * u) @ p["experts_down"][e])
    want = x + by_hand.reshape(x.shape)
    numpy.testing.assert_allclose(whole.apply(params, xj), want, rtol=1e-4,
                                  atol=1e-4)
    total = x.copy()
    for chip in range(4):
        share, _ = expert_block(scoring_func=scoring, norm_topk_eps=0.0,
                                experts_held=2, experts_offset=2 * chip)
        assert (share.held, share.offset, share.n_experts) == (
            2, 2 * chip, EXPERTS)
        mine = dict(params, **{
            name: params[name][2 * chip:2 * chip + 2]
            for name in ("experts_gate_up", "experts_down")})
        part, stats = share.apply_stats(mine, xj)
        assert int(stats["moe_rows"]) == int(stats["moe_routed"]) \
            == int((dense[:, 2 * chip:2 * chip + 2] > 0).sum())
        total += numpy.asarray(part, numpy.float64) - x
    numpy.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-4)


def test_a_quarter_share_gets_half_of_every_choice_as_its_buffer():
    """``BUFFER_SHARES`` x a share of 1/4: at the cell's shapes 65,536
    rows where every token's every choice is 131,072 and the expected
    load 32,768."""
    unit, _ = expert_block(n_routed_experts=64, experts_held=16)
    unit.top_k = 8
    assert unit.buffer_rows(16384) == 65536 == 16384 * 8 // 2


# -- the balancing update's two forms --------------------------------------------------

def test_the_proportional_update_is_its_rule():
    unit, p = expert_block(bias_update_rate=0.01,
                           bias_update_rule="proportional")
    load = numpy.array([0, 6, 12, 3, 3, 0, 0, 0], numpy.int32)
    (name, new), = unit.update_buffers(
        p, {"router_load": jnp.asarray(load)}).items()
    assert name == "router_bias"
    want = numpy.asarray(p["router_bias"]) + 0.01 * (3.0 - load) / 3.0
    numpy.testing.assert_allclose(new, want, rtol=0, atol=1e-7)
    # an expert at the mean stays where it is; an idle one rises by the
    # rate, as under the sign; one at four times the mean falls by three
    moved = numpy.asarray(new) - numpy.asarray(p["router_bias"])
    numpy.testing.assert_allclose(moved[[3, 0, 2]], [0, 0.01, -0.03],
                                  atol=1e-7)


def test_the_sign_is_the_default_rule_and_no_third_is_known():
    unit, p = expert_block(bias_update_rate=0.01)
    assert unit.bias_update_rule == "sign"
    load = jnp.asarray([0, 6, 12, 3, 3, 0, 0, 0], jnp.int32)
    moved = numpy.asarray(unit.update_buffers(
        p, {"router_load": load})["router_bias"]) \
        - numpy.asarray(p["router_bias"])
    numpy.testing.assert_allclose(
        moved, [.01, -.01, -.01, 0, 0, .01, .01, .01], atol=1e-7)
    with pytest.raises(ValueError, match="no balancing update"):
        expert_block(bias_update_rule="momentum")


@pytest.mark.parametrize("rule,evened", [("sign", False),
                                         ("proportional", True)])
def test_a_lump_that_flips_averages_out_under_the_proportional_rule(
        rule, evened):
    """Three quarters of the tokens are one vector (the commonest id of
    a Zipf batch, or a component every token shares), so they move
    between experts together.  Under the sign a bias goes up as often as
    down, which holds an expert's MEDIAN step at the mean load and its
    average wherever the lump leaves it; under the error itself a bias
    that stays bounded has moved up as far as down, which is the average
    load at the mean: what a share's rows, and so a step's time, follow."""
    unit, p = expert_block(bias_update_rate=0.02, bias_update_rule=rule,
                           scoring_func="softmax")
    p = dict(p, router_bias=jnp.zeros(EXPERTS))
    rng = numpy.random.default_rng(3)
    x = rng.standard_normal((1, 256, HIDDEN)).astype(numpy.float32)
    x[0, :192] = x[0, 0]
    x = jnp.asarray(x)

    @jax.jit
    def step(p):
        stats = unit.apply_stats(p, x)[1]
        return dict(p, **unit.update_buffers(p, stats)), stats["router_load"]
    loads = []
    for i in range(400):
        p, load = step(p)
        if i >= 100:
            loads.append(numpy.asarray(load))
    mean = numpy.mean(loads, axis=0) / (256 * TOP_K / EXPERTS)
    assert (numpy.abs(mean - 1).max() < 0.1) == evened, mean


# -- what a step of both kinds of layer files ---------------------------------------

def test_a_step_files_each_attention_units_window_and_rope_type():
    def unit(kind, name, **forward):
        forward.update(hidden_size=32, name=name)
        return {"type": kind, "->": forward, "<-": {"learning_rate": 0.05}}
    heads = {"num_attention_heads": 4, "num_key_value_heads": 2,
             "head_dim": 8, "use_pallas": True}
    events.reset()
    wf = StandardWorkflow(
        None, name="mellum2", loader_factory=TokenLoader,
        loader={"minibatch_size": 4, "normalization_type": "none",
                "prng": RandomGenerator().seed(5)},
        layers=[unit("token_embedding", "embed", vocab_size=32),
                unit("gqa_attention_block", "attn0", sliding_window=16,
                     rope_parameters={"rope_type": "default",
                                      "rope_theta": 5e5}, **heads),
                unit("expert_block", "moe0", moe_intermediate_size=16,
                     n_routed_experts=4, num_experts_per_tok=2,
                     scoring_func="softmax", norm_topk_eps=0.0),
                unit("gqa_attention_block", "attn1",
                     rope_parameters=dict(SMALL_YARN), **heads),
                unit("expert_block", "moe1", moe_intermediate_size=16,
                     n_routed_experts=4, num_experts_per_tok=2,
                     scoring_func="softmax", norm_topk_eps=0.0),
                unit("lm_head", "head", vocab_size=32)],
        loss_function="token", decision={"max_epochs": 1, "silent": True},
        fused=True, epoch_scan=True, trainer={"compute_dtype": "float32"})
    wf.initialize(device=Device(backend="cpu"))
    (span,) = named(events.spans(), "step.remat")
    assert span.info["units"] == "attn0,moe0,attn1,moe1"
    assert span.info["saves"] == ("attn0:flash_out+flash_lse "
                                  "attn1:flash_out+flash_lse")
    assert span.info["notes"] == ("attn0:window=16,rope=default "
                                  "attn1:window=None,rope=yarn")
    wf.run()
    assert numpy.isfinite(float(wf.fused_step.loss))
    for moe in ("moe0", "moe1"):
        stats = wf.fused_step.unit_stats["train"][moe]
        assert int(stats["moe_rows"]) == int(stats["moe_routed"]) \
            == 8 * 64 * 2
