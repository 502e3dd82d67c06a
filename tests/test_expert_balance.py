"""The expert block's balancing update (``bias_update_rate``): the rule
by hand, that it evens out a lopsided router, that both trainers apply
it once a train step and never in evaluation, and that at rate 0 the
bias is the untouched buffer it was."""

import jax
import jax.numpy as jnp
import numpy
import pytest

from veles_tpu.backends import Device
from veles_tpu.prng import RandomGenerator
from veles_tpu.workflow import Workflow
from veles_tpu.znicz import transformer
from veles_tpu.znicz.standard_workflow import StandardWorkflow

from test_remat_saves import SEQ, TokenLoader

HIDDEN, EXPERTS, TOP = 16, 8, 2


def expert_block(**kwargs):
    unit = transformer.ExpertBlock(
        Workflow(name="balance"), name="moe", seed=11, hidden_size=HIDDEN,
        moe_intermediate_size=8, n_routed_experts=EXPERTS,
        num_experts_per_tok=TOP, **kwargs)
    unit.init_params()
    return unit, dict(unit.params)


def tokens(n=64):
    return jax.random.normal(jax.random.key(2), (2, n, HIDDEN))


def test_the_update_is_its_rule():
    unit, params = expert_block(bias_update_rate=0.01, experts_held=2,
                                experts_offset=4)
    assert unit.stats_shapes()["router_load"] == (EXPERTS,)
    x = tokens()
    _, stats = unit.apply_stats(params, x)
    # the load: every token's every choice, over ALL routed experts
    chosen, _ = unit.route(params, unit._norm(
        x, params["norm"]).reshape(-1, HIDDEN))
    load = numpy.bincount(numpy.asarray(chosen).ravel(), minlength=EXPERTS)
    assert numpy.array_equal(stats["router_load"], load)
    assert load.sum() == 2 * 64 * TOP
    assert numpy.array_equal(stats["expert_tokens"], load[4:6])
    (name, new), = unit.update_buffers(params, stats).items()
    assert name == "router_bias"
    want = numpy.asarray(params["router_bias"]) + 0.01 * numpy.sign(
        load.mean() - load)
    numpy.testing.assert_allclose(new, want, rtol=0, atol=1e-9)
    # an expert at the mean stays where it is
    level = dict(stats, router_load=jnp.full((EXPERTS,), 32, jnp.int32))
    assert numpy.array_equal(
        unit.update_buffers(params, level)["router_bias"],
        params["router_bias"])


def test_at_rate_zero_the_bias_is_a_buffer():
    unit, params = expert_block()
    assert unit.bias_update_rate == 0.0
    assert sorted(unit.stats_shapes()) == ["expert_tokens", "moe_routed",
                                           "moe_rows", "moe_spilled"]
    _, stats = unit.apply_stats(params, tokens())
    assert "router_load" not in stats
    assert unit.update_buffers(params, stats) == {}


def test_the_update_evens_out_a_lopsided_router():
    """A bias that sends every token to two experts: the rule walks it
    back until every expert is within 15 % of the mean load, and the
    scores the outputs are weighed by never see the bias."""
    unit, params = expert_block(bias_update_rate=0.005, weights_stddev=0.5)
    params["router_bias"] = jnp.zeros(EXPERTS).at[:TOP].set(1.0)
    x = tokens(256)
    step = jax.jit(lambda p: unit.update_buffers(
        p, unit.apply_stats(p, x)[1]))
    load = unit.apply_stats(params, x)[1]["router_load"]
    assert int(load[:TOP].sum()) == 2 * 256 * TOP       # all of them
    for _ in range(300):
        params = dict(params, **step(params))
    load = numpy.asarray(unit.apply_stats(params, x)[1]["router_load"])
    assert load.max() < 1.15 * load.mean() and load.min() > 0.85 * load.mean()


def decoder(scan, rate):
    def unit(kind, name, **forward):
        forward.update(hidden_size=HIDDEN, name=name)
        return {"type": kind, "->": forward, "<-": {"learning_rate": 0.05}}
    wf = StandardWorkflow(
        None, name="balance", loader_factory=TokenLoader,
        loader={"minibatch_size": 4, "normalization_type": "none",
                "prng": RandomGenerator().seed(5)},
        layers=[unit("token_embedding", "embed", vocab_size=32),
                unit("expert_block", "moe0", moe_intermediate_size=8,
                     n_routed_experts=EXPERTS, num_experts_per_tok=TOP,
                     experts_held=4, bias_update_rate=rate),
                unit("lm_head", "head", vocab_size=32)],
        loss_function="token", decision={"max_epochs": 3, "silent": True},
        fused=True, epoch_scan=scan, trainer={"compute_dtype": "float32"})
    wf.initialize(device=Device(backend="cpu"))
    return wf


@pytest.mark.parametrize("scan", [False, True], ids=["step", "scan"])
def test_both_trainers_apply_it_once_a_train_step(scan):
    wf = decoder(scan, 0.001)
    step = wf.fused_step
    start = numpy.array(step._params_[1]["router_bias"])
    # one train step by hand: the bias moves by the rate against the load
    # of that step's tokens, the solver's state of it stays zero
    ids = jnp.asarray(wf.loader.original_data.mem[4:8])
    labels = jnp.asarray(wf.loader.original_targets.mem[4:8])
    params = jax.tree.map(jnp.array, step._params_)
    opt = [{n: gd.solver.init(p, jnp) for n, p in layer.items()}
           for gd, layer in zip(step.gd_units, params)]
    _, stats = wf.forwards[1].apply_stats(
        params[1], wf.forwards[0].apply(params[0], ids))
    load = numpy.asarray(stats["router_load"], numpy.float64)
    new, opt, macc, _, _ = step._step_fns_[0](
        params, opt, step._macc_init(), ids, labels, numpy.int32(4), None,
        1.0)
    numpy.testing.assert_allclose(
        new[1]["router_bias"], start + 0.001 * numpy.sign(
            load.mean() - load), rtol=0, atol=1e-9)
    assert not any(numpy.asarray(s).any() for s in opt[1]["router_bias"])
    assert not numpy.array_equal(new[1]["router"], params[1]["router"])
    assert numpy.array_equal(macc["units"]["moe0"]["router_load"], load)
    # evaluation counts and moves nothing
    macc, _, _ = step._step_fns_[1](new, step._macc_init(), ids, labels,
                                    numpy.int32(4))
    assert int(macc["units"]["moe0"]["router_load"].sum()) \
        == 4 * SEQ * TOP
    # three epochs of two train steps: six moves of at most the rate,
    # and the counters filed per class
    wf.run()
    moved = numpy.abs(numpy.asarray(step._params_[1]["router_bias"])
                      - start) / 0.001
    assert moved.max() <= 6 + 1e-3 and moved.max() >= 1 - 1e-3
    numpy.testing.assert_allclose(moved, numpy.round(moved), atol=1e-3)
    stats = step.unit_stats
    assert int(stats["train"]["moe0"]["router_load"].sum()) \
        == 3 * 8 * SEQ * TOP
    assert int(stats["validation"]["moe0"]["router_load"].sum()) \
        == 3 * 4 * SEQ * TOP


def test_training_at_rate_zero_leaves_the_bias_alone():
    wf = decoder(True, 0.0)
    start = numpy.array(wf.fused_step._params_[1]["router_bias"])
    wf.run()
    assert numpy.array_equal(wf.fused_step._params_[1]["router_bias"],
                             start)
    assert "router_load" not in wf.fused_step.unit_stats["train"]["moe0"]


def test_a_share_leaves_its_router_out_of_the_backward_pass():
    """``train_router`` False: the same output; no gradient for the
    router matrix, and none through it into the block's input; the
    experts' own gradients are what they were."""
    taught, params = expert_block(weights_stddev=0.5, experts_held=4)
    share, _ = expert_block(weights_stddev=0.5, experts_held=4,
                            train_router=False)
    assert taught.train_router and not share.train_router
    x = tokens()

    def loss(unit):
        return lambda p, x: jnp.sum(jnp.sin(unit.apply(p, x)))
    assert numpy.array_equal(taught.apply(params, x), share.apply(params, x))
    g_taught, gx_taught = jax.grad(loss(taught), (0, 1))(params, x)
    g_share, gx_share = jax.grad(loss(share), (0, 1))(params, x)
    assert numpy.asarray(g_taught["router"]).any()
    assert not numpy.asarray(g_share["router"]).any()
    for name in ("experts_gate_up", "experts_down"):
        numpy.testing.assert_allclose(g_share[name], g_taught[name],
                                      rtol=1e-5, atol=1e-7)
    assert not numpy.allclose(gx_share, gx_taught, atol=1e-4)
    # what is left of the input's gradient: the residual and the held
    # experts' rows under constant weights
    chosen, weights = share.route(params, share._norm(
        x, params["norm"]).reshape(-1, HIDDEN))
    constant = jax.grad(lambda x: jnp.sum(jnp.sin(
        x + _experts_by_hand(share, params, x, chosen, weights))))(x)
    numpy.testing.assert_allclose(gx_share, constant, rtol=1e-4, atol=1e-6)


def _experts_by_hand(unit, params, x, chosen, weights):
    """sum over a token's choices on held experts of weight x expert,
    the weights given (constants), one expert at a time."""
    h = unit._norm(x, params["norm"]).reshape(-1, HIDDEN)
    f = unit.width
    y = jnp.zeros_like(h)
    for e in range(unit.held):
        gate_up = h @ params["experts_gate_up"][e]
        out = (jax.nn.silu(gate_up[:, :f]) * gate_up[:, f:]) \
            @ params["experts_down"][e]
        w = jnp.where(chosen == e + unit.offset, weights, 0.0).sum(-1)
        y = y + w[:, None] * out
    return y.reshape(x.shape)
