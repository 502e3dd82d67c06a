"""The expert block's compact row buffer (``ExpertBlock.buffer_rows``):
a buffer of a chip's share of the rows against one of every token's
every choice, the step whose rows exceed the buffer, what
the rows the grouped product leaves unwritten may hold, what the lowered
block contains, and both trainers through it.  CPU, tiny shapes, kernels
in interpret mode."""

import jax
import jax.numpy as jnp
import numpy
import pytest

from veles_tpu.backends import Device
from veles_tpu.prng import RandomGenerator
from veles_tpu.workflow import Workflow
from veles_tpu.znicz import gemm, transformer
from veles_tpu.znicz.standard_workflow import StandardWorkflow

from test_remat_saves import SEQ, TokenLoader

HIDDEN, EXPERTS, HELD, TOKENS = 16, 16, 2, 640


def expert_block(top, shared=0, held=HELD, **kwargs):
    unit = transformer.ExpertBlock(
        Workflow(name="compact"), name="moe", seed=11, hidden_size=HIDDEN,
        moe_intermediate_size=8, n_routed_experts=EXPERTS,
        num_experts_per_tok=top, n_shared_experts=shared, experts_held=held,
        experts_offset=4 if held < EXPERTS else 0, weights_stddev=0.3,
        **kwargs)
    unit.init_params()
    return unit, dict(unit.params)


def at_the_full_bound(unit):
    """The same block with a buffer of every token's every choice."""
    unit.buffer_rows = lambda tokens: tokens * unit.top_k
    return unit


def tokens():
    return jax.random.normal(jax.random.key(2), (2, TOKENS // 2, HIDDEN))


def crowded(params):
    """A bias under which every token chooses the held experts first."""
    bias = jnp.zeros(EXPERTS).at[4:4 + HELD].set(4.0)
    return dict(params, router_bias=bias)


def value_and_grads(unit, params, x):
    weight = jnp.cos(jnp.arange(x.size, dtype=jnp.float32)).reshape(x.shape)
    (_, (y, stats)), grads = jax.value_and_grad(
        lambda p, x: (lambda out: ((out[0] * weight).sum(), out))(
            unit.apply_stats(p, x)), argnums=(0, 1), has_aux=True)(params, x)
    return y, stats, grads


def primitives(jaxpr):
    """Every primitive's name in ``jaxpr`` and the jaxprs inside it, a
    kernel's own body left out."""
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name
        if eqn.primitive.name == "pallas_call":
            continue
        for value in eqn.params.values():
            for inner in (value if isinstance(value, (tuple, list))
                          else (value,)):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from primitives(inner)


@pytest.mark.parametrize("tokens, top, held, experts, want", [
    (16384, 6, 16, 128, 24576),      # kanana2_scan_seq8k: of 98,304
    (16384, 4, 8, 64, 16384),        # lfm2_scan_seq8k: of 65,536
    (16384, 4, 64, 64, 65536),       # every expert held: today's buffer
    (256, 4, 2, 16, 512),            # whole row tiles
    (64, 4, 2, 16, 256)])           # never more than the full bound
def test_the_buffer_is_twice_a_chips_share_in_whole_tiles(
        tokens, top, held, experts, want):
    unit = transformer.ExpertBlock(
        Workflow(name="compact"), name="moe", hidden_size=HIDDEN,
        moe_intermediate_size=8, n_routed_experts=experts,
        num_experts_per_tok=top, experts_held=held)
    assert unit.buffer_rows(tokens) == want


@pytest.mark.parametrize("taught", [True, False], ids=["taught", "constant"])
@pytest.mark.parametrize("shared", [0, 2], ids=["routed", "shared"])
@pytest.mark.parametrize("top", [4, 6])
def test_the_compact_buffer_gives_what_the_full_bound_gives(top, shared, taught):
    unit, params = expert_block(top, shared, train_router=taught)
    x = tokens()
    assert unit.buffer_rows(TOKENS) == 1024 < TOKENS * top
    y, stats, grads = value_and_grads(unit, params, x)
    assert int(stats["moe_spilled"]) == 0
    assert 0 < int(stats["moe_rows"]) == int(stats["moe_routed"]) <= 1024
    full_y, full_stats, full_grads = value_and_grads(
        at_the_full_bound(unit), params, x)
    assert int(full_stats["moe_spilled"]) == 0
    numpy.testing.assert_allclose(y, full_y, rtol=0, atol=1e-6)
    for name, want in full_grads[0].items():
        numpy.testing.assert_allclose(grads[0][name], want, rtol=1e-5,
                                      atol=1e-5, err_msg=name)
    numpy.testing.assert_allclose(grads[1], full_grads[1], rtol=1e-5,
                                  atol=1e-5)
    assert bool(jnp.any(grads[0]["router"] != 0)) == taught
    for name in ("experts_gate_up", "experts_down", "norm"):
        assert bool(jnp.any(grads[0][name] != 0)), name


@pytest.mark.parametrize("top", [4, 6])
def test_a_step_over_the_buffer_runs_block_after_block(top):
    unit, params = expert_block(top, shared=1)
    params, x = crowded(params), tokens()
    y, stats, grads = value_and_grads(unit, params, x)
    # every token's first two choices are the two held experts: 640 rows
    # each, so the second group lies across the first block's end
    assert int(stats["moe_rows"]) == int(stats["moe_routed"]) \
        == TOKENS * HELD > unit.buffer_rows(TOKENS)
    assert int(stats["moe_spilled"]) == 1
    full_y, full_stats, full_grads = value_and_grads(
        at_the_full_bound(unit), params, x)
    assert int(full_stats["moe_spilled"]) == 0
    for got, want in zip(jax.tree.leaves((y, grads)),
                         jax.tree.leaves((full_y, full_grads))):
        numpy.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("spilled", [False, True], ids=["compact", "spilled"])
def test_rows_the_grouped_product_leaves_unwritten_are_never_read(
        monkeypatch, spilled):
    """On the chip the rows past the last group hold whatever the buffer
    held: NaN there, in the products and in the rows' cotangents, must
    reach neither the output nor a gradient."""
    real = gemm.grouped_matmul

    def poisoned(lhs, rhs, sizes):
        def poison(rows):
            filled = jnp.arange(rows.shape[0]) < sizes.sum()
            return jnp.where(filled[:, None], rows, jnp.nan)

        @jax.custom_vjp
        def product(lhs, rhs):
            return poison(real(lhs, rhs, sizes))

        def fwd(lhs, rhs):
            out, vjp = jax.vjp(lambda a, b: real(a, b, sizes), lhs, rhs)
            return poison(out), vjp

        def bwd(vjp, g):
            g_lhs, g_rhs = vjp(g)
            return poison(g_lhs), g_rhs
        product.defvjp(fwd, bwd)
        return product(lhs, rhs)
    unit, params = expert_block(4, shared=1)
    if spilled:
        params = crowded(params)
    want = value_and_grads(unit, params, tokens())
    monkeypatch.setattr(gemm, "grouped_matmul", poisoned)
    got = value_and_grads(unit, params, tokens())
    assert int(got[1]["moe_spilled"]) == int(spilled)
    assert int(got[1]["moe_rows"]) < TOKENS * 4       # some rows are empty
    for g, w in zip(jax.tree.leaves((got[0], got[2])),
                    jax.tree.leaves((want[0], want[2]))):
        assert bool(jnp.isfinite(g).all())
        numpy.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("held, loops", [(EXPERTS, 0), (HELD, 1)],
                         ids=["all-held", "a-share"])
def test_a_block_that_holds_every_expert_lowers_to_no_loop(held, loops):
    """One instance of the routine either way: a share's inside a loop
    over the blocks that hold rows, and never a ``cond`` between two."""
    unit, params = expert_block(4, held=held)
    x = tokens()
    forward = list(primitives(jax.make_jaxpr(unit.apply)(params, x).jaxpr))
    assert (forward.count("while"), forward.count("cond")) == (loops, 0)
    assert forward.count("pallas_call") == 2
    y, vjp = jax.vjp(unit.apply, params, x)
    backward = list(primitives(jax.make_jaxpr(vjp)(jnp.ones_like(y)).jaxpr))
    assert (backward.count("while"), backward.count("cond")) == (loops, 0)


@pytest.mark.parametrize("top", [4, 6])
def test_the_backward_pass_sorts_nothing(top):
    """The sort and its inverse cross from the forward pass; the backward
    pass makes neither again."""
    unit, params = expert_block(top, shared=1)
    x = tokens()
    assert "sort" in set(primitives(
        jax.make_jaxpr(unit.apply)(params, x).jaxpr))
    y, vjp = jax.vjp(unit.apply, params, x)
    backward = set(primitives(jax.make_jaxpr(vjp)(jnp.ones_like(y)).jaxpr))
    assert "sort" not in backward and "gather" in backward


def trained(scan, monkeypatch=None):
    """(the expert block's parameters, the step's counters) after three
    epochs of a decoder whose expert block holds 2 of 16 experts."""
    if monkeypatch:
        monkeypatch.setattr(transformer.ExpertBlock, "buffer_rows",
                            lambda self, tokens: tokens * self.top_k)

    def unit(kind, name, **forward):
        forward.update(hidden_size=HIDDEN, name=name)
        return {"type": kind, "->": forward, "<-": {"learning_rate": 0.05}}
    wf = StandardWorkflow(
        None, name="compact", loader_factory=TokenLoader,
        loader={"minibatch_size": 4, "normalization_type": "none",
                "prng": RandomGenerator().seed(5)},
        layers=[unit("token_embedding", "embed", vocab_size=32),
                unit("expert_block", "moe0", moe_intermediate_size=8,
                     n_routed_experts=EXPERTS, num_experts_per_tok=4,
                     experts_held=HELD, experts_offset=4,
                     weights_stddev=0.3),
                unit("lm_head", "head", vocab_size=32)],
        loss_function="token", decision={"max_epochs": 3, "silent": True},
        fused=True, epoch_scan=scan, trainer={"compute_dtype": "float32"})
    wf.initialize(device=Device(backend="cpu"))
    assert wf.forwards[1].buffer_rows(4 * SEQ) == (
        4 * SEQ * 4 if monkeypatch else 512)
    wf.run()
    step = wf.fused_step
    return jax.tree.map(numpy.asarray, step._params_[1]), step.unit_stats


@pytest.mark.parametrize("scan", [False, True], ids=["step", "scan"])
def test_both_trainers_train_through_the_compact_buffer(scan, monkeypatch):
    params, stats = trained(scan)
    for cls, steps in (("train", 6), ("validation", 3)):
        moe = stats[cls]["moe0"]
        assert int(moe["moe_spilled"]) == 0
        assert 0 < int(moe["moe_rows"]) == int(moe["moe_routed"]) \
            <= steps * 512
    full, full_stats = trained(scan, monkeypatch)
    assert int(full_stats["train"]["moe0"]["moe_rows"]) \
        == int(stats["train"]["moe0"]["moe_rows"])
    for name, want in full.items():
        numpy.testing.assert_allclose(params[name], want, rtol=1e-4,
                                      atol=1e-5, err_msg=name)
