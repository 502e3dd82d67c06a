"""What a checkpointed unit keeps across its ``jax.checkpoint``
(``fused.applier``; a unit's ``remat`` and ``remat_saves``): the flash
kernels' forward rules name their output and row statistics, the
attention block's checkpoint saves those two names, and the backward
pass then reruns the projections around the kernel but not the forward
kernel.  Counted in the jaxpr of the gradient, compared bit for bit with
the bare checkpoint and with none, and read back from the record the
step files when it is built (span ``step.remat``).  All on the CPU, the
kernels in interpret mode."""

import collections

import jax
import jax.numpy as jnp
import numpy
import pytest

from veles_tpu.backends import Device
from veles_tpu.loader.fullbatch import FullBatchLoaderMSE
from veles_tpu.logger import events
from veles_tpu.prng import RandomGenerator
from veles_tpu.workflow import Workflow
from veles_tpu.znicz import flash_attention, fused, transformer
from veles_tpu.znicz.standard_workflow import StandardWorkflow

from test_place_data import images
from test_spans import named

HIDDEN, SEQ = 32, 64
ATTENTION = {"hidden_size": HIDDEN, "num_attention_heads": 2,
             "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
             "v_head_dim": 16, "kv_lora_rank": 16, "use_pallas": True}


def latent_block():
    """A ``LatentAttentionBlock`` on the kernels, with weights."""
    block = transformer.LatentAttentionBlock(
        Workflow(name="remat"), name="attn", seed=3, weights_stddev=0.2,
        **ATTENTION)
    block.init_params()
    return block


class PlainAttention:
    """A stand-in unit around the plain family's ``flash_attention``,
    declaring what ``LatentAttentionBlock`` declares."""

    remat = True
    remat_saves = flash_attention.SAVED_NAMES

    def __init__(self):
        self.params = {"wqkv": 0.2 * jax.random.normal(
            jax.random.key(1), (HIDDEN, 3, 2, 16))}

    def apply(self, params, x):
        q, k, v = jnp.moveaxis(
            jnp.einsum("bsd,dchk->bschk", x, params["wqkv"]), 2, 0)
        out = flash_attention.flash_attention(
            q, k, v, causal=True, block_q=32, block_k=32)
        return x + jnp.tanh(out.reshape(x.shape))


FAMILIES = {
    "latent": (latent_block, ("mla_flash_fwd", "mla_flash_dq",
                              "mla_flash_dkv")),
    "plain": (PlainAttention, ("gqa_flash_fwd", "gqa_flash_dq",
                               "gqa_flash_dkv")),
}


def activations():
    return jax.random.normal(jax.random.key(2), (2, SEQ, HIDDEN))


def value_and_grads(fn):
    """``jax.value_and_grad`` of a scalar of ``fn(params, x)``, with
    respect to both, as a function of ``(params, x)``."""
    weight = jnp.cos(jnp.arange(float(HIDDEN)))
    return jax.value_and_grad(
        lambda params, x: (fn(params, x) * weight).sum(), argnums=(0, 1))


def kernel_calls(fn, unit):
    """{kernel: times it is called} in the jaxpr of the gradient."""
    jaxpr = jax.make_jaxpr(value_and_grads(fn))(
        unit.params, activations())
    return collections.Counter(
        eqn.params["name"] or eqn.params["jaxpr"].debug_info.func_name
        for eqn in fused.jaxpr_equations(jaxpr.jaxpr)
        if eqn.primitive.name == "pallas_call")


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_the_forward_kernel_runs_once_in_a_train_step(family):
    make, (fwd, dq, dkv) = FAMILIES[family]
    unit = make()
    assert kernel_calls(fused.applier(unit), unit) == {fwd: 1, dq: 1,
                                                       dkv: 1}
    # what the count above can fail on: all of the block recomputed
    assert kernel_calls(jax.checkpoint(unit.apply), unit) == {
        fwd: 2, dq: 1, dkv: 1}
    assert kernel_calls(unit.apply, unit) == {fwd: 1, dq: 1, dkv: 1}


def test_the_attention_block_names_what_its_kernel_names():
    assert transformer.LatentAttentionBlock.remat_saves \
        == flash_attention.MLA_SAVED_NAMES
    assert transformer.LatentAttentionBlock.remat
    for cls in (transformer.GatedMLPBlock, transformer.ExpertBlock):
        assert cls.remat and cls.remat_saves == ()
    for cls in (transformer.TokenEmbedding, transformer.NormHead):
        assert not cls.remat


def checkpoints(fn, unit):
    """The ``policy`` of every checkpoint in ``fn``'s jaxpr."""
    jaxpr = jax.make_jaxpr(fn)(unit.params, activations())
    return [eqn.params["policy"]
            for eqn in fused.jaxpr_equations(jaxpr.jaxpr)
            if eqn.primitive.name in ("checkpoint", "remat", "remat2")]


@pytest.mark.parametrize("declared", ["saves_names", "remat_alone",
                                      "no_remat"])
def test_applier_builds_what_the_unit_declares(declared, monkeypatch):
    unit = latent_block()
    if declared != "saves_names":
        monkeypatch.setattr(type(unit), "remat_saves", ())
    monkeypatch.setattr(type(unit), "remat", declared != "no_remat")
    fn = fused.applier(unit)
    if declared == "no_remat":
        assert fn == unit.apply                 # handed back unwrapped
        assert checkpoints(fn, unit) == []
    elif declared == "remat_alone":
        assert checkpoints(fn, unit) == [None]  # jax.checkpoint(fn)
    else:
        (policy,) = checkpoints(fn, unit)
        assert policy is not None
    # the same numbers whatever is kept, to the last bit (operation by
    # operation: under one jit XLA fuses the two programs' sums apart)
    x = activations()
    got = value_and_grads(fn)(unit.params, x)
    want = value_and_grads(unit.apply)(unit.params, x)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert numpy.array_equal(numpy.asarray(g), numpy.asarray(w))
    assert float(jnp.abs(got[1][1]).max()) > 0


# -- the record a step files when it is built ----------------------------------

class TokenLoader(FullBatchLoaderMSE):
    """Twelve sequences of ``SEQ`` int32 token ids and their next
    tokens."""

    def __init__(self, workflow, **kwargs):
        kwargs["dtype"] = "int32"
        super().__init__(workflow, **kwargs)

    def load_data(self):
        ids = numpy.random.RandomState(3).randint(
            0, 32, (12, SEQ + 1)).astype(numpy.int32)
        self.original_data.mem = ids[:, :-1]
        self.original_targets.mem = ids[:, 1:]
        self.class_lengths[:] = [0, 4, 8]

    def analyze_dataset(self):
        pass        # ids are served as they are


def decoder(scan, compute_dtype):
    """Embedding, two attention blocks on the kernels with a dense block
    between them, the head: initialized on the CPU."""
    def unit(kind, name, **forward):
        forward.update(hidden_size=HIDDEN, name=name)
        return {"type": kind, "->": forward, "<-": {"learning_rate": 0.05}}
    attention = {k: v for k, v in ATTENTION.items() if k != "hidden_size"}
    wf = StandardWorkflow(
        None, name="decoder", loader_factory=TokenLoader,
        loader={"minibatch_size": 4, "normalization_type": "none",
                "prng": RandomGenerator().seed(5)},
        layers=[unit("token_embedding", "embed", vocab_size=32),
                unit("latent_attention_block", "attn0", **attention),
                unit("gated_mlp_block", "mlp0", intermediate_size=24),
                unit("latent_attention_block", "attn1", **attention),
                unit("lm_head", "head", vocab_size=32)],
        loss_function="token", decision={"max_epochs": 1, "silent": True},
        fused=True, epoch_scan=scan,
        trainer={"compute_dtype": compute_dtype})
    wf.initialize(device=Device(backend="cpu"))
    return wf


@pytest.mark.parametrize("scan,compute_dtype,itemsize", [
    (False, "float32", 4), (True, "bfloat16", 2)])
def test_a_decoder_step_files_what_its_checkpoints_keep(
        scan, compute_dtype, itemsize):
    events.reset()
    wf = decoder(scan, compute_dtype)
    (span,) = named(events.spans(), "step.remat")
    assert span.info["units"] == "attn0,mlp0,attn1"
    assert span.info["saves"] == (
        "attn0:mla_flash_out+mla_flash_lse "
        "attn1:mla_flash_out+mla_flash_lse")
    # a block: the output [4, 2, SEQ, 16] in the chain's arithmetic and
    # the row statistics [4 * 2, SEQ, 1] float32
    assert span.info["bytes"] == 2 * (4 * 2 * SEQ * 16 * itemsize
                                      + 4 * 2 * SEQ * 4)
    # filed while the step was built, beside the rest of its set-up
    (init,) = named(events.spans(), "workflow.initialize")
    assert init.start_ns <= span.start_ns \
        and span.start_ns + span.duration_ns \
        <= init.start_ns + init.duration_ns
    wf.run()
    assert len(named(events.spans(), "step.remat")) == 1
    assert numpy.isfinite(float(wf.fused_step.loss))


def test_a_chain_without_checkpoints_files_nothing():
    events.reset()
    wf = images()
    assert not any(getattr(f, "remat", False) for f in wf.forwards)
    assert named(events.spans(), "step.remat") == []
    assert named(events.spans(), "workflow.initialize")
