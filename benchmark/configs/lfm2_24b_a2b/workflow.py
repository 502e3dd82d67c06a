"""The benchmark's decoder workflow for ``config.json`` beside it, as a
user of veles-tpu writes one:

    python -m veles_tpu benchmark/configs/lfm2_24b_a2b/workflow.py \
        --mode scan --compute-dtype bfloat16

A ``StandardWorkflow`` whose ``layers`` are the transformer units of
``veles_tpu/znicz/transformer.py`` — token embedding, then for each entry
of ``layer_types`` an operator block (``conv``: the gated short
convolution; ``full_attention``: grouped-query attention with per-head
q/k norms) followed by a gated-MLP block (the first ``num_dense_layers``
layers) or an expert block, a normalised head over the vocabulary slice
— each configured with ``config.json``'s own keys, AdamW on every unit,
the ``token`` loss.  The benchmark's own (the loader and the clock are
those of ``configs/kanana2_30b_a3b/workflow.py``, copied: files under
``benchmark/`` are not imported across configurations):

- the loader makes the token ids on the device from
  ``root.lfm2_bench.loader.seed``: Zipf-distributed over the slice's ids
  (rank r with probability proportional to ``r ** -exponent``), so the
  routing is uneven as on text; the labels are the next token;
- a ``LearningRateAdjuster`` for the linear warm-up ``config.json``
  assumes (``solver.warmup_steps``);
- an :class:`EpochClock` unit linked after the decision, which reads the
  host clock at every epoch's end and stops the workflow when the
  driver's window closes.

``root.lfm2_bench.model`` holds the model's keys, so a test or a
rehearsal shrinks them with ``root.lfm2_bench.model.hidden_size=64`` on
the command line like any other setting.
"""

import json
import os
import time

from veles_tpu.config import root
from veles_tpu.loader.base import TEST, TRAIN, VALID
from veles_tpu.loader.fullbatch import FullBatchLoaderMSE
from veles_tpu.units import Unit
from veles_tpu.znicz import transformer             # noqa: F401 registers
from veles_tpu.znicz.lr_adjust import LearningRateAdjuster
from veles_tpu.znicz.samples import build_standard

with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "config.json")) as _f:
    CONFIG = json.load(_f)

#: the keys of config.json the units read (``rope_theta`` from its
#: ``rope_parameters`` group)
MODEL_KEYS = (
    "hidden_size", "intermediate_size", "moe_intermediate_size",
    "num_attention_heads", "num_key_value_heads", "head_dim",
    "conv_L_cache", "norm_eps", "layer_types", "num_dense_layers",
    "num_experts", "router_width", "experts_offset",
    "num_experts_per_tok", "routed_scaling_factor", "norm_topk_prob",
    "norm_topk_eps", "bias_update_rate", "train_router", "vocab_size")

root.lfm2_bench.update({
    "loader": {"minibatch_size": 2, "normalization_type": "none",
               "n_train": CONFIG["data"]["n_train"],
               "n_valid": CONFIG["data"]["n_valid"],
               "sequence_length": CONFIG["data"]["sequence_length"],
               "zipf_exponent": CONFIG["data"]["zipf_exponent"],
               "seed": 0},
    "model": dict({key: CONFIG[key] for key in MODEL_KEYS},
                  rope_theta=CONFIG["rope_parameters"]["rope_theta"]),
    "solver": dict(CONFIG["solver"]),
    "init": dict(CONFIG["init"]),
    # out of reach: the clock ends the run, never the decision
    "decision": {"max_epochs": 10 ** 9, "fail_iterations": 10 ** 9,
                 "silent": True},
})

#: a published layer is an operator block, then a feed-forward block:
#: ``layer_types`` entry -> (unit, its name, the model keys it reads)
OPERATORS = {
    "conv": ("short_conv_block", "conv", ("conv_L_cache",)),
    "full_attention": ("gqa_attention_block", "attn", (
        "num_attention_heads", "num_key_value_heads", "head_dim",
        "rope_theta"))}


def layers(model, solver, init, seed):
    """The ``layers`` list of the decoder ``model`` describes."""
    solver = dict(solver)
    solver.pop("warmup_steps", None)        # the schedule is a unit's
    backward = {"solver": solver.pop("name"),
                "learning_rate": solver.pop("learning_rate"),
                "learning_rate_bias": 0.0, "weights_decay": 0.0,
                "solver_parameters": solver}

    def unit(kind, index, **forward):
        forward.setdefault("weights_stddev", init["weights_stddev"])
        forward.update(rms_norm_eps=model["norm_eps"],
                       hidden_size=model["hidden_size"],
                       seed=(int(seed) << 8) + index,
                       name="%s%d" % (forward.pop("name"), index))
        return {"type": kind, "->": forward, "<-": dict(backward)}
    out = [unit("token_embedding", 0, name="embed",
                vocab_size=model["vocab_size"],
                weights_stddev=init["embedding_stddev"])]
    for i, kind in enumerate(model["layer_types"]):
        operator, name, keys = OPERATORS[kind]
        out.append(unit(operator, i, name=name,
                        **{k: model[k] for k in keys}))
        if i < model["num_dense_layers"]:
            out.append(unit("gated_mlp_block", i, name="mlp",
                            intermediate_size=model["intermediate_size"]))
        else:
            out.append(unit(
                "expert_block", i, name="moe",
                # the router keeps its published width; the chip holds
                # num_experts of them from experts_offset
                n_routed_experts=model["router_width"],
                experts_held=model["num_experts"],
                experts_offset=model["experts_offset"],
                bias_stddev=init["router_bias_stddev"],
                moe_intermediate_size=model["moe_intermediate_size"],
                n_shared_experts=0,
                **{k: model[k] for k in (
                    "num_experts_per_tok", "routed_scaling_factor",
                    "norm_topk_prob", "norm_topk_eps",
                    "bias_update_rate", "train_router")}))
    out.append(unit("lm_head", 0, name="head",
                    vocab_size=model["vocab_size"]))
    return out


class DeviceTokenLoader(FullBatchLoaderMSE):
    """Sequences of Zipf-distributed token ids and their next tokens,
    made on the device from a seed and kept resident in HBM."""

    MAPPING = "benchmark_lfm2_token_loader"

    def __init__(self, workflow, **kwargs):
        self.n_train = int(kwargs.pop("n_train"))
        self.n_valid = int(kwargs.pop("n_valid"))
        self.sequence_length = int(kwargs.pop("sequence_length"))
        self.vocab_size = int(kwargs.pop("vocab_size"))
        self.zipf_exponent = float(kwargs.pop("zipf_exponent"))
        self.seed = int(kwargs.pop("seed"))
        kwargs["dtype"] = "int32"
        super().__init__(workflow, **kwargs)

    def load_data(self):
        import jax
        import jax.numpy as jnp
        n, s = self.n_train + self.n_valid, self.sequence_length
        vocab, exponent = self.vocab_size, self.zipf_exponent
        # two 32-bit words: --seed may be wider than int32
        key = jnp.asarray([self.seed >> 32 & 0xFFFFFFFF,
                           self.seed & 0xFFFFFFFF], jnp.uint32)

        def draw(k):
            weight = jnp.arange(1, vocab + 1, dtype=jnp.float32) ** -exponent
            cdf = jnp.cumsum(weight) / weight.sum()
            u = jax.random.uniform(
                jax.random.wrap_key_data(k, impl="threefry2x32"),
                (n, s + 1), jnp.float32)
            ids = jnp.minimum(jnp.searchsorted(cdf, u), vocab - 1)
            ids = ids.astype(jnp.int32)
            return ids[:, :-1], ids[:, 1:]
        self.original_data.devmem, self.original_targets.devmem = \
            jax.jit(draw)(key)
        self.class_lengths[TEST] = 0
        self.class_lengths[VALID] = self.n_valid
        self.class_lengths[TRAIN] = self.n_train

    def analyze_dataset(self):
        pass        # ids are served as they were made

    prepare_restored_dataset = analyze_dataset


class EpochClock(Unit):
    """Host-clock reading at each epoch's end, after the parameters the
    epoch produced are ready on the device.  ``on_epoch(clock)`` is the
    driver's hook; it returns True to stop the workflow."""

    def __init__(self, workflow, **kwargs):
        super().__init__(workflow, **kwargs)
        self.on_epoch = None
        self.epoch_ends = []        # time.perf_counter() per epoch

    def run(self):
        import jax
        wf = self._workflow
        if not bool(wf.loader.epoch_ended):
            return
        jax.block_until_ready(wf.fused_step._params_)
        self.epoch_ends.append(time.perf_counter())
        if self.on_epoch is not None and self.on_epoch(self):
            wf.stop()


def create_workflow(**overrides):
    cfg = root.lfm2_bench
    model = cfg.model.todict()
    overrides["loader"] = dict(overrides.get("loader", {}),
                               vocab_size=model["vocab_size"])
    wf = build_standard(
        cfg, "Lfm2DecoderBench", DeviceTokenLoader, "token",
        layers=layers(model, cfg.solver.todict(), cfg.init.todict(),
                      cfg.loader.get("seed", 0)),
        **overrides)
    wf.epoch_clock = EpochClock(wf, name="epoch_clock")
    wf.epoch_clock.link_from(wf.decision)
    warmup = int(cfg.solver.get("warmup_steps", 0))
    if warmup:
        # linear warm-up to the peak rate, set once an epoch through the
        # step's dynamic lr_scale (an argument of the jitted scan: no
        # retrace); at the peak rate from the first step the router
        # collapses onto a few experts within 50 steps
        steps = -(-int(cfg.loader.n_train) // int(cfg.loader.minibatch_size))
        points = [(epoch, min(1.0, (epoch + 1) * steps / warmup))
                  for epoch in range(-(-warmup // steps))]
        wf.lr_adjuster = LearningRateAdjuster(
            wf, policy="arbitrary", points=points, name="lr_adjuster")
        wf.lr_adjuster.link_from(wf.decision)
        wf.lr_adjuster.link_loader(wf.loader)
        wf.lr_adjuster.link_fused(wf.fused_step)
        wf.fused_step.lr_scale = points[0][1]
    return wf


def run(load, main):
    load(create_workflow)
    main()
