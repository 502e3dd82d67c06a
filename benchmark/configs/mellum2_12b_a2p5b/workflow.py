"""The benchmark's decoder workflow for ``config.json`` beside it, as a
user of veles-tpu writes one:

    python -m veles_tpu benchmark/configs/mellum2_12b_a2p5b/workflow.py \
        --mode scan --compute-dtype bfloat16

A ``StandardWorkflow`` whose ``layers`` are the transformer units of
``veles_tpu/znicz/transformer.py`` — token embedding, then for each entry
of ``layer_types`` ONE kind of attention unit with the entry's own
arguments (``sliding_attention``: grouped-query attention inside
``sliding_window`` with the default rotary angles; ``full_attention``:
the same unit without a window and with the YaRN angles of its
``rope_parameters`` group) followed by an expert block with the softmax router
(``mlp_layer_types`` names nothing but ``sparse``), a normalised head
over the vocabulary slice — each configured with ``config.json``'s own
keys, AdamW on every unit, the ``token`` loss.  The benchmark's own (the
loader and the clock are those of ``configs/kanana2_30b_a3b/workflow.py``,
copied: files under ``benchmark/`` are not imported across
configurations):

- the loader makes the token ids on the device from
  ``root.mellum2_bench.loader.seed``: Zipf-distributed over the slice's
  ids (rank r with probability proportional to ``r ** -exponent``), so
  the routing is uneven as on text; the labels are the next token;
- a ``LearningRateAdjuster`` for the linear warm-up ``config.json``
  assumes (``solver.warmup_steps``);
- an :class:`EpochClock` unit linked after the decision, which reads the
  host clock at every epoch's end and stops the workflow when the
  driver's window closes.

``root.mellum2_bench.model`` holds the model's keys, so a test or a
rehearsal shrinks them with ``root.mellum2_bench.model.hidden_size=64``
on the command line like any other setting.
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

#: the keys of config.json the units read
MODEL_KEYS = (
    "hidden_size", "moe_intermediate_size",
    "num_attention_heads", "num_key_value_heads", "head_dim",
    "rms_norm_eps", "layer_types", "mlp_layer_types", "sliding_window",
    "use_sliding_window", "rope_parameters", "num_experts", "router_width",
    "experts_offset", "num_experts_per_tok", "scoring_func",
    "routed_scaling_factor", "norm_topk_prob", "norm_topk_eps",
    "bias_update_rate", "bias_update_rule", "train_router", "vocab_size")

root.mellum2_bench.update({
    "loader": {"minibatch_size": 2, "normalization_type": "none",
               "n_train": CONFIG["data"]["n_train"],
               "n_valid": CONFIG["data"]["n_valid"],
               "sequence_length": CONFIG["data"]["sequence_length"],
               "zipf_exponent": CONFIG["data"]["zipf_exponent"],
               "seed": 0},
    "model": {key: CONFIG[key] for key in MODEL_KEYS},
    "solver": dict(CONFIG["solver"]),
    "init": dict(CONFIG["init"]),
    # out of reach: the clock ends the run, never the decision
    "decision": {"max_epochs": 10 ** 9, "fail_iterations": 10 ** 9,
                 "silent": True},
})


def window_of(model, kind):
    """The window of a layer of kind ``kind``; None: a full layer."""
    windowed = kind == "sliding_attention" and model["use_sliding_window"]
    return model["sliding_window"] if windowed else None


def layers(model, solver, init, seed):
    """The ``layers`` list of the decoder ``model`` describes: a
    published layer is an attention block, then a feed-forward block."""
    solver = dict(solver)
    solver.pop("warmup_steps", None)        # the schedule is a unit's
    backward = {"solver": solver.pop("name"),
                "learning_rate": solver.pop("learning_rate"),
                "learning_rate_bias": 0.0, "weights_decay": 0.0,
                "solver_parameters": solver}

    def unit(kind, index, **forward):
        forward.setdefault("weights_stddev", init["weights_stddev"])
        forward.update(rms_norm_eps=model["rms_norm_eps"],
                       hidden_size=model["hidden_size"],
                       seed=(int(seed) << 8) + index,
                       name="%s%d" % (forward.pop("name"), index))
        return {"type": kind, "->": forward, "<-": dict(backward)}
    out = [unit("token_embedding", 0, name="embed",
                vocab_size=model["vocab_size"],
                weights_stddev=init["embedding_stddev"])]
    for i, kind in enumerate(model["layer_types"]):
        out.append(unit(
            "gqa_attention_block", i, name="attn",
            # the layer kind's own angles; a window in its kind alone
            rope_parameters=dict(model["rope_parameters"][kind]),
            sliding_window=window_of(model, kind),
            **{k: model[k] for k in ("num_attention_heads",
                                     "num_key_value_heads", "head_dim")}))
        if model["mlp_layer_types"][i] != "sparse":
            raise ValueError("layer %d is %r: this family's published "
                             "layers are all sparse"
                             % (i, model["mlp_layer_types"][i]))
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
                "num_experts_per_tok", "scoring_func",
                "routed_scaling_factor", "norm_topk_prob", "norm_topk_eps",
                "bias_update_rate", "bias_update_rule", "train_router")}))
    out.append(unit("lm_head", 0, name="head",
                    vocab_size=model["vocab_size"]))
    return out


class DeviceTokenLoader(FullBatchLoaderMSE):
    """Sequences of Zipf-distributed token ids and their next tokens,
    made on the device from a seed and kept resident in HBM."""

    MAPPING = "benchmark_mellum2_token_loader"

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


def check_units(wf, model):
    """The units took the arguments that make this model: a program
    whose units do not know ``sliding_window``, ``rope_parameters``,
    ``scoring_func`` or ``bias_update_rule`` ignores them and would train another model under
    this one's name; it is refused before anything is allocated."""
    kinds = iter(model["layer_types"])
    for unit in wf.forwards:
        if unit.MAPPING == "gqa_attention_block":
            kind = next(kinds)
            want = {"sliding_window": window_of(model, kind),
                    "rope_type": model["rope_parameters"][kind]["rope_type"]}
        elif unit.MAPPING == "expert_block":
            want = {"scoring_func": model["scoring_func"],
                    "bias_update_rule": model["bias_update_rule"]}
        else:
            continue
        got = {key: getattr(unit, key, "unknown to this unit")
               for key in want}
        if got != want:
            raise RuntimeError("%s %s: the configuration asks for %r, the "
                               "unit has %r" % (unit.MAPPING, unit.name,
                                                want, got))


def create_workflow(**overrides):
    cfg = root.mellum2_bench
    model = cfg.model.todict()
    overrides["loader"] = dict(overrides.get("loader", {}),
                               vocab_size=model["vocab_size"])
    wf = build_standard(
        cfg, "Mellum2DecoderBench", DeviceTokenLoader, "token",
        layers=layers(model, cfg.solver.todict(), cfg.init.todict(),
                      cfg.loader.get("seed", 0)),
        **overrides)
    check_units(wf, model)
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
