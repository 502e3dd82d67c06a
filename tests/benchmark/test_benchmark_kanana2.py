"""The ``kanana2_30b_a3b`` configuration on the CPU at a small preset
(float32, seeded): the program's units through ``StandardWorkflow``
against the configuration's plain reference — logits, loss, every
gradient, two AdamW steps —, the shares of an expert-parallel group
adding up to the uncut layer, droplessness under total imbalance, and
``work.py``'s counts by hand."""

import jax
import jax.numpy as jnp
import numpy
import pytest

from benchlib import config as load_config, load

from veles_tpu.backends import Device
from veles_tpu.config import root

reference = load("configs/kanana2_30b_a3b/reference.py")
work = load("configs/kanana2_30b_a3b/work.py")
workflow = load("configs/kanana2_30b_a3b/workflow.py")

SMALL = {"hidden_size": 64, "num_attention_heads": 4,
         "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
         "kv_lora_rank": 32, "intermediate_size": 96,
         "moe_intermediate_size": 32, "router_width": 8,
         "n_routed_experts": 8, "experts_offset": 0,
         "num_experts_per_tok": 2, "n_shared_experts": 1,
         "vocab_size": 128, "n_layers": 3}
SEQ = 32


def small_config(**changes):
    cfg = dict(load_config("kanana2_30b_a3b"), **SMALL)
    cfg.update(changes)
    cfg["data"] = dict(cfg["data"], sequence_length=SEQ, n_train=8,
                       n_valid=2)
    return cfg


def build(cfg, scan=True, seed=5, minibatch=2, compute_dtype=None):
    """The benchmark's workflow at ``cfg``'s sizes, initialized on the
    CPU."""
    saved = root.kanana2_bench.todict()
    try:
        root.kanana2_bench.model.update(
            {k: cfg[k] for k in workflow.MODEL_KEYS})
        root.kanana2_bench.loader.update(
            {"n_train": cfg["data"]["n_train"],
             "n_valid": cfg["data"]["n_valid"],
             "sequence_length": cfg["data"]["sequence_length"],
             "minibatch_size": minibatch, "seed": seed})
        trainer = {"compute_dtype": compute_dtype} if compute_dtype else {}
        wf = workflow.create_workflow(epoch_scan=scan, trainer=trainer)
    finally:
        root.kanana2_bench.update(saved)
    wf.initialize(device=Device(backend="cpu"))
    return wf


def batch(wf, n=2):
    step = wf.fused_step
    return step._data_dev_[2:2 + n], step._y_dev_[2:2 + n]


def host(tree):
    return jax.tree.map(numpy.asarray, tree)


def close(got, want, tol):
    got, want = numpy.asarray(got, numpy.float64), numpy.asarray(
        want, numpy.float64)
    scale = max(numpy.sqrt(numpy.mean(want ** 2)), 1e-30)
    return numpy.sqrt(numpy.mean((got - want) ** 2)) / scale <= tol


@pytest.fixture(scope="module")
def system():
    cfg = small_config()
    wf = build(cfg)
    return cfg, wf


def test_the_workflow_is_the_configurations_chain(system):
    cfg, wf = system
    kinds = [type(f).MAPPING for f in wf.forwards]
    assert kinds == ["token_embedding", "latent_attention_block",
                     "gated_mlp_block", "latent_attention_block",
                     "expert_block", "latent_attention_block",
                     "expert_block", "lm_head"]
    assert type(wf.fused_step).__name__ == "ScanEpochStep"
    assert all(gd.solver_name == "adamw" for gd in wf.gds)
    # on the device, from the seed: another seed, other weights
    other = build(cfg, seed=6)
    assert not numpy.array_equal(
        numpy.asarray(wf.forwards[1].params["wq"]),
        numpy.asarray(other.forwards[1].params["wq"]))
    ids, labels = batch(wf)
    assert ids.dtype == jnp.int32 and ids.shape == (2, SEQ)
    assert numpy.array_equal(numpy.asarray(ids)[:, 1:],
                             numpy.asarray(labels)[:, :-1])
    assert int(ids.max()) < cfg["vocab_size"]


@pytest.mark.parametrize("what", ["logits", "loss", "gradients", "adamw"])
def test_system_against_reference(system, what):
    cfg, wf = system
    step = wf.fused_step
    ids, labels = batch(wf)
    params = jax.tree.map(jnp.array, step._params_)
    if what == "logits":
        got = step._forward_(params, ids)
        want = reference.forward(cfg, params, ids)
        assert got.shape == (2, SEQ, cfg["vocab_size"])
        assert close(got, want, 1e-4)
    elif what == "loss":
        _, loss, pred = step._eval_step_(params, step._macc_init(), ids,
                                         labels, numpy.int32(2))
        want = reference.forward(cfg, params, ids)
        assert abs(float(loss) - float(reference.token_loss(want, labels))) \
            < 1e-5
        assert numpy.array_equal(numpy.asarray(pred),
                                 numpy.asarray(want.argmax(-1)))
    else:
        opt = [{n: gd.solver.init(p, jnp) for n, p in layer.items()}
               for gd, layer in zip(step.gd_units, params)]
        start = host(params)
        steps = 1 if what == "gradients" else 2
        macc = step._macc_init()
        new = jax.tree.map(jnp.array, params)
        for _ in range(steps):
            new, opt, macc, _, _ = step._train_step_(
                new, opt, macc, ids, labels, numpy.int32(2), None, 1.0)
        if what == "gradients":
            # the first Adam moment after one step is (1 - beta1) x the
            # gradient: every tensor's gradient, through the step itself
            _, grads = reference.loss_and_grads(cfg, start, ids, labels)
            for layer, ref_layer in zip(opt, grads):
                for name, g in ref_layer.items():
                    if name == "router_bias":
                        assert not numpy.asarray(g).any()
                        continue
                    assert close(layer[name][0] / (1 - 0.9), g, 2e-3), name
        else:
            want, m, v = reference.adamw_steps(cfg, start, ids, labels, 2)
            for i, layer in enumerate(want):
                for name in layer:
                    before = start[i][name]
                    if name == "router_bias":    # a buffer: never moves
                        assert numpy.array_equal(new[i][name], before)
                        continue
                    assert close(new[i][name] - before,
                                 layer[name] - before, 5e-3), (i, name)
                    assert close(opt[i][name][0], m[i][name], 5e-3)
                    assert close(opt[i][name][1], v[i][name], 5e-3)


def test_the_shares_add_up():
    """Eight chips hold one expert each: their routed parts, with the
    shared expert and the residual counted once, add up to the uncut
    reference layer."""
    cfg = small_config()
    wf = build(cfg)
    whole = wf.forwards[4]
    params = dict(whole.params)
    x = jax.random.normal(jax.random.key(3), (2, SEQ, cfg["hidden_size"]))
    want = reference.expert_layer(cfg, params, x, "highest")
    common = reference.expert_layer(
        dict(cfg, n_routed_experts=0), params, x, "highest")
    total = common
    for chip in range(8):
        share_cfg = small_config(n_routed_experts=1, experts_offset=chip)
        unit = build(share_cfg).forwards[4]
        assert (unit.held, unit.offset, unit.n_experts) == (1, chip, 8)
        share = dict(params,
                     experts_gate_up=params["experts_gate_up"][chip:chip + 1],
                     experts_down=params["experts_down"][chip:chip + 1])
        part = unit.apply(share, x)
        ref_part = reference.expert_layer(share_cfg, share, x, "highest")
        assert close(part, ref_part, 1e-4)
        total = total + (part - common)
    assert close(total, want, 1e-4)
    assert close(whole.apply(params, x), want, 1e-4)


@pytest.mark.parametrize("case", ["all_to_one_held", "none_held"])
def test_dropless_under_total_imbalance(case):
    """Every token to one held expert, or every token to experts that
    are not here: no row is lost, none is invented."""
    cfg = small_config(n_routed_experts=2, experts_offset=3)
    unit = build(cfg).forwards[4]
    params = dict(unit.params)
    bias = numpy.zeros(8, numpy.float32)
    # the bias decides the choice alone: expert 4 (held) and 0, or 0, 1
    bias[[4, 0] if case == "all_to_one_held" else [0, 1]] = 50.0
    params["router_bias"] = jnp.asarray(bias)
    x = jax.random.normal(jax.random.key(4), (2, SEQ, cfg["hidden_size"]))
    y, stats = unit.apply_stats(params, x)
    tokens = 2 * SEQ
    want = [0, tokens] if case == "all_to_one_held" else [0, 0]
    assert numpy.asarray(stats["expert_tokens"]).tolist() == want
    assert int(stats["moe_rows"]) == int(stats["moe_routed"]) == sum(want)
    assert close(y, reference.expert_layer(cfg, params, x, "highest"), 1e-4)
    grads = jax.grad(lambda p: unit.apply(p, x).sum())(params)
    assert all(numpy.isfinite(numpy.asarray(g)).all()
               for g in grads.values())


def test_integer_inputs_survive_a_bfloat16_compute_dtype():
    """Token ids above 256 are not representable in bfloat16: the
    trainer's boundary cast leaves integer inputs alone."""
    cfg = small_config(vocab_size=1024)
    wf = build(cfg, compute_dtype="bfloat16")
    step = wf.fused_step
    ids = jnp.full((2, SEQ), 1001, jnp.int32).at[:, ::2].set(257)
    params = step._params_
    got = step._forward_(params, ids)
    want = reference.forward(cfg, params, ids, "default")
    assert got.dtype == jnp.float32
    assert close(got, want, 3e-2)
    # and they differ from what ids rounded to bfloat16 would give
    rounded = ids.astype(jnp.bfloat16).astype(jnp.int32)
    assert not close(got, reference.forward(cfg, params, rounded,
                                            "default"), 3e-2)


def test_training_runs_and_counts(system):
    cfg, _ = system
    wf = build(cfg)
    wf.decision.max_epochs = 3
    wf.run()
    step = wf.fused_step
    stats = step.unit_stats
    assert sorted(stats) == ["train", "validation"]
    for cls, sequences in (("train", 8), ("validation", 2)):
        assert sorted(stats[cls]) == ["moe1", "moe2"]
        rows = 2 * sequences * SEQ * 3      # two choices a token, 3 epochs
        for layer in stats[cls].values():
            # all eight experts are held: every choice falls on one
            assert int(layer["expert_tokens"].sum()) == rows
            assert int(layer["moe_rows"]) == int(layer["moe_routed"]) \
                == rows
    assert float(step.metrics[0]) > 0
    assert wf.decision.epoch_n_err[2] > 0
    # wrong TOKENS over the class's tokens, not over its sequences
    assert 0 < wf.decision.epoch_n_err_pt[2] <= 100.0
    assert wf.decision.epoch_n_err_pt[2] == pytest.approx(
        100.0 * wf.decision.epoch_n_err[2] / (8 * SEQ))


def test_work_counts_by_hand():
    c = load_config("kanana2_30b_a3b")
    assert work.attention_parameter_count(c) == 26_345_984 == (
        2048 * 32 * 192 + 2048 * 576 + 512 + 512 * 32 * 256 + 4096 * 2048)
    assert work.expert_parameter_count(c) == 4_718_592 == 3 * 2048 * 768
    assert work.dense_layer_parameter_count(c) == 64_098_816
    assert work.expert_layer_parameter_count(c) == 111_547_008
    assert work.parameter_count(c) == 575_955_968
    macs = work.forward_macs_per_token(c)
    assert macs["routed_experts"] == 4 * 0.75 * 4_718_592
    assert macs["attention_core"] == 5 * 32 * 320 * 8193 / 2
    flops = work.train_flops_per_token(c)
    assert 2.7e9 < flops < 2.9e9
    assert work.train_flops_per_image(c) == flops * 8192
    ops, moved = work.mla_flash_work(c, sequences=1, seq=8)
    assert ops == 5 * 2 * 36 * 32 * (320 + 512 + 640)
    more, _ = work.mla_flash_work(c, sequences=1, forward_only=2, seq=8)
    assert more - ops == 2 * 5 * 2 * 36 * 32 * 320
    ops, moved = work.grouped_matmul_work(c, rows=10, steps=1)
    assert ops == 6 * 10 * 3 * 2048 * 768
    assert moved == 3 * 10 * 2 * (2048 + 1536 + 768 + 2048) \
        + 3 * 4 * 16 * 4_718_592 * 2


def test_config_holds_every_published_key():
    import json
    import os
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    c = load_config("kanana2_30b_a3b")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["source_url"] == c["source"])
    for key, value in row["config"].items():
        if key in c["reduced"]:
            assert c["published"][key] == value
        else:
            assert c[key] == value, key
    assert c["reduced"] == ["n_layers", "n_routed_experts", "vocab_size",
                            "data"]
    assert c["published"]["num_hidden_layers"] == c["num_hidden_layers"]
