"""The ``lfm2_24b_a2b`` configuration on the CPU at a small preset
(float32, seeded): the program's units through ``StandardWorkflow``
against the configuration's plain reference — logits, loss, every
gradient, two AdamW steps —, the shares of an expert-parallel group
adding up to the uncut layer, ``work.py``'s counts by hand and its
parameter count against the program's own."""

import jax
import jax.numpy as jnp
import numpy
import pytest

from benchlib import config as load_config, load
from test_benchmark_kanana2 import batch, close, host

from veles_tpu.backends import Device
from veles_tpu.config import root

reference = load("configs/lfm2_24b_a2b/reference.py")
work = load("configs/lfm2_24b_a2b/work.py")
workflow = load("configs/lfm2_24b_a2b/workflow.py")

#: every kind of pair the published depth has: conv + MLP, attention +
#: experts, conv + experts
SMALL = {"hidden_size": 64, "num_attention_heads": 4,
         "num_key_value_heads": 2, "head_dim": 16,
         "intermediate_size": 96, "moe_intermediate_size": 32,
         "router_width": 8, "num_experts": 8, "experts_offset": 0,
         "num_experts_per_tok": 2, "vocab_size": 128,
         "layer_types": ["conv", "full_attention", "conv"],
         "num_dense_layers": 1}
SEQ = 32


def small_config(**changes):
    cfg = dict(load_config("lfm2_24b_a2b"), **SMALL)
    cfg.update(changes)
    cfg["n_layers"] = len(cfg["layer_types"])
    cfg["data"] = dict(cfg["data"], sequence_length=SEQ, n_train=8,
                       n_valid=2)
    return cfg


def build(cfg, scan=True, seed=5, minibatch=2, initialize=True):
    """The benchmark's workflow at ``cfg``'s sizes, initialized on the
    CPU."""
    saved = root.lfm2_bench.todict()
    try:
        root.lfm2_bench.model.update(
            {k: cfg[k] for k in workflow.MODEL_KEYS})
        root.lfm2_bench.loader.update(
            {"n_train": cfg["data"]["n_train"],
             "n_valid": cfg["data"]["n_valid"],
             "sequence_length": cfg["data"]["sequence_length"],
             "minibatch_size": minibatch, "seed": seed})
        wf = workflow.create_workflow(epoch_scan=scan)
    finally:
        root.lfm2_bench.update(saved)
    if initialize:
        wf.initialize(device=Device(backend="cpu"))
    return wf


@pytest.fixture(scope="module")
def system():
    cfg = small_config()
    wf = build(cfg)
    return cfg, wf


def test_the_workflow_is_the_configurations_chain(system):
    cfg, wf = system
    kinds = [type(f).MAPPING for f in wf.forwards]
    assert kinds == ["token_embedding", "short_conv_block",
                     "gated_mlp_block", "gqa_attention_block",
                     "expert_block", "short_conv_block", "expert_block",
                     "lm_head"]
    assert type(wf.fused_step).__name__ == "ScanEpochStep"
    assert all(gd.solver_name == "adamw" for gd in wf.gds)
    attn, moe = wf.forwards[3], wf.forwards[4]
    assert (attn.heads, attn.kv_heads, attn.head_dim) == (4, 2, 16)
    assert (moe.n_experts, moe.held, moe.top_k, moe.n_shared) == (8, 8, 2, 0)
    assert moe.norm_topk_eps == cfg["norm_topk_eps"] == 1e-6
    assert moe.scaling == 1.0 and wf.forwards[1].taps == 3
    # on the device, from the seed: another seed, other weights
    other = build(cfg, seed=6)
    assert not numpy.array_equal(
        numpy.asarray(wf.forwards[1].params["in_proj"]),
        numpy.asarray(other.forwards[1].params["in_proj"]))
    ids, labels = batch(wf)
    assert ids.dtype == jnp.int32 and ids.shape == (2, SEQ)
    assert numpy.array_equal(numpy.asarray(ids)[:, 1:],
                             numpy.asarray(labels)[:, :-1])
    assert int(ids.max()) < cfg["vocab_size"]


def test_the_traffic_is_the_mix_kanana2_draws():
    """ISSUE 33's parameters: Zipf (exponent 1.0) over the slice's ids by
    ONE assignment of ids to ranks, id r - 1 the r-th commonest in every
    sequence as in ``configs/kanana2_30b_a3b/workflow.py``, from the
    seed; the 2,000-step warm-up."""
    assert load_config("lfm2_24b_a2b")["solver"]["warmup_steps"] == 2000
    cfg = small_config()
    cfg["data"] = dict(cfg["data"], sequence_length=2048)
    ids = numpy.asarray(build(cfg).fused_step._data_dev_)
    assert ids.shape == (10, 2048) and ids.max() < cfg["vocab_size"]
    counts = numpy.stack([numpy.bincount(row, minlength=128)
                          for row in ids])
    # Zipf over 128 ids: the commonest is 1 / H(128) = 18 % of a sequence
    assert (numpy.abs(counts[:, 0] / 2048 - 0.184) < 0.03).all()
    assert (counts.argmax(1) == 0).all()
    # the same seed, the same ids; another seed, others
    assert numpy.array_equal(ids, numpy.asarray(
        build(cfg).fused_step._data_dev_))
    assert not numpy.array_equal(ids, numpy.asarray(
        build(cfg, seed=6).fused_step._data_dev_))


def test_the_published_depth_builds_the_published_chain():
    """``config.json`` as it is: conv + MLP, then one whole period
    [full_attention, conv, conv, conv] with experts (built, not
    initialized: nothing of the real widths is allocated)."""
    cfg = load_config("lfm2_24b_a2b")
    wf = build(dict(cfg), initialize=False)
    kinds = [type(f).MAPPING for f in wf.forwards]
    assert kinds == ["token_embedding", "short_conv_block",
                     "gated_mlp_block", "gqa_attention_block",
                     "expert_block"] + ["short_conv_block",
                                        "expert_block"] * 3 + ["lm_head"]
    attn = wf.forwards[3]
    assert (attn.heads, attn.kv_heads, attn.head_dim, attn.rope_theta) \
        == (32, 8, 64, 1e6)
    moe = wf.forwards[4]
    assert (moe.n_experts, moe.held, moe.offset, moe.top_k, moe.width) \
        == (64, 8, 0, 4, 1536)
    assert wf.forwards[2].intermediate_size == 11776
    assert wf.forwards[-1].vocab_size == 8192
    # work.py's count is the program's own: every tensor of every unit
    program = sum(int(numpy.prod(shape))
                  for f in wf.forwards
                  for shape, _ in f.tensor_shapes().values())
    assert work.parameter_count(cfg) == program == 486_062_464


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
                    if name in ("router_bias", "router"):
                        # the bias has none; the matrix has none on a
                        # share (train_router false), in either
                        assert not cfg["train_router"]
                        assert not numpy.asarray(g).any()
                        assert not numpy.asarray(layer[name][0]).any()
                        continue
                    assert close(layer[name][0] / (1 - 0.9), g, 2e-3), name
        else:
            want, m, v = reference.adamw_steps(cfg, start, ids, labels, 2)
            for i, layer in enumerate(want):
                for name in layer:
                    before = start[i][name]
                    assert close(new[i][name] - before,
                                 layer[name] - before, 5e-3), (i, name)
                    if name == "router_bias":
                        # no gradient: the balancing update alone moves
                        # it, by the rate a step, and not every time the
                        # same way
                        moved = numpy.abs(new[i][name] - before) \
                            / cfg["bias_update_rate"]
                        assert cfg["bias_update_rate"] == 1e-2
                        assert set(numpy.round(moved)) <= {0, 1, 2} \
                            and moved.max() > 0.5
                        continue
                    assert close(opt[i][name][0], m[i][name], 5e-3)
                    assert close(opt[i][name][1], v[i][name], 5e-3)


def test_probe_parameters_move_every_block(system):
    """Under unit-gain weights (what the driver's probe draws: matrices
    normal over their fan-in, the taps [L, d] over L) a wrong tensor
    anywhere shows in the logits, the convolution's taps and the head
    norms among them."""
    cfg, wf = system
    step = wf.fused_step
    ids, _ = batch(wf)
    key = jax.random.key(9)
    params = []
    for i, layer in enumerate(step._params_):
        new = {}
        for j, (name, p) in enumerate(sorted(layer.items())):
            noise = jax.random.normal(jax.random.fold_in(
                jax.random.fold_in(key, i), j), p.shape)
            new[name] = 1.0 + 0.1 * noise if p.ndim == 1 else \
                noise if i == 0 else noise / numpy.sqrt(p.shape[-2])
        params.append(new)
    want = reference.forward(cfg, params, ids)
    assert close(step._forward_(params, ids), want, 1e-4)
    for layer, name in ((1, "conv"), (3, "q_norm"), (3, "wv"),
                        (5, "out_proj")):
        wrong = [dict(p) for p in params]
        wrong[layer][name] = wrong[layer][name] * 1.5
        assert not close(step._forward_(wrong, ids), want, 2e-2), name


def test_the_shares_add_up():
    """Eight chips hold one expert each (offsets 0..7): their routed
    parts, with the residual (what every chip computes alike) counted
    once, add up to the uncut reference layer."""
    cfg = small_config()
    wf = build(cfg)
    whole = wf.forwards[4]
    params = dict(whole.params)
    x = jax.random.normal(jax.random.key(3), (2, SEQ, cfg["hidden_size"]))
    want = reference.expert_layer(cfg, params, x, "highest")
    common = reference.expert_layer(
        dict(cfg, num_experts=0), params, x, "highest")
    assert numpy.array_equal(numpy.asarray(common), numpy.asarray(x))
    total = common
    for chip in range(8):
        share_cfg = small_config(num_experts=1, experts_offset=chip)
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


def test_the_normalising_constant_is_the_configurations():
    """1e-6 under the sum of the chosen scores, not the block's default
    1e-20: with scores this small the two differ."""
    cfg = small_config()
    unit = build(cfg).forwards[4]
    params = dict(unit.params)
    params["router"] = params["router"] * 0.0       # every score 0.5
    x = jax.random.normal(jax.random.key(4), (2, SEQ, cfg["hidden_size"]))
    _, weights = unit.route(params, x.reshape(-1, cfg["hidden_size"]))
    numpy.testing.assert_allclose(weights, 0.5 / (1.0 + 1e-6), rtol=1e-7)
    assert close(unit.apply(params, x),
                 reference.expert_layer(cfg, params, x, "highest"), 1e-5)


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
            assert int(layer["expert_tokens"].sum()) == rows
            assert int(layer["moe_rows"]) == int(layer["moe_routed"]) \
                == rows
            # all 8 routed experts are held at this preset
            assert numpy.array_equal(layer["router_load"],
                                     layer["expert_tokens"])
    assert float(step.metrics[0]) > 0


def test_work_counts_by_hand():
    c = load_config("lfm2_24b_a2b")
    assert work.conv_parameter_count(c) == 16_785_408 == (
        2048 + 2048 * 6144 + 3 * 2048 + 2048 * 2048)
    assert work.attention_parameter_count(c) == 10_487_936 == (
        2048 + 2048 * 2048 + 2 * 2048 * 512 + 2 * 64 + 2048 * 2048)
    assert work.expert_parameter_count(c) == 9_437_184 == 3 * 2048 * 1536
    assert work.dense_mlp_parameter_count(c) == 72_353_792
    assert work.expert_block_parameter_count(c) == 75_630_656 == (
        2048 + 2048 * 64 + 64 + 8 * 9_437_184)
    assert work.parameter_count(c) == 486_062_464 == (
        4 * 16_785_408 + 10_487_936 + 72_353_792 + 4 * 75_630_656
        + 2 * 8192 * 2048 + 2048)
    macs = work.forward_macs_per_token(c)
    assert macs["routed_experts"] == 4 * 0.5 * 9_437_184
    assert macs["attention_core"] == 32 * 128 * 8193 / 2
    assert macs["conv_projections"] == 4 * (2048 * 6144 + 2048 * 2048)
    assert macs["conv_taps"] == 4 * 3 * 2048
    assert 202e6 < sum(macs.values()) < 204e6
    flops = work.train_flops_per_token(c)
    assert 1.21e9 < flops < 1.23e9
    assert work.train_flops_per_image(c) == flops * 8192
    # one layer, 32 query heads of 64 on 8 key-value heads, seq 8: 36
    # (query, key) pairs a head
    ops, moved = work.gqa_flash_work(c, sequences=1, seq=8)
    assert ops == 2 * 36 * 32 * (128 + 192 + 256)
    q, kv, stats = 8 * 32 * 64 * 2, 8 * 8 * 64 * 2, 8 * 32 * 4
    assert moved == (2 * q + 2 * kv + stats) + (3 * q + 2 * kv + 2 * stats) \
        + (2 * q + 4 * kv + 2 * stats)
    more, more_moved = work.gqa_flash_work(c, sequences=1, forward_only=2,
                                           seq=8)
    assert more - ops == 2 * 2 * 36 * 32 * 128
    assert more_moved - moved == 2 * (2 * q + 2 * kv + stats)
    ops, moved = work.grouped_matmul_work(c, rows=10, steps=1)
    assert ops == 6 * 10 * 3 * 2048 * 1536
    assert moved == 3 * 10 * 2 * (2048 + 3072 + 1536 + 2048) \
        + 3 * 4 * 8 * 9_437_184 * 2


def test_config_holds_every_published_key():
    import json
    import os
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    c = load_config("lfm2_24b_a2b")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["source_url"] == c["source"])
    for key, value in row["config"].items():
        if key in c["reduced"]:
            if key != "layer_types":    # told in words, checked below
                assert c["published"][key] == value
        else:
            assert c[key] == value, key
    assert c["reduced"] == ["n_layers", "layer_types", "num_dense_layers",
                            "num_experts", "vocab_size", "data"]
    assert c["published"]["num_hidden_layers"] == c["num_hidden_layers"] \
        == len(row["config"]["layer_types"])
    # the depth run: published layer 0, then layers 2-5, one whole period
    types = row["config"]["layer_types"]
    assert c["layer_types"] == [types[0]] + types[2:6]
    assert c["n_layers"] == len(c["layer_types"]) == 5
    assert types[2:6] == ["full_attention", "conv", "conv", "conv"]
    assert types.count("conv") == 30 and types.count("full_attention") == 10
    assert c["router_width"] == row["config"]["num_experts"] == 64
    assert c["head_dim"] * c["num_attention_heads"] == c["hidden_size"]
