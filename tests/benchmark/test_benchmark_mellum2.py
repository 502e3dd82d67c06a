"""The ``mellum2_12b_a2p5b`` configuration on the CPU at a small preset
(float32, seeded): the program's units through ``StandardWorkflow``
against the configuration's plain reference — logits, loss, every
gradient, two AdamW steps, with and without the balancing update —, a
reference with the window on the wrong kind of layer or without YaRN
told apart, the four shares of the expert-parallel group adding up to
the uncut layer, ``work.py``'s counts by hand and its parameter count
against the program's own."""

import jax
import jax.numpy as jnp
import numpy
import pytest

from benchlib import config as load_config, load
from test_benchmark_kanana2 import batch, close, host

from veles_tpu.backends import Device
from veles_tpu.config import root

reference = load("configs/mellum2_12b_a2p5b/reference.py")
work = load("configs/mellum2_12b_a2p5b/work.py")
workflow = load("configs/mellum2_12b_a2p5b/workflow.py")

#: one period of the published pattern, cut to two window layers and a
#: full one; the full layers' group blends inside a head of 16 (pairs 1
#: to 5 of 8) and stretches at these lengths
SMALL = {"hidden_size": 64, "num_attention_heads": 4,
         "num_key_value_heads": 2, "head_dim": 16,
         "moe_intermediate_size": 32, "router_width": 8, "num_experts": 8,
         "experts_offset": 0, "num_experts_per_tok": 2, "vocab_size": 128,
         "sliding_window": 8,
         "layer_types": ["sliding_attention", "sliding_attention",
                         "full_attention"],
         "rope_parameters": {
             "full_attention": {
                 "rope_type": "yarn", "rope_theta": 10000, "factor": 16,
                 "original_max_position_embeddings": 64, "beta_fast": 2,
                 "beta_slow": 0.05, "attention_factor": 1.2772588722239782},
             "sliding_attention": {"rope_type": "default",
                                   "rope_theta": 10000}}}
SEQ = 32


def small_config(**changes):
    cfg = dict(load_config("mellum2_12b_a2p5b"), **SMALL)
    cfg.update(changes)
    cfg["n_layers"] = len(cfg["layer_types"])
    cfg["data"] = dict(cfg["data"], sequence_length=SEQ, n_train=8,
                       n_valid=2)
    return cfg


def build(cfg, scan=True, seed=5, minibatch=2, initialize=True):
    """The benchmark's workflow at ``cfg``'s sizes, initialized on the
    CPU."""
    saved = root.mellum2_bench.todict()
    try:
        root.mellum2_bench.model.update(
            {k: cfg[k] for k in workflow.MODEL_KEYS})
        root.mellum2_bench.loader.update(
            {"n_train": cfg["data"]["n_train"],
             "n_valid": cfg["data"]["n_valid"],
             "sequence_length": cfg["data"]["sequence_length"],
             "minibatch_size": minibatch, "seed": seed})
        wf = workflow.create_workflow(epoch_scan=scan)
    finally:
        root.mellum2_bench.update(saved)
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
    assert kinds == ["token_embedding"] + ["gqa_attention_block",
                                           "expert_block"] * 3 + ["lm_head"]
    assert type(wf.fused_step).__name__ == "ScanEpochStep"
    assert all(gd.solver_name == "adamw" for gd in wf.gds)
    # windowed and full layers are ONE unit with different arguments
    attns = wf.forwards[1:7:2]
    assert len({type(a) for a in attns}) == 1
    assert [(a.sliding_window, a.rope_type) for a in attns] == [
        (8, "default"), (8, "default"), (None, "yarn")]
    assert all((a.heads, a.kv_heads, a.head_dim, a.rope_theta)
               == (4, 2, 16, 1e4) for a in attns)
    assert attns[2].rope_scaling["factor"] == 16
    moe = wf.forwards[2]
    assert (moe.n_experts, moe.held, moe.top_k, moe.n_shared) == (8, 8, 2, 0)
    assert (moe.scoring_func, moe.norm_topk_eps, moe.scaling) == (
        "softmax", 0.0, 1.0)
    assert not moe.train_router and moe.bias_update_rate == 0.01
    assert moe.bias_update_rule == "proportional"
    # the published router has no bias: the block's starts at zero
    assert not numpy.asarray(moe.params["router_bias"]).any()
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


def test_the_traffic_is_the_mix_the_other_decoders_draw():
    """Zipf (exponent 1.0) over the slice's ids by ONE assignment of ids
    to ranks, from the seed, as ``configs/kanana2_30b_a3b/workflow.py``
    draws them; the 2,000-step warm-up."""
    assert load_config("mellum2_12b_a2p5b")["solver"]["warmup_steps"] == 2000
    cfg = small_config()
    cfg["data"] = dict(cfg["data"], sequence_length=2048)
    ids = numpy.asarray(build(cfg).fused_step._data_dev_)
    assert ids.shape == (10, 2048) and ids.max() < cfg["vocab_size"]
    counts = numpy.stack([numpy.bincount(row, minlength=128)
                          for row in ids])
    assert (numpy.abs(counts[:, 0] / 2048 - 0.184) < 0.03).all()
    assert (counts.argmax(1) == 0).all()
    assert numpy.array_equal(ids, numpy.asarray(
        build(cfg).fused_step._data_dev_))
    assert not numpy.array_equal(ids, numpy.asarray(
        build(cfg, seed=6).fused_step._data_dev_))


def test_the_published_depth_builds_the_published_chain():
    """``config.json`` as it is: one whole period [sliding, sliding,
    sliding, full], every layer with experts (built, not initialized:
    nothing of the real widths is allocated)."""
    cfg = load_config("mellum2_12b_a2p5b")
    wf = build(dict(cfg), initialize=False)
    kinds = [type(f).MAPPING for f in wf.forwards]
    assert kinds == ["token_embedding"] + ["gqa_attention_block",
                                           "expert_block"] * 4 + ["lm_head"]
    attns = wf.forwards[1:9:2]
    assert [(a.sliding_window, a.rope_type) for a in attns] == [
        (1024, "default")] * 3 + [(None, "yarn")]
    assert all((a.heads, a.kv_heads, a.head_dim, a.rope_theta,
                a.hidden_size) == (32, 4, 128, 5e5, 2304) for a in attns)
    assert attns[3].rope_scaling == cfg["rope_parameters"]["full_attention"]
    moe = wf.forwards[2]
    assert (moe.n_experts, moe.held, moe.offset, moe.top_k, moe.width) \
        == (64, 16, 0, 8, 896)
    # BUFFER_SHARES x a share of 1/4: half of every token's every choice
    assert moe.buffer_rows(2 * 8192) == 65536
    assert wf.forwards[-1].vocab_size == wf.forwards[0].vocab_size == 24576
    # work.py's count is the program's own: every tensor of every unit
    program = sum(int(numpy.prod(shape))
                  for f in wf.forwards
                  for shape, _ in f.tensor_shapes().values())
    assert work.parameter_count(cfg) == program == 595_154_432


@pytest.fixture(scope="module", params=[0.01, 0.0], ids=["balanced",
                                                         "as-published"])
def either(request, system):
    """The configuration as it is, with the one balancing means the
    block has (``bias_update_rate``), and as published, without."""
    if request.param:
        return system
    cfg = small_config(bias_update_rate=request.param)
    return cfg, build(cfg)


@pytest.mark.parametrize("what", ["logits", "loss", "gradients", "adamw"])
def test_system_against_reference(either, what):
    cfg, wf = either
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
            rate = cfg["bias_update_rate"]
            for i, layer in enumerate(want):
                for name in layer:
                    before = start[i][name]
                    if name == "router_bias":
                        # no gradient: only the balancing update moves
                        # it, by the rate a step, the same way in both
                        moved = numpy.asarray(new[i][name] - before)
                        assert numpy.allclose(
                            moved, numpy.asarray(layer[name] - before),
                            atol=1e-7)
                        if not rate:
                            assert not moved.any()
                        else:
                            # by the error itself: a step's errors add
                            # up to nothing, and an idle expert rises
                            # by the rate a step, the most one can
                            assert cfg["bias_update_rule"] == "proportional"
                            assert abs(moved.sum()) < 1e-6
                            assert moved.max() <= 2 * rate + 1e-7
                            assert numpy.abs(moved).max() > 0.1 * rate
                        continue
                    assert close(new[i][name] - before,
                                 layer[name] - before, 5e-3), (i, name)
                    assert close(opt[i][name][0], m[i][name], 5e-3)
                    assert close(opt[i][name][1], v[i][name], 5e-3)


def probe(step, key=9):
    """Unit-gain weights, as the driver's probe draws them."""
    key = jax.random.key(key)
    params = []
    for i, layer in enumerate(step._params_):
        new = {}
        for j, (name, p) in enumerate(sorted(layer.items())):
            noise = jax.random.normal(jax.random.fold_in(
                jax.random.fold_in(key, i), j), p.shape)
            new[name] = (0.01 * noise if "bias" in name
                         else 1.0 + 0.1 * noise) if p.ndim == 1 else \
                noise if i == 0 else noise / numpy.sqrt(p.shape[-2])
        params.append(new)
    return params


def test_probe_parameters_move_every_block(system):
    """Under unit-gain weights a wrong tensor anywhere shows in the
    logits, both kinds of attention layer and the head norms among
    them; the probe's router bias enters the choice in both."""
    cfg, wf = system
    step = wf.fused_step
    ids, _ = batch(wf)
    params = probe(step)
    assert numpy.asarray(params[2]["router_bias"]).any()
    want = reference.forward(cfg, params, ids)
    assert close(step._forward_(params, ids), want, 1e-4)
    # (a scaled W_q or W_k is undone by the head's norm)
    for layer, name in ((1, "wv"), (3, "q_norm"), (5, "wo"),
                        (5, "k_norm"), (6, "experts_down")):
        wrong = [dict(p) for p in params]
        wrong[layer][name] = wrong[layer][name] * 1.5
        assert not close(step._forward_(wrong, ids), want, 2e-2), (layer,
                                                                   name)


@pytest.mark.parametrize("change", [
    {"layer_types": ["sliding_attention", "full_attention",
                     "full_attention"]},
    {"layer_types": ["full_attention"] * 3},
    {"use_sliding_window": False},
    {"sliding_window": 9},
    {"rope_parameters": dict(SMALL["rope_parameters"], full_attention=SMALL[
        "rope_parameters"]["sliding_attention"])},
    {"rope_parameters": dict(SMALL["rope_parameters"], full_attention=dict(
        SMALL["rope_parameters"]["full_attention"], attention_factor=1.0))},
    {"scoring_func": "sigmoid"}],
    ids=["window-off-in-one-layer", "no-window", "windows-switched-off",
         "window-off-by-one", "yarn-left-out", "no-attention-factor",
         "sigmoid-router"])
def test_a_reference_of_another_model_is_told_apart(system, change):
    """What the cell's comparison has to see: the window on the wrong
    kind of layer, a band one key too wide, YaRN or its factor left out,
    the other router.  The program follows the same change."""
    cfg, wf = system
    step = wf.fused_step
    ids, _ = batch(wf)
    params = probe(step)
    want = reference.forward(cfg, params, ids)
    other = dict(cfg, **change)
    assert not close(reference.forward(other, params, ids), want, 1e-2)
    changed = build(other)
    assert close(changed.fused_step._forward_(params, ids),
                 reference.forward(other, params, ids), 1e-4)


def test_the_four_shares_add_up():
    """Four chips hold 16 experts of 64 each (offsets 0, 16, 32, 48):
    their routed parts, with the residual (what every chip computes
    alike) counted once, add up to the uncut reference layer."""
    sizes = dict(router_width=64, num_experts=64, num_experts_per_tok=8)
    cfg = small_config(**sizes)
    whole = build(cfg).forwards[2]
    params = dict(whole.params)
    x = jax.random.normal(jax.random.key(3), (2, SEQ, cfg["hidden_size"]))
    want = reference.expert_layer(cfg, params, x, "highest")
    common = reference.expert_layer(
        dict(cfg, num_experts=0), params, x, "highest")
    assert numpy.array_equal(numpy.asarray(common), numpy.asarray(x))
    total, rows = common, 0
    for chip in range(4):
        first = 16 * chip
        share_cfg = small_config(**dict(sizes, num_experts=16,
                                        experts_offset=first))
        unit = build(share_cfg).forwards[2]
        assert (unit.held, unit.offset, unit.n_experts, unit.top_k) == (
            16, first, 64, 8)
        share = dict(
            params,
            experts_gate_up=params["experts_gate_up"][first:first + 16],
            experts_down=params["experts_down"][first:first + 16])
        part, stats = unit.apply_stats(share, x)
        ref_part = reference.expert_layer(share_cfg, share, x, "highest")
        # what the experts add, apart from the residual it is small
        # beside at these weights
        assert close(part - common, ref_part - common, 1e-3)
        total = total + (part - common)
        rows += int(stats["moe_rows"])
    assert close(total - common, want - common, 1e-3)
    assert close(whole.apply(params, x) - common, want - common, 1e-3)
    assert rows == 2 * SEQ * 8      # every token's every choice, once


def test_training_runs_and_counts(system):
    cfg, _ = system
    wf = build(cfg)
    wf.decision.max_epochs = 3
    wf.run()
    step = wf.fused_step
    stats = step.unit_stats
    assert sorted(stats) == ["train", "validation"]
    for cls, sequences in (("train", 8), ("validation", 2)):
        assert sorted(stats[cls]) == ["moe0", "moe1", "moe2"]
        rows = 2 * sequences * SEQ * 3      # two choices a token, 3 epochs
        for layer in stats[cls].values():
            assert int(layer["expert_tokens"].sum()) == rows
            assert int(layer["moe_rows"]) == int(layer["moe_routed"]) \
                == rows
            assert int(layer["moe_spilled"]) == 0
            # all 8 routed experts are held at this preset
            assert numpy.array_equal(layer["router_load"],
                                     layer["expert_tokens"])
    assert float(step.metrics[0]) > 0


def test_work_counts_by_hand():
    c = load_config("mellum2_12b_a2p5b")
    assert work.attention_parameter_count(c) == 21_236_224 == (
        2304 + 2304 * 4096 + 2 * 2304 * 512 + 2 * 128 + 4096 * 2304)
    assert work.expert_parameter_count(c) == 6_193_152 == 3 * 2304 * 896
    assert work.expert_block_parameter_count(c) == 99_240_256 == (
        2304 + 2304 * 64 + 64 + 16 * 6_193_152)
    assert work.parameter_count(c) == 595_154_432 == (
        4 * (21_236_224 + 99_240_256) + 2 * 24576 * 2304 + 2304)
    # the band: T W - W (W - 1) / 2 pairs, the triangle where it is wider
    assert work.attended_pairs(8) == 36 == work.attended_pairs(8, 8) \
        == work.attended_pairs(8, 100)
    assert work.attended_pairs(8, 3) == 8 * 3 - 3 == sum(
        min(i + 1, 3) for i in range(8))
    assert work.attended_pairs(8192, 1024) == 8192 * 1024 - 1024 * 1023 // 2
    macs = work.forward_macs_per_token(c)
    assert macs["attention_projections"] == 4 * (
        2 * 2304 * 4096 + 2 * 2304 * 512)
    assert macs["attention_core_full"] == 32 * 256 * 8193 / 2
    assert macs["attention_core_window"] == 3 * 32 * 256 * (
        8192 * 1024 - 1024 * 1023 // 2) / 8192
    assert macs["routed_experts"] == 4 * 2 * 6_193_152
    assert macs["router"] == 4 * 2304 * 64
    assert macs["head"] == 2304 * 24576
    assert 248e6 < sum(macs.values()) < 250e6
    flops = work.train_flops_per_token(c)
    assert 1.49e9 < flops < 1.50e9
    assert work.train_flops_per_image(c) == flops * 8192
    # one full layer, 32 query heads of 128 on 4 key-value heads, seq 8:
    # 36 (query, key) pairs a head
    ops, moved = work.gqa_flash_work(c, sequences=1, seq=8)
    assert ops == 2 * 36 * 32 * (256 + 384 + 512)
    q, kv, stats = 8 * 32 * 128 * 2, 8 * 4 * 128 * 2, 8 * 32 * 4
    one = (2 * q + 2 * kv + stats) + (3 * q + 2 * kv + 2 * stats) \
        + (2 * q + 4 * kv + 2 * stats)
    assert moved == one
    # three window layers: at seq 8 a window of 1,024 is the triangle
    assert work.window_flash_work(c, sequences=1, seq=8) == (3 * ops,
                                                             3 * one)
    narrow = dict(c, sliding_window=3)
    ops3, moved3 = work.window_flash_work(narrow, sequences=1, seq=8)
    assert ops3 == 3 * 2 * 21 * 32 * (256 + 384 + 512) and moved3 == 3 * one
    more, more_moved = work.window_flash_work(narrow, sequences=1,
                                              forward_only=2, seq=8)
    assert more - ops3 == 3 * 2 * 2 * 21 * 32 * 256
    assert more_moved - moved3 == 3 * 2 * (2 * q + 2 * kv + stats)
    # with the windows switched off every layer is a full one
    off = dict(c, use_sliding_window=False)
    assert work.window_flash_work(off, 1, seq=8) == (0, 0)
    assert work.gqa_flash_work(off, 1, seq=8) == (4 * ops, 4 * one)
    ops, moved = work.grouped_matmul_work(c, rows=10, steps=1)
    assert ops == 6 * 10 * 3 * 2304 * 896
    assert moved == 3 * 10 * 2 * (2304 + 1792 + 896 + 2304) \
        + 3 * 4 * 16 * 6_193_152 * 2


def test_config_holds_every_published_key():
    import json
    import os
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    c = load_config("mellum2_12b_a2p5b")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["source_url"] == c["source"])
    for key, value in row["config"].items():
        if key in c["reduced"]:
            if key != "layer_types":    # told in words, checked below
                assert c["published"][key] == value
        else:
            assert c[key] == value, key
    assert c["reduced"] == ["n_layers", "layer_types", "num_experts",
                            "vocab_size", "data"]
    assert c["published"]["num_hidden_layers"] == c["num_hidden_layers"] \
        == len(row["config"]["layer_types"]) == 28
    # the depth run: published layers 0-3, one whole period
    types = row["config"]["layer_types"]
    assert c["layer_types"] == types[:4] == ["sliding_attention"] * 3 + [
        "full_attention"]
    assert types == c["layer_types"] * 7
    assert c["n_layers"] == len(c["layer_types"]) == 4
    assert c["router_width"] == row["config"]["num_experts"] == 64
    assert c["num_experts"] * 4 == 64 and c["vocab_size"] * 4 == 98304
    assert c["scoring_func"] == "softmax" and c["norm_topk_eps"] == 0
    assert not c["train_router"] and c["bias_update_rate"] == 0.01
    assert c["bias_update_rule"] == "proportional"
    assert c["init"]["router_bias_stddev"] == 0
    # the floors of a cut: a whole period and four layers, 8 experts a
    # layer, an eighth of the vocabulary
    assert c["num_experts"] >= 8 and c["vocab_size"] * 8 >= 98304
