"""Rehearsal of the cell ``mellum2_scan_seq8k`` through the ``train_lm``
driver on the CPU at tiny sizes, as ``test_benchmark_rehearsal_lfm2.py``
rehearses LFM2's: the driver is called as ``run.py`` calls it, with the
sizes and the device check replaced HERE.  Checked: the control flow,
the result line, the counters the readers need, that ``correct`` is
decided by the plain reference, and that a reference with the window on
the wrong kind of layer, without YaRN or with the other router makes it
false.  No number these runs print is a device number."""

import pytest

from benchlib import config as load_config, load, manifest
from test_benchmark_mellum2 import SMALL
from test_benchmark_rehearsal_lm import CHECKS, failed_checks

bench = load("run.py")


def tiny_run(trace=0, seconds=1.0):
    import jax
    run = bench.Run(manifest(), "mellum2_scan_seq8k", 2 ** 31 + 35, seconds,
                    trace)
    run.backend = "cpu"                         # in place of check_device
    run.devices = jax.devices()[:1]
    run.peaks = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11,
                 "hbm_bytes": 0}
    small = dict(SMALL, num_experts=4)
    run.config_overrides = {"model.%s" % k: v for k, v in small.items()}
    run.config_overrides.update({"loader.n_train": 8, "loader.n_valid": 2,
                                 "loader.sequence_length": 64})
    run.mix = dict(run.mix, trace_epochs=2)
    run.config = dict(run.config, **small)
    run.config["data"] = dict(run.config["data"], n_train=8, n_valid=2,
                              sequence_length=64)
    return run


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_through_the_driver(trace):
    run = tiny_run(trace=trace)
    assert (run.cell["config"], run.cell["traffic"], run.chips) == (
        "mellum2_12b_a2p5b", "scan_seq8k", 1)
    line = bench.execute(run)
    assert line["correct"] is True, line["checks"]
    assert set(line["checks"]) == CHECKS
    assert line["attempted"] > 0 and line["failed"] == 0
    c = run.counters
    assert c["train_steps"] == c["epochs"] * 4
    assert c["images"] == c["epochs"] * 8 and c["images_per_step"] == 2
    assert c["tokens"] == c["images"] * 64
    assert c["moe_rows_train"] > 0 and c["moe_rows_valid"] > 0
    assert sorted(c["expert_tokens"]) == ["moe0", "moe1", "moe2"]
    declared = {m["name"] for m in run.metrics_of(
        "per_layer" if trace else "end_to_end")}
    assert set(line["metrics"]) <= declared
    if trace:
        assert {"window_flash_roofline_pct", "gqa_flash_roofline_pct",
                "moe_grouped_matmul_roofline_pct", "train_step_mfu_pct"} \
            <= declared
        assert "mla_flash_roofline_pct" not in declared
        assert {"setup_compile_s", "moe_expert_load_max_over_mean"} \
            <= set(line["metrics"])
        # on the CPU the core is explicit scores: no kernel event, and
        # the readers say nothing rather than raising
        assert "window_flash_roofline_pct" not in line["metrics"]
        assert "gqa_flash_roofline_pct" not in line["metrics"]
    else:
        assert set(line["metrics"]) == {"train_img_per_s", "setup_s"}


@pytest.mark.parametrize("change", [
    {"layer_types": ["sliding_attention", "full_attention",
                     "full_attention"]},
    {"rope_parameters": dict(SMALL["rope_parameters"], full_attention=SMALL[
        "rope_parameters"]["sliding_attention"])},
    {"scoring_func": "sigmoid"}],
    ids=["window-off-in-one-layer", "yarn-left-out", "sigmoid-router"])
def test_a_reference_of_another_model_is_not_correct(monkeypatch, change):
    run = tiny_run()
    reference = run.config_module("reference")
    real = reference.forward

    def wrong(config, params, ids, *rest):
        return real(dict(config, **change), params, ids, *rest)
    monkeypatch.setattr(reference, "forward", wrong)
    line = bench.execute(run)
    assert line["correct"] is False
    # YaRN in one layer of three reads 1.2e-1 at these sizes, where 1e-1
    # and 1.2e-1 are the limits: refused by the first for certain; the
    # others by both
    assert "stated_precision_logits.probe" in failed_checks(line)
    if "rope_parameters" not in change:
        assert "reference_logits.probe" in failed_checks(line)
    assert line["checks"]["no_compile_in_window"]["ok"] is True


def test_the_reader_counts_the_window_calls_and_no_others():
    """``window_flash_roofline_pct`` on a hand-made window: the three
    calls made with a window by instruction name; the full layer's and
    the latent family's left out, as the full layer's reader leaves
    these out; nothing where there is no such event or no such work
    function."""
    import types
    reader = load("layer_metrics/window_flash_roofline_pct.py")
    full_reader = load("layer_metrics/gqa_flash_roofline_pct.py")
    work = load("configs/mellum2_12b_a2p5b/work.py")
    run = tiny_run()
    config = load_config("mellum2_12b_a2p5b")
    events = [("jvp_gqa_window_flash_fwd_.1 = custom-call", 0, 2_000_000),
              ("transpose_jvp_gqa_window_flash_dq__.3", 0, 3_000_000),
              ("transpose_jvp_gqa_window_flash_dkv__.1", 0, 5_000_000),
              ("jvp_gqa_flash_fwd_.1", 0, 13_000_000),
              ("transpose_jvp_gqa_flash_dkv__.2", 0, 17_000_000),
              ("jvp_mla_flash_fwd_.1", 0, 7_000_000),
              ("fusion.12", 0, 11_000_000)]
    run.config = config
    run.reduced = types.SimpleNamespace(devices={0: events})
    run.counters = {"images": 16, "valid_images": 2}
    run.peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    ops, moved = work.window_flash_work(config, 16, 2)
    assert ops / 197e12 > moved / 819e9            # the products bound it
    assert reader.read(run) == pytest.approx(
        100.0 * (ops / 197e12) / 0.010)
    # the full layer's reader sees its two events and none of the three
    full_ops, _ = work.gqa_flash_work(config, 16, 2)
    assert full_reader.read(run) == pytest.approx(
        100.0 * (full_ops / 197e12) / 0.030)
    run.reduced = types.SimpleNamespace(devices={0: events[3:]})
    assert reader.read(run) is None
    # another configuration's work has no window_flash_work: nothing read
    run.reduced = types.SimpleNamespace(devices={0: events})
    run.config_module = lambda name: load("configs/lfm2_24b_a2b/work.py")
    assert reader.read(run) is None
