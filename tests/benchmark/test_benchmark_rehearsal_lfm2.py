"""Rehearsal of the cell ``lfm2_scan_seq8k`` through the ``train_lm``
driver on the CPU at tiny sizes, as ``test_benchmark_rehearsal_lm.py``
rehearses Kanana's: the driver is called as ``run.py`` calls it, with the
sizes and the device check replaced HERE.  Checked: the control flow,
the result line, the counters the readers need, that ``correct`` is
decided by the plain reference, and that a wrong convolution and a wrong
attention make it false.  No number these runs print is a device
number."""

import pytest

from benchlib import config as load_config, load, manifest
from test_benchmark_lfm2 import SMALL
from test_benchmark_rehearsal_lm import CHECKS, failed_checks

bench = load("run.py")


def tiny_run(trace=0, seconds=1.0):
    import jax
    run = bench.Run(manifest(), "lfm2_scan_seq8k", 2 ** 31 + 91, seconds,
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
        "lfm2_24b_a2b", "scan_seq8k", 1)
    line = bench.execute(run)
    assert line["correct"] is True, line["checks"]
    assert set(line["checks"]) == CHECKS
    assert line["attempted"] > 0 and line["failed"] == 0
    c = run.counters
    assert c["train_steps"] == c["epochs"] * 4
    assert c["images"] == c["epochs"] * 8 and c["images_per_step"] == 2
    assert c["tokens"] == c["images"] * 64
    assert c["moe_rows_train"] > 0 and c["moe_rows_valid"] > 0
    assert sorted(c["expert_tokens"]) == ["moe1", "moe2"]
    declared = {m["name"] for m in run.metrics_of(
        "per_layer" if trace else "end_to_end")}
    assert set(line["metrics"]) <= declared
    if trace:
        assert "gqa_flash_roofline_pct" in declared
        assert "mla_flash_roofline_pct" not in declared
        assert {"setup_compile_s", "moe_expert_load_max_over_mean"} \
            <= set(line["metrics"])
        # on the CPU the core is explicit scores: no kernel event, and
        # the reader says nothing rather than raising
        assert "gqa_flash_roofline_pct" not in line["metrics"]
    else:
        assert set(line["metrics"]) == {"train_img_per_s", "setup_s"}


@pytest.mark.parametrize("layer,tensor", [(1, "conv"), (3, "wv")])
def test_a_wrong_operator_is_not_correct(monkeypatch, layer, tensor):
    run = tiny_run()
    reference = run.config_module("reference")
    real = reference.forward

    def wrong(config, params, ids, *rest):
        params = [dict(p) for p in params]
        params[layer][tensor] = params[layer][tensor] * 1.5
        return real(config, params, ids, *rest)
    monkeypatch.setattr(reference, "forward", wrong)
    line = bench.execute(run)
    assert line["correct"] is False
    assert {"reference_logits.probe", "stated_precision_logits.probe",
            "reference_update.moments"} <= failed_checks(line)
    assert line["checks"]["no_compile_in_window"]["ok"] is True


def test_the_reader_counts_the_kernels_events_and_no_others():
    """``gqa_flash_roofline_pct`` on a hand-made window: the three
    kernels' events by instruction name, the latent family's left out;
    nothing where there is no such event or no such work function."""
    import types
    reader = load("layer_metrics/gqa_flash_roofline_pct.py")
    work = load("configs/lfm2_24b_a2b/work.py")
    run = tiny_run()
    config = load_config("lfm2_24b_a2b")
    events = [("jvp_gqa_flash_fwd_.1 = custom-call", 0, 2_000_000),
              ("transpose_jvp_gqa_flash_dq__.3", 0, 3_000_000),
              ("transpose_jvp_gqa_flash_dkv__.1", 0, 5_000_000),
              ("jvp_mla_flash_fwd_.1", 0, 7_000_000),
              ("fusion.12", 0, 11_000_000)]
    run.config = config
    run.reduced = types.SimpleNamespace(devices={0: events})
    run.counters = {"images": 16, "valid_images": 2}
    run.peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    ops, moved = work.gqa_flash_work(config, 16, 2)
    assert ops / 197e12 > moved / 819e9            # the products bound it
    assert reader.read(run) == pytest.approx(
        100.0 * (ops / 197e12) / 0.010)
    run.reduced = types.SimpleNamespace(devices={0: events[3:]})
    assert reader.read(run) is None
    # another configuration's work has no gqa_flash_work: nothing read
    run.reduced = types.SimpleNamespace(devices={0: events})
    run.config_module = lambda name: load(
        "configs/kanana2_30b_a3b/work.py")
    assert reader.read(run) is None
