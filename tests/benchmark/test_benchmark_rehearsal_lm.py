"""Rehearsal of the ``train_lm`` driver on the CPU at tiny sizes: the
driver is called as ``run.py`` calls it, with the sizes and the device
check replaced HERE.  What is checked is the control flow, the result
line, the counters the readers need, that ``correct`` is decided by the
plain reference and that a wrong weight and a wrong solver make it
false.  No number these runs print is a device number."""

import pytest

from benchlib import load, manifest
from test_benchmark_kanana2 import SMALL

bench = load("run.py")

CHECKS = {"reference_logits.trained", "stated_precision_logits.trained",
          "reference_loss.trained", "reference_logits.probe",
          "stated_precision_logits.probe", "reference_loss.probe",
          "reference_update.moments", "reference_update.change",
          "dropless", "loss_finite_and_lower", "no_compile_in_window",
          "parameters_on_every_chip"}


def tiny_run(trace=0, seconds=1.0, **model):
    import jax
    run = bench.Run(manifest(), "kanana2_scan_seq8k", 2 ** 31 + 77, seconds,
                    trace)
    run.backend = "cpu"                         # in place of check_device
    run.devices = jax.devices()[:1]
    run.peaks = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11,
                 "hbm_bytes": 0}
    small = dict(SMALL, n_routed_experts=4, **model)
    run.config_overrides = {"model.%s" % k: v for k, v in small.items()}
    run.config_overrides.update({"loader.n_train": 8, "loader.n_valid": 2,
                                 "loader.sequence_length": 64})
    run.mix = dict(run.mix, trace_epochs=2)
    run.config = dict(run.config, **small)
    run.config["data"] = dict(run.config["data"], n_train=8, n_valid=2,
                              sequence_length=64)
    return run


def failed_checks(line):
    return {name for name, c in line["checks"].items() if not c["ok"]}


@pytest.mark.parametrize("trace", [0, 1])
def test_train_lm_driver(trace):
    run = tiny_run(trace=trace)
    line = bench.execute(run)
    assert line["correct"] is True, line["checks"]
    assert set(line["checks"]) == CHECKS
    assert line["attempted"] > 0 and line["failed"] == 0
    c = run.counters
    assert c["train_steps"] == c["epochs"] * 4
    assert c["images"] == c["epochs"] * 8 and c["images_per_step"] == 2
    assert c["tokens"] == c["images"] * 64
    assert c["moe_rows_train"] > 0 and c["moe_rows_valid"] > 0
    assert c["expert_load_max_over_mean"] >= 1.0
    declared = {m["name"] for m in run.metrics_of(
        "per_layer" if trace else "end_to_end")}
    assert set(line["metrics"]) <= declared
    if trace:
        assert {"setup_compile_s", "moe_expert_load_max_over_mean"} \
            <= set(line["metrics"])
    else:
        assert set(line["metrics"]) == {"train_img_per_s", "setup_s"}


def test_train_lm_wrong_weight_is_not_correct(monkeypatch):
    run = tiny_run()
    driver = bench.load_module(bench.os.path.join(
        bench.BENCH, "drivers", "train_lm.py"))
    reference = run.config_module("reference")
    real = reference.forward

    def wrong(config, params, ids, precision="highest"):
        params = [dict(p) for p in params]
        params[3]["wo"] = params[3]["wo"] * 1.5     # one projection off
        return real(config, params, ids, precision)
    monkeypatch.setattr(reference, "forward", wrong)
    line = bench.execute(run)
    assert line["correct"] is False
    assert {"reference_logits.probe", "stated_precision_logits.probe",
            "reference_update.moments"} <= failed_checks(line)
    assert line["checks"]["no_compile_in_window"]["ok"] is True
    assert driver.UPDATE_STEPS == 2


@pytest.mark.parametrize("key,value", [("beta1", 0.8), ("beta2", 0.999),
                                       ("weight_decay", 20.0)])
def test_train_lm_wrong_solver_is_not_correct(key, value):
    """The update comparison sees the two betas; the decay, a fiftieth of
    an Adam step at these weights, only when it is grossly wrong (AdamW
    by hand and against the reference at 5e-3: the CPU tests)."""
    run = tiny_run()
    run.config["solver"] = dict(run.config["solver"], **{key: value})
    line = bench.execute(run)
    assert line["correct"] is False
    assert failed_checks(line) <= {"reference_update.moments",
                                   "reference_update.change"}
