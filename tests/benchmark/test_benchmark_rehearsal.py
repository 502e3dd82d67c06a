"""Rehearsal of the benchmark's driver on the CPU at tiny sizes: the
``train`` driver is called as ``run.py`` calls it, with the sizes and the
device check replaced HERE (never by an option of the command).  What is
checked is the control flow, the result line's keys, that ``correct`` is
decided by the plain reference, and that a wrong weight, a wrong solver
and activations in less than the stated precision each make it false.
No number these runs print is a device number.
"""

import pytest

from benchlib import load, manifest

bench = load("run.py")

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


CHECKS = {"reference_loss.trained", "reference_logits.trained",
          "reference_loss.probe", "reference_logits.probe",
          "stated_precision_logits.probe", "reference_update.probe",
          "loss_finite_and_lower",
          "no_compile_in_window", "parameters_on_every_chip"}


def failed_checks(line):
    return {name for name, c in line["checks"].items() if not c["ok"]}


def tiny_run(cell, seconds=1.5, trace=0):
    import jax
    run = bench.Run(manifest(), cell, 2 ** 31 + 99, seconds, trace)
    run.backend = "cpu"                         # in place of check_device
    run.devices = jax.devices()[:run.chips]
    run.peaks = {"bf16_flops_per_s": 1e12, "hbm_bytes": 0}
    n = run.chips
    run.config_overrides = {
        "loader.n_train": 64 * n, "loader.n_valid": 16 * n,
        "loader.side": 67, "loader.n_classes": 10}
    run.mix = dict(run.mix, minibatch_per_chip=16, trace_epochs=2)
    run.config = dict(run.config, input={"side": 67, "channels": 3},
                      data={"n_train": 64 * n, "n_valid": 16 * n})
    return run


def check_line(line, run, end_to_end):
    assert RESULT_KEYS <= set(line)
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert line["attempted"] > 0 and line["failed"] == 0
    declared = {m["name"]: m["unit"] for m in run.metrics_of(
        "end_to_end" if end_to_end else "per_layer")}
    assert set(line["metrics"]) <= set(declared)
    for name, metric in line["metrics"].items():
        assert metric["unit"] == declared[name]
        assert isinstance(metric["value"], float)
    if end_to_end:
        assert set(line["metrics"]) == set(declared)
        assert all(m["value"] > 0 for m in line["metrics"].values())
    else:
        assert {"busy_s", "window_s"} <= set(line["device"])
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
        assert "setup_compile_s" in line["metrics"]
    assert all(c["ok"] for c in line["checks"].values()), line["checks"]
    assert line["counters"] == run.counters


@pytest.mark.parametrize("cell,trace", [("alexnet_scan", 0),
                                        ("alexnet_scan", 1),
                                        ("alexnet_scan_data4", 0)])
def test_train_driver(cell, trace, monkeypatch):
    import jax
    run = tiny_run(cell, trace=trace)
    if run.chips > 1:
        # the suite's eight virtual devices cut to the cell's four: the
        # program's --mesh has to cover every device JAX shows
        real = jax.devices
        monkeypatch.setattr(jax, "devices",
                            lambda *a: real(*a)[:run.chips])
    line = bench.execute(run)
    check_line(line, run, end_to_end=not trace)
    assert line["correct"] is True
    assert set(line["checks"]) == CHECKS
    assert "on %d device(s)" % run.chips \
        in line["checks"]["parameters_on_every_chip"]["detail"]
    assert run.counters["train_steps"] == run.counters["epochs"] * 4
    assert run.counters["images"] == run.counters["epochs"] * 64 * run.chips


def train_driver():
    return bench.load_module(bench.os.path.join(
        bench.BENCH, "drivers", "train.py"))


def test_train_driver_wrong_weight_is_not_correct(monkeypatch):
    run = tiny_run("alexnet_scan")
    driver = train_driver()
    real = driver.reference_weights

    def wrong(params):
        weights = real(params)
        # one convolution kernel 5 % too large in the reference's copy
        weights[2] = (weights[2][0] * 1.05, weights[2][1])
        return weights

    monkeypatch.setattr(driver, "reference_weights", wrong)
    line = bench.execute(run)
    assert line["correct"] is False
    assert {"reference_logits.probe", "stated_precision_logits.probe",
            "reference_update.probe"} <= failed_checks(line)
    assert line["checks"]["no_compile_in_window"]["ok"] is True


@pytest.mark.parametrize("key,value", [("momentum", 0.8),
                                       ("weight_decay", 0.05),
                                       ("learning_rate", 0.011)])
def test_train_driver_wrong_solver_is_not_correct(key, value):
    """The update comparison sees the momentum, the decay and the rate:
    a reference told another solver than the system runs disagrees."""
    run = tiny_run("alexnet_scan")
    run.config = dict(run.config,
                      solver=dict(run.config["solver"], **{key: value}))
    line = bench.execute(run)
    assert line["correct"] is False
    assert failed_checks(line) == {"reference_update.probe"}


def test_train_driver_bfloat16_activations_are_not_correct():
    """A system that computes in less than the configuration states: the
    comparison of its output with the mathematics passes (its tolerance
    covers one bf16 rounding of every operand); the one in the stated
    arithmetic fails, and so does the update at ``highest`` precision,
    which no precision setting rescues from bfloat16 activations."""
    run = tiny_run("alexnet_scan")
    run.config = dict(run.config, compute_dtype="bfloat16")
    line = bench.execute(run)
    assert line["correct"] is False
    failed = failed_checks(line)
    assert {"stated_precision_logits.probe",
            "reference_update.probe"} <= failed
    assert not {"reference_logits.probe", "reference_logits.trained",
                "loss_finite_and_lower"} & failed


def test_check_device_refuses_what_is_not_in_the_table(monkeypatch):
    import jax
    run = bench.Run(manifest(), "alexnet_scan", 1, 1.0, 0)
    with pytest.raises(bench.Refused, match="not 'tpu'"):
        bench.check_device(run)

    class Chip:
        platform, device_kind = "tpu", "TPU v9 imaginary"

    monkeypatch.setattr(jax, "devices", lambda: [Chip()])
    with pytest.raises(bench.Refused, match="peaks.json"):
        bench.check_device(run)
    four = bench.Run(manifest(), "alexnet_scan_data4", 1, 1.0, 0)
    Chip.device_kind = "TPU v5 lite"
    with pytest.raises(bench.Refused, match="asks for 4"):
        bench.check_device(four)
    bench.check_device(run)
    assert run.peaks["bf16_flops_per_s"] == 197e12
