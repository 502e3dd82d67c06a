"""The training mixes: a mix only says how the trainer is invoked."""

import pytest

from benchlib import load, load_json

epochs = load("generators/epochs.py")


def mix(name):
    return load_json("benchmark", "traffic", name + ".json")


@pytest.mark.parametrize("name,chips,argv", [
    ("scan", 1, ["--mode", "scan", "--random-seed", "5"]),
    ("scan_data4", 4, ["--mode", "scan", "--random-seed", "5", "--mesh",
                       "data=4"])])
def test_epochs_plan(name, chips, argv):
    plan = epochs.generate(mix(name), 5, chips)
    assert plan == {"argv": argv, "minibatch": 256 * chips}
