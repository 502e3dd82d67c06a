"""``BENCHMARK.json`` against the contract it is written to, and against
the files it names."""

import os
import re
import subprocess
import sys

import pytest

from benchlib import BENCH, REPO, config, load_json, manifest

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def cells():
    return manifest()["workloads"]


def metrics(group):
    return manifest()[group]


def cells_of(metric):
    return metric.get("workloads", [c["name"] for c in cells()])


def test_top_level_keys_and_command():
    m = manifest()
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert m["command"] == ["python3", "benchmark/run.py"]
    assert m["paths"] == ["benchmark", "tests/benchmark"]
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 64 * 1024


def test_a_full_check_fits_with_24_cells():
    seconds = manifest()["run_seconds"]
    assert isinstance(seconds, int) and 1 <= seconds <= 51
    full = (2 + 14 * 24) * (seconds + 60) + 24 * 2 * 90 + 1200
    assert full <= 43200


@pytest.mark.parametrize("group", ["configs", "workloads", "end_to_end",
                                   "per_layer"])
def test_names_are_unique_and_of_allowed_characters(group):
    names = [e["name"] for e in manifest()[group]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names), names


def test_metric_entries():
    names = [m["name"] for g in ("end_to_end", "per_layer")
             for m in metrics(g)]
    assert len(names) == len(set(names))
    for m in metrics("end_to_end"):
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in metrics("per_layer"):
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert m["source"] in SOURCES
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]
    for m in metrics("end_to_end") + metrics("per_layer"):
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    setup = [m for m in metrics("end_to_end") if m["name"] == "setup_s"]
    assert len(setup) == 1 and "workloads" not in setup[0]
    assert setup[0]["bound"] <= 0.1


def test_every_cell_reports_setup_one_more_metric_and_a_layer_metric():
    for cell in cells():
        mine = [m["name"] for m in metrics("end_to_end")
                if cell["name"] in cells_of(m)]
        assert "setup_s" in mine and len(mine) >= 2, cell["name"]
        assert any(cell["name"] in cells_of(m)
                   for m in metrics("per_layer")), cell["name"]


def test_moves_is_reported_by_every_cell_of_the_layer_metric():
    end_to_end = {m["name"]: m for m in metrics("end_to_end")}
    known = {c["name"] for c in cells()}
    for m in metrics("per_layer"):
        assert m["moves"] in end_to_end, m
        assert set(cells_of(m)) <= known
        assert set(cells_of(m)) <= set(cells_of(end_to_end[m["moves"]])), m


def test_cells_and_their_files():
    m = manifest()
    configs = {c["name"]: c for c in m["configs"]}
    pairs = [(c["config"], c["traffic"]) for c in cells()]
    assert len(pairs) == len(set(pairs))
    assert {c["config"] for c in cells()} == set(configs)
    files = [c["file"] for c in m["configs"]]
    assert len(files) == len(set(files))
    for cell in cells():
        assert set(cell) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(cell["config"]) and NAME.match(cell["traffic"])
        assert cell["chips"] in (1, 4)
        assert 1 <= len(cell["why"]) <= 200 and "\n" not in cell["why"]
        entry = configs[cell["config"]]
        cfg = load_json(entry["file"])
        directory = os.path.dirname(os.path.join(REPO, entry["file"]))
        for piece in ("reference.py", "work.py"):
            assert os.path.isfile(os.path.join(directory, piece))
        assert os.path.isfile(os.path.join(
            BENCH, "drivers", cfg["driver"] + ".py"))
        mix = load_json("benchmark", "traffic", cell["traffic"] + ".json")
        assert os.path.isfile(os.path.join(
            BENCH, "generators", mix["generator"] + ".py"))
    four = [c for c in cells() if c["chips"] == 4]
    assert len(four) <= max(1, len(cells()) // 4)


def test_configs_entries():
    for entry in manifest()["configs"]:
        assert set(entry) == {"name", "source", "file", "reduced", "why"}
        assert entry["file"].startswith("benchmark/")
        assert 1 <= len(entry["source"]) <= 200
        assert 1 <= len(entry["why"]) <= 200
        cfg = load_json(entry["file"])
        assert cfg["name"] == entry["name"]
        assert cfg["reduced"] == entry["reduced"]
        assert len(entry["reduced"]) <= 16
        for key in entry["reduced"]:
            assert NAME.match(key) and key in cfg
            assert not re.search(r"(_dim|_rank|hidden|width|head)", key)
        assert isinstance(cfg["assumed"], dict) and cfg["assumed"]


def test_every_layer_metric_has_its_reader():
    for m in metrics("per_layer"):
        path = os.path.join(BENCH, "layer_metrics", m["name"] + ".py")
        assert os.path.isfile(path), path
    readers = {f[:-3] for f in os.listdir(os.path.join(BENCH,
                                                       "layer_metrics"))
               if f.endswith(".py")}
    assert readers == {m["name"] for m in metrics("per_layer")}


def test_files_under_paths_are_named_from_allowed_characters():
    for path in manifest()["paths"]:
        for directory, dirs, files in os.walk(os.path.join(REPO, path)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for name in files:
                assert re.match(r"^[A-Za-z0-9_.\-]+$", name), name


def test_alexnet_config_is_the_samples_network():
    """config.json's layer list is what the program's sample builds."""
    from veles_tpu.config import root
    from veles_tpu.znicz.samples import alexnet  # noqa: F401 — registers
    kinds = {"conv_str": "conv", "norm": "lrn", "max_pooling": "max_pool",
             "all2all_str": "fc", "dropout": "dropout",
             "softmax": "softmax"}
    ours = config("alexnet")["layers"]
    theirs = root.alexnet.layers
    assert [kinds[layer["type"]] for layer in theirs] \
        == [layer["type"] for layer in ours]
    for mine, sample in zip(ours, theirs):
        fwd = sample["->"]
        if mine["type"] == "conv":
            assert (mine["kernels"], mine["kx"], mine["ky"]) == (
                fwd["n_kernels"], fwd["kx"], fwd["ky"])
            assert mine["stride"] == fwd.get("sliding", (1, 1))[0]
            assert mine["padding"] == fwd.get("padding", 0)
        elif mine["type"] == "lrn":
            assert (mine["n"], mine["alpha"], mine["beta"], mine["k"]) == (
                fwd["n"], fwd["alpha"], fwd["beta"], fwd["k"])
        elif mine["type"] == "max_pool":
            assert (mine["kx"], mine["ky"], mine["stride"]) == (
                fwd["kx"], fwd["ky"], fwd["sliding"][0])
        elif mine["type"] in ("fc", "softmax"):
            assert mine["neurons"] == fwd["output_sample_shape"]
        elif mine["type"] == "dropout":
            assert mine["ratio"] == fwd["dropout_ratio"]
        if "<-" in sample:
            solver = config("alexnet")["solver"]
            assert (solver["learning_rate"], solver["momentum"],
                    solver["weight_decay"]) == (
                sample["<-"]["learning_rate"],
                sample["<-"]["gradient_moment"],
                sample["<-"]["weights_decay"])


def test_peaks_table():
    peaks = load_json("benchmark", "peaks.json")
    assert "TPU v5e" in peaks["source"]
    v5e = peaks["devices"]["TPU v5 lite"]
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["int8_ops_per_s"] == 393e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["hbm_bytes"] == 16 * 2 ** 30


def _run_command(args, cwd=REPO, env=None):
    command = manifest()["command"] + args
    return subprocess.run([sys.executable] + command[1:], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_the_command_refuses_a_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = _run_command(["--workload", "alexnet_scan", "--seed", "1",
                         "--seconds", "1", "--trace", "0"], env=env)
    assert done.returncode == 2
    assert done.stdout.strip() == ""
    assert "not 'tpu'" in done.stderr


def test_the_command_refuses_an_unknown_cell_and_a_bare_directory(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = _run_command(["--workload", "no_such_cell", "--seed", "1",
                         "--seconds", "1", "--trace", "0"], env=env)
    assert done.returncode == 2 and done.stdout.strip() == ""
    # a directory that holds only BENCHMARK.json and the files under paths
    import shutil
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    done = _run_command(["--workload", "alexnet_scan", "--seed", "1",
                         "--seconds", "1", "--trace", "0"],
                        cwd=str(tmp_path), env=env)
    assert done.returncode == 2 and done.stdout.strip() == ""
    assert "veles_tpu" in done.stderr
