"""Helpers of the benchmark's tests: the benchmark's files are found by
path (``benchmark/`` is a directory of programs and data, not a package)."""

import importlib.util
import json
import os

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmark")


def load(relative):
    """A Python file under ``benchmark/`` as a module."""
    path = os.path.join(BENCH, relative)
    name = "benchtest_" + relative.replace(os.sep, "_").replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_json(*parts):
    with open(os.path.join(REPO, *parts)) as f:
        return json.load(f)


def manifest():
    return load_json("BENCHMARK.json")


def config(name):
    entry = next(c for c in manifest()["configs"] if c["name"] == name)
    return load_json(entry["file"])
