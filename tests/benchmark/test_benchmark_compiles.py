"""The AlexNet cells' programs are the ones they were before the token
model went through the trainer: the tiny AlexNet rehearsal, in a process
of its own, compiles the same programs under the same names as the
parent of PR 28 did (counted there with this same script)."""

import json
import os
import subprocess
import sys

from benchlib import REPO

SCRIPT = r"""
import collections, json, sys, os
sys.path.insert(0, os.path.join(%(repo)r, "tests", "benchmark"))
sys.path.insert(0, %(repo)r)
import jax, jax.monitoring
names = collections.Counter()
jax.monitoring.register_event_duration_secs_listener(
    lambda name, secs, **kw: names.update([kw.get("fun_name", "?")])
    if name.endswith("backend_compile_duration") else None)
import test_benchmark_rehearsal as t
run = t.tiny_run("alexnet_scan")
line = t.bench.execute(run)
assert line["correct"], line["checks"]
print("RESULT " + json.dumps(dict(names)))
"""

#: backend compiles of the parent commit (fbd1f69), by jit name
BEFORE = {
    "jit(<lambda>)": 1, "jit(<unknown>)": 2, "jit(_normal)": 13,
    "jit(_take)": 2, "jit(_threefry_fold_in)": 1, "jit(_threefry_seed)": 1,
    "jit(add)": 1, "jit(broadcast_in_dim)": 14,
    "jit(convert_element_type)": 4, "jit(copy)": 13,
    "jit(dynamic_slice)": 2, "jit(eval_scan)": 1, "jit(eval_step)": 2,
    "jit(multiply)": 13, "jit(squeeze)": 1, "jit(train_scan)": 1,
    "jit(train_step)": 2, "jit(update)": 1}


def test_alexnet_tiny_workflow_compiles_what_it_compiled_before(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    done = subprocess.run(
        [sys.executable, "-c", SCRIPT % {"repo": REPO}], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    result = next(line for line in done.stdout.splitlines()
                  if line.startswith("RESULT "))
    after = json.loads(result[7:])
    assert sum(after.values()) == sum(BEFORE.values()) == 75
    assert after == BEFORE
