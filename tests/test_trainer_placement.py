"""The mesh is an argument of the two step classes (``FusedTrainStep``,
``ScanEpochStep``) and the sharding rule one object they hold
(``parallel.mesh.TrainerPlacement``).  Held here: over a mesh the steps
compile the programs the mesh subclasses compiled before them; with no
mesh they make the calls they made (no committed array); a mesh pickles
as its spec from one place.  All on the CPU's virtual devices."""

import json
import os
import pickle
import subprocess
import sys

import jax
import pytest

from veles_tpu.backends import Device
from veles_tpu.parallel.mesh import make_mesh, mesh_spec

from test_standard_workflow import build

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# -- over a mesh: the programs the twins compiled -----------------------------

#: the script of tests/benchmark/test_benchmark_compiles.py, turned on a
#: tiny workflow over a mesh of the virtual devices: two epochs, backend
#: compiles counted by jit name
SCRIPT = r"""
import collections, json, sys, os
sys.path.insert(0, os.path.join(%(repo)r, "tests"))
sys.path.insert(0, %(repo)r)
import jax, jax.monitoring
names = collections.Counter()
jax.monitoring.register_event_duration_secs_listener(
    lambda name, secs, **kw: names.update([kw.get("fun_name", "?")])
    if name.endswith("backend_compile_duration") else None)
from veles_tpu.parallel.mesh import make_mesh
from test_standard_workflow import build
axes = %(axes)r
mesh = make_mesh(axes, devices=jax.devices()[:4])
wf = build(fused=True, minibatch=40, epoch_scan=%(scan)r, max_epochs=2,
           mesh=mesh, model_axis="model" if "model" in axes else None)
wf.run()
assert type(wf.fused_step).__name__ == %(cls)r
print("RESULT " + json.dumps(dict(names)))
"""

#: backend compiles of the parent commit (99b034a, where a step over a
#: mesh was a subclass of its own in ``parallel/dp.py`` and
#: ``parallel/scan.py``), by jit name, counted there with this same script
BEFORE = {
    "scan_data4": {
        "jit(add)": 1, "jit(broadcast_in_dim)": 6,
        "jit(convert_element_type)": 2, "jit(copy)": 8,
        "jit(eval_scan)": 1, "jit(gather)": 2, "jit(train_scan)": 1},
    "scan_data2_model2": {
        "jit(_multi_slice)": 4, "jit(add)": 1,
        "jit(broadcast_in_dim)": 6, "jit(convert_element_type)": 2,
        "jit(copy)": 8, "jit(eval_scan)": 1, "jit(gather)": 2,
        "jit(train_scan)": 1},
    "per_step_data4": {
        "jit(_multi_slice)": 2, "jit(add)": 1,
        "jit(broadcast_in_dim)": 5, "jit(convert_element_type)": 2,
        "jit(copy)": 8, "jit(eval_step)": 1, "jit(gather)": 1,
        "jit(train_step)": 1},
    "per_step_data2_model2": {
        "jit(_multi_slice)": 6, "jit(add)": 1,
        "jit(broadcast_in_dim)": 5, "jit(convert_element_type)": 2,
        "jit(copy)": 8, "jit(eval_step)": 1, "jit(gather)": 1,
        "jit(train_step)": 1},
}


@pytest.mark.parametrize("case", sorted(BEFORE))
def test_a_mesh_step_compiles_what_its_twin_compiled(case, tmp_path):
    scan = case.startswith("scan")
    axes = {"data": 4} if case.endswith("data4") else \
        {"data": 2, "model": 2}
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    script = SCRIPT % {
        "repo": REPO, "axes": axes, "scan": scan,
        "cls": "ScanEpochStep" if scan else "FusedTrainStep"}
    done = subprocess.run([sys.executable, "-c", script], cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    result = next(line for line in done.stdout.splitlines()
                  if line.startswith("RESULT "))
    assert json.loads(result[7:]) == BEFORE[case]


def test_a_mesh_scan_leaves_its_per_minibatch_programs_plain():
    """The scan class dispatches scans; its per-minibatch programs name
    no sharding, so a caller may hand them arrays wherever they lie:
    the benchmark's correctness check (``benchmark/drivers/train.py``)
    evaluates ``_eval_step_`` on a batch it replicated itself, which a
    program with a batch-split ``in_shardings`` refuses."""
    import numpy
    mesh = make_mesh({"data": 4}, devices=jax.devices()[:4])
    wf = build(fused=True, minibatch=40, epoch_scan=True, max_epochs=1,
               mesh=mesh)
    step = wf.fused_step
    rep = step._placement_.shardings(("rep",))[0]
    x = jax.device_put(numpy.zeros((40, 8), numpy.float32), rep)
    y = jax.device_put(numpy.zeros(40, numpy.int32), rep)
    _, loss, out = step._eval_step_(
        jax.tree.map(jax.numpy.array, step._params_), step._macc_init(),
        x, y, 40)
    assert out.shape == (40, 4) and numpy.isfinite(float(loss))


# -- no mesh: the calls the step always made ----------------------------------

def operands(step):
    return jax.tree.leaves((step._params_, step._opt_, step._macc_))


@pytest.mark.parametrize("scan", [False, True], ids=["per_step", "scan"])
def test_with_no_mesh_nothing_of_the_step_is_committed(scan):
    """A jit specialises on whether an argument is committed to its
    device (PERF.md section 6, PR 29): with no mesh the step holds no
    placement, names no sharding, and its parameters, solver state and
    accumulator are uncommitted arrays on the default device, before the
    first dispatch and after an epoch of them."""
    wf = build(fused=True, minibatch=40, epoch_scan=scan, max_epochs=1)
    step = wf.fused_step
    assert step.mesh is None and step._placement_ is None
    for when in ("initialized", "after an epoch"):
        leaves = operands(step)
        assert len(leaves) > 8
        for leaf in leaves:
            assert not leaf.committed, when
            assert leaf.devices() == {jax.devices()[0]}, when
        wf.run()


# -- a mesh pickles as its spec, from one place -------------------------------

def data_mesh(n):
    return make_mesh({"data": n}, devices=jax.devices()[:n])


@pytest.mark.parametrize("scan", [False, True], ids=["per_step", "scan"])
def test_a_pickled_mesh_comes_back_from_its_spec(scan):
    """Workflow and step pickle their mesh as ``{axis: size}`` and
    rebuild it over the restoring process's devices; a Mesh assigned to
    the workflow before ``initialize`` wins over both specs, and the
    step's operands lie on it."""
    wf = build(fused=True, minibatch=40, epoch_scan=scan, max_epochs=1,
               mesh=data_mesh(4))
    wf.run()
    blob = pickle.dumps(wf)
    assert wf.mesh is wf.fused_step.mesh      # pickling left them live

    back = pickle.loads(blob)
    assert back.mesh == back.fused_step.mesh == mesh_spec(data_mesh(4))
    back.initialize(device=Device(backend="cpu"))
    step = back.fused_step
    assert step.mesh is back.mesh and back.mesh == data_mesh(4)
    assert step._placement_.mesh is step.mesh

    other = pickle.loads(blob)
    other.mesh = data_mesh(2)
    other.decision.max_epochs = 2
    other.initialize(device=Device(backend="cpu"))
    step = other.fused_step
    assert step.mesh is other.mesh and dict(step.mesh.shape) == {"data": 2}
    for when in ("initialized", "after an epoch"):
        for leaf in operands(step):
            assert leaf.sharding.mesh == other.mesh, when
        other.run()
