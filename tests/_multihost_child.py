"""Child process for the 2-process init_multihost tests.

Usage: python _multihost_child.py RANK PORT OUT_FILE [MODE]

Joins a 2-process jax.distributed cluster (2 virtual CPU devices per
process -> one 4-device global mesh) and writes the resulting
(replicated) first-layer weights to OUT_FILE so the parent can assert
both hosts hold identical params.  MODE:

- "step" (default): ONE fused per-minibatch step, dp=4
  (FusedTrainStep over the mesh);
- "scan": TWO full train epochs in one lax.scan dispatch, dp=4
  (ScanEpochStep over the mesh) — the multi-host epoch-scan path (VERDICT
  round-3 item 4)."""

import os
import sys

rank = int(sys.argv[1])
port = sys.argv[2]
out_file = sys.argv[3]
mode = sys.argv[4] if len(sys.argv) > 4 else "step"

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy  # noqa: E402

from veles_tpu.distributed import init_multihost  # noqa: E402
from veles_tpu.backends import Device  # noqa: E402
from veles_tpu.parallel.mesh import make_mesh  # noqa: E402
from veles_tpu.prng import RandomGenerator  # noqa: E402
from veles_tpu import loader as loader_mod  # noqa: E402
from veles_tpu.znicz.samples import mnist  # noqa: E402

pid, n = init_multihost(coordinator_address="127.0.0.1:%s" % port,
                        num_processes=2, process_id=rank)
assert (pid, n) == (rank, 2), (pid, n)
assert len(jax.devices()) == 4, jax.devices()
assert len(jax.local_devices()) == 2

mesh = make_mesh({"data": 4})
wf = mnist.create_workflow(
    loader={"minibatch_size": 16, "n_train": 64, "n_valid": 16,
            "prng": RandomGenerator().seed(3)},
    decision={"max_epochs": 1, "silent": True},
    mesh=mesh, epoch_scan=(mode == "scan"))
wf.initialize(device=Device(backend="cpu"))
if mode == "scan":
    wf.fused_step.train_epochs(2)
else:
    while True:
        wf.loader.run()
        if wf.loader.minibatch_class == loader_mod.TRAIN:
            break
    wf.fused_step.run()
loss = float(wf.fused_step.loss)
assert loss == loss, "NaN loss"
weights = numpy.asarray(wf.fused_step._params_[0]["weights"])
numpy.save(out_file, weights)
print("rank %d ok loss=%.6f" % (rank, loss))
