"""The benchmark's AlexNet workflow, as a user of veles-tpu writes one:
``python -m veles_tpu benchmark/configs/alexnet/workflow.py --mode scan``.

It reuses the layers of the Znicz sample (``veles_tpu/znicz/samples/
alexnet.py``: single tower, 227 x 227 x 3, 1000 classes) and differs in
two things, both the benchmark's own:

- the loader makes the synthetic data set on the device, in float32, in
  one jitted call from ``root.alexnet_bench.loader.seed`` (the sample's
  loader draws 1.3 G float64 numbers on the host from a fixed seed), and
  under ``--mesh`` makes it replicated over the mesh at once: the mesh
  scan step replicates the data set anyway, and a copy made on one chip
  first would stay there beside the replica (10 of that chip's 16 GB);
- an :class:`EpochClock` unit, linked after the decision like a plotter
  or a reporter, reads the host clock at every epoch's end once the
  step's parameters are ready, and stops the workflow at the end of the
  epoch in which the window closes.
"""

import time

import numpy

from veles_tpu.config import root
from veles_tpu.loader.base import TEST, TRAIN, VALID
from veles_tpu.loader.fullbatch import FullBatchLoader
from veles_tpu.units import Unit
from veles_tpu.znicz.samples import alexnet as sample    # registers layers
from veles_tpu.znicz.samples import build_standard

root.alexnet_bench.update({
    "loader": {"minibatch_size": 256, "normalization_type": "none",
               "n_train": 8192, "n_valid": 512, "n_classes": 1000,
               "side": 227, "seed": 0},
    # out of reach: the clock ends the run, never the decision
    "decision": {"max_epochs": 10 ** 9, "fail_iterations": 10 ** 9,
                 "silent": True},
})


class DeviceSyntheticLoader(FullBatchLoader):
    """ImageNet-shaped uniform noise in [-0.5, 0.5) with random labels,
    made on the device from a seed and kept resident in HBM."""

    MAPPING = "benchmark_device_synthetic_loader"

    def __init__(self, workflow, **kwargs):
        self.n_train = int(kwargs.pop("n_train"))
        self.n_valid = int(kwargs.pop("n_valid"))
        self.n_classes = int(kwargs.pop("n_classes"))
        self.side = int(kwargs.pop("side"))
        self.seed = int(kwargs.pop("seed"))
        self.mesh = kwargs.pop("mesh", None)
        super().__init__(workflow, **kwargs)

    def load_data(self):
        import jax
        import jax.numpy as jnp
        n = self.n_train + self.n_valid
        shape = (n, self.side, self.side, 3)
        # two 32-bit words: --seed may be wider than int32
        key = jnp.asarray([self.seed >> 32 & 0xFFFFFFFF,
                           self.seed & 0xFFFFFFFF], jnp.uint32)
        replicated = {}
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            replicated["out_shardings"] = NamedSharding(self.mesh,
                                                        PartitionSpec())
        self.original_data.devmem = jax.jit(
            lambda k: jax.random.uniform(
                jax.random.wrap_key_data(k, impl="threefry2x32"), shape,
                jnp.float32, -0.5, 0.5), **replicated)(key)
        rng = numpy.random.default_rng(self.seed)
        self.original_labels = [
            int(c) for c in rng.integers(0, self.n_classes, n)]
        self.class_lengths[TEST] = 0
        self.class_lengths[VALID] = self.n_valid
        self.class_lengths[TRAIN] = self.n_train

    def analyze_dataset(self):
        # the data is made in its served dtype and range: nothing to
        # analyze or bake in, and no 5 GB round trip through the host
        self._dense_labels = numpy.asarray(self.original_labels,
                                           self.LABEL_DTYPE)
        for raw in sorted(set(self.original_labels)):
            self.labels_mapping[raw] = raw

    prepare_restored_dataset = analyze_dataset


class EpochClock(Unit):
    """Host-clock reading at each epoch's end, after the parameters the
    epoch produced are ready on the device.  ``on_epoch(clock)`` is the
    driver's hook; it returns True to stop the workflow."""

    def __init__(self, workflow, **kwargs):
        super().__init__(workflow, **kwargs)
        self.on_epoch = None
        self.epoch_ends = []        # time.perf_counter() per epoch

    def run(self):
        import jax
        wf = self._workflow
        if not bool(wf.loader.epoch_ended):
            return
        jax.block_until_ready(wf.fused_step._params_)
        self.epoch_ends.append(time.perf_counter())
        if self.on_epoch is not None and self.on_epoch(self):
            wf.stop()


def create_workflow(**overrides):
    if overrides.get("mesh") is not None:
        overrides["loader"] = dict(overrides.get("loader", {}),
                                   mesh=overrides["mesh"])
    wf = build_standard(root.alexnet_bench, "AlexNetBench",
                        DeviceSyntheticLoader, "softmax",
                        layers=root.alexnet.layers, **overrides)
    wf.epoch_clock = EpochClock(wf, name="epoch_clock")
    wf.epoch_clock.link_from(wf.decision)
    return wf


def run(load, main):
    load(create_workflow)
    main()
