"""ImageNet AlexNet sample — the BASELINE.json headline workflow.

Re-creation of the Znicz AlexNet (absent submodule; model status
/root/reference/docs/source/manualrst_veles_algorithms.rst:56-63).
Canonical single-tower AlexNet (the two-GPU grouping of the 2012 paper is
an artifact of 3GB GPUs; on TPU the MXU wants the full-width convs, and
the Znicz ZeroFiller grouping trick remains available via the
``zero_filler`` unit for strict parity experiments):

conv11x11/4x96 → LRN → max3x3/2 → conv5x5x256 → LRN → max3x3/2 →
conv3x3x384 → conv3x3x384 → conv3x3x256 → max3x3/2 → fc4096 → dropout →
fc4096 → dropout → softmax1000

Input: 227x227x3.  Real ImageNet is not distributable with the repo; the
loader serves deterministic synthetic ImageNet-shaped data (the bench
measures throughput; accuracy parity runs require user-supplied data, as
with the reference).
"""

import numpy

from ...config import root
from ...loader.fullbatch import FullBatchLoader
from ...loader.base import TEST, VALID, TRAIN
from ..standard_workflow import StandardWorkflow

_LR = {"learning_rate": 0.01, "gradient_moment": 0.9,
       "weights_decay": 0.0005}

# a layer's "name" is its unit's name, and so its scope in a device trace
root.alexnet.update({
    "loader": {"minibatch_size": 128, "normalization_type": "none"},
    "layers": [
        {"name": "conv1",
         "type": "conv_str", "->": {"n_kernels": 96, "kx": 11, "ky": 11,
                                    "sliding": (4, 4),
                                    "weights_stddev": 0.01}, "<-": _LR},
        {"name": "lrn1",
         "type": "norm", "->": {"alpha": 1e-4, "beta": 0.75, "n": 5,
                                "k": 2.0}},
        {"name": "pool1",
         "type": "max_pooling", "->": {"kx": 3, "ky": 3,
                                       "sliding": (2, 2)}},
        {"name": "conv2",
         "type": "conv_str", "->": {"n_kernels": 256, "kx": 5, "ky": 5,
                                    "padding": 2,
                                    "weights_stddev": 0.01}, "<-": _LR},
        {"name": "lrn2",
         "type": "norm", "->": {"alpha": 1e-4, "beta": 0.75, "n": 5,
                                "k": 2.0}},
        {"name": "pool2",
         "type": "max_pooling", "->": {"kx": 3, "ky": 3,
                                       "sliding": (2, 2)}},
        {"name": "conv3",
         "type": "conv_str", "->": {"n_kernels": 384, "kx": 3, "ky": 3,
                                    "padding": 1,
                                    "weights_stddev": 0.01}, "<-": _LR},
        {"name": "conv4",
         "type": "conv_str", "->": {"n_kernels": 384, "kx": 3, "ky": 3,
                                    "padding": 1,
                                    "weights_stddev": 0.01}, "<-": _LR},
        {"name": "conv5",
         "type": "conv_str", "->": {"n_kernels": 256, "kx": 3, "ky": 3,
                                    "padding": 1,
                                    "weights_stddev": 0.01}, "<-": _LR},
        {"name": "pool5",
         "type": "max_pooling", "->": {"kx": 3, "ky": 3,
                                       "sliding": (2, 2)}},
        {"name": "fc6",
         "type": "all2all_str", "->": {"output_sample_shape": 4096,
                                       "weights_stddev": 0.005},
         "<-": _LR},
        {"name": "dropout6",
         "type": "dropout", "->": {"dropout_ratio": 0.5}},
        {"name": "fc7",
         "type": "all2all_str", "->": {"output_sample_shape": 4096,
                                       "weights_stddev": 0.005},
         "<-": _LR},
        {"name": "dropout7",
         "type": "dropout", "->": {"dropout_ratio": 0.5}},
        {"name": "fc8",
         "type": "softmax", "->": {"output_sample_shape": 1000,
                                   "weights_stddev": 0.01}, "<-": _LR},
    ],
    "decision": {"max_epochs": 90, "fail_iterations": 1000},
})


class SyntheticImagenetLoader(FullBatchLoader):
    """Deterministic ImageNet-shaped data resident in HBM (bench)."""

    MAPPING = "synthetic_imagenet_loader"

    def __init__(self, workflow, **kwargs):
        self.n_train = kwargs.pop("n_train", 2048)
        self.n_valid = kwargs.pop("n_valid", 256)
        self.n_classes = kwargs.pop("n_classes", 1000)
        self.side = kwargs.pop("side", 227)
        super().__init__(workflow, **kwargs)

    def load_data(self):
        rng = numpy.random.RandomState(11)
        n = self.n_train + self.n_valid
        self.original_data.mem = rng.uniform(
            -0.5, 0.5, (n, self.side, self.side, 3)).astype(numpy.float32)
        self.original_labels = list(
            rng.randint(0, self.n_classes, n).astype(numpy.int32))
        self.class_lengths[TEST] = 0
        self.class_lengths[VALID] = self.n_valid
        self.class_lengths[TRAIN] = self.n_train


def create_workflow(fused=True, **overrides):
    from . import build_standard
    return build_standard(root.alexnet, "AlexNet", SyntheticImagenetLoader, "softmax",
                          fused=fused, **overrides)

def run(load, main):
    load(create_workflow)
    main()
