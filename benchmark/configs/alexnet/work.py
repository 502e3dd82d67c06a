"""Operations and bytes of the ``alexnet`` configuration, from the layer
shapes of ``config.json`` alone (never from XLA's ``cost_analysis``,
which counts padding and recomputation).

A multiply-add is two operations.  The forward pass costs ``2 x MACs``;
the backward pass costs that twice over (gradient with respect to the
input and to the weights) for every layer but the first, whose input
gradient nobody needs.  Pooling, LRN, ReLU, dropout, the loss and the
momentum update are not counted: the figure is the model FLOPs a
utilisation is quoted against, not everything the chip does.
"""


def layer_macs(config):
    """[(layer index, multiply-adds per image)] of the layers that
    multiply, walking the activation shape through ``config['layers']``."""
    side = config["input"]["side"]
    h = w = side
    c = config["input"]["channels"]
    out = []
    for i, layer in enumerate(config["layers"]):
        kind = layer["type"]
        if kind == "conv":
            pad, s = layer["padding"], layer["stride"]
            h = (h + 2 * pad - layer["ky"]) // s + 1
            w = (w + 2 * pad - layer["kx"]) // s + 1
            out.append((i, h * w * layer["kernels"]
                        * layer["ky"] * layer["kx"] * c))
            c = layer["kernels"]
        elif kind == "max_pool":
            h = (h - layer["ky"]) // layer["stride"] + 1
            w = (w - layer["kx"]) // layer["stride"] + 1
        elif kind in ("fc", "softmax"):
            out.append((i, h * w * c * layer["neurons"]))
            h, w, c = 1, 1, layer["neurons"]
    return out


def forward_flops_per_image(config):
    return 2 * sum(m for _, m in layer_macs(config))


def train_flops_per_image(config):
    """Forward plus backward, no recomputation."""
    macs = layer_macs(config)
    return 3 * 2 * sum(m for _, m in macs) - 2 * macs[0][1]


def parameter_count(config):
    side, c = config["input"]["side"], config["input"]["channels"]
    h = w = side
    n = 0
    for layer in config["layers"]:
        kind = layer["type"]
        if kind == "conv":
            pad, s = layer["padding"], layer["stride"]
            h = (h + 2 * pad - layer["ky"]) // s + 1
            w = (w + 2 * pad - layer["kx"]) // s + 1
            n += layer["ky"] * layer["kx"] * c * layer["kernels"] \
                + layer["kernels"]
            c = layer["kernels"]
        elif kind == "max_pool":
            h = (h - layer["ky"]) // layer["stride"] + 1
            w = (w - layer["kx"]) // layer["stride"] + 1
        elif kind in ("fc", "softmax"):
            n += h * w * c * layer["neurons"] + layer["neurons"]
            h, w, c = 1, 1, layer["neurons"]
    return n


def dataset_bytes(config):
    """Bytes of the resident data set on every chip that holds it."""
    d = config["data"]
    side, c = config["input"]["side"], config["input"]["channels"]
    return (d["n_train"] + d["n_valid"]) * side * side * c * 4


def allreduce_bytes_per_step(config):
    """Bytes of gradients a data-parallel step reduces (float32)."""
    return parameter_count(config) * 4
