"""Model FLOP/s utilisation of the train step, in per cent: the
operations the forward and backward passes require (the configuration's
``work.py``, from layer shapes, no recomputation, no padding) for the
images trained in the traced window, over the device-busy time summed
over the chips, over one chip's bf16 peak."""


def read(run):
    images = run.counters.get("images")
    busy = sum(run.reduced.busy_s().values())
    if not images or not busy:
        return None
    work = run.config_module("work")
    flops = work.train_flops_per_image(run.config) * images
    return 100.0 * flops / busy / run.peaks["bf16_flops_per_s"]
