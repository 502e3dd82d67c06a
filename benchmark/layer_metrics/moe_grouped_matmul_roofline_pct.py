"""Share of the roofline, in per cent, that the expert layers' grouped
matrix products reach: the least time the chip could take for the
operations and bytes the COUNTED rows require (``work.py``
``grouped_matmul_work`` from the ``moe_rows_*`` counters of the traced
window: rows the step's device accumulator counted, not the expected
load) over the device time of the megablox kernels' events (``gmm``,
``tgmm``: found by instruction name)."""

import re

KERNEL = re.compile(r"(^|_)t?gmm(_|\.|$)")


def read(run):
    work = run.config_module("work")
    rows = run.counters.get("moe_rows_train")
    seconds = sum(duration for events in run.reduced.devices.values()
                  for name, _, duration in events
                  if KERNEL.search(name.split(" ")[0])) / 1e9
    if not hasattr(work, "grouped_matmul_work") or not rows or not seconds:
        return None
    ops, moved = work.grouped_matmul_work(
        run.config, rows, run.counters["train_steps"],
        run.counters.get("moe_rows_valid", 0),
        run.counters.get("valid_steps", 0))
    least = max(ops / run.peaks["bf16_flops_per_s"],
                moved / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
