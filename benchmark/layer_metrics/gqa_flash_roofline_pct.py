"""Share of the roofline, in per cent, that the three grouped-query flash
kernels reach together: the least time the chip could take for the
operations and bytes they REQUIRE (the configuration's ``work.py``
``gqa_flash_work``, from shapes: the causal lower triangle, forward and
backward once a trained sequence, forward once an evaluated one, K and V
read at their own head count; a checkpointed block's recomputed forward
is not required work) over the device time of their events in the traced
window.  The events are found by instruction name: a Pallas call's
``name`` is its HLO instruction's (``jvp_gqa_flash_fwd_.1``,
``transpose_jvp_gqa_flash_dkv__.1``).  A program without these kernels,
or a configuration without ``gqa_flash_work``, reads nothing."""

KERNELS = ("gqa_flash_fwd", "gqa_flash_dq", "gqa_flash_dkv")


def read(run):
    work = run.config_module("work")
    sequences = run.counters.get("images")
    seconds = sum(duration for events in run.reduced.devices.values()
                  for name, _, duration in events
                  if any(k in name.split(" ")[0] for k in KERNELS)) / 1e9
    if not hasattr(work, "gqa_flash_work") or not sequences or not seconds:
        return None
    ops, moved = work.gqa_flash_work(
        run.config, sequences, run.counters.get("valid_images", 0))
    least = max(ops / run.peaks["bf16_flops_per_s"],
                moved / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
