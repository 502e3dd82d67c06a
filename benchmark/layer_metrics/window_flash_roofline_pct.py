"""Share of the roofline, in per cent, that the three flash kernels reach
together in the layers that attend inside a sliding window: the least
time the chip could take for the operations and bytes they REQUIRE (the
configuration's ``work.py`` ``window_flash_work``, from shapes: the band
of ``sliding_window`` keys a query, ``T W - W (W - 1) / 2`` pairs a head
a sequence, forward and backward once a trained sequence, forward once an
evaluated one, K and V read at their own head count; a checkpointed
block's recomputed forward is not required work) over the device time of
their events in the traced window.  The events are found by instruction
name: a call of the plain flash kernels made with a window is named
``gqa_window_flash_fwd`` / ``_dq`` / ``_dkv`` (``jvp_gqa_window_flash_
fwd_.1``), which holds no other reader's name and no other reader's
holds.  A program without these calls, or a configuration without
``window_flash_work``, reads nothing."""

KERNELS = ("gqa_window_flash_fwd", "gqa_window_flash_dq",
           "gqa_window_flash_dkv")


def read(run):
    work = run.config_module("work")
    sequences = run.counters.get("images")
    seconds = sum(duration for events in run.reduced.devices.values()
                  for name, _, duration in events
                  if any(k in name.split(" ")[0] for k in KERNELS)) / 1e9
    if not hasattr(work, "window_flash_work") or not sequences \
            or not seconds:
        return None
    ops, moved = work.window_flash_work(
        run.config, sequences, run.counters.get("valid_images", 0))
    least = max(ops / run.peaks["bf16_flops_per_s"],
                moved / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
