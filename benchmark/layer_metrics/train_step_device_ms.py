"""Device-busy milliseconds per train step (``images_per_step`` images
over all chips, so ``minibatch_per_chip`` a chip): the busy time of the
traced window, averaged over the chips, over the train steps the window
held.  The validation pass and the gather from the resident data set are
inside the busy time, as they are inside the epoch."""


def read(run):
    steps = run.counters.get("train_steps")
    if not steps:
        return None
    return 1e3 * run.reduced.mean_busy_s() / steps
