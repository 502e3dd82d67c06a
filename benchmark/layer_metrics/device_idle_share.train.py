"""Idle share of the traced window on the chip that idles most, in
per cent: one minus the union of the device-operation intervals over
the window (``tracing.Reduced.idle_share``)."""


def read(run):
    share = run.reduced.idle_share()
    return None if share is None else 100.0 * share
