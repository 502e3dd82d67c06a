"""Share of the traced window, in per cent, that the chip which shows
most of it spends inside ``all-reduce*`` operations while no other
operation runs on it: the part of the gradient all-reduce that the
backward pass does not hide."""


def read(run):
    if len(run.reduced.devices) < 2 or run.reduced.window_s <= 0:
        return None
    exposed = max(run.tracing.exposed_ns(events, run.tracing.is_allreduce)
                  for events in run.reduced.devices.values())
    return 100.0 * exposed / 1e9 / run.reduced.window_s
