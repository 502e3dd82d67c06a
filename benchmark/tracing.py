"""From the JAX profiler's trace to the benchmark's device numbers.

Two halves.  :func:`start` / :func:`stop` / :func:`load` drive the
profiler and turn its ``.xplane.pb`` into a plain dictionary (the
*recorded trace*: what the test fixture under ``tests/benchmark`` is),

    {"devices": {"/device:TPU:0": [[name, start_ns, duration_ns], ...]},
     "host":    [[name, start_ns, duration_ns], ...]}

where a device's list is its ``XLA Ops`` line and ``host`` holds the
``bench.*`` spans the drivers wrote with ``jax.profiler.TraceAnnotation``.
The profiler names a device event by the whole text of its HLO
instruction (kilobytes for a ``while``); :func:`short_name` keeps what
the reductions read, ``<instruction> = <result shape> <opcode>[ <custom
call target>]``, e.g. ``copy.166 = f32[10241,64,8,32] copy`` or ``fn.4 =
f32[256,8,32] custom-call tpu_custom_call`` (a Pallas kernel).
The other half reduces a recorded trace to numbers, and is pure Python
over that dictionary, so the arithmetic is checked on the CPU:

- *busy* is the union of the intervals in which an operation ran on a
  device; the idle share is one minus busy over the window;
- an operation's *self time* is its duration less its direct children's
  (``while`` and ``conditional`` enclose the operations of their bodies on
  the same line), so shares by name add up to the busy time;
- an idle gap is labelled by the ``bench.*`` host span that covers most
  of it.
"""

import glob
import os
import re
import shutil
import tempfile

#: device line that holds one event per executed HLO operation
OPS_LINE = "XLA Ops"
#: host spans the drivers write; everything else on the host is dropped
SPAN_PREFIX = "bench."


_OPCODE = re.compile(r"[ )]([a-z][a-z0-9\-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]*)"')


def short_name(text):
    """``<instruction> = <result shape> <opcode>[ <target>]`` of an HLO
    instruction's text; text that is no instruction is kept (cut)."""
    left, eq, right = text.partition(" = ")
    if not eq:
        return text[:120]
    shape = "(tuple)" if right.startswith("(") else re.split(r"[{ ]", right)[0]
    opcode = _OPCODE.search(" " + right)
    parts = [left.lstrip("%"), "=", shape, opcode.group(1) if opcode else "?"]
    if parts[-1] == "custom-call":
        target = _TARGET.search(right)
        parts.append(target.group(1) if target else "?")
    return " ".join(parts)


def opcode(name):
    """``copy`` of ``copy.166 = f32[10241,64,8,32] copy``."""
    parts = name.split(" ")
    return parts[3] if len(parts) > 3 and parts[1] == "=" else name


def op_family(name):
    """What an operation is counted under in a breakdown: its
    instruction's name without the serial number, and its result shape:
    ``copy f32[10241,64,8,32]``, ``fn f32[256,8,32]``."""
    parts = name.split(" ")
    head = parts[0].split(".")[0]
    return "%s %s" % (head, parts[2]) if len(parts) > 3 else head


def is_allreduce(name):
    return opcode(name).startswith("all-reduce")


def start(directory):
    """Start the profiler without the Python tracer (hundreds of server
    threads would drown in it) and with host TraceMe spans on."""
    import jax
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    os.makedirs(directory, exist_ok=True)
    jax.profiler.start_trace(directory, profiler_options=options)


def stop():
    import jax
    jax.profiler.stop_trace()


def span(name):
    """A host span on the profiler's clock (no-op cost when no trace is
    running: a TraceMe checks one flag)."""
    import jax
    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name)


def annotate(obj, method, name):
    """Put a host span around one bound method of the program (the
    benchmark's own span around its call into a layer; the program has
    none yet).  Methods looked up on the instance at each call, as the
    workflow engine and the decode worker do, see the wrapper."""
    inner = getattr(obj, method)

    def wrapped(*args, **kwargs):
        with span(name):
            return inner(*args, **kwargs)
    setattr(obj, method, wrapped)


class TracedWindow:
    """The profiler around one window: ``open()`` at its first instant,
    ``close()`` at its last, ``reduce(devices)`` afterwards.  The window
    is marked by a ``bench.window`` host span, so its edges are on the
    trace's own clock."""

    def __init__(self):
        self.directory = tempfile.mkdtemp(prefix="bench-trace-")
        self._span = None

    def open(self):
        start(self.directory)
        self._span = span("window")
        self._span.__enter__()

    def close(self):
        self._span.__exit__(None, None, None)
        stop()

    def reduce(self, devices):
        """The traced window as a :class:`Reduced`; the trace is deleted."""
        try:
            recorded = load(self.directory)
        finally:
            shutil.rmtree(self.directory, ignore_errors=True)
        mark = next(s for s in recorded["host"]
                    if s[0] == SPAN_PREFIX + "window")
        return Reduced(recorded, mark[1], mark[1] + mark[2], devices=devices)


def load(directory):
    """The newest ``.xplane.pb`` under ``directory`` as a recorded trace."""
    import jax
    paths = sorted(glob.glob(os.path.join(
        directory, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError("no .xplane.pb under %s" % directory)
    data = jax.profiler.ProfileData.from_file(paths[-1])
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = [
                        [short_name(e.name), int(e.start_ns),
                         int(e.duration_ns)] for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend([e.name, int(e.start_ns), int(e.duration_ns)]
                            for e in line.events
                            if e.name.startswith(SPAN_PREFIX))
    return {"devices": devices, "host": host}


# -- reductions over a recorded trace -----------------------------------------

def clip(events, t0, t1):
    """Events cut to the window ``[t0, t1)``; what lies outside is gone."""
    out = []
    for name, start, duration in events:
        lo, hi = max(start, t0), min(start + duration, t1)
        if hi > lo:
            out.append([name, lo, hi - lo])
    return out


def union(intervals):
    """Sorted, disjoint ``[start, end]`` pairs covering ``intervals``."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def busy_ns(events):
    return sum(end - start for start, end in union(
        (s, s + d) for _, s, d in events))


def idle_gaps(events, t0, t1):
    """``[start, end]`` of every stretch of ``[t0, t1)`` in which no
    operation ran."""
    gaps, cursor = [], t0
    for start, end in union((s, s + d) for _, s, d in events):
        if start > cursor:
            gaps.append([cursor, start])
        cursor = max(cursor, end)
    if t1 > cursor:
        gaps.append([cursor, t1])
    return gaps


def self_times(events):
    """``[name, start, self_ns]`` per event: its duration less that of
    its direct children (events of the same line that it encloses)."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -events[i][2]))
    child = [0] * len(events)
    stack = []
    for i in order:
        _, start, duration = events[i]
        # a parent encloses its child whole; what only overlaps is a sibling
        while stack and start + duration > \
                events[stack[-1]][1] + events[stack[-1]][2]:
            stack.pop()
        if stack:
            child[stack[-1]] += duration
        stack.append(i)
    return [[events[i][0], events[i][1], max(events[i][2] - child[i], 0)]
            for i in range(len(events))]


def time_by_name(events, normalise=None):
    """{name: self nanoseconds}, largest first; ``normalise`` maps an
    event name to the name it is counted under."""
    totals = {}
    for name, _, ns in self_times(events):
        key = normalise(name) if normalise else name
        totals[key] = totals.get(key, 0) + ns
    return dict(sorted(totals.items(), key=lambda kv: -kv[1]))


def leaves(events):
    """Events that enclose no other event of their line."""
    return [e for e, s in zip(events, self_times(events)) if s[2] == e[2]]


def exposed_ns(events, match):
    """Nanoseconds inside leaf events that ``match`` accepts during which
    no other leaf event ran on that device."""
    mine, others = [], []
    for name, start, duration in leaves(events):
        (mine if match(name) else others).append((start, start + duration))
    covered = union(others)
    total = 0
    for start, end in union(mine):
        total += end - start
        for lo, hi in covered:
            if hi <= start:
                continue
            if lo >= end:
                break
            total -= min(hi, end) - max(lo, start)
    return total


def label_gap(gap, spans):
    """Name of the host span covering most of ``gap``; among equals the
    shortest span, which is the innermost."""
    best, best_key = "no bench span", (0, 0)
    for name, start, duration in spans:
        if name == SPAN_PREFIX + "window":
            continue
        overlap = min(gap[1], start + duration) - max(gap[0], start)
        if overlap > 0 and (overlap, -duration) > best_key:
            best, best_key = name, (overlap, -duration)
    return best


class Reduced:
    """A recorded trace cut to one window, with the numbers every reader
    asks for worked out once."""

    def __init__(self, recorded, t0, t1, devices=None):
        self.t0, self.t1 = t0, t1
        names = sorted(recorded["devices"])
        if devices is not None:
            names = names[:devices]
        self.devices = {n: clip(recorded["devices"][n], t0, t1)
                        for n in names}
        self.spans = clip(recorded["host"], t0, t1)

    @property
    def window_s(self):
        return (self.t1 - self.t0) / 1e9

    def busy_s(self):
        """Per device, seconds in which an operation ran."""
        return {n: busy_ns(e) / 1e9 for n, e in self.devices.items()}

    def mean_busy_s(self):
        busy = self.busy_s()
        return sum(busy.values()) / len(busy) if busy else 0.0

    def idle_share(self):
        """Idle share of the window on the device that idles most."""
        busy = self.busy_s()
        if not busy or self.window_s <= 0:
            return None
        return 1.0 - min(busy.values()) / self.window_s

    def breakdown(self, ops=10, gaps=5):
        """The contract's ``breakdown``: the operations that took most
        self time (summed over devices, by family) and the longest idle
        gaps of the device that idles most, by what the host was doing."""
        totals = {}
        for events in self.devices.values():
            for name, ns in time_by_name(events, op_family).items():
                totals[name] = totals.get(name, 0) + ns
        device_ops = [[n, ns / 1e9] for n, ns in sorted(
            totals.items(), key=lambda kv: -kv[1])[:ops]]
        idle = []
        if self.devices:
            busy = self.busy_s()
            laziest = min(busy, key=busy.get)
            found = sorted(idle_gaps(self.devices[laziest], self.t0, self.t1),
                           key=lambda g: g[0] - g[1])[:gaps]
            idle = [[label_gap(g, self.spans), (g[1] - g[0]) / 1e9]
                    for g in found]
        return {"device_ops": device_ops, "idle_gaps": idle}
