"""The program's own spans, laid on a traced window's clock.

Since PR 25 the program times its regions itself (``veles_tpu.logger``:
``events.timed``): a bounded ring of spans on the wall clock, and totals
by name.  This helper reads both, for the per-layer metrics whose source
is ``program_span``:

- *the ring's clock.*  ``tracing.load`` keeps only the ``bench.*`` host
  spans of a profile, so the ring is anchored on a pair of spans around
  one call: the benchmark's ``bench.train.epoch_dispatch`` (trace clock)
  is opened immediately before the ``veles.step.run`` (wall clock) of the
  method it wraps.  The offset between the clocks is the median
  difference of the paired starts; where those differences spread by
  more than :data:`MAX_SPREAD_NS` the clocks cannot be laid on each other
  and every metric is ``None``, not a number on a wrong clock.
- *idle time by program span.*  Every idle gap of the chip that idles
  most (``tracing.idle_gaps``) is cut where a program span starts or
  ends, so that each piece has one innermost span around it
  (``tracing.label_gap`` finds it), and each piece is counted under one
  of three layers: ``step_prepare`` (inside ``veles.step.run``
  until its dispatch returns), ``step_finish`` (inside it after that) and
  ``engine`` (everything else: the other units, the engine's scheduling
  between them, the benchmark's own wrappers).  The three add up to the
  window's idle time on that chip, which is what
  ``device_idle_share.train`` reports as a share.

A program without the ring (any commit before PR 25) gives ``None``
everywhere and raises nothing.
"""

STEP = "veles.step.run"
DISPATCH = "veles.step.dispatch"
#: children of a ``veles.step.run`` and the layer their gaps count under
PREPARE = ("veles.step.shuffle", "veles.step.index_matrix", DISPATCH)
FINISH = ("veles.step.flush_metrics", "veles.step.sync_weights")
INITIALIZE = "veles.workflow.initialize"
#: the benchmark's span that is opened just before a ``veles.step.run``
ANCHOR = "bench.train.epoch_dispatch"
#: the widest spread of the paired starts' differences that still counts
#: as one clock (the pairs differ by microseconds: PERF.md, PR 25)
MAX_SPREAD_NS = 100_000
LAYERS = ("engine", "step_prepare", "step_finish")


def event_log():
    """The program's event log if it keeps a ring, else None."""
    try:
        from veles_tpu.logger import events
    except ImportError:
        return None
    return events if hasattr(events, "spans") else None


def ring():
    """The ring as plain records ``{name, seq, parent, start_ns,
    duration_ns}`` (wall clock), or None where the program has none."""
    log = event_log()
    if log is None:
        return None
    return [{"name": s.name, "seq": s.seq, "parent": s.parent,
             "start_ns": s.start_ns, "duration_ns": s.duration_ns}
            for s in log.spans()]


def anchor(bench_spans, records):
    """``(offset_ns, spread_ns)`` such that a ring record started at
    ``start_ns - offset_ns`` on the trace's clock, or None where nothing
    pairs.  ``bench_spans`` are the window's ``[name, start, duration]``
    host spans.  The ring also holds the runs before the window, so the
    window's :data:`ANCHOR` spans are paired with that run of consecutive
    :data:`STEP` records whose differences spread least (the latest of
    equals).  The median is taken in whole nanoseconds: a wall clock in
    nanoseconds is beyond what a float holds."""
    bench = sorted(s[1] for s in bench_spans if s[0] == ANCHOR)
    steps = sorted(r["start_ns"] for r in records if r["name"] == STEP)
    if not bench or len(steps) < len(bench):
        return None
    best = None
    for k in range(len(steps) - len(bench) + 1):
        differences = [steps[k + i] - b for i, b in enumerate(bench)]
        spread = max(differences) - min(differences)
        if best is None or spread <= best[1]:
            best = (sorted(differences)[len(differences) // 2], spread)
    return best


def on_trace_clock(run, records=None):
    """``(spans, spread_ns)``: the ring's records that touch the window,
    with ``start_ns`` on the trace's clock; None where there is no ring,
    no pair, or a spread over the limit."""
    records = ring() if records is None else records
    if not records or run.reduced is None:
        return None
    found = anchor(run.reduced.spans, records)
    if found is None or found[1] > MAX_SPREAD_NS:
        return None
    offset, spread = found
    t0, t1 = run.reduced.t0, run.reduced.t1
    spans = []
    for r in records:
        start = r["start_ns"] - offset
        if start < t1 and start + r["duration_ns"] > t0:
            spans.append(dict(r, start_ns=start))
    return spans, spread


def layer_of(span, middle, by_seq, dispatched):
    """The layer a gap with its middle at ``middle`` counts under, given
    the program span that covers it (None: no span does) and, by the
    number of each ``veles.step.run``, when its dispatch returned."""
    inner = None
    while span is not None and span["name"] != STEP:
        inner, span = span, by_seq.get(span["parent"])
    if span is None:
        return "engine"             # no veles.step.run around it
    if inner is not None:           # a child of the step, or below one
        if inner["name"] in PREPARE:
            return "step_prepare"
        if inner["name"] in FINISH:
            return "step_finish"
    # the step's own time: before or after its dispatch returned
    returned = dispatched.get(span["seq"])
    if returned is not None and middle >= returned:
        return "step_finish"
    return "step_prepare"


def idle_gaps_labelled(run, records=None):
    """``[(piece_ns, span name or None, layer)]`` for every piece of
    every idle gap of the window on the chip that idles most, or None
    (see :func:`on_trace_clock`).  A gap between two dispatches reaches
    over several units and phases: it is cut at every start and end of a
    program span, so that each piece lies in one innermost span.  Worked
    out once a run."""
    cached = getattr(run, "_idle_gaps_labelled", None)
    if cached is not None and records is None:
        return cached
    placed = on_trace_clock(run, records)
    if placed is None or not run.reduced.devices:
        return None
    spans, _ = placed
    tracing, reduced = run.tracing, run.reduced
    busy = reduced.busy_s()
    laziest = min(busy, key=busy.get)
    by_seq = {s["seq"]: s for s in spans}
    dispatched = {s["parent"]: s["start_ns"] + s["duration_ns"]
                  for s in spans if s["name"] == DISPATCH}
    # label_gap knows names: a span's is made unique by its number
    named = [["%s#%d" % (s["name"], s["seq"]), s["start_ns"],
              s["duration_ns"]] for s in spans]
    edges = sorted({t for s in spans for t in (
        s["start_ns"], s["start_ns"] + s["duration_ns"])})
    out = []
    for lo, hi in tracing.idle_gaps(reduced.devices[laziest],
                                    reduced.t0, reduced.t1):
        cuts = [lo] + [t for t in edges if lo < t < hi] + [hi]
        for piece in zip(cuts, cuts[1:]):
            label = tracing.label_gap(piece, named)
            span = by_seq.get(int(label.rsplit("#", 1)[1])) \
                if "#" in label else None
            out.append((piece[1] - piece[0], span["name"] if span else None,
                        layer_of(span, (piece[0] + piece[1]) / 2, by_seq,
                                 dispatched)))
    if records is None:
        run._idle_gaps_labelled = out
    return out


def idle_ms_per_epoch(run, records=None):
    """``{layer: milliseconds of idle gaps an epoch}`` over
    :data:`LAYERS`, or None."""
    epochs = run.counters.get("epochs")
    gaps = idle_gaps_labelled(run, records)
    if gaps is None or not epochs:
        return None
    totals = dict.fromkeys(LAYERS, 0)
    for ns, _, layer in gaps:
        totals[layer] += ns
    return {layer: ns / 1e6 / epochs for layer, ns in totals.items()}


def idle_ms_per_epoch_by_span(run, records=None):
    """``{span name: [milliseconds an epoch, pieces]}``, largest first
    (``None`` as a name: no program span covers the piece): the table
    ``PERF.md`` section 5 gives for each cell."""
    epochs = run.counters.get("epochs")
    gaps = idle_gaps_labelled(run, records)
    if gaps is None or not epochs:
        return None
    totals = {}
    for ns, name, _ in gaps:
        entry = totals.setdefault(name, [0.0, 0])
        entry[0] += ns / 1e6 / epochs
        entry[1] += 1
    return dict(sorted(totals.items(), key=lambda kv: -kv[1][0]))


def initialize_seconds():
    """Seconds inside ``veles.workflow.initialize`` from the per-name
    totals (no clock needed), or None."""
    log = event_log()
    if log is None:
        return None
    total = log.totals().get(INITIALIZE)
    return None if total is None else total["seconds"]
