"""Logger subsystem: class-level loggers + JSONL event tracing.

TPU-native re-creation of /root/reference/veles/logger.py: the reference
gave every class a colored console logger (:1-200) and an
``event(name, "begin"|"end"|"single", **info)`` stream duplicated into
MongoDB (:264-289).  Here the event stream is a **Chrome-trace JSONL
file** (one event object per line, ``ph`` B/E/X/i phases) — loadable in
Perfetto/chrome://tracing next to jax-profiler traces, greppable, and
zero-dependency — instead of a Mongo collection.

Enable the file via config::

    root.common.trace.enabled = True
    root.common.trace.file = "events.jsonl"      # default: events dir

The program times a region in ONE way, ``with events.timed(name):``
(:class:`Span`).  Such a span is always kept in a bounded in-memory
ring and in per-name totals, is mirrored to the JSONL file when that is
enabled, and is a ``jax.profiler.TraceAnnotation`` named ``veles.<name>``
whenever JAX is loaded, so that a profile taken by anyone (the
benchmark's ``--trace 1``, ``--profiler-port``, a builder) shows the
program's spans on the device operations' own clock.  This module
imports no JAX: it looks it up in ``sys.modules``.
"""

import atexit
import collections
import itertools
import json
import logging
import os
import sys
import threading
import time

from .config import root
from .observability import trace as _trace

_COLORS = {"DEBUG": "\033[37m", "INFO": "\033[32m", "WARNING": "\033[33m",
           "ERROR": "\033[31m", "CRITICAL": "\033[41m"}
_RESET = "\033[0m"


class ColorFormatter(logging.Formatter):
    """Reference-style colored console lines (logger.py:60-120)."""

    def format(self, record):
        text = super().format(record)
        if sys.stderr.isatty():
            color = _COLORS.get(record.levelname, "")
            return "%s%s%s" % (color, text, _RESET) if color else text
        return text


def setup_logging(level=logging.INFO, file=None):
    """Install the colored console handler (+ optional duplicate-to-file,
    reference Logger.redirect_all_logging_to_file)."""
    rt = logging.getLogger()
    rt.setLevel(level)
    rt.handlers = [h for h in rt.handlers
                   if not getattr(h, "_veles_tpu", False)]
    console = logging.StreamHandler()
    console.setFormatter(ColorFormatter(
        "%(asctime)s %(levelname)s %(name)s: %(message)s", "%H:%M:%S"))
    console._veles_tpu = True
    rt.addHandler(console)
    if file:
        fh = logging.FileHandler(file)
        fh.setFormatter(logging.Formatter(
            "%(asctime)s %(levelname)s %(name)s: %(message)s"))
        fh._veles_tpu = True
        rt.addHandler(fh)


class Logger:
    """Mixin giving every class its own named logger (reference
    veles/logger.py Logger mixin)."""

    @property
    def logger(self):
        return logging.getLogger(type(self).__name__)

    def debug(self, msg, *args):
        self.logger.debug(msg, *args)

    def info(self, msg, *args):
        self.logger.info(msg, *args)

    def warning(self, msg, *args):
        self.logger.warning(msg, *args)

    def error(self, msg, *args):
        self.logger.error(msg, *args)


#: what the program's spans are called on the profiler's timeline, in
#: the ring and in the totals: ``veles.`` + the name the site gives (the
#: JSONL file and the ``span_sink`` keep the site's own name)
SPAN_PREFIX = "veles."
#: spans the ring holds; the oldest is dropped for the newest
RING_CAPACITY = 8192


class Span:
    """One timed region of the program: what ``events.timed`` returns and
    what the ring holds.

    ``name`` (with :data:`SPAN_PREFIX`), ``info`` (what the site said
    about the region), ``seq`` (a process-wide sequence number),
    ``parent`` (the ``seq`` of the enclosing span of the same thread, or
    None), ``thread``, ``work`` (the unit of work the thread was on when
    the span closed, see :meth:`EventLog.set_work`: the epoch for the
    trainer, else the active ``trace_id``), ``start_ns``
    (``time.time_ns()``: the wall clock, which a profile's clock differs
    from by a constant) and ``duration_ns`` (from ``perf_counter_ns``, so
    a stepped wall clock cannot make it negative)."""

    __slots__ = ("name", "info", "seq", "parent", "thread", "work",
                 "start_ns", "duration_ns", "counts", "_log", "_t0",
                 "_annotation")

    def __init__(self, log, name, info):
        self._log = log
        self.name = SPAN_PREFIX + name
        self.info = info
        self.counts = None
        self.seq = self.parent = self.thread = self.work = None
        self.start_ns = self.duration_ns = None
        self._annotation = None

    def count(self, **counts):
        """Work done inside the region, said from inside it (a count is
        often known only there): kept in ``info`` and SUMMED in the
        per-name totals, so that seconds per step or per image are ratios
        of numbers taken at one place."""
        if self.counts is None:
            self.counts = {}
        self.counts.update(counts)

    @property
    def seconds(self):
        return self.duration_ns / 1e9

    def _begin(self):
        """Number the span and find its parent; returns the thread's
        stack of open spans."""
        stack = self._log._stack()
        self.parent = stack[-1].seq if stack else None
        self.seq = next(self._log._seq)
        return stack

    def __enter__(self):
        log = self._log
        self._begin().append(self)
        annotation = log._annotation_type()
        if annotation is not None:
            # one flag check when no profile is being taken
            self._annotation = annotation(self.name, **self.info)
            self._annotation.__enter__()
        self.start_ns = time.time_ns()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb):
        self.duration_ns = time.perf_counter_ns() - self._t0
        if self._annotation is not None:
            self._annotation.__exit__(exc_type, exc, tb)
            self._annotation = None
        stack = self._log._stack()
        while stack and stack.pop() is not self:
            pass
        self._log._close(self)
        return False    # an exception closes the span and goes on


class EventLog:
    """The program's spans: a bounded ring and per-name totals in memory
    (always), a Chrome-trace JSONL file (when enabled), the profiler's
    timeline (when a profile is being taken).

    ``timed`` is how a region is timed.  ``span(name, seconds)`` reports
    a region after the fact, for the sites that still time themselves.
    JSONL phases: ``begin``/``end`` spans, ``single`` instants, and
    ``span`` complete events with explicit duration — mapping to
    trace-viewer ``B``/``E``/``i``/``X``."""

    _PH = {"begin": "B", "end": "E", "single": "i", "span": "X"}

    def __init__(self, path=None, capacity=RING_CAPACITY):
        self._path = path
        self._ring = collections.deque(maxlen=capacity)
        self._totals = {}
        self._ring_lock = threading.Lock()
        self._seq = itertools.count(1)
        self._local = threading.local()
        self._annotation = None
        self._file = None
        self._lock = threading.Lock()
        self.path = None
        #: optional in-process mirror (the flight recorder's span
        #: bridge): called as ``sink(name, kind, duration, info)``
        #: BEFORE the enabled gate, so per-request timelines work even
        #: when file tracing is off.  Exceptions are swallowed —
        #: observability never takes down the caller.
        self.span_sink = None
        # perf_counter, not time.time(): a wall-clock jump (NTP step,
        # suspend/resume) must never produce out-of-order or
        # negative-duration trace events
        self._t0 = time.perf_counter()

    @property
    def enabled(self):
        # VELES_TRACE_DIR enables tracing in ANY veles_tpu process —
        # the zero-plumbing switch that makes spawned workers trace
        # (jobserver.WorkerPool children inherit the environment)
        return bool(root.common.trace.get("enabled", False) or
                    os.environ.get("VELES_TRACE_DIR"))

    def _ensure_open(self):
        if self._file is not None:
            return
        trace_dir = os.environ.get("VELES_TRACE_DIR")
        path = (self._path or root.common.trace.get("file") or
                (os.path.join(trace_dir, "events-%d.jsonl" % os.getpid())
                 if trace_dir else None) or
                os.path.join(root.common.dirs.get("events", "."),
                             "events-%d.jsonl" % os.getpid()))
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        self._file = open(path, "a", buffering=1)  # line buffered
        self.path = path
        # wall-clock anchor: ts values are per-process perf_counter
        # deltas; this record lets tools/merge_traces.py align several
        # processes' files onto one absolute timeline
        self._file.write(json.dumps({
            "name": "trace_start", "ph": "i",
            "ts": round((time.perf_counter() - self._t0) * 1e6, 1),
            "pid": os.getpid(), "tid": threading.get_ident(),
            "args": {"unix_time_s": time.time()}}) + "\n")
        atexit.register(self.close)

    # -- timed regions -------------------------------------------------------
    def timed(self, name, **info):
        """Context manager around a region of the program::

            with events.timed("step.run", cls="train", epoch=3) as span:
                ...
                span.count(steps=32, images=8192)

        ``info`` values are strings or numbers (they become the
        profiler's event arguments).  An exception inside the region
        closes the span and propagates."""
        return Span(self, name, info)

    def span(self, name, seconds, start_ns=None, counts=None, **info):
        """Complete span lasting ``seconds``, from ``start_ns`` on the
        wall clock (``time.time_ns()``) or else ending now: for a site
        that timed itself.  ``counts`` are summed in the totals as
        :meth:`Span.count` sums them.  It is in the ring, the totals, the
        file and the sink, but not on a profiler's timeline (that cannot
        be told afterwards)."""
        done = Span(self, name, info)
        done._begin()
        if counts:
            done.count(**counts)
        done.duration_ns = int(seconds * 1e9)
        done.start_ns = time.time_ns() - done.duration_ns \
            if start_ns is None else start_ns
        self._close(done)

    def instant(self, name, **info):
        """Something that happened now: a span of no length, in the ring
        and on the profiler's timeline like any other, a ``single`` event
        in the file."""
        now = Span(self, name, info)
        now._begin()
        annotation = self._annotation_type()
        if annotation is not None:
            with annotation(now.name, **info):
                pass
        now.start_ns, now.duration_ns = time.time_ns(), 0
        self._close(now)

    def set_work(self, work):
        """Name the unit of work this thread is on from now on (the
        trainer: the epoch number).  Every span closed on the thread
        carries it, the enclosing ones too, until the next call; a thread
        that never calls this carries the active ``trace_id``."""
        self._local.work = work

    def spans(self):
        """The ring, oldest first (a copy)."""
        with self._ring_lock:
            return list(self._ring)

    def totals(self):
        """``{name: {"count", "seconds", "longest", <count>: sum, ...}}``
        since the process started (or ``reset``): the ring forgets, this
        does not."""
        with self._ring_lock:
            return {name: dict(total)
                    for name, total in self._totals.items()}

    def _stack(self):
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def _annotation_type(self):
        """``jax.profiler.TraceAnnotation`` once somebody has imported
        JAX, else None (this module never imports it)."""
        if self._annotation is None:
            jax = sys.modules.get("jax")
            self._annotation = getattr(
                getattr(jax, "profiler", None), "TraceAnnotation", None)
        return self._annotation

    def _close(self, span):
        """A finished span goes to the ring, the totals, the sink and the
        file."""
        span.thread = threading.get_ident()
        work = span.work = getattr(self._local, "work", None)
        if work is None:
            ctx = _trace.current()
            span.work = ctx.trace_id if ctx is not None else None
        if span.counts:
            span.info.update(span.counts)
        seconds = span.duration_ns / 1e9
        with self._ring_lock:
            self._ring.append(span)
            total = self._totals.get(span.name)
            if total is None:
                total = self._totals[span.name] = {
                    "count": 0, "seconds": 0.0, "longest": 0.0}
            total["count"] += 1
            total["seconds"] += seconds
            total["longest"] = max(total["longest"], seconds)
            for key, value in (span.counts or {}).items():
                total[key] = total.get(key, 0) + value
        info, enabled = span.info, self.enabled
        if enabled:
            info = dict(info, seq=span.seq)
            if span.parent is not None:
                info["parent_seq"] = span.parent
            if work is not None:    # a trace_id is written by event()
                info.setdefault("work", work)
        name = span.name[len(SPAN_PREFIX):]
        if span.duration_ns:
            self._emit(name, "span", seconds, info, enabled)
        else:
            self._emit(name, "single", None, info, enabled)

    # -- the file and the sink -----------------------------------------------
    def event(self, name, kind="single", duration=None, **info):
        """Record one event in the file; no-op unless tracing is enabled
        (the ``span_sink`` mirror fires regardless — it is memory-only)."""
        self._emit(name, kind, duration, info, self.enabled)

    def _emit(self, name, kind, duration, info, enabled):
        sink = self.span_sink
        if sink is not None:
            try:
                sink(name, kind, duration, info)
            except Exception:  # noqa: BLE001 — diagnostics never raise
                pass
        if not enabled:
            return
        ctx = _trace.current()
        with self._lock:
            self._ensure_open()
            ts = time.perf_counter() - self._t0
            if duration is not None:
                ts -= duration  # trace-viewer X events anchor at start
            record = {"name": name, "ph": self._PH.get(kind, "i"),
                      "ts": round(ts * 1e6, 1),
                      "pid": os.getpid(), "tid": threading.get_ident()}
            if duration is not None:
                record["dur"] = round(duration * 1e6, 1)
            if ctx is not None:
                # causal links ride in args (trace viewers show them;
                # explicit trace_id/span kwargs win via setdefault)
                info = dict(info) if info else {}
                info.setdefault("trace_id", ctx.trace_id)
                info.setdefault("span", ctx.span_id)
                if ctx.parent_id:
                    info.setdefault("parent_span", ctx.parent_id)
            if info:
                record["args"] = info
            self._file.write(json.dumps(record) + "\n")

    def close(self):
        with self._lock:
            if self._file is not None:
                self._file.close()
                self._file = None

    def reset(self):
        """Close the output and forget every path decision so the next
        event re-resolves its destination from config/env — THE way for
        tests (and forked workers) to return the process-global log to
        its pristine state instead of poking ``_path``/``_file``."""
        self.close()
        with self._lock:
            self._path = None
            self.path = None
            self._t0 = time.perf_counter()
        with self._ring_lock:
            self._ring.clear()
            self._totals.clear()


#: process-global event log (reference: per-node Mongo duplication)
events = EventLog()
