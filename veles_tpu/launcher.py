"""Launcher: the runtime owner that takes a built workflow end-to-end.

TPU-native re-design of /root/reference/veles/launcher.py:100-906.  The
reference Launcher's job was mode selection (master/slave/standalone), the
Twisted reactor, SSH node spawning, and service side-cars.  On TPU the
tensor-level distribution lives *inside* the jitted step (mesh shardings,
parallel/dp.py), so the Launcher keeps the surviving responsibilities:

- device construction and workflow ``initialize``/``run`` lifecycle
  (reference launcher.py:431-512, :550-564);
- run modes: ``standalone`` (this process computes) and the dry-run
  levels consumed by the CLI (reference __main__.py "--dry-run");
- results gathering + ``--result-file`` JSON (reference workflow.py:827);
- per-run stats printing and wall-clock accounting (launcher.py:779-786);
- graceful stop + finished callbacks;
- service side-cars (web status reporter, event log) hook in here once
  built — the attachment points are ``on_initialized``/``on_finished``.

Mesh parallelism is requested by the *workflow* (``mesh=`` kwarg), not the
launcher; meta-level multi-process scheduling (ensembles, GA) re-invokes
the CLI per trial, as the reference did via subprocess (SURVEY.md §2.11).
"""

import sys
import time

from .config import root
from .logger import events
from .observability import trace as _trace


def memory_report(device=None):
    """Peak host RSS + per-device HBM peak for the devices the RUN
    actually used, as printable lines (the reference printed max RSS
    and device memory at exit, /root/reference/veles/__main__.py:
    787-799).  Only inspects ``device`` (the Launcher's) — never calls
    global ``jax.devices()``, which could first-time-initialize a
    backend the run never used (and take a chip another process needs)
    from an exit diagnostic."""
    lines = []
    try:
        import resource
        peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        if sys.platform == "darwin":
            peak /= 1024.0  # BSD reports bytes, Linux kilobytes
        lines.append("Peak host RSS: %.1f MiB" % (peak / 1024.0))
    except Exception:  # noqa: BLE001 — diagnostics must never raise
        pass
    for dev in getattr(device, "jax_devices", None) or []:
        try:  # per device: one platform's failure must not hide the rest
            stats = dev.memory_stats() or {}
            peak = stats.get("peak_bytes_in_use")
        except Exception:  # noqa: BLE001
            continue
        if peak:
            lines.append(
                "Device %s peak memory: %.1f MiB" %
                (dev, peak / (1024.0 * 1024.0)))
    return lines


class Launcher:
    """Owns device + lifecycle for one workflow run."""

    def __init__(self, backend=None, result_file=None, stealth=False,
                 **kwargs):
        self.backend = backend or root.common.engine.get("backend", "auto")
        self.result_file = result_file
        self.stealth = stealth          # no external reporting side-cars
        self.workflow = None
        self.device = None
        self.profiler = None
        # a parent process (jobserver worker, ElasticRunner, GA trial
        # farm) may have handed us its trace context — join it so this
        # run's events share the distributed trace_id
        _trace.adopt_env()
        self.start_time = None
        self.finish_time = None
        self.on_initialized = []        # callbacks(workflow)
        self.on_finished = []           # callbacks(workflow)
        self.status_server = None
        status_port = kwargs.pop("status_port", None)
        if status_port is None:
            status_port = root.common.web_status.get("port", None)
        if status_port is not None and not stealth:
            # in-process HTTP status side-car (reference launcher.py:
            # 852-885 posted heartbeats to an external Tornado server);
            # serve() reuses a live server on the same port
            from .web_status import serve
            self.status_server = serve(int(status_port))
        #: ``--profiler-port``: where ``initialize`` starts JAX's profiler
        #: server, the operator's door to a trace of the running trainer
        self.profiler_port = kwargs.pop("profiler_port", None)
        self._profiler_server = None
        self._extra = kwargs

    # -- lifecycle -----------------------------------------------------------
    def add_workflow(self, workflow):
        self.workflow = workflow
        return workflow

    def initialize(self, **kwargs):
        from .backends import Device
        if self.workflow is None:
            raise ValueError("no workflow attached (call add_workflow)")
        if self.device is None:
            self.device = Device(backend=self.backend)
        if self.profiler_port is not None and self._profiler_server is None:
            import jax
            self._profiler_server = jax.profiler.start_server(
                int(self.profiler_port))
        self.workflow.initialize(device=self.device, **kwargs)
        if root.common.observability.get("profile", False) and \
                not self.stealth:
            # opt-in step profiler side-car (fencing is honest but not
            # free — see observability/profiler.py): CLI flag or
            # root.common.observability.profile = True
            try:
                self.profiler = self.workflow.attach_profiler()
            except ValueError:
                self.profiler = None    # no training step (e.g. eval wf)
        for cb in self.on_initialized:
            cb(self.workflow)
        return self

    def run(self):
        self.start_time = time.time()
        # one span context per run: every event the run emits (unit
        # spans, train.step, serving) then shares a trace_id — fresh
        # unless a parent process's context was adopted at construction
        with _trace.span_context(), events.timed(
                "main.run", workflow=self.workflow.name):
            try:
                self.workflow.run()
            finally:
                self.finish_time = time.time()
        for cb in self.on_finished:
            cb(self.workflow)
        if self.result_file:
            self.write_results(self.result_file)
        return self.workflow

    def stop(self):
        if self.workflow is not None:
            self.workflow.stop()
        if self.status_server is not None:
            self.status_server.stop()
            self.status_server = None
        if self._profiler_server is not None:
            import jax
            jax.profiler.stop_server()
            self._profiler_server = None

    # -- results -------------------------------------------------------------
    def gather_results(self):
        results = self.workflow.gather_results()
        results.setdefault("name", self.workflow.name)
        if self.start_time is not None:
            results["seconds"] = round(
                (self.finish_time or time.time()) - self.start_time, 3)
        results["backend"] = getattr(self.device, "backend", self.backend)
        if self.profiler is not None:
            results["profile"] = self.profiler.summary()
        return results

    def write_results(self, file):
        return self.workflow.write_results(file,
                                           results=self.gather_results())

    def print_stats(self, file=None):
        self.workflow.print_stats(file=file)
        if self.start_time is not None:
            print("Total run time: %.3f s" %
                  ((self.finish_time or time.time()) - self.start_time),
                  file=file or sys.stdout)
        for line in memory_report(self.device):
            print(line, file=file or sys.stdout)
