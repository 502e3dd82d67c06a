"""StepProfiler: where does a training step actually spend its time?

The TPU-compilation literature (TVM; "Automatic Full Compilation ... to
Cloud TPUs", PAPERS.md) is unambiguous about where training-loop wins
hide: recompiles and host/device transfer stalls.  This profiler makes
both visible for any veles_tpu workflow by wrapping the two hot units —
the loader and the fused train step — and splitting every step into:

- **data-wait**: host-side minibatch preparation (the loader's run).
  With a :class:`~veles_tpu.loader.prefetch.MinibatchPrefetcher`
  attached (attach it BEFORE the profiler), the loader's run() merely
  pops the prefetch queue, so this phase measures time the step loop is
  *actually blocked* on input — the number the prefetcher exists to
  drive to zero;
- **host**: python + dispatch time of the step's ``run()`` (with XLA's
  async dispatch this is the enqueue cost, not the math);
- **device**: the remaining device-compute tail, measured by fencing on
  the step's outputs (``block_until_ready``) after dispatch returns.

Per step it also counts JAX recompiles (jit cache-size deltas across
every jitted function the step owns — an AOT-warm loop shows zero),
examples/sec over a sliding window, and per-device HBM peak watermarks.
Everything is emitted twice: into the process-global
:class:`~veles_tpu.observability.registry.MetricsRegistry` (scraped at
``/metrics``) and as ``train.step`` spans into the Chrome-trace
:class:`~veles_tpu.logger.EventLog`.

Fencing serializes the dispatch pipeline, which is precisely what makes
the breakdown honest — and is why the profiler is opt-in
(``Workflow.attach_profiler()``, ``root.common.observability.profile``).
"""

import collections
import time

from ..logger import events
from .registry import REGISTRY

#: examples/sec sliding window (steps)
RATE_WINDOW = 256
#: device-memory watermark poll period (steps) — memory_stats() is a
#: host call; every step would be pure overhead for a slow-moving number
MEM_POLL_STEPS = 16


def _find_step(workflow):
    step = getattr(workflow, "fused_step", None)
    if step is not None:
        return step
    controller = getattr(workflow, "graph_controller_", None)
    if controller is not None and controller.traced_unit_count:
        # whole-workflow compilation: the traced-region flush IS the
        # step — wrap it so recompiles and host/device phase slices
        # report exactly like the fused path
        return controller
    for unit in workflow:
        if getattr(unit, "view_group", None) == "TRAINER":
            return unit
    raise ValueError("no training step found in %r (pass step=...)"
                     % workflow)


def _find_loader(workflow):
    loader = getattr(workflow, "loader", None)
    if loader is not None and hasattr(loader, "run"):
        return loader
    return None


def _transient(method):
    """Wrap a bound method in a plain function marked ``transient_`` so
    ``Pickleable.__getstate__`` (and the snapshotter's deepcopy-based
    capture) drops the instrumentation instead of dragging the profiler
    — registry children, locks and all — into a snapshot."""
    def call():
        return method()
    call.transient_ = True
    return call


class StepProfiler:
    """Wraps ``loader.run``/``step.run`` of one workflow with timing,
    recompile and memory accounting.  ``detach()`` restores both."""

    def __init__(self, workflow=None, step=None, loader=None,
                 registry=None, fence=True, name=None):
        if step is None:
            step = _find_step(workflow)
        if loader is None and workflow is not None:
            loader = _find_loader(workflow)
        self.workflow = workflow
        self.step = step
        self.loader = loader
        self.fence = fence
        self.name = name or (workflow.name if workflow is not None
                             else type(step).__name__)
        reg = registry or REGISTRY
        lbl = {"workflow": self.name}
        self._c_steps = reg.counter(
            "veles_training_steps_total",
            "Training/eval steps executed", ("workflow",)).labels(**lbl)
        self._c_examples = reg.counter(
            "veles_training_examples_total",
            "Examples consumed by training steps",
            ("workflow",)).labels(**lbl)
        self._c_recompiles = reg.counter(
            "veles_training_recompiles_total",
            "JAX jit cache misses observed on the step's functions",
            ("workflow",)).labels(**lbl)
        self._h_phase = reg.histogram(
            "veles_training_step_phase_seconds",
            "Per-step time split: data_wait | host | device",
            ("workflow", "phase"))
        self._h_data = self._h_phase.labels(phase="data_wait", **lbl)
        self._h_host = self._h_phase.labels(phase="host", **lbl)
        self._h_device = self._h_phase.labels(phase="device", **lbl)
        self._h_snapshot = self._h_phase.labels(phase="snapshot", **lbl)
        self._g_rate = reg.gauge(
            "veles_training_examples_per_sec",
            "Sliding-window training throughput",
            ("workflow",)).labels(**lbl)
        self._g_mem = reg.gauge(
            "veles_device_peak_memory_bytes",
            "Per-device HBM peak watermark",
            ("workflow", "device"))
        # totals for summary() (per-instance; the registry children are
        # process-global and shared across same-named workflows)
        self.steps = 0
        self.examples = 0
        self.recompiles = 0
        self.data_wait_s = 0.0
        self.host_s = 0.0
        self.device_s = 0.0
        self.peak_memory = {}
        self._rate = collections.deque(maxlen=RATE_WINDOW)
        self._pending_data_wait = 0.0
        # examples come from the loader's samples_served delta when
        # available — correct on BOTH the per-minibatch path and the
        # epoch-scan path (where one run() consumes a whole class)
        self._last_served = int(getattr(loader, "samples_served", 0)
                                or 0)
        self._jits = self._discover_jits()
        self._jit_cache = self._jit_cache_size()
        self._orig_step_run = step.run
        self._orig_loader_run = loader.run if loader is not None else None
        # keep STABLE wrapper objects: detach()'s identity check must
        # compare against the exact object installed here.  Transient
        # plain-function closures, not bound methods — a snapshot taken
        # with the profiler attached must drop the wrappers, not pickle
        # the profiler (see _transient)
        self._step_wrapper = _transient(self._step_run)
        self._loader_wrapper = _transient(self._loader_run_wrapped)
        step.run = self._step_wrapper
        if loader is not None:
            loader.run = self._loader_wrapper
        # snapshot capture stall as a distinct slice: wrap the
        # snapshotter's run and attribute its measured export stall
        self.snapshotter = getattr(workflow, "snapshotter", None) \
            if workflow is not None else None
        self.snapshot_s = 0.0
        self._orig_snap_run = None
        self._snap_wrapper = None
        if self.snapshotter is not None:
            self._orig_snap_run = self.snapshotter.run
            self._snap_wrapper = _transient(self._snap_run)
            self.snapshotter.run = self._snap_wrapper

    # -- instrumentation -----------------------------------------------------
    def _discover_jits(self):
        """Every jitted callable the step owns (``_train_step_``,
        ``_eval_step_g_``, ...) — anything exposing ``_cache_size``.  A
        graph-compiler step publishes its own accounting via
        ``profiled_jits`` (one aggregate counting variant builds plus any
        inner-jit retraces)."""
        hook = getattr(self.step, "profiled_jits", None)
        if callable(hook):
            return list(hook())
        jits = []
        for value in vars(self.step).values():
            if callable(getattr(value, "_cache_size", None)):
                jits.append(value)
        return jits

    def _jit_cache_size(self):
        total = 0
        for fn in self._jits:
            try:
                total += int(fn._cache_size())
            except Exception:  # noqa: BLE001 — diagnostics never raise
                pass
        return total

    def _loader_run_wrapped(self):
        t0 = time.perf_counter()
        try:
            return self._orig_loader_run()
        finally:
            # attributed to the NEXT step: the loader prepares the
            # minibatch the step consumes
            self._pending_data_wait += time.perf_counter() - t0

    def _consumed_examples(self):
        ld = self.loader
        if ld is not None and hasattr(ld, "samples_served"):
            served = int(ld.samples_served)
            n, self._last_served = max(0, served - self._last_served), \
                served
            return n
        size = getattr(self.step, "minibatch_size", None)
        return int(size) if size is not None else 0

    def _fence_outputs(self):
        """Block until the step's device work is done.  Prefers the loss
        scalar (always produced last), falls back to the param tree."""
        for probe in (getattr(self.step, "loss", None),
                      getattr(self.step, "_params_", None)):
            if probe is None:
                continue
            try:
                import jax
                jax.block_until_ready(probe)
                return
            except Exception:  # noqa: BLE001
                continue

    def _step_run(self):
        data_wait = self._pending_data_wait
        self._pending_data_wait = 0.0
        t0 = time.perf_counter()
        try:
            result = self._orig_step_run()
        except Exception:
            # a crashed step still counts its host time; re-raise
            self.host_s += time.perf_counter() - t0
            raise
        t1 = time.perf_counter()
        if self.fence:
            self._fence_outputs()
        t2 = time.perf_counter()
        host, device = t1 - t0, t2 - t1
        n = self._consumed_examples()
        cache = self._jit_cache_size()
        recompiled = max(0, cache - self._jit_cache)
        self._jit_cache = cache
        # per-instance totals
        self.steps += 1
        self.examples += n
        self.recompiles += recompiled
        self.data_wait_s += data_wait
        self.host_s += host
        self.device_s += device
        # registry
        self._c_steps.inc()
        if n:
            self._c_examples.inc(n)
        if recompiled:
            self._c_recompiles.inc(recompiled)
        self._h_data.observe(data_wait)
        self._h_host.observe(host)
        self._h_device.observe(device)
        self._rate.append((t2, n))
        if len(self._rate) >= 2:
            span = self._rate[-1][0] - self._rate[0][0]
            if span > 0:
                self._g_rate.set(
                    sum(c for _, c in self._rate) / span)
        if self.steps % MEM_POLL_STEPS == 1:
            self._poll_memory()
        events.span("train.step", data_wait + host + device,
                    workflow=self.name,
                    data_wait_ms=round(data_wait * 1e3, 3),
                    host_ms=round(host * 1e3, 3),
                    device_ms=round(device * 1e3, 3),
                    examples=n, recompiles=recompiled)
        return result

    def _snap_run(self):
        """The snapshotter accounts its own training-thread stall
        (``stall_s``, zero for throttled-away calls) — read the delta so
        a gating-only run never floods the phase histogram."""
        snap = self.snapshotter
        before = float(getattr(snap, "stall_s", 0.0) or 0.0)
        result = self._orig_snap_run()
        stalled = float(getattr(snap, "stall_s", 0.0) or 0.0) - before
        if stalled > 0:
            self.snapshot_s += stalled
            self._h_snapshot.observe(stalled)
        return result

    def _poll_memory(self):
        device = getattr(self.step, "device", None)
        for dev in getattr(device, "jax_devices", None) or []:
            try:
                stats = dev.memory_stats() or {}
                peak = stats.get("peak_bytes_in_use")
            except Exception:  # noqa: BLE001 — cpu backends may not have it
                continue
            if peak:
                key = str(dev)
                self.peak_memory[key] = max(
                    self.peak_memory.get(key, 0), int(peak))
                self._g_mem.labels(workflow=self.name,
                                   device=key).set_max(peak)

    # -- lifecycle / reading -------------------------------------------------
    def detach(self):
        """Restore the wrapped run() methods (idempotent; tolerant of
        being attached on top of an earlier profiler — the original
        callable is restored rather than the class default)."""
        for obj, wrapper, orig in (
                (self.step, self._step_wrapper, self._orig_step_run),
                (self.loader, self._loader_wrapper,
                 self._orig_loader_run),
                (self.snapshotter, self._snap_wrapper,
                 self._orig_snap_run)):
            if obj is None:
                continue
            if obj.__dict__.get("run") is wrapper:
                del obj.__dict__["run"]
                # a pre-existing instance-level run (an OUTER profiler's
                # wrapper, or a MinibatchPrefetcher's plain-function
                # consume wrapper — no __func__) must come back
                if orig is not None and \
                        getattr(orig, "__func__", None) is not \
                        type(obj).run:
                    obj.__dict__["run"] = orig

    def summary(self):
        """Aggregate breakdown for results JSON / humans."""
        self._poll_memory()
        total = self.data_wait_s + self.host_s + self.device_s
        out = {"steps": self.steps, "examples": self.examples,
               "recompiles": self.recompiles,
               "data_wait_s": round(self.data_wait_s, 4),
               "host_s": round(self.host_s, 4),
               "device_s": round(self.device_s, 4)}
        if self.snapshot_s:
            out["snapshot_stall_s"] = round(self.snapshot_s, 4)
        if total > 0:
            out["examples_per_sec"] = round(self.examples / total, 1)
            out["phase_pct"] = {
                "data_wait": round(100 * self.data_wait_s / total, 1),
                "host": round(100 * self.host_s / total, 1),
                "device": round(100 * self.device_s / total, 1)}
            if self.snapshot_s:
                # share of the whole loop including checkpoint stalls —
                # the slice async snapshotting exists to shrink
                loop = total + self.snapshot_s
                out["phase_pct"]["snapshot"] = round(
                    100 * self.snapshot_s / loop, 1)
        if self.peak_memory:
            out["device_peak_memory_bytes"] = dict(self.peak_memory)
        prefetcher = getattr(self.loader, "prefetcher_", None)
        if prefetcher is not None:
            out["prefetch"] = prefetcher.stats()
        return out
