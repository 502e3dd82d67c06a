"""Dynamic micro-batching over shape-bucketed XLA executables.

The seed serving path (restful_api.py) paid one XLA dispatch — and, for
exported packages, one ``jax.export`` call-wrapper rebuild — per HTTP
request.  This module amortizes both the way the TPU-inference
literature does (Ragged Paged Attention, PAPERS.md: pad to buckets,
serve every bucket from one compiled program; TVM, PAPERS.md:
ahead-of-time compiled end-to-end serving):

- concurrent requests are concatenated into one batch and **padded to
  the next power-of-two bucket**, so the steady state only ever sees
  ``log2(max_batch)+1`` distinct shapes;
- every bucket is **AOT-compiled once at startup**
  (``jax.jit(...).lower(...).compile()``) — warm executables, zero
  recompilation after warmup, asserted via :meth:`BucketScheduler.stats`;
- batching is **continuous** (vLLM-style): a dispatch worker drains
  whatever is queued and executes immediately — while a batch runs, the
  next one accumulates; no fixed batching window adds latency;
- backpressure is a bounded count of outstanding requests: when full,
  :meth:`submit` raises :class:`SchedulerOverflow` and the server
  answers 429 instead of letting the queue grow without bound.

Works on any JAX backend.  What batching amortizes on the present chip
is not measured; the numbers tools/serve_bench.py has recorded are CPU
scheduling counts.
"""

import logging
import queue
import threading
import time
from concurrent.futures import Future

import numpy

from ..compilecache import WarmupManifest, default_cache
from ..logger import events
from ..observability import trace as _trace
from ..observability.flight import RECORDER as _flight
from .metrics import ServingMetrics


log = logging.getLogger("veles_tpu.serving")


class SchedulerOverflow(RuntimeError):
    """The bounded request queue is full — shed load (HTTP 429)."""


class SchedulerClosed(RuntimeError):
    """The scheduler is draining or stopped — no new requests."""


class DeadlineExpired(RuntimeError):
    """The request's end-to-end deadline passed before it reached the
    device — shed (HTTP 504) instead of spending batch rows on an
    answer nobody is waiting for."""


def deadline_expired(deadline, now=None):
    """True when an absolute ``time.monotonic()`` deadline has passed
    (None = no deadline)."""
    if deadline is None:
        return False
    return (time.monotonic() if now is None else now) >= deadline


def bucket_sizes(max_batch):
    """The power-of-two bucket ladder: 1, 2, 4, ... max_batch."""
    if max_batch < 1:
        raise ValueError("max_batch must be >= 1")
    sizes, b = [], 1
    while b < max_batch:
        sizes.append(b)
        b <<= 1
    sizes.append(int(max_batch))  # top bucket even when not a power of two
    return sizes


# -- model adapters ----------------------------------------------------------
# One scheduler serves any of: a live StandardWorkflow (its forward
# chain), an exported package (PackageLoader / path to the zip), or an
# opaque python callable (tests, custom runtimes).


class JaxModel:
    """A pure ``fn(params, x)`` compiled per bucket via jax.jit AOT."""

    def __init__(self, fn, params, sample_shape):
        import jax
        self._jit = jax.jit(fn)
        # params live on device once; per-dispatch host->device traffic
        # is the padded batch only
        self._params = jax.device_put(params)
        self.sample_shape = tuple(int(d) for d in sample_shape)

    def compile(self, bucket, cache=None):
        """-> (runner, cache_hit): the bucket's executable, off the
        persistent cache when one is active (hit True/False) or a plain
        AOT compile (hit None)."""
        import jax
        struct = jax.ShapeDtypeStruct((int(bucket),) + self.sample_shape,
                                      numpy.float32)
        hit = None
        if cache is not None:
            compiled, hit = cache.get_or_compile(
                self._jit, self._params, struct,
                name="serving.bucket%d" % int(bucket))
        else:
            compiled = self._jit.lower(self._params, struct).compile()
        params = self._params
        return (lambda xs: compiled(params, xs)), hit

    def jit_cache_size(self):
        """Eager-jit cache entries — stays 0 when every call went
        through a warm AOT executable (the zero-recompile assertion)."""
        try:
            return self._jit._cache_size()
        except Exception:
            return None


class OpaqueModel:
    """An opaque callable ``fn(x) -> y``; no compilation to manage."""

    def __init__(self, fn, sample_shape=None):
        self._fn = fn
        self.sample_shape = (tuple(int(d) for d in sample_shape)
                             if sample_shape is not None else None)

    def compile(self, bucket, cache=None):
        return self._fn, None

    def jit_cache_size(self):
        return None


def adapt_model(model, sample_shape=None):
    """model → adapter with ``compile(bucket)`` + ``sample_shape``.

    Accepts a package path, a PackageLoader, anything with a non-empty
    ``forwards`` chain (StandardWorkflow), or a bare callable.
    """
    if isinstance(model, (JaxModel, OpaqueModel)):
        return model                # pre-built adapter (tests, tools)
    if isinstance(model, str):
        from ..export.loader import PackageLoader
        model = PackageLoader(model)
    if hasattr(model, "deserialize") and hasattr(model, "unit_params"):
        exported = model.deserialize()
        meta = model.model_metadata
        if meta is None:
            raise ValueError("package has no model.json metadata")
        return JaxModel(lambda p, x: exported.call(p, x),
                        model.unit_params(),
                        meta["input"]["sample_shape"])
    forwards = getattr(model, "forwards", None)
    if forwards:
        from ..export.model import forward_fn
        return JaxModel(forward_fn(forwards),
                        [f.params for f in forwards],
                        forwards[0].input.shape[1:])
    if callable(model):
        return OpaqueModel(model, sample_shape)
    raise TypeError("cannot serve %r: want a package path, PackageLoader, "
                    "a workflow with forwards, or a callable" % (model,))


class _Pending:
    __slots__ = ("x", "n", "future", "enqueued", "trace", "deadline")

    def __init__(self, x, deadline=None):
        self.x = x
        self.n = int(x.shape[0])
        self.future = Future()
        self.enqueued = time.perf_counter()
        # the submitting thread's trace context (the HTTP handler's
        # request span): the dispatch worker links the batch span back
        # to every request it served
        self.trace = _trace.current()
        # absolute time.monotonic() end-to-end deadline (None = none):
        # checked at admission AND again just before batching, so work
        # that expired in the queue never reaches the executable
        self.deadline = deadline


_STOP = object()


class BucketScheduler:
    """Collect concurrent requests into padded power-of-two batches.

    ``workers`` dispatch threads pull from one queue; each drains what
    is available (continuous batching), pads to the smallest bucket
    that fits, and runs that bucket's warm executable.  ``queue_limit``
    bounds *outstanding* requests (queued + in a forming batch); beyond
    it :meth:`submit` raises :class:`SchedulerOverflow`.
    """

    def __init__(self, model, max_batch=64, queue_limit=256, workers=1,
                 max_wait=0.0, warmup=True, name="default",
                 metrics=None, sample_shape=None, cache=None,
                 manifest=None, background_warmup=None, buckets=None):
        from ..config import root
        self.name = name
        self.max_batch = int(max_batch)
        self.queue_limit = int(queue_limit)
        self.max_wait = float(max_wait)
        self.metrics = metrics or ServingMetrics(name)
        self._adapter = adapt_model(model, sample_shape)
        self.sample_shape = self._adapter.sample_shape
        # the bucket ladder is a TUNABLE SITE (serving.bucket_ladder):
        # an explicit ``buckets`` list pins it; otherwise a tuning
        # record for this max_batch picks the measured shape, and the
        # tuner-off fallback ("pow2") is byte-identical to the old
        # hard-wired bucket_sizes() ladder
        if buckets is not None:
            self.buckets = sorted({int(b) for b in buckets})
            if self.buckets[-1] != self.max_batch or self.buckets[0] < 1:
                raise ValueError(
                    "buckets %r must be >= 1 and end at max_batch %d"
                    % (buckets, self.max_batch))
            self.bucket_config = {"shape": "explicit"}
            self.config_source = "explicit"
        else:
            from ..autotune import dispatch as _autotune
            from ..autotune import space as _space
            cfg, src = _autotune.resolve(
                "serving.bucket_ladder", "mb%d" % self.max_batch,
                default={"shape": "pow2"})
            self.buckets = _space.ladder(cfg["shape"], self.max_batch)
            self.bucket_config = dict(cfg)
            self.config_source = src
        self._executables = {}
        self._compiles = 0              # fresh XLA compiles only
        self._cache_hits = 0            # executables loaded off disk
        self._compile_seconds = 0.0
        self._warmup_compiles = 0
        self._compile_lock = threading.Lock()
        # the persistent executable cache + warmup manifest (compilecache
        # subsystem): None kwargs resolve from root.common.compile_cache
        # — no configured dir means both stay off (seed behavior)
        if cache is None:
            cache = default_cache()
        self._cache = cache or None     # cache=False forces OFF
        if manifest is None:
            self._manifest = (self._cache.manifest
                              if self._cache is not None else None)
        elif isinstance(manifest, str):
            self._manifest = WarmupManifest(manifest)
        else:
            self._manifest = manifest or None
        if self._manifest is not None and self.config_source == "tuned":
            # ship the winner inside the warmup manifest: a warm
            # restart reads the SAME ladder before compiling anything,
            # so tuned geometry never causes a fresh compile
            self._manifest.record_config(
                self.name, "serving.bucket_ladder",
                dict(self.bucket_config, buckets=list(self.buckets)))
        if background_warmup is None:
            background_warmup = bool(root.common.compile_cache.get(
                "background_warmup", False))
        self._background_warmup = bool(background_warmup)
        self._warmup_thread = None
        self._queue = queue.Queue()     # unbounded; bound enforced below
        self._depth = 0                 # outstanding requests
        self._depth_lock = threading.Lock()
        self._closed = False
        if warmup:
            self.warmup()
        self._workers = [
            threading.Thread(target=self._worker_loop, daemon=True,
                             name="veles-serve-%s-%d" % (name, i))
            for i in range(max(int(workers), 1))]
        for t in self._workers:
            t.start()

    # -- compilation ---------------------------------------------------------
    def _warmup_order(self):
        """The ladder, warmup-manifest buckets first: a restart warms
        the shapes real traffic used before the speculative tail."""
        order = list(self.buckets)
        if self._manifest is None:
            return order
        first = [b for b in self._manifest.buckets(self.name)
                 if b in order]
        return first + [b for b in order if b not in first]

    def warmup(self, background=None):
        """Compile every bucket up front so steady state never compiles.

        Buckets the model cannot take (a static-batch package artifact)
        are dropped from the ladder instead of failing the whole model;
        at least one bucket must survive.  With ``background`` (default:
        the ``background_warmup`` knob) the tail of the ladder compiles
        on a daemon thread after the first usable bucket, so a server
        answers its first warm bucket before the tail finishes — on a
        warm cache the whole ladder is deserialization-fast anyway.
        """
        if background is None:
            background = self._background_warmup
        pending = self._warmup_order()
        usable = []
        while pending:                 # sync until one bucket works
            b = pending.pop(0)
            if self._warm_one(b):
                usable.append(b)
                break
        if not usable:
            raise ValueError(
                "model %r compiled for no bucket size" % self.name)
        if background and pending:
            self.buckets = sorted(usable + pending)
            self.max_batch = self.buckets[-1]
            self._warmup_compiles = self._compiles
            self._warmup_thread = threading.Thread(
                target=self._warmup_tail, args=(pending,), daemon=True,
                name="veles-serve-%s-warmup" % self.name)
            self._warmup_thread.start()
            return
        for b in pending:
            if self._warm_one(b):
                usable.append(b)
        self.buckets = sorted(usable)
        self.max_batch = self.buckets[-1]
        self._warmup_compiles = self._compiles

    def _warm_one(self, bucket):
        try:
            self._get_executable(bucket)
            return True
        except Exception as exc:  # noqa: BLE001 — drop, don't fail all
            # a static-batch package legitimately takes one bucket only,
            # so the drop stays — but loudly: whoever expects the whole
            # ladder (chip_smoke.py) compares stats()["buckets"] with it
            log.warning("serving %r: bucket %d dropped from the ladder "
                        "(%s: %s)", self.name, bucket,
                        type(exc).__name__, str(exc)[:500])
            events.event("serving.warmup_skip", model=self.name,
                         bucket=bucket, error=str(exc)[:200])
            return False

    def _warmup_tail(self, pending):
        """Background tail: compile the rest of the ladder, pruning
        buckets the model rejects; tail compiles count as warmup."""
        for b in pending:
            if self._closed:
                return
            ok = self._warm_one(b)
            with self._compile_lock:
                if not ok:
                    self.buckets = [x for x in self.buckets if x != b]
                    self.max_batch = self.buckets[-1]
                self._warmup_compiles = self._compiles

    def _get_executable(self, bucket):
        run = self._executables.get(bucket)
        if run is not None:
            return run
        with self._compile_lock:
            run = self._executables.get(bucket)
            if run is None:
                t0 = time.perf_counter()
                run, hit = self._adapter.compile(bucket,
                                                 cache=self._cache)
                dt = time.perf_counter() - t0
                if hit:
                    self._cache_hits += 1
                else:
                    self._compiles += 1
                self._compile_seconds += dt
                self.metrics.record_compile(dt)
                self._executables[bucket] = run
                events.span("serving.compile", dt, model=self.name,
                            bucket=int(bucket),
                            cache_hit=bool(hit) if hit is not None
                            else None)
                if self._manifest is not None:
                    self._manifest.record(self.name, bucket,
                                          self.sample_shape)
        return run

    def _bucket_for(self, rows):
        for b in self.buckets:
            if b >= rows:
                return b
        return self.buckets[-1]

    # -- request side --------------------------------------------------------
    def validate(self, x):
        """Shape-check a request batch; raises ValueError (client error)."""
        if x.ndim < 2:
            raise ValueError("input must be a batch of samples")
        if self.sample_shape is not None and \
                tuple(x.shape[1:]) != self.sample_shape:
            raise ValueError(
                "sample shape %s does not match the model's %s"
                % (list(x.shape[1:]), list(self.sample_shape)))

    def submit(self, x, deadline=None):
        """Enqueue one request batch (≤ max_batch rows) → Future of the
        output rows.  Raises SchedulerOverflow / SchedulerClosed /
        DeadlineExpired / ValueError (bad shape)."""
        x = numpy.ascontiguousarray(x, numpy.float32)
        self.validate(x)
        if x.shape[0] > self.max_batch:
            raise ValueError("request of %d rows exceeds max_batch=%d "
                             "(use infer(), which chunks)"
                             % (x.shape[0], self.max_batch))
        return self._enqueue(x, deadline)

    def _enqueue(self, x, deadline=None):
        """The validated hot path: bound check, depth accounting, queue."""
        if self._closed:
            raise SchedulerClosed("scheduler %r is shut down" % self.name)
        if deadline_expired(deadline):
            self.metrics.record_expired()
            raise DeadlineExpired(
                "deadline passed before admission to %r" % self.name)
        with self._depth_lock:
            if self._depth >= self.queue_limit:
                self.metrics.record_reject()
                raise SchedulerOverflow(
                    "queue full (%d outstanding, limit %d)"
                    % (self._depth, self.queue_limit))
            self._depth += 1
        req = _Pending(x, deadline)
        if req.trace is not None:
            _flight.record(req.trace.trace_id, "queue.enter",
                           model=self.name, rows=int(x.shape[0]))
        self._queue.put(req)
        return req.future

    def infer(self, x, timeout=None, deadline=None):
        """Blocking inference of any batch size: chunk to ≤ max_batch,
        submit, concatenate.  Returns the output as a numpy array."""
        x = numpy.ascontiguousarray(x, numpy.float32)
        self.validate(x)
        t0 = time.perf_counter()
        futures = [self._enqueue(x[i:i + self.max_batch], deadline)
                   for i in range(0, x.shape[0], self.max_batch)]
        try:
            parts = [f.result(timeout) for f in futures]
        except Exception:
            self.metrics.record_request(
                x.shape[0], time.perf_counter() - t0, ok=False)
            raise
        out = parts[0] if len(parts) == 1 else numpy.concatenate(parts)
        self.metrics.record_request(x.shape[0], time.perf_counter() - t0)
        return out

    # -- dispatch side -------------------------------------------------------
    def _take_next(self, deadline):
        try:
            return self._queue.get_nowait()
        except queue.Empty:
            if deadline is None:
                return None
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                return None
            try:
                return self._queue.get(timeout=remaining)
            except queue.Empty:
                return None

    def _worker_loop(self):
        carry = None
        while True:
            req = carry if carry is not None else self._queue.get()
            carry = None
            if req is _STOP:
                return
            batch, rows = [req], req.n
            # optional linger (off by default): continuous batching
            # self-clocks under load — while this batch runs, the next
            # accumulates — so waiting only ever adds latency
            deadline = (time.monotonic() + self.max_wait
                        if self.max_wait > 0 else None)
            while rows < self.max_batch:
                nxt = self._take_next(deadline)
                if nxt is None:
                    break
                if nxt is _STOP:
                    carry = _STOP
                    break
                if rows + nxt.n > self.max_batch:
                    carry = nxt     # starts the next batch
                    break
                batch.append(nxt)
                rows += nxt.n
            self._execute(batch, rows)

    def _execute(self, batch, rows):
        # pre-batch deadline check: a request that expired while queued
        # is shed HERE — it never occupies a bucket row or device time
        now = time.monotonic()
        expired = [r for r in batch if deadline_expired(r.deadline, now)]
        if expired:
            exc = DeadlineExpired("deadline passed in queue")
            for r in expired:
                self.metrics.record_expired()
                if r.future.set_running_or_notify_cancel():
                    r.future.set_exception(exc)
                rows -= r.n
            self._release(len(expired))
            batch = [r for r in batch if r not in expired]
            if not batch:
                return
        t0 = time.perf_counter()
        try:
            bucket = self._bucket_for(rows)
            run = self._executables.get(bucket) or \
                self._get_executable(bucket)
            if len(batch) == 1 and batch[0].n == bucket:
                xs = batch[0].x
            else:
                parts = [r.x for r in batch]
                if bucket > rows:
                    parts.append(numpy.zeros(
                        (bucket - rows,) + batch[0].x.shape[1:],
                        numpy.float32))
                xs = numpy.concatenate(parts)
            out = numpy.asarray(run(xs))
        except Exception as exc:
            for r in batch:
                if not r.future.set_running_or_notify_cancel():
                    continue
                r.future.set_exception(exc)
            self._release(len(batch))
            return
        off = 0
        for r in batch:
            if r.future.set_running_or_notify_cancel():
                r.future.set_result(out[off:off + r.n])
            off += r.n
        self._release(len(batch))
        dt = time.perf_counter() - t0
        # request span ids riding this batch (bounded: a full 64-batch
        # of tiny requests must not bloat every span record)
        links = [r.trace.span_id for r in batch
                 if r.trace is not None][:16] or None
        # per-request flight share: batch cost split by row count, so
        # co-batched requests attribute the device time fairly
        for r in batch:
            if r.trace is not None:
                _flight.record(r.trace.trace_id, "queue.admit",
                               bucket=int(bucket))
                _flight.record(r.trace.trace_id, "batch.execute",
                               seconds=round(dt * r.n / max(rows, 1),
                                             6),
                               bucket=int(bucket), rows=int(rows))
        self.metrics.record_batch(bucket, rows, dt, len(batch),
                                  links=links)

    def _release(self, n):
        with self._depth_lock:
            self._depth -= n

    # -- lifecycle / introspection -------------------------------------------
    def close(self, drain=True, timeout=10.0):
        """Stop accepting requests; by default finish everything queued
        (graceful drain), then stop the dispatch workers."""
        if self._closed:
            return
        self._closed = True
        if not drain:
            while True:
                try:
                    req = self._queue.get_nowait()
                except queue.Empty:
                    break
                if req is _STOP:
                    continue
                if req.future.set_running_or_notify_cancel():
                    req.future.set_exception(
                        SchedulerClosed("scheduler shut down"))
                self._release(1)
        for _ in self._workers:
            self._queue.put(_STOP)
        for t in self._workers:
            t.join(timeout)
        # a submit that raced the closed flag could still be queued with
        # no worker left to serve it — fail it rather than hang its client
        while True:
            try:
                req = self._queue.get_nowait()
            except queue.Empty:
                break
            if req is _STOP:
                continue
            if req.future.set_running_or_notify_cancel():
                req.future.set_exception(
                    SchedulerClosed("scheduler shut down"))
            self._release(1)

    @property
    def queue_depth(self):
        return self._depth

    @property
    def ready(self):
        """True once the warmup ladder is fully compiled (background
        tail included) and the scheduler is accepting — the signal
        behind ``GET /readyz`` and fleet-router admission."""
        if self._closed or not self._executables:
            return False
        t = self._warmup_thread
        return t is None or not t.is_alive()

    def load(self):
        """Cheap backpressure snapshot for routers: no locks beyond
        int reads, safe to poll at high frequency."""
        depth = self._depth
        return {"kind": "bucket",
                "queue_depth": depth,
                "queue_limit": self.queue_limit,
                "utilization": round(depth / self.queue_limit, 4)}

    def retry_after_s(self, cap=30):
        """Seconds until the current backlog plausibly drains: queued
        batches ahead x the recent per-batch wall time, spread over the
        dispatch workers.  The shed response's ``Retry-After`` — a
        computed hint instead of the old hardcoded ``1``."""
        batch_p50 = self.metrics.batch_latency.summary().get("p50_ms")
        if not batch_p50:
            return 1
        batches_ahead = -(-self._depth // self.max_batch)  # ceil
        est = batches_ahead * (batch_p50 / 1e3) / len(self._workers)
        return max(1, min(int(cap), int(est + 0.999)))

    def join_warmup(self, timeout=None):
        """Block until a background warmup tail finishes (no-op when
        warmup was synchronous).  Returns True when nothing is left
        warming."""
        t = self._warmup_thread
        if t is not None:
            t.join(timeout)
            return not t.is_alive()
        return True

    def stats(self):
        """Executable-cache accounting — the zero-recompile evidence.

        ``compiles`` counts FRESH XLA compilations only; executables
        deserialized off the persistent cache land in ``cache_hits``
        (a warm-cache restart therefore shows ``compiles == 0``).
        """
        return {
            "buckets": list(self.buckets),
            "bucket_config": dict(self.bucket_config,
                                  config_source=self.config_source),
            "executables": len(self._executables),
            "compiles": self._compiles,
            "cache_hits": self._cache_hits,
            "compile_seconds": round(self._compile_seconds, 4),
            "warmup_compiles": self._warmup_compiles,
            "post_warmup_compiles": self._compiles - self._warmup_compiles,
            "warming": (self._warmup_thread.is_alive()
                        if self._warmup_thread is not None else False),
            "jit_cache_size": self._adapter.jit_cache_size(),
            "queue_depth": self._depth,
            "queue_limit": self.queue_limit,
            "max_batch": self.max_batch,
            "workers": len(self._workers),
            "ready": self.ready,
            "closed": self._closed,
        }
