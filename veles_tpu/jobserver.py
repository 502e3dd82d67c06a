"""Cross-host trial scheduler: a TCP job-queue master + worker clients.

Re-creation of the reference's meta-level distribution: its ZeroMQ/Twisted
master kept a job queue and farmed GA chromosomes / ensemble instances to
slave processes on other hosts, requeueing jobs whose slave dropped and
respawning dead slaves over SSH (/root/reference/veles/server.py:369-430
job queue, :637-655 respawn; ensemble/base_workflow.py:134-141 trial
farm-out; launcher.py:808-842 remote node launch).

TPU-native redesign: the *gradient* path the reference also pushed through
this channel is gone — in-program XLA collectives over the mesh own it
(``parallel/``).  What remains for an out-of-band control plane is exactly
the meta level: independent CLI trials.  So this module is deliberately
small and dependency-free — newline-delimited JSON over stdlib TCP
sockets, a worklist with drop/requeue semantics mirroring the Loader's
master-index contract, and an elastic local/remote worker pool:

- :class:`JobMaster` — binds, accepts workers, hands each an outstanding
  job, requeues a job when its worker's connection drops mid-trial
  (``max_attempts`` bounds redelivery, like the loader's requeue/drop).
- :func:`worker_loop` / ``python -m veles_tpu.jobserver HOST PORT`` —
  a worker: receives jobs, runs them via :func:`veles_tpu.subproc
  .run_trial`, reports results.  Start it on any host that can reach the
  master (the SSH analog: ``ssh h python -m veles_tpu.jobserver ...``).
- :class:`WorkerPool` — spawns N worker subprocesses (local by default,
  arbitrary launch command for remote) and respawns dead ones with
  exponential backoff, the reference's slave-respawn behavior.

Wired into ``--ensemble-train`` / ``--optimize`` through the CLI's
``--listen ADDR`` / ``--workers N`` flags (__main__.py).
"""

import json
import os
import queue
import socket
import subprocess
import sys
import threading
import time

from .backends import refuse_children_sharing_the_tpu
from .observability import trace as _trace

_SENTINEL_TIMEOUT = 0.1


class Job:
    """One unit of work; ``result`` is set exactly once ``done`` fires.

    ``span`` is the job's span id in the master's trace: the worker
    adopts it as parent, so the merged per-process event files show
    dispatch (master) and execution (worker) causally linked."""

    __slots__ = ("id", "payload", "attempts", "done", "result", "worker",
                 "span")

    def __init__(self, job_id, payload):
        self.id = job_id
        self.payload = payload
        self.attempts = 0
        self.done = threading.Event()
        self.result = None
        self.worker = None
        self.span = _trace.new_id()


def _send(wfile, msg):
    wfile.write((json.dumps(msg) + "\n").encode())
    wfile.flush()


def _recv(rfile):
    line = rfile.readline()
    if not line:
        return None
    return json.loads(line)


class JobMaster:
    """Accepts workers; each connection drains the shared job queue.

    A worker that disconnects mid-job gets its job REQUEUED (attempts+1);
    after ``max_attempts`` deliveries the job fails with the last error —
    the same bounded-redelivery contract the Loader applies to minibatches
    of dropped slaves (loader/base.py requeue/drop_slave)."""

    def __init__(self, host="127.0.0.1", port=0, max_attempts=3,
                 silent=True, secret=None):
        self.max_attempts = max_attempts
        self.silent = silent
        # shared-secret handshake: a hello without the matching token is
        # dropped before any payload (argv/env) is handed out.  Defaults
        # from $VELES_JOB_SECRET so master and workers agree without
        # plumbing; unset = open (fine for the 127.0.0.1 default bind,
        # set it whenever you --listen on a routable address)
        self.secret = secret if secret is not None else \
            os.environ.get("VELES_JOB_SECRET")
        if not self.secret and host not in ("127.0.0.1", "localhost",
                                            "::1"):
            print("jobmaster: WARNING — listening on %s with NO shared "
                  "secret: any host that can reach the port will receive "
                  "trial payloads (argv + env) and can forge results. "
                  "Set VELES_JOB_SECRET on master and workers."
                  % host, file=sys.stderr)
        self.active_workers = 0
        # one trace for everything this master farms out: joins an
        # already-active context (e.g. a traced ensemble run) or starts
        # a fresh trace; carried to workers on every job message
        ctx = _trace.current()
        self.trace_id = ctx.trace_id if ctx else _trace.new_id()
        self._sock = socket.create_server((host, port))
        self.address = self._sock.getsockname()[:2]
        self._pending = queue.Queue()
        self._lock = threading.Lock()
        self._next_id = 0
        self._closing = threading.Event()
        self._conns = []
        self.workers_seen = 0
        self._accept_thread = threading.Thread(
            target=self._accept_loop, daemon=True,
            name="jobmaster-accept")
        self._accept_thread.start()

    def _finish(self, job, result):
        """Complete ``job`` exactly once (result write + done) — the
        single completion protocol; late writers (a worker replying
        after map() timed the job out, a drop racing a timeout) become
        no-ops.  Returns whether THIS call completed the job."""
        with self._lock:
            if job.done.is_set():
                return False
            job.result = result
            job.done.set()
            return True

    # -- submission ----------------------------------------------------------
    def submit(self, payload):
        with self._lock:
            job = Job(self._next_id, payload)
            self._next_id += 1
        self._pending.put(job)
        return job

    def map(self, payloads, timeout=None):
        """Submit every payload, block until all finish, return results
        in submission order."""
        jobs = [self.submit(p) for p in payloads]
        deadline = None if timeout is None else time.monotonic() + timeout
        last_warn = time.monotonic()
        for job in jobs:
            while not job.done.is_set():
                remaining = None if deadline is None else \
                    max(0.0, deadline - time.monotonic())
                if job.done.wait(5.0 if remaining is None
                                 else min(5.0, remaining)):
                    break
                now = time.monotonic()
                if self.active_workers == 0 and now - last_warn >= 30.0:
                    # a hang here is otherwise silent (e.g. every pool
                    # worker crashed and the respawn budget is spent)
                    print("jobmaster: jobs pending but no workers "
                          "connected on %s:%d" % self.address,
                          file=sys.stderr)
                    last_warn = now
                if deadline is not None and now >= deadline:
                    self._finish(job, {"rc": -1, "results": None,
                                       "error": "scheduler timeout",
                                       "worker": job.worker,
                                       "attempts": job.attempts})
        return [j.result for j in jobs]

    def close(self):
        self._closing.set()
        try:
            self._sock.close()
        except OSError:
            pass
        with self._lock:
            conns = list(self._conns)
        # idle handlers notice _closing within _SENTINEL_TIMEOUT and say
        # bye; give them that window before cutting live connections
        time.sleep(2 * _SENTINEL_TIMEOUT)
        for conn in conns:
            try:
                conn.close()
            except OSError:
                pass

    # -- internals -----------------------------------------------------------
    def _accept_loop(self):
        while not self._closing.is_set():
            try:
                conn, _addr = self._sock.accept()
            except OSError:
                return
            with self._lock:
                self._conns.append(conn)
            threading.Thread(target=self._serve, args=(conn,),
                             daemon=True, name="jobmaster-worker").start()

    def _serve(self, conn):
        rfile = conn.makefile("rb")
        wfile = conn.makefile("wb")
        current = None
        name = "?"
        admitted = False
        try:
            hello = _recv(rfile)
            if not hello or hello.get("op") != "hello":
                return
            if self.secret and hello.get("token") != self.secret:
                if not self.silent:
                    print("jobmaster: rejected worker with bad token",
                          file=sys.stderr)
                return
            name = hello.get("name", "?")
            with self._lock:
                self.workers_seen += 1
                self.active_workers += 1
                admitted = True
            while not self._closing.is_set():
                try:
                    job = self._pending.get(timeout=_SENTINEL_TIMEOUT)
                except queue.Empty:
                    continue
                if job.done.is_set():  # e.g. failed by map() timeout
                    continue
                current = job
                job.attempts += 1
                job.worker = name
                t_dispatch = time.perf_counter()
                _send(wfile, {"op": "job", "id": job.id,
                              "payload": job.payload,
                              "trace": {"trace_id": self.trace_id,
                                        "parent_span": job.span}})
                msg = _recv(rfile)
                if msg is None:
                    raise ConnectionError("worker %s died mid-job" % name)
                if msg.get("op") != "result" or msg.get("id") != job.id:
                    raise ConnectionError(
                        "protocol error from %s: %r" % (name, msg))
                # map() may have already failed this job with a timeout
                # result; the late worker reply must not silently
                # overwrite what map() returned
                self._finish(job, {"rc": msg.get("rc"),
                                   "results": msg.get("results"),
                                   "error": msg.get("error"),
                                   "worker": name,
                                   "attempts": job.attempts})
                # master-side view of the same job span the worker ran
                # under — merged traces link the two via span ids
                from .logger import events
                events.span("job.dispatch",
                            time.perf_counter() - t_dispatch,
                            job=job.id, worker=name,
                            attempts=job.attempts,
                            trace_id=self.trace_id, span=job.span)
                current = None
            try:
                _send(wfile, {"op": "bye"})
            except OSError:
                pass
        except Exception as exc:  # noqa: BLE001 — ANY handler failure
            # (socket drop, bad JSON, malformed message shape) must give
            # the in-flight job back to the queue, or map() hangs forever
            if current is not None:
                self._requeue(current, "%s: %s" % (type(exc).__name__,
                                                   exc))
        finally:
            if admitted:
                with self._lock:
                    self.active_workers -= 1
            try:
                conn.close()
            except OSError:
                pass

    def _requeue(self, job, reason):
        if job.done.is_set():
            return  # e.g. map() already timed it out — nothing to redo
        if job.attempts >= self.max_attempts:
            if self._finish(job, {"rc": -1, "results": None,
                                  "error": "job failed after %d "
                                           "deliveries: %s"
                                           % (job.attempts, reason),
                                  "worker": job.worker,
                                  "attempts": job.attempts}) \
                    and not self.silent:
                print("jobmaster: dropping job %d (%s)"
                      % (job.id, reason), file=sys.stderr)
        else:
            if not self.silent:
                print("jobmaster: requeueing job %d (%s)"
                      % (job.id, reason), file=sys.stderr)
            self._pending.put(job)


# -- worker ------------------------------------------------------------------
def execute_payload(payload, python=None):
    """Run one job payload; returns {"rc", "results", "error"}.

    Kinds: ``trial`` — a CLI model trial via subproc.run_trial (the real
    workload); ``eval`` — echo ``value`` after ``sleep`` seconds (tests,
    liveness probes); ``crash_once`` — simulate a worker crash the FIRST
    time the job is delivered anywhere (flag-file guarded), used by the
    requeue drill."""
    kind = payload.get("kind", "trial")
    if kind == "trial":
        from .subproc import run_trial
        rc, results, error = run_trial(
            payload["model"], payload.get("argv", ()),
            timeout=payload.get("timeout"), python=python,
            env=payload.get("env"))
        return {"rc": rc, "results": results, "error": error}
    if kind == "eval":
        time.sleep(payload.get("sleep", 0))
        return {"rc": 0, "results": {"value": payload.get("value")},
                "error": None}
    if kind == "crash_once":
        flag = payload["flag"]
        try:
            fd = os.open(flag, os.O_CREAT | os.O_EXCL | os.O_WRONLY)
        except FileExistsError:
            time.sleep(payload.get("sleep", 0))
            return {"rc": 0, "results": {"value": payload.get("value")},
                    "error": None}
        os.close(fd)
        os._exit(17)  # hard crash mid-job: the master must requeue
    return {"rc": -2, "results": None,
            "error": "unknown payload kind %r" % kind}


def worker_loop(host, port, name=None, python=None, secret=None):
    """Connect to the master and serve jobs until it says bye."""
    name = name or "%s-%d" % (socket.gethostname(), os.getpid())
    secret = secret if secret is not None else \
        os.environ.get("VELES_JOB_SECRET")
    sock = socket.create_connection((host, port))
    rfile = sock.makefile("rb")
    wfile = sock.makefile("wb")
    try:
        hello = {"op": "hello", "name": name, "pid": os.getpid()}
        if secret:
            hello["token"] = secret
        _send(wfile, hello)
        while True:
            msg = _recv(rfile)
            if msg is None or msg.get("op") == "bye":
                return
            if msg.get("op") != "job":
                continue
            # run under the master's trace context: this worker's
            # events (and any trial subprocess it spawns — run_trial
            # injects the context into the child env) share the
            # master's trace_id, parented on the job's span
            with _trace.adopt(msg.get("trace")):
                t0 = time.perf_counter()
                result = execute_payload(msg["payload"], python=python)
                from .logger import events
                events.span("job.run", time.perf_counter() - t0,
                            job=msg["id"], worker=name,
                            payload_kind=msg["payload"].get("kind",
                                                            "trial"),
                            rc=result.get("rc"))
            result.update({"op": "result", "id": msg["id"]})
            _send(wfile, result)
    finally:
        try:
            sock.close()
        except OSError:
            pass


class WorkerPool:
    """Spawn ``n`` worker processes and respawn dead ones with backoff.

    ``command`` is the launch template (list; ``{host}``/``{port}``
    placeholders substituted) — the default launches local subprocesses;
    pass e.g. ``["ssh", "node7", sys.executable, "-m",
    "veles_tpu.jobserver", "{host}", "{port}"]`` for the reference's
    remote-slave behavior (server.py:637-655)."""

    def __init__(self, address, n=2, python=None, command=None,
                 max_respawns=3, backoff=0.5, env=None):
        self.address = address
        self.python = python or sys.executable
        self.command = command
        self.max_respawns = max_respawns
        self.backoff = backoff
        self.env = env
        self.respawns = 0
        self._cap_warned = False
        self._procs = [None] * n
        self._closing = threading.Event()
        if not command:         # local workers; a remote template's
            # hosts are not this one's
            refuse_children_sharing_the_tpu(n, "jobserver worker pool",
                                            env)
        for i in range(n):
            self._spawn(i)
        self._monitor = threading.Thread(target=self._watch, daemon=True,
                                         name="workerpool-monitor")
        self._monitor.start()

    def _spawn(self, i):
        host, port = self.address
        if self.command:
            cmd = [str(a).replace("{host}", str(host))
                   .replace("{port}", str(port)) for a in self.command]
        else:
            cmd = [self.python, "-m", "veles_tpu.jobserver",
                   str(host), str(port), "--name", "pool-%d" % i]
        repo_root = os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))
        self._procs[i] = subprocess.Popen(cmd, cwd=repo_root, env=self.env)

    def _watch(self):
        while not self._closing.is_set():
            for i, proc in enumerate(self._procs):
                if proc is None or proc.poll() is None:
                    continue
                if proc.returncode == 0 or self._closing.is_set():
                    continue
                if self.respawns >= self.max_respawns:
                    if not self._cap_warned:
                        self._cap_warned = True
                        print("workerpool: respawn budget (%d) spent; "
                              "worker %d stays down" % (self.max_respawns,
                                                        i),
                              file=sys.stderr)
                    continue
                self.respawns += 1
                # exponential backoff per respawn, reference-style
                time.sleep(self.backoff * 2 ** (self.respawns - 1))
                if not self._closing.is_set():
                    self._spawn(i)
            time.sleep(_SENTINEL_TIMEOUT)

    def alive(self):
        return sum(1 for p in self._procs
                   if p is not None and p.poll() is None)

    def close(self, timeout=5.0):
        self._closing.set()
        deadline = time.monotonic() + timeout
        for proc in self._procs:
            if proc is None:
                continue
            try:
                proc.wait(max(0.0, deadline - time.monotonic()))
            except subprocess.TimeoutExpired:
                proc.terminate()
                try:
                    proc.wait(1.0)
                except subprocess.TimeoutExpired:
                    proc.kill()


def parse_address(text, default_host="127.0.0.1"):
    """'host:port' | ':port' | 'port' -> (host, port)."""
    host, _, port = str(text).rpartition(":")
    return (host or default_host), int(port)


def main(argv=None):
    import argparse
    p = argparse.ArgumentParser(
        prog="python -m veles_tpu.jobserver",
        description="Trial worker: connect to a --listen'ing master and "
                    "serve CLI trials (reference slave role).")
    p.add_argument("host")
    p.add_argument("port", type=int)
    p.add_argument("--name", default=None)
    p.add_argument("--secret", default=None,
                   help="shared handshake secret (default: "
                        "$VELES_JOB_SECRET)")
    args = p.parse_args(argv)
    worker_loop(args.host, args.port, name=args.name, secret=args.secret)
    return 0


if __name__ == "__main__":
    sys.exit(main())
