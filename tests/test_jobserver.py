"""Cross-host trial scheduler tests (reference network-stack test bar:
/root/reference/veles/tests/test_network.py:52-116 ran master + slaves in
one process; we do the same, plus worker-death requeue drills)."""

import os
import socket
import threading
import time

from veles_tpu.jobserver import (JobMaster, WorkerPool, execute_payload,
                                 parse_address, worker_loop, _send, _recv)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _thread_worker(address, name):
    t = threading.Thread(target=worker_loop,
                         args=(address[0], address[1]),
                         kwargs={"name": name}, daemon=True)
    t.start()
    return t


def test_parse_address():
    assert parse_address("1234") == ("127.0.0.1", 1234)
    assert parse_address(":8080") == ("127.0.0.1", 8080)
    assert parse_address("node7:9000") == ("node7", 9000)


def test_master_two_workers_share_the_queue():
    """Master + 2 workers in one process: every job completes and both
    workers take a share (the sleeps force overlap)."""
    master = JobMaster()
    try:
        _thread_worker(master.address, "w0")
        _thread_worker(master.address, "w1")
        results = master.map(
            [{"kind": "eval", "value": i, "sleep": 0.05}
             for i in range(8)], timeout=30)
        assert [r["results"]["value"] for r in results] == list(range(8))
        assert all(r["rc"] == 0 and r["attempts"] == 1 for r in results)
        workers = {r["worker"] for r in results}
        assert workers == {"w0", "w1"}, workers
        assert master.workers_seen == 2
    finally:
        master.close()


def test_connection_drop_requeues_job():
    """A worker whose socket dies mid-job loses the job back to the
    queue; a healthy worker finishes it (attempts == 2)."""
    master = JobMaster(silent=True)
    try:
        # flaky worker: takes the first job it is handed, then vanishes
        def flaky():
            sock = socket.create_connection(master.address)
            rfile, wfile = sock.makefile("rb"), sock.makefile("wb")
            _send(wfile, {"op": "hello", "name": "flaky"})
            _recv(rfile)  # receive a job...
            sock.close()  # ...and die without answering

        threading.Thread(target=flaky, daemon=True).start()
        # let the flaky worker grab the first job before a healthy
        # worker exists
        job = master.submit({"kind": "eval", "value": 42})
        deadline = time.monotonic() + 10
        while job.attempts == 0 and time.monotonic() < deadline:
            time.sleep(0.02)
        _thread_worker(master.address, "healthy")
        assert job.done.wait(30)
        assert job.result["rc"] == 0
        assert job.result["results"]["value"] == 42
        assert job.result["attempts"] == 2
        assert job.result["worker"] == "healthy"
    finally:
        master.close()


def test_max_attempts_drops_job():
    """After max_attempts dead deliveries the job fails instead of
    looping forever (the loader's bounded-requeue contract)."""
    master = JobMaster(max_attempts=2, silent=True)
    try:
        def flaky():
            sock = socket.create_connection(master.address)
            rfile, wfile = sock.makefile("rb"), sock.makefile("wb")
            _send(wfile, {"op": "hello", "name": "flaky"})
            _recv(rfile)
            sock.close()

        job = master.submit({"kind": "eval", "value": 1})
        for _ in range(2):
            threading.Thread(target=flaky, daemon=True).start()
            attempts = job.attempts
            deadline = time.monotonic() + 10
            while job.attempts == attempts and not job.done.is_set() \
                    and time.monotonic() < deadline:
                time.sleep(0.02)
        assert job.done.wait(10)
        assert job.result["rc"] == -1
        assert "failed after 2 deliveries" in job.result["error"]
    finally:
        master.close()


def test_worker_process_crash_requeue_and_respawn(tmp_path):
    """The reference drill (server.py:637-655): a worker PROCESS crashes
    hard mid-job; the master requeues the job onto a surviving worker
    and the elastic pool respawns the dead one."""
    master = JobMaster(silent=True)
    pool = None
    try:
        pool = WorkerPool(master.address, n=2, backoff=0.1)
        flag = str(tmp_path / "crashed-once")
        payloads = [{"kind": "crash_once", "flag": flag, "value": 7}]
        payloads += [{"kind": "eval", "value": i, "sleep": 0.02}
                     for i in range(4)]
        results = master.map(payloads, timeout=60)
        assert results[0]["rc"] == 0, results[0]
        assert results[0]["results"]["value"] == 7
        assert results[0]["attempts"] == 2  # died once, requeued once
        assert all(r["rc"] == 0 for r in results[1:])
        deadline = time.monotonic() + 10
        while pool.respawns == 0 and time.monotonic() < deadline:
            time.sleep(0.05)
        assert pool.respawns >= 1
        deadline = time.monotonic() + 10
        while pool.alive() < 2 and time.monotonic() < deadline:
            time.sleep(0.05)
        assert pool.alive() == 2
    finally:
        if pool is not None:
            pool.close()
        master.close()


def test_secret_handshake():
    """A master with a secret serves matching workers and rejects
    mismatched tokens (the cross-host auth story)."""
    master = JobMaster(secret="s3cret", silent=True)
    try:
        # the bad worker FIRST, synchronously: the master closes its
        # connection on the failed token check, which makes worker_loop
        # return — so a successful join IS the observed rejection
        bad = threading.Thread(
            target=worker_loop, args=(master.address[0], master.address[1]),
            kwargs={"name": "bad", "secret": "wrong"}, daemon=True)
        bad.start()
        bad.join(10)
        assert not bad.is_alive(), "bad-token worker was not disconnected"
        assert master.workers_seen == 0  # never admitted
        threading.Thread(
            target=worker_loop, args=(master.address[0], master.address[1]),
            kwargs={"name": "good", "secret": "s3cret"},
            daemon=True).start()
        results = master.map([{"kind": "eval", "value": i}
                              for i in range(4)], timeout=30)
        assert all(r["rc"] == 0 for r in results)
        assert {r["worker"] for r in results} == {"good"}
        assert master.workers_seen == 1
    finally:
        master.close()


def test_worker_pool_custom_command_template():
    """The launch template ({host}/{port} substitution) is the remote
    (SSH) spawn hook; exercised with a local python command."""
    import sys as sys_mod
    master = JobMaster(silent=True)
    pool = None
    try:
        pool = WorkerPool(
            master.address, n=1,
            command=[sys_mod.executable, "-m", "veles_tpu.jobserver",
                     "{host}", "{port}", "--name", "templated"])
        results = master.map([{"kind": "eval", "value": 5}], timeout=30)
        assert results[0]["rc"] == 0
        assert results[0]["worker"] == "templated"
    finally:
        if pool is not None:
            pool.close()
        master.close()


def test_worker_pool_refuses_workers_that_would_share_the_tpu():
    """`--workers N` on a TPU host: N trial processes, one chip.  The
    pool refuses at start; a remote command template is not this
    host's business and is left alone (previous test)."""
    import pytest
    with pytest.raises(RuntimeError, match="more children than chips"):
        WorkerPool(("127.0.0.1", 1), n=2,
                   env=dict(os.environ, JAX_PLATFORMS="tpu"))


def test_execute_payload_unknown_kind():
    out = execute_payload({"kind": "nope"})
    assert out["rc"] == -2 and "unknown payload kind" in out["error"]


def test_ga_distributes_trials_with_worker_death(tmp_path):
    """VERDICT round-2 'done' bar: a GA run distributes trials over >=2
    worker processes with one connection killed mid-trial and the trial
    re-queued — asserted from the scheduler's own outcome records."""
    from veles_tpu.config import Range, fix_config, root
    from veles_tpu.genetics import GeneticsOptimizer
    from veles_tpu.prng import RandomGenerator
    import veles_tpu.znicz.samples.mnist  # noqa: F401 — registers defaults

    cfg_file = str(tmp_path / "ga-dist-cfg.py")
    with open(cfg_file, "w") as f:
        f.write(
            "root.mnist.update({'loader': {'minibatch_size': 100, "
            "'n_train': 300, 'n_valid': 100}, "
            "'decision': {'max_epochs': 1, 'silent': True}})\n"
            "root.mnist.layers[0]['<-']['learning_rate'] = "
            "Range(0.03, 0.005, 0.2)\n")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    master = JobMaster(silent=True)
    pool = None
    outcomes = []
    real_map = master.map

    def recording_map(payloads, timeout=None):
        res = real_map(payloads, timeout=timeout)
        outcomes.extend(res)
        return res
    master.map = recording_map
    try:
        pool = WorkerPool(master.address, n=2, env=env, backoff=0.1)

        # one flaky connection that dies mid-trial, deterministically:
        # a blocked queue-getter always receives one of the first jobs
        def flaky():
            sock = socket.create_connection(master.address)
            rfile, wfile = sock.makefile("rb"), sock.makefile("wb")
            _send(wfile, {"op": "hello", "name": "flaky"})
            _recv(rfile)
            sock.close()

        threading.Thread(target=flaky, daemon=True).start()
        exec(open(cfg_file).read(), {"root": root, "Range": Range})
        opt = GeneticsOptimizer(
            model="veles_tpu/znicz/samples/mnist.py", config=root.mnist,
            size=2, generations=1,
            argv=[cfg_file, "--random-seed", "3"], silent=True, env=env,
            rand=RandomGenerator().seed(4), timeout=540,
            scheduler=master)
        best = opt.run()
        assert best["fitness"] > -100.0, best
        assert opt.trials >= 2
        ok = [o for o in outcomes if o["rc"] == 0]
        assert len(ok) == len(outcomes), outcomes  # every trial recovered
        assert {o["worker"] for o in ok} >= {"pool-0", "pool-1"} or \
            len({o["worker"] for o in ok}) >= 2, outcomes
        assert any(o["attempts"] >= 2 for o in ok), \
            "no trial was requeued: %r" % outcomes
    finally:
        fix_config(root)
        if pool is not None:
            pool.close()
        master.close()
