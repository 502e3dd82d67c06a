"""veles_tpu.fleet: router, replica lifecycle, rolling updates.

The contract under test (ISSUE 7 acceptance, fast variants):
- a 3-replica fleet sustains ≥ 2.4x the single-replica closed-loop
  req/s on a device-time-bound model (per-row ``sleep:`` stand-in —
  on a single-core CI host CPU-bound work cannot scale across
  processes by construction, see fleet/replica.py);
- SIGKILL of a replica mid-load yields ZERO failed (non-429)
  responses: the router retries the in-flight idempotent request
  exactly once on another replica, and the supervisor respawns the
  victim warm (``compiles == 0`` off the shared executable cache);
- a rolling model update completes with zero downtime — every replica
  flips to the new version while the open load keeps answering 200;
- the router merges ``/metrics`` ``/healthz`` ``/readyz`` ``/models``
  and one trace id links router → replica request → ``serving.batch``;
- the shared RestartBackoff policy walks base·factor^n with bounded
  jitter, honors the max-restart budget, and resets its exponent (not
  the budget) after a healthy run.
"""

import glob
import json
import os
import signal
import threading
import time
import urllib.error
import urllib.request

import pytest

from veles_tpu.distributed import RestartBackoff
from veles_tpu.fleet import Fleet


# -- RestartBackoff (shared respawn policy) -----------------------------------

def test_supervisor_refuses_replicas_that_would_share_the_tpu():
    """N replicas, one chip, no chip assignment (ROADMAP R6): start()
    says so before it spawns anything, instead of N children hanging
    on a device the first one holds."""
    from veles_tpu.fleet.supervisor import ReplicaSupervisor
    sup = ReplicaSupervisor({"lat": "sleep:0.001"}, replicas=2,
                            env=dict(os.environ, JAX_PLATFORMS="tpu"))
    with pytest.raises(RuntimeError, match="more children than chips"):
        sup.start()
    assert all(h.proc is None for h in sup._replicas.values())


def test_restart_backoff_schedule_deterministic():
    """base·factor^streak, capped, budget-bounded — rng pinned to the
    midpoint so jitter contributes exactly nothing."""
    policy = RestartBackoff(base=1.0, factor=2.0, cap=10.0, jitter=0.5,
                            max_restarts=6, rng=lambda: 0.5)
    delays = [policy.next_delay() for _ in range(7)]
    assert delays == [1.0, 2.0, 4.0, 8.0, 10.0, 10.0, None]
    assert policy.exhausted and policy.restarts == 6


def test_restart_backoff_jitter_bounds():
    lo = RestartBackoff(base=4.0, jitter=0.25, rng=lambda: 0.0)
    hi = RestartBackoff(base=4.0, jitter=0.25, rng=lambda: 1.0)
    assert lo.next_delay() == pytest.approx(3.0)   # 4 * (1 - 0.25)
    assert hi.next_delay() == pytest.approx(5.0)   # 4 * (1 + 0.25)


def test_restart_backoff_healthy_uptime_resets_streak_not_budget():
    policy = RestartBackoff(base=1.0, factor=2.0, cap=60.0, jitter=0.0,
                            max_restarts=4, reset_after=30.0)
    assert policy.next_delay() == 1.0
    assert policy.next_delay() == 2.0
    policy.note_uptime(31.0)               # healthy run: exponent resets
    assert policy.next_delay() == 1.0
    policy.note_uptime(5.0)                # crash-loop: keeps climbing
    assert policy.next_delay() == 2.0
    assert policy.next_delay() is None     # ...but the budget stands


# -- fleet fixtures -----------------------------------------------------------

ROW_SLEEP = 0.02      # device-time-per-row of the stand-in model


@pytest.fixture(scope="module")
def sleep_fleet(tmp_path_factory):
    """3 replicas of the per-row-sleep model; replicas trace into a
    shared VELES_TRACE_DIR (the merged-trace acceptance check)."""
    trace_dir = str(tmp_path_factory.mktemp("fleet_trace"))
    fleet = Fleet({"m": "sleep:%s:4" % ROW_SLEEP}, replicas=3,
                  max_batch=4, queue_limit=256, poll_interval=0.1,
                  env=dict(os.environ, VELES_TRACE_DIR=trace_dir),
                  backoff={"base": 0.1, "factor": 2.0, "cap": 2.0,
                           "max_restarts": 10})
    fleet.start(ready_timeout=120)
    fleet.trace_dir = trace_dir
    yield fleet
    fleet.stop()


def _post(url, payload, headers=None, timeout=60):
    req = urllib.request.Request(
        url, json.dumps(payload).encode(),
        {"Content-Type": "application/json", **(headers or {})})
    try:
        resp = urllib.request.urlopen(req, timeout=timeout)
        return resp.status, json.loads(resp.read()), dict(resp.headers)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"{}"), dict(e.headers)


def _get(url, timeout=10):
    try:
        resp = urllib.request.urlopen(url, timeout=timeout)
        return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"{}")


def _closed_loop(url, clients, seconds, route="/api/m", rows=1, dim=4):
    """N client threads posting back to back; returns status-class
    counts — the zero-downtime checks need 429 split from real
    failures."""
    counts = {"ok": 0, "shed": 0, "failed": 0}
    lock = threading.Lock()
    payload = {"input": [[0.5] * dim] * rows}
    stop = time.perf_counter() + seconds

    def client():
        while time.perf_counter() < stop:
            try:
                status, _, _ = _post(url + route, payload)
            except Exception:
                status = -1
            with lock:
                if status == 200:
                    counts["ok"] += 1
                elif status == 429:
                    counts["shed"] += 1
                else:
                    counts["failed"] += 1
    threads = [threading.Thread(target=client) for _ in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return counts


def _wait_ready_replicas(fleet, n, timeout=60):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if fleet.router.ready_count() >= n:
            return
        time.sleep(0.05)
    raise AssertionError("only %d/%d replicas ready after %.0fs: %r"
                         % (fleet.router.ready_count(), n, timeout,
                            fleet.supervisor.describe()))


# -- scaling ------------------------------------------------------------------

def test_fleet_scaling_efficiency(sleep_fleet):
    """3 admitted replicas sustain >= 2.4x ONE admitted replica on the
    same router/processes (ISSUE 7 acceptance a).  The model is
    device-time-bound (20 ms/row), so the ratio measures the router's
    least-loaded spreading, not host CPU."""
    rids = sleep_fleet.router.replica_ids()
    assert len(rids) == 3
    try:
        for rid in rids[1:]:
            sleep_fleet.router.set_admitting(rid, False)
        _closed_loop(sleep_fleet.url, 2, 0.2)              # warm
        single = _closed_loop(sleep_fleet.url, 9, 1.0)
        for rid in rids:
            sleep_fleet.router.set_admitting(rid, True)
        _closed_loop(sleep_fleet.url, 2, 0.2)
        full = _closed_loop(sleep_fleet.url, 9, 1.0)
    finally:
        for rid in rids:
            sleep_fleet.router.set_admitting(rid, True)
    assert single["failed"] == full["failed"] == 0
    assert single["ok"] > 0
    speedup = full["ok"] / single["ok"]
    assert speedup >= 2.4, \
        "fleet %d vs single %d req (%.2fx < 2.4x)" % (
            full["ok"], single["ok"], speedup)
    # the spread itself: every replica took real traffic
    met = sleep_fleet.router.merged_metrics()
    dispatched = {rid: met["router"]["replicas"][rid]["dispatched"]
                  for rid in rids}
    assert all(n > 0 for n in dispatched.values()), dispatched


# -- merged control plane ------------------------------------------------------

def test_fleet_merged_endpoints(sleep_fleet):
    status, health = _get(sleep_fleet.url + "/healthz")
    assert status == 200 and health["ready_replicas"] == 3
    assert set(health["replicas"]) == {"r0", "r1", "r2"}
    for rep in health["replicas"].values():
        assert rep["up"] and rep["ready"] and rep["admitting"]

    status, ready = _get(sleep_fleet.url + "/readyz")
    assert status == 200 and ready["ready"]

    status, models = _get(sleep_fleet.url + "/models")
    assert status == 200
    assert set(models["models"]) == {"m"}
    assert set(models["models"]["m"]) == {"r0", "r1", "r2"}

    status, met = _get(sleep_fleet.url + "/metrics")
    assert status == 200
    for rid in ("r0", "r1", "r2"):
        router_view = met["router"]["replicas"][rid]
        # per-replica up/ready + dispatch and retry counts (ISSUE 7)
        assert router_view["up"] is True
        assert router_view["ready"] is True
        assert isinstance(router_view["dispatched"], int)
        assert isinstance(router_view["retries"], int)
        # ...and the replica's OWN serving metrics merged alongside
        assert "m" in met["replicas"][rid]
    # the same signals as process-global registry series
    from veles_tpu.observability.registry import REGISTRY
    up = REGISTRY.gauge("veles_fleet_replica_up", labels=("replica",))
    assert {key[0] for key in up.children()} >= {"r0", "r1", "r2"}


def test_supervisor_restart_budget_in_metrics(sleep_fleet):
    """The supervisor's restart budget and crash-looper state ride the
    one merged /metrics payload operators already poll — no separate
    endpoint to discover after a replica starts flapping."""
    status, met = _get(sleep_fleet.url + "/metrics")
    assert status == 200
    sup = met["supervisor"]
    assert set(sup) == {"r0", "r1", "r2"}
    for rid, view in sup.items():
        assert view["state"] == "up", (rid, view)
        assert view["failed"] is False
        # backoff policy: max_restarts=10, so the remaining budget is
        # 10 minus whatever earlier tests in this module burned
        assert 0 <= view["restarts_remaining"] <= 10
        assert view["restarts_remaining"] == 10 - view["restarts"]
        assert view["crash_streak"] >= 0
    # describe() is the same source of truth, router-wiring aside
    desc = sleep_fleet.supervisor.describe()
    for rid, view in desc.items():
        assert view["restarts_remaining"] == sup[rid]["restarts_remaining"]


def test_fleet_trace_one_id_router_to_batch(sleep_fleet, tmp_path):
    """One trace id spans router -> replica request -> serving.batch
    (the replicas trace via VELES_TRACE_DIR; the in-process router via
    the config switch)."""
    from veles_tpu.config import root
    from veles_tpu.logger import events
    router_file = os.path.join(sleep_fleet.trace_dir,
                               "events-router.jsonl")
    events.reset()
    root.common.trace.enabled = True
    root.common.trace.file = router_file
    trace_id = "feedfacefeedface"
    try:
        status, _, headers = _post(sleep_fleet.url + "/api/m",
                                   {"input": [[1, 2, 3, 4]]},
                                   headers={"X-Trace-Id": trace_id})
        assert status == 200
        assert headers.get("X-Trace-Id") == trace_id
    finally:
        root.common.trace.enabled = False
        root.common.trace.file = None
        events.reset()

    from tools.merge_traces import merge
    paths = glob.glob(os.path.join(sleep_fleet.trace_dir, "events-*"))
    merged = merge(paths)["traceEvents"]
    ours = [e for e in merged
            if (e.get("args") or {}).get("trace_id") == trace_id]
    by_name = {}
    for e in ours:
        by_name.setdefault(e["name"], []).append(e)
    assert "fleet.route" in by_name, sorted(by_name)
    assert "serving.request" in by_name, sorted(by_name)
    # the batch span links back to the request span of the same trace
    request_spans = {(e["args"].get("span"))
                     for e in by_name["serving.request"]}
    batches = [e for e in merged if e["name"] == "serving.batch"
               and set((e.get("args") or {}).get("links") or ())
               & request_spans]
    assert batches, "no serving.batch linked to the traced request"
    # router and replica recorded from DIFFERENT processes
    assert {e["pid"] for e in by_name["fleet.route"]} != \
        {e["pid"] for e in by_name["serving.request"]}


# -- rolling update -----------------------------------------------------------

def test_fleet_rolling_update_zero_downtime(sleep_fleet):
    """Version rollout under load: zero failed (non-429) responses and
    every replica reports the new version (ISSUE 7 acceptance c)."""
    counts = {}

    def load():
        counts.update(_closed_loop(sleep_fleet.url, 6, 2.0))
    loader = threading.Thread(target=load)
    loader.start()
    time.sleep(0.3)
    result = sleep_fleet.rolling_update(
        "m", "sleep:0.01:4", version="v2")
    loader.join()
    assert result["updated"] == ["r0", "r1", "r2"]
    assert counts["failed"] == 0, counts
    assert counts["shed"] == 0, counts
    assert counts["ok"] > 0
    _, models = _get(sleep_fleet.url + "/models")
    versions = {rid: view["version"]
                for rid, view in models["models"]["m"].items()}
    assert versions == {"r0": "v2", "r1": "v2", "r2": "v2"}


# -- failover -----------------------------------------------------------------

def test_fleet_inflight_retry_on_sigkill(sleep_fleet):
    """A request IN FLIGHT on a SIGKILLed replica is answered 200 via
    the exactly-once retry on another replica; the victim respawns."""
    router = sleep_fleet.router
    rids = router.replica_ids()
    victim = rids[0]
    before = int(router._c_retry.labels(replica=victim).value)
    result = {}
    try:
        for rid in rids[1:]:
            router.set_admitting(rid, False)   # pin dispatch to victim

        def fire():
            # 40 rows x 20 ms/row ≈ 0.8 s on the victim
            result.update(dict(zip(
                ("status", "body", "headers"),
                _post(sleep_fleet.url + "/api/m",
                      {"input": [[1, 2, 3, 4]]* 40}))))
        t = threading.Thread(target=fire)
        t.start()
        time.sleep(0.25)                       # in flight on victim
        for rid in rids[1:]:
            router.set_admitting(rid, True)    # retry destinations
        sleep_fleet.supervisor.kill(victim, signal.SIGKILL)
        t.join(30)
    finally:
        for rid in rids:
            router.set_admitting(rid, True)
    assert result.get("status") == 200, result
    assert int(router._c_retry.labels(replica=victim).value) == \
        before + 1
    _wait_ready_replicas(sleep_fleet, 3, timeout=60)
    assert sleep_fleet.supervisor.describe()[victim]["restarts"] >= 1


def test_fleet_sigkill_zero_failures_and_warm_respawn(tmp_path_factory):
    """The full ISSUE 7 acceptance (b) on a REAL exported package: a
    2-replica fleet over a shared compile cache, SIGKILL one replica
    under load — zero non-429 failures, and the respawned replica goes
    ready with compiles == 0 (warm manifest + executable cache)."""
    import tempfile
    from tools.serve_bench import build_mnist_package
    tmp = tmp_path_factory.mktemp("fleet_mnist")
    package = build_mnist_package(str(tmp / "mnist_pkg.zip"))
    fleet = Fleet({"mnist": package}, replicas=2, max_batch=4,
                  cache_dir=str(tmp / "compile_cache"),
                  poll_interval=0.1,
                  backoff={"base": 0.1, "factor": 2.0, "cap": 2.0,
                           "max_restarts": 10})
    fleet.start(ready_timeout=240)
    counts = {}
    try:
        victim = fleet.router.replica_ids()[-1]

        def load():
            counts.update(_closed_loop(fleet.url, 4, 2.5,
                                       route="/api/mnist", rows=2,
                                       dim=784))
        loader = threading.Thread(target=load)
        loader.start()
        time.sleep(0.6)
        fleet.supervisor.kill(victim, signal.SIGKILL)
        loader.join()
        assert counts["failed"] == 0, counts
        assert counts["ok"] > 0, counts
        _wait_ready_replicas(fleet, 2, timeout=120)
        met = fleet.router.merged_metrics()
        respawned = met["replicas"][victim]["mnist"]
        # the warm-spawn guarantee: the respawn deserialized its whole
        # bucket ladder off the shared cache — zero fresh XLA compiles
        assert respawned["compiles"] == 0, respawned
        assert respawned["cache_hits"] >= 1, respawned
        assert fleet.supervisor.describe()[victim]["restarts"] >= 1
    finally:
        fleet.stop()
