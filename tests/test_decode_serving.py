"""Token-level decode serving: scheduler, KV paging, drain (ISSUE 6).

The contract under test (acceptance):
- the paged decode path generates EXACTLY the tokens the cache-free
  oracle (full forward recompute per token) generates, across mixed
  prompt/output lengths served concurrently;
- one warm executable serves arbitrary admit/retire mixes with zero
  steady-state recompiles, and a warm restart through the persistent
  executable cache + warmup manifest compiles NOTHING;
- retiring and re-admitting sequences never corrupts surviving
  sequences' KV blocks (property test over random admit/retire
  schedules — every sequence's tokens match its solo run);
- graceful drain finishes every submitted sequence, sheds new submits
  with 429 + Retry-After, and leaks neither threads nor KV blocks;
- both scheduler kinds register their metrics through the same
  idempotent declaration path (no double-declared collectors in one
  process).
"""

import json
import threading
import time
import urllib.error
import urllib.request

import numpy
import pytest

from veles_tpu.serving import (BucketScheduler, DecodeMetrics,
                               DecodeScheduler, InferenceServer,
                               KVBlockPool, SchedulerClosed,
                               SchedulerOverflow, ServingMetrics)
from veles_tpu.znicz.samples.flagship import (FlagshipDecodeModel,
                                              generate_reference)

GEOM = dict(max_batch=4, block_size=4, max_prompt_len=8,
            max_new_tokens=8)


@pytest.fixture(scope="module")
def model():
    return FlagshipDecodeModel(stages=2, experts=2, d=16, heads=2,
                               hidden=32, vocab=32, seed=0)


@pytest.fixture(scope="module")
def oracle(model):
    """Memoized cache-free greedy oracle."""
    memo = {}

    def run(prompt, n):
        key = (tuple(prompt), n)
        if key not in memo:
            memo[key] = generate_reference(model.params, prompt, n)
        return memo[key]
    return run


@pytest.fixture(scope="module")
def scheduler(model):
    s = DecodeScheduler(model, name="dectest", **GEOM)
    yield s
    s.close(drain=True)


def _mixed_requests(rng, n):
    return [(rng.randint(0, 32, rng.randint(1, 9)).tolist(),
             int(rng.randint(1, 9))) for _ in range(n)]


def test_generate_matches_cachefree_oracle(scheduler, oracle):
    """Concurrent mixed-length sequences through the paged cache emit
    exactly the oracle's greedy tokens."""
    rng = numpy.random.RandomState(1)
    requests = _mixed_requests(rng, 10)
    futures = [scheduler.submit(p, n) for p, n in requests]
    for (prompt, n), future in zip(requests, futures):
        result = future.result(60)
        assert result["tokens"] == oracle(prompt, n)
        assert result["prompt_tokens"] == len(prompt)
        assert result["ttft_s"] > 0


def test_reference_logits_is_the_oracle_teacher_forced(model, oracle):
    """``reference_logits`` over prompt + oracle tokens: the argmax of
    row ``t`` is the oracle's token after ``tokens[:t + 1]``, and a row
    never sees what follows it — so one padded call checks a whole
    answer (what ``chip_smoke.py`` does on the chip)."""
    from veles_tpu.znicz.samples.flagship import reference_logits
    prompt, n = [5, 1, 30, 7], 6
    tokens = oracle(prompt, n)
    seq = prompt + tokens
    logits = numpy.asarray(reference_logits(model.params, seq))
    assert logits.shape == (len(seq), model.vocab)
    served = logits[len(prompt) - 1:-1].argmax(axis=-1)
    assert served.tolist() == tokens
    padded = numpy.asarray(reference_logits(model.params,
                                            seq + [0, 9, 3]))
    numpy.testing.assert_allclose(padded[:len(seq)], logits, rtol=1e-5,
                                  atol=1e-5)


def test_zero_steady_state_recompiles(scheduler):
    """compiles is flat across waves of ragged traffic — one warm
    executable serves every admit/retire mix."""
    before = scheduler.stats()
    rng = numpy.random.RandomState(2)
    for _ in range(2):
        futures = [scheduler.submit(p, n)
                   for p, n in _mixed_requests(rng, 6)]
        for f in futures:
            f.result(60)
    after = scheduler.stats()
    assert after["compiles"] == before["compiles"]
    assert after["post_warmup_compiles"] == 0
    assert after["executables"] == 1 + len(after["buckets"])


def test_all_blocks_reclaimed(scheduler):
    """After traffic drains, every block is back on the free list."""
    rng = numpy.random.RandomState(3)
    futures = [scheduler.submit(p, n)
               for p, n in _mixed_requests(rng, 8)]
    for f in futures:
        f.result(60)
    deadline = time.time() + 5
    while scheduler.active_sequences and time.time() < deadline:
        time.sleep(0.01)
    stats = scheduler.stats()
    assert stats["free_blocks"] == stats["num_blocks"] - 1
    assert stats["active_sequences"] == 0


def test_admit_retire_never_corrupts_survivors(model, oracle):
    """Property test: under a random admit/retire churn (staggered
    lengths force constant block recycling), every sequence's tokens
    equal its solo run — no sequence ever reads another's KV."""
    s = DecodeScheduler(model, name="churn", max_batch=3, block_size=4,
                        max_prompt_len=8, max_new_tokens=8,
                        num_blocks=10)   # tight pool: heavy recycling
    try:
        rng = numpy.random.RandomState(4)
        requests = _mixed_requests(rng, 24)
        futures = []
        for i, (prompt, n) in enumerate(requests):
            futures.append(s.submit(prompt, n))
            if i % 3 == 0:      # stagger arrivals to vary batch mixes
                time.sleep(0.005)
        for (prompt, n), future in zip(requests, futures):
            assert future.result(60)["tokens"] == oracle(prompt, n)
    finally:
        s.close(drain=True)


def test_kv_block_pool_invariants():
    """Allocator property test: random alloc/free schedules keep the
    free+live partition exact; misuse raises."""
    rng = numpy.random.RandomState(5)
    pool = KVBlockPool(num_blocks=17, block_size=4)
    live = {}
    for step in range(300):
        if live and rng.rand() < 0.45:
            key = rng.choice(list(live))
            pool.free(live.pop(key))
        else:
            blocks = pool.alloc(int(rng.randint(1, 5)))
            if blocks is None:
                assert pool.free_blocks < 4
                continue
            assert 0 not in blocks          # trash never handed out
            flat = [b for bs in live.values() for b in bs]
            assert not set(blocks) & set(flat)   # no double ownership
            live[step] = blocks
        assert pool.free_blocks + pool.live_blocks == pool.capacity
    with pytest.raises(ValueError):
        pool.free([0])
    taken = pool.alloc(1)
    pool.free(taken)
    with pytest.raises(ValueError):
        pool.free(taken)                    # double free


def test_graceful_drain_finishes_inflight_sheds_new(model):
    """server drain: every submitted sequence completes, a submit
    arriving mid-drain gets 429 + Retry-After on the generate route,
    and neither threads nor KV blocks leak."""
    threads_before = {t.name for t in threading.enumerate()}
    srv = InferenceServer({"flag": model}, **GEOM, queue_limit=64)
    sched = srv.registry.get("flag").scheduler
    port = srv.port
    futures = [sched.submit([1 + i % 8] * 4, 8) for i in range(12)]
    stopper = threading.Thread(target=srv.stop, kwargs={"drain": True})
    stopper.start()
    deadline = time.time() + 5
    while not srv.draining and time.time() < deadline:
        time.sleep(0.001)
    # mid-drain submit: the scheduler is closed, the listener is not
    code, headers = None, {}
    try:
        req = urllib.request.Request(
            "http://127.0.0.1:%d/api/flag/generate" % port,
            json.dumps({"prompt": [1, 2], "max_new_tokens": 2}).encode(),
            {"Content-Type": "application/json"})
        resp = urllib.request.urlopen(req, timeout=10)
        code = resp.status
    except urllib.error.HTTPError as e:
        code, headers = e.code, dict(e.headers)
    except OSError:
        code = "conn"   # drain won the race and closed the listener
    if code != "conn":
        assert code == 429
        assert headers.get("Retry-After") == "1"
    stopper.join(30)
    assert not stopper.is_alive()
    for f in futures:                       # admitted AND queued finish
        assert len(f.result(10)["tokens"]) == 8
    with pytest.raises(SchedulerClosed):
        sched.submit([1, 2], 2)
    stats = sched.stats()
    assert stats["free_blocks"] == stats["num_blocks"] - 1
    deadline = time.time() + 5
    while time.time() < deadline:
        leaked = {t.name for t in threading.enumerate()} - threads_before
        leaked = {n for n in leaked
                  if n.startswith(("veles-decode", "veles-serve",
                                   "veles-tpu-serving"))}
        if not leaked:
            break
        time.sleep(0.05)
    assert not leaked, "leaked threads: %r" % leaked


def test_overflow_sheds_429_with_retry_after(model):
    """queue_limit exhausted → SchedulerOverflow inproc, 429 +
    Retry-After over HTTP."""
    srv = InferenceServer({"flag": model}, **GEOM, queue_limit=2)
    try:
        sched = srv.registry.get("flag").scheduler
        futures = []
        with pytest.raises(SchedulerOverflow):
            for _ in range(20):
                futures.append(sched.submit([1, 2, 3], 8))
        code, body = None, None
        for _ in range(10):     # keep the queue full while probing
            try:
                futures.append(sched.submit([1, 2, 3], 8))
            except SchedulerOverflow:
                pass
            try:
                req = urllib.request.Request(
                    "http://127.0.0.1:%d/api/flag/generate" % srv.port,
                    json.dumps({"prompt": [1], "max_new_tokens":
                                8}).encode(),
                    {"Content-Type": "application/json"})
                urllib.request.urlopen(req, timeout=10)
            except urllib.error.HTTPError as e:
                if e.code == 429:
                    code = e.code
                    assert e.headers.get("Retry-After") == "1"
                    body = json.loads(e.read())
                    break
        for f in futures:
            f.result(60)
        assert code == 429 and "error" in body
        assert sched.metrics.rejected >= 1
    finally:
        srv.stop()


def test_http_generate_roundtrip_and_errors(model, oracle):
    srv = InferenceServer({"flag": model}, **GEOM)
    try:
        def post(payload, route="/api/flag/generate"):
            req = urllib.request.Request(
                "http://127.0.0.1:%d%s" % (srv.port, route),
                json.dumps(payload).encode(),
                {"Content-Type": "application/json"})
            return json.loads(urllib.request.urlopen(req).read())

        out = post({"prompt": [3, 1, 4, 1], "max_new_tokens": 5})
        assert out["tokens"] == oracle([3, 1, 4, 1], 5)
        assert out["model"] == "flag" and out["ttft_s"] > 0
        # default max_new_tokens
        out = post({"prompt": [2, 6]})
        assert len(out["tokens"]) == GEOM["max_new_tokens"]

        def err(payload, route="/api/flag/generate"):
            try:
                post(payload, route)
            except urllib.error.HTTPError as e:
                return e.code, json.loads(e.read())
            raise AssertionError("expected an HTTP error")

        assert err({"input": [1]})[0] == 400          # wrong schema
        assert err({"prompt": "xyz"})[0] == 400       # non-tokens
        assert err({"prompt": [1] * 99})[0] == 400    # prompt too long
        assert err({"prompt": [1], "max_new_tokens": 999})[0] == 400
        code, body = err({"prompt": [1]}, "/api/nope/generate")
        assert code == 404 and "models" in body
    finally:
        srv.stop()


def test_generate_route_rejects_non_decode_model(model):
    """A classifier entry answers 400 (not a crash) on /generate."""
    srv = InferenceServer(max_batch=4)
    try:
        srv.registry.add("clf", lambda x: x, sample_shape=(2,))
        req = urllib.request.Request(
            "http://127.0.0.1:%d/api/clf/generate" % srv.port,
            json.dumps({"prompt": [1]}).encode(),
            {"Content-Type": "application/json"})
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(req)
        assert e.value.code == 400
        assert "not a decode model" in json.loads(e.value.read())["error"]
    finally:
        srv.stop()


def test_warm_restart_compiles_nothing(model, tmp_path, oracle):
    """The compile cache + warmup manifest make a restart zero-compile:
    the second scheduler deserializes its whole ladder and generates
    identical tokens."""
    from veles_tpu.compilecache import reset_default_caches
    from veles_tpu.config import root
    prior = root.common.compile_cache.get("dir", None)
    root.common.compile_cache.dir = str(tmp_path / "cache")
    reset_default_caches()
    try:
        s1 = DecodeScheduler(model, name="restart", **GEOM)
        first = s1.stats()
        r1 = s1.generate([5, 4, 3], 6, timeout=60)
        s1.close(drain=True)
        assert first["compiles"] == first["executables"]
        assert first["cache_hits"] == 0
        s2 = DecodeScheduler(model, name="restart", **GEOM)
        warm = s2.stats()
        r2 = s2.generate([5, 4, 3], 6, timeout=60)
        s2.close(drain=True)
        assert warm["compiles"] == 0
        assert warm["cache_hits"] == warm["executables"]
        assert r1["tokens"] == r2["tokens"] == oracle([5, 4, 3], 6)
        # the manifest learned the decode + prefill entries
        from veles_tpu.compilecache import default_cache
        manifest = default_cache().manifest
        assert manifest.buckets("restart@decode") == [GEOM["max_batch"]]
        assert manifest.buckets("restart@prefill")
    finally:
        root.common.compile_cache.dir = prior
        reset_default_caches()


def test_metrics_declaration_idempotent_across_scheduler_kinds():
    """Satellite: both scheduler kinds (and repeated same-name
    instances) declare through the shared idempotent path — one
    registry family each, no redeclaration conflict, baselines keep
    per-instance snapshots scoped."""
    from veles_tpu.observability.registry import REGISTRY
    m1 = ServingMetrics("dual")
    d1 = DecodeMetrics("dual")
    d1.record_step(2, 4, 0.001)
    snap_before = d1.snapshot()
    # same names again (hot swap): must reuse, not raise
    m2 = ServingMetrics("dual")
    d2 = DecodeMetrics("dual")
    assert d2.snapshot()["steps"] == 0          # baseline-scoped
    assert snap_before["steps"] == 1
    d2.record_step(1, 4, 0.002)
    assert d1.snapshot()["steps"] == 2          # same global series
    text = REGISTRY.render_prometheus()
    assert text.count("# TYPE veles_serving_decode_steps_total") == 1
    assert text.count("# TYPE veles_serving_requests_total") == 1
    assert m1 is not m2


def test_validation_errors(scheduler):
    with pytest.raises(ValueError):
        scheduler.submit([], 2)                     # empty prompt
    with pytest.raises(ValueError):
        scheduler.submit([1] * 99, 2)               # too long
    with pytest.raises(ValueError):
        scheduler.submit([1, 2], 0)                 # no tokens asked
    with pytest.raises(ValueError):
        scheduler.submit([1, 2], 999)               # too many
    with pytest.raises(ValueError):
        scheduler.submit([[1], [2]], 2)             # not 1-D
    with pytest.raises(ValueError):
        scheduler.submit([1.5, 2.25], 2)            # not integers
    with pytest.raises(ValueError):
        scheduler.submit([1, 77], 2)                # out of vocab
