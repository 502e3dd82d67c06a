"""Serving load generator: req/s + tail latency for the inference path.

Measures, on the SAME exported MNIST package and the SAME closed-loop
load shape (N concurrent clients, mixed request batch sizes):

- ``per_request_rps`` — the seed ``RESTfulAPI`` per-request path,
  preserved here as the baseline: one ``PackageLoader.run`` (= one
  ``jax.export`` call-wrapper rebuild + dispatch) per request, exactly
  what restful_api.py did before the serving subsystem existed;
- ``serve_rps`` — the bucketed dynamic-batching scheduler
  (:class:`veles_tpu.serving.BucketScheduler`): warm AOT executables,
  power-of-two padding, continuous batching.  The ratio is
  ``serve_speedup_vs_per_request``;
- ``serve_http_rps`` — the full :class:`InferenceServer` end to end
  over HTTP/1.1 keep-alive (reported for context; on a small host this
  measures the JSON+HTTP stack more than the scheduler);
- open-loop mode (``--sustained``) — paced arrivals at
  ``--offered-rps``, recording achieved rate, tail latency and shed
  (429/overflow) counts, the way serving SLOs are actually stated.

Emits ONE JSON line:
    {"metric": "serve_rps", "value": N, "unit": "req/s", ...}

Smoke mode (``--smoke``) keeps everything under ~10 s so it can ride in
the tier-1 suite; the sustained variant is the ``slow``-marked load
test.  No training happens here — the model is an initialized (or
``--package``-provided) MNIST FC net; throughput does not care about
weight quality.
"""

import argparse
import http.client
import json
import os
import socket
import sys
import tempfile
import threading
import time

import numpy

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from veles_tpu.backends import cache_dir as fixed_cache_dir  # noqa: E402

DEFAULT_SIZES = (1, 2, 3, 5, 8)


def build_mnist_package(path):
    """Initialize (not train) the MNIST FC sample and export it."""
    from veles_tpu.backends import Device
    from veles_tpu.export import export_model
    from veles_tpu.prng import RandomGenerator
    from veles_tpu.znicz.samples import mnist
    wf = mnist.create_workflow(
        loader={"minibatch_size": 100, "n_train": 400, "n_valid": 100,
                "prng": RandomGenerator().seed(3)},
        decision={"max_epochs": 1, "silent": True})
    wf.initialize(device=Device(backend="auto"))
    export_model(wf, path)
    return path


def _closed_loop(target, clients, seconds, sizes, sample_shape):
    """N threads calling ``target(x)`` back to back; returns
    (count, elapsed, latencies, errors)."""
    xs = {bs: numpy.random.RandomState(bs).uniform(
        -1, 1, (bs,) + tuple(sample_shape)).astype(numpy.float32)
        for bs in sizes}
    latencies = [[] for _ in range(clients)]
    errors = [0] * clients
    counts = [0] * clients
    start = time.perf_counter()
    stop = start + seconds
    def client(i):
        j = i
        while time.perf_counter() < stop:
            x = xs[sizes[j % len(sizes)]]
            t0 = time.perf_counter()
            try:
                target(x)
            except Exception:
                errors[i] += 1
            else:
                counts[i] += 1
                latencies[i].append(time.perf_counter() - t0)
            j += 1
    threads = [threading.Thread(target=client, args=(i,))
               for i in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    elapsed = time.perf_counter() - start
    flat = [lat for per in latencies for lat in per]
    return sum(counts), elapsed, flat, sum(errors)


def _measure_interleaved(targets, clients, seconds, sizes, sample_shape,
                         round_s=0.5):
    """Alternate short closed-loop windows across ``targets`` (a dict of
    name → callable) so slow drifts in background machine load hit every
    path equally — the RATIO between paths is the published number, and
    interleaving is what makes it stable on a shared box.  Returns
    {name: {"rps", "latencies", "errors"}}."""
    rounds = max(1, int(round(seconds / round_s)))
    acc = {name: {"n": 0, "t": 0.0, "latencies": [], "errors": 0}
           for name in targets}
    for _ in range(rounds):
        for name, target in targets.items():
            n, t, lat, err = _closed_loop(
                target, clients, seconds / rounds, sizes, sample_shape)
            a = acc[name]
            a["n"] += n
            a["t"] += t
            a["latencies"].extend(lat)
            a["errors"] += err
    for a in acc.values():
        a["rps"] = a["n"] / a["t"] if a["t"] else 0.0
    return acc


def _open_loop(submit, offered_rps, seconds, sizes, sample_shape):
    """Paced arrivals at ``offered_rps``; returns
    (achieved_rps, latencies, shed)."""
    from veles_tpu.serving import SchedulerOverflow
    xs = {bs: numpy.random.RandomState(bs).uniform(
        -1, 1, (bs,) + tuple(sample_shape)).astype(numpy.float32)
        for bs in sizes}
    latencies, shed, done = [], [0], [0]
    lock = threading.Lock()
    interval = 1.0 / offered_rps
    threads = []
    start = time.perf_counter()
    n_arrivals = int(offered_rps * seconds)
    def fire(x):
        t0 = time.perf_counter()
        try:
            submit(x)
        except SchedulerOverflow:
            with lock:
                shed[0] += 1
        except Exception:
            with lock:
                shed[0] += 1
        else:
            with lock:
                done[0] += 1
                latencies.append(time.perf_counter() - t0)
    for k in range(n_arrivals):
        due = start + k * interval
        delay = due - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        t = threading.Thread(
            target=fire, args=(xs[sizes[k % len(sizes)]],))
        t.start()
        threads.append(t)
    for t in threads:
        t.join()
    elapsed = time.perf_counter() - start
    return done[0] / elapsed, latencies, shed[0]


def _quantiles_ms(latencies):
    if not latencies:
        return {}
    ordered = sorted(latencies)
    pick = lambda q: ordered[min(len(ordered) - 1,  # noqa: E731
                                 int(q * len(ordered)))]
    return {"p50_ms": round(pick(0.50) * 1e3, 3),
            "p95_ms": round(pick(0.95) * 1e3, 3),
            "p99_ms": round(pick(0.99) * 1e3, 3)}


def _http_closed_loop(port, clients, seconds, sizes, sample_shape,
                      route="/api"):
    """Closed loop over persistent HTTP/1.1 connections."""
    bodies = {bs: json.dumps({"input": numpy.random.RandomState(bs).uniform(
        -1, 1, (bs,) + tuple(sample_shape)).round(4).tolist()}).encode()
        for bs in sizes}
    def mkconn():
        conn = http.client.HTTPConnection("127.0.0.1", port)
        conn.connect()
        conn.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        return conn
    def make_target():
        state = {"conn": mkconn()}
        def post(body):
            try:
                state["conn"].request(
                    "POST", route, body,
                    {"Content-Type": "application/json"})
                resp = state["conn"].getresponse()
                resp.read()
                if resp.status != 200:
                    raise RuntimeError("HTTP %d" % resp.status)
            except (http.client.HTTPException, ConnectionError, OSError):
                state["conn"].close()
                state["conn"] = mkconn()
                raise
        return post
    # each client thread owns one connection: route through a
    # thread-local-ish trick — target receives the prebuilt body
    locals_ = [make_target() for _ in range(clients)]
    latencies = [[] for _ in range(clients)]
    counts = [0] * clients
    errors = [0] * clients
    start = time.perf_counter()
    stop = start + seconds
    def client(i):
        post = locals_[i]
        j = i
        while time.perf_counter() < stop:
            body = bodies[sizes[j % len(sizes)]]
            t0 = time.perf_counter()
            try:
                post(body)
            except Exception:
                errors[i] += 1
            else:
                counts[i] += 1
                latencies[i].append(time.perf_counter() - t0)
            j += 1
    threads = [threading.Thread(target=client, args=(i,))
               for i in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    elapsed = time.perf_counter() - start
    flat = [lat for per in latencies for lat in per]
    return sum(counts) / elapsed, flat, sum(errors)


def run_bench(package=None, clients=8, seconds=2.0, sizes=DEFAULT_SIZES,
              max_batch=64, transport="both", offered_rps=None,
              open_seconds=None, keep_package=False):
    """Run the comparison; returns the result dict (see module doc)."""
    from veles_tpu.export.loader import PackageLoader
    from veles_tpu.serving import BucketScheduler

    tmp = None
    if package is None:
        tmp = tempfile.mkdtemp(prefix="serve_bench_")
        package = build_mnist_package(os.path.join(tmp, "mnist_pkg.zip"))
    loader = PackageLoader(package)
    sample_shape = tuple(loader.model_metadata["input"]["sample_shape"])

    out = {"clients": clients, "seconds": seconds,
           "batch_sizes": list(sizes), "max_batch": max_batch,
           "package": os.path.basename(package)}

    # -- closed loop: seed per-request path vs the bucketed scheduler --------
    # the baseline IS the seed RESTfulAPI dispatch (restful_api.py at
    # the seed): one PackageLoader.run per request; the serving path is
    # the scheduler's request interface (submit → batched executable)
    seed_infer = lambda x: numpy.asarray(loader.run(x))  # noqa: E731
    seed_infer(numpy.zeros((1,) + sample_shape, numpy.float32))  # warm
    # time-to-first-response: scheduler construction (bucket-ladder
    # warmup — compiles, or deserializes off a warm executable cache)
    # through the first answered request (tools/cold_start.py measures
    # the same path across fresh processes)
    t0 = time.perf_counter()
    scheduler = BucketScheduler(loader, max_batch=max_batch,
                                queue_limit=max(4 * clients, 64),
                                name="serve_bench")
    scheduler.infer(numpy.zeros((1,) + sample_shape, numpy.float32))
    out["serve_time_to_first_response_s"] = round(
        time.perf_counter() - t0, 4)
    assert max(sizes) <= max_batch, "request sizes must fit max_batch"
    sched_infer = lambda x: scheduler.submit(x).result()  # noqa: E731
    try:
        _closed_loop(seed_infer, 2, 0.15, sizes, sample_shape)
        _closed_loop(sched_infer, 2, 0.15, sizes, sample_shape)
        measured = _measure_interleaved(
            {"per_request": seed_infer, "serve": sched_infer},
            clients, seconds, sizes, sample_shape)
        base, serve = measured["per_request"], measured["serve"]
        out["per_request_rps"] = round(base["rps"], 1)
        out["per_request_errors"] = base["errors"]
        out.update({"per_request_" + k: v
                    for k, v in _quantiles_ms(base["latencies"]).items()})
        stats = scheduler.stats()
        out["serve_rps"] = round(serve["rps"], 1)
        out["serve_errors"] = serve["errors"]
        out.update({"serve_" + k: v
                    for k, v in _quantiles_ms(serve["latencies"]).items()})
        out["serve_speedup_vs_per_request"] = round(
            serve["rps"] / base["rps"], 2) if base["rps"] else None
        out["compiles"] = stats["compiles"]
        out["warmup_compiles"] = stats["warmup_compiles"]
        out["post_warmup_compiles"] = stats["post_warmup_compiles"]
        out["jit_cache_size"] = stats["jit_cache_size"]
        out["buckets"] = stats["buckets"]
        snap = scheduler.metrics.snapshot()
        out["batch_fill"] = snap["batch_fill"]
        out["rows_per_batch"] = snap["rows_per_batch"]

        if offered_rps:
            achieved, open_lat, shed = _open_loop(
                scheduler.infer, offered_rps,
                open_seconds or seconds, sizes, sample_shape)
            out["offered_rps"] = offered_rps
            out["serve_open_rps"] = round(achieved, 1)
            out["serve_open_shed"] = shed
            out.update({"serve_open_" + k: v
                        for k, v in _quantiles_ms(open_lat).items()})
    finally:
        scheduler.close(drain=True)

    # -- end-to-end HTTP -----------------------------------------------------
    if transport in ("http", "both"):
        from veles_tpu.serving import InferenceServer
        server = InferenceServer({"mnist": package},
                                 max_batch=max_batch,
                                 queue_limit=max(4 * clients, 64))
        try:
            _http_closed_loop(server.port, 2, min(0.3, seconds), sizes,
                              sample_shape)
            http_rps, http_lat, http_err = _http_closed_loop(
                server.port, clients, seconds, sizes, sample_shape)
            out["serve_http_rps"] = round(http_rps, 1)
            out["serve_http_errors"] = http_err
            out.update({"serve_http_" + k: v
                        for k, v in _quantiles_ms(http_lat).items()})
        finally:
            server.stop()

    if tmp and not keep_package:
        try:
            os.unlink(package)
            os.rmdir(tmp)
        except OSError:
            pass
    return out


# -- decode load mode ---------------------------------------------------------
#
# The token-level counterpart of the request benchmark above (ISSUE 6):
# the SAME mixed prompt/output-length traffic is served twice by the
# SAME DecodeScheduler (same executables, same KV pools) under two load
# patterns —
#
# - ``continuous``: every request submitted up front; the scheduler
#   admits a new sequence the moment a row frees (token-level
#   continuous batching);
# - ``static``: requests submitted in gangs of max_batch, the next gang
#   only after the whole gang finishes — exactly the request-
#   granularity bucket policy, where every early-finishing row idles
#   until the gang's straggler completes.
#
# The tok/s ratio between them isolates the SCHEDULING policy: kernels,
# pools and compilation are shared, so nothing else differs.  An
# optional paced open-loop window (--offered-rps) reports achieved
# tok/s, shed count and tail latency the way decode SLOs are stated.


def _decode_requests(n, max_prompt_len, max_new_tokens, vocab, seed=7):
    """The mixed-length request set: prompt/output lengths uniform over
    the full supported range (the raggedness the scheduler must absorb)."""
    rng = numpy.random.RandomState(seed)
    return [(rng.randint(0, vocab, rng.randint(
        1, max_prompt_len + 1)).tolist(),
        int(rng.randint(1, max_new_tokens + 1)))
        for _ in range(n)]


def _run_continuous(scheduler, requests):
    t0 = time.perf_counter()
    futures = [scheduler.submit(p, n) for p, n in requests]
    results = [f.result(120) for f in futures]
    elapsed = time.perf_counter() - t0
    tokens = sum(len(r["tokens"]) for r in results)
    return tokens, elapsed, results


def _run_static(scheduler, requests, gang):
    """Request-granularity gangs: admit ``gang`` sequences, wait for
    ALL of them before admitting the next gang."""
    t0 = time.perf_counter()
    tokens = 0
    for i in range(0, len(requests), gang):
        futures = [scheduler.submit(p, n)
                   for p, n in requests[i:i + gang]]
        tokens += sum(len(f.result(120)["tokens"]) for f in futures)
    return tokens, time.perf_counter() - t0


def run_decode_bench(seconds=2.0, n_requests=None, max_batch=8,
                     block_size=8, max_prompt_len=16, max_new_tokens=16,
                     offered_rps=None, rounds=2, cache_dir=None):
    """Continuous vs static decode throughput on the flagship
    transformer; returns the result dict (keys ride into the bench
    JSON like the request path's ``serve_rps``)."""
    from veles_tpu.serving import DecodeScheduler, SchedulerOverflow
    from veles_tpu.znicz.samples.flagship import FlagshipDecodeModel

    if cache_dir:
        from veles_tpu.config import root
        root.common.compile_cache.dir = cache_dir
    model = FlagshipDecodeModel(stages=2, experts=2, d=32, heads=2,
                                hidden=64, vocab=128, seed=0)
    t0 = time.perf_counter()
    scheduler = DecodeScheduler(
        model, max_batch=max_batch, block_size=block_size,
        max_prompt_len=max_prompt_len, max_new_tokens=max_new_tokens,
        queue_limit=4096, name="decode_bench")
    warmup_s = time.perf_counter() - t0
    if n_requests is None:
        # sized so one continuous window runs ~`seconds` (rough CPU
        # budget); static rounds reuse the same set
        n_requests = max(4 * max_batch, int(16 * seconds))
    requests = _decode_requests(n_requests, max_prompt_len,
                                max_new_tokens, model.vocab)
    out = {"decode_requests": n_requests, "decode_max_batch": max_batch,
           "decode_block_size": block_size,
           "decode_max_prompt_len": max_prompt_len,
           "decode_max_new_tokens": max_new_tokens,
           "decode_warmup_s": round(warmup_s, 4)}
    try:
        # warm both load patterns untimed (first D2H, allocator paths)
        _run_continuous(scheduler, requests[:max_batch])
        _run_static(scheduler, requests[:max_batch], max_batch)
        warm_stats = scheduler.stats()
        cont = {"tokens": 0, "t": 0.0}
        stat = {"tokens": 0, "t": 0.0}
        results = None
        for _ in range(max(1, rounds)):    # interleaved: drift cancels
            tok, dt, results = _run_continuous(scheduler, requests)
            cont["tokens"] += tok
            cont["t"] += dt
            tok, dt = _run_static(scheduler, requests, max_batch)
            stat["tokens"] += tok
            stat["t"] += dt
        out["decode_tok_s"] = round(cont["tokens"] / cont["t"], 1)
        out["decode_static_tok_s"] = round(stat["tokens"] / stat["t"],
                                           1)
        out["decode_vs_static_speedup"] = round(
            out["decode_tok_s"] / out["decode_static_tok_s"], 2)
        ttft = sorted(r["ttft_s"] for r in results)
        pick = lambda q: ttft[min(len(ttft) - 1,  # noqa: E731
                                  int(q * len(ttft)))]
        out["decode_ttft_p50_ms"] = round(pick(0.50) * 1e3, 3)
        out["decode_ttft_p99_ms"] = round(pick(0.99) * 1e3, 3)
        snap = scheduler.metrics.snapshot()
        for q in ("p50_ms", "p95_ms", "p99_ms"):
            out["decode_token_%s" % q] = snap["step_latency"][q]
        out["decode_row_fill"] = snap["row_fill"]
        stats = scheduler.stats()
        out["decode_compiles"] = stats["compiles"]
        out["decode_cache_hits"] = stats["cache_hits"]
        out["decode_post_warmup_compiles"] = (
            stats["compiles"] - warm_stats["compiles"])
        out["decode_free_blocks"] = stats["free_blocks"]

        if offered_rps:
            # paced open loop: arrivals at offered_rps requests/s
            shed = done_tokens = 0
            futures = []
            start = time.perf_counter()
            n_arrivals = max(1, int(offered_rps * seconds))
            for k in range(n_arrivals):
                due = start + k / offered_rps
                delay = due - time.perf_counter()
                if delay > 0:
                    time.sleep(delay)
                prompt, n = requests[k % len(requests)]
                try:
                    futures.append(scheduler.submit(prompt, n))
                except SchedulerOverflow:
                    shed += 1
            for f in futures:
                done_tokens += len(f.result(120)["tokens"])
            elapsed = time.perf_counter() - start
            out["decode_open_offered_rps"] = offered_rps
            out["decode_open_tok_s"] = round(done_tokens / elapsed, 1)
            out["decode_open_shed"] = shed
    finally:
        scheduler.close(drain=True)
    return out


# -- flight-recorder overhead mode --------------------------------------------


def _run_traced(scheduler, requests):
    """``_run_continuous`` with one fresh trace context per request, so
    every submission opens its own flight timeline (the bench drives
    the scheduler directly — there is no HTTP layer minting
    ``X-Trace-Id`` here)."""
    from veles_tpu.observability import trace as _trace
    t0 = time.perf_counter()
    futures = []
    for p, n in requests:
        with _trace.span_context():
            futures.append(scheduler.submit(p, n))
    results = [f.result(120) for f in futures]
    elapsed = time.perf_counter() - t0
    tokens = sum(len(r["tokens"]) for r in results)
    return tokens, elapsed, results


def run_flight_bench(seconds=2.0, n_requests=None, rounds=6,
                     cache_dir=None):
    """The flight-recorder overhead gate (ISSUE 17 acceptance:
    recorder-on decode tok/s within 2% of recorder-off) plus one
    organically captured anomaly timeline.

    Phase A fills the rolling TTFT window with calm one-at-a-time
    short requests, then bursts full-length prompts — the stragglers'
    TTFT lands above the calm p99, which IS the anomaly trigger, so
    the timelines persist to the JSONL spool exactly as they would in
    production.  Phase B interleaves recorder-on and recorder-off
    windows of the same flagship decode workload (drift cancels, like
    the continuous/static pair) and reports the throughput delta."""
    from veles_tpu.observability import attribution
    from veles_tpu.observability.flight import RECORDER
    from veles_tpu.serving import DecodeScheduler
    from veles_tpu.znicz.samples.flagship import FlagshipDecodeModel

    if cache_dir:
        from veles_tpu.config import root
        root.common.compile_cache.dir = cache_dir
    max_batch, block_size = 8, 8
    max_prompt_len, max_new_tokens = 16, 16
    model = FlagshipDecodeModel(stages=2, experts=2, d=32, heads=2,
                                hidden=64, vocab=128, seed=0)
    scheduler = DecodeScheduler(
        model, max_batch=max_batch, block_size=block_size,
        max_prompt_len=max_prompt_len, max_new_tokens=max_new_tokens,
        queue_limit=4096, name="flight_bench")
    if n_requests is None:
        # longer windows than --decode: the on/off delta being gated
        # is small, so each timed window must dominate scheduler noise
        n_requests = max(24 * max_batch, int(96 * seconds))
    requests = _decode_requests(n_requests, max_prompt_len,
                                max_new_tokens, model.vocab)
    long_prompt = list(range(1, max_prompt_len + 1))
    spool = tempfile.mkdtemp(prefix="veles-flight-bench-")
    RECORDER.reset()
    RECORDER.configure(persist_dir=spool, replica="bench",
                       enabled=False)
    out = {"flight_requests": n_requests, "flight_rounds": rounds,
           "flight_spool_dir": spool}
    on = {"tokens": 0.0, "t": 0.0}
    off = {"tokens": 0.0, "t": 0.0}
    try:
        # warm every shape FIRST, recorder off: the one giant
        # first-compile TTFT must not land in the rolling window, else
        # the burst below compares against it and the p99 trigger
        # never fires
        _run_traced(scheduler, [([3, 1], 1)])
        _run_traced(scheduler,
                    [(long_prompt, max_new_tokens)] * max_batch)
        _run_traced(scheduler, requests[:max_batch])
        RECORDER.configure(enabled=True)

        # -- phase A: capture a real anomaly ------------------------------
        for _ in range(RECORDER.min_samples + 4):  # calm: tiny TTFTs
            _run_traced(scheduler, [([3, 1], 1)])
        _run_traced(scheduler,
                    [(long_prompt, max_new_tokens)] * (2 * max_batch))
        anomalous = [tl for tl in RECORDER.snapshot(limit=256)
                     if tl.get("anomalies")]
        out["flight_anomalies_captured"] = len(anomalous)
        if anomalous:
            out["flight_anomaly_timeline"] = anomalous[0]
            out["flight_anomaly_reasons"] = sorted(
                {r for tl in anomalous for r in tl["anomalies"]})
        out["flight_persisted_records"] = _spool_records(spool)
        RECORDER.reset()            # fresh windows for the timed phase
        # a fresh p99 window would flag the timed phase's own tail as
        # anomalous and pay JSONL writes mid-measurement — persistence
        # is phase A's job, the timed phase measures recording alone
        RECORDER.configure(persist_dir="")

        # -- phase B: recorder-on vs recorder-off, interleaved ------------
        _run_traced(scheduler, requests[:max_batch])   # warm untimed
        for r in range(max(1, rounds)):
            order = (True, False) if r % 2 == 0 else (False, True)
            for enabled in order:   # alternating order cancels drift
                RECORDER.configure(enabled=enabled)
                tok, dt, _res = _run_traced(scheduler, requests)
                acc = on if enabled else off
                acc["tokens"] += tok
                acc["t"] += dt
        RECORDER.configure(enabled=True)
        tls = RECORDER.snapshot(limit=256)
    finally:
        scheduler.close(drain=True)
    out["flight_on_tok_s"] = round(on["tokens"] / on["t"], 1)
    out["flight_off_tok_s"] = round(off["tokens"] / off["t"], 1)
    out["flight_overhead_pct"] = round(
        100.0 * (out["flight_off_tok_s"] - out["flight_on_tok_s"])
        / out["flight_off_tok_s"], 2)
    covs = [b["coverage"] for b in map(attribution.phase_breakdown, tls)
            if b.get("coverage") is not None]
    if covs:
        out["flight_attr_coverage_mean"] = round(
            sum(covs) / len(covs), 4)
    return out


def _spool_records(spool):
    count = 0
    for fn in os.listdir(spool):
        if fn.startswith("flight-") and fn.endswith(".jsonl"):
            with open(os.path.join(spool, fn)) as f:
                count += sum(1 for line in f if line.strip())
    return count


def attribution_summary(group_by=("model",), limit=256):
    """Phase-share table over the process-global recorder's finished
    timelines — the ``--attribution`` payload appended to a bench's
    JSON line (acceptance: phase shares cover >= 95% of wall-clock
    TTFT on the shared-prefix bench)."""
    from veles_tpu.observability import attribution
    from veles_tpu.observability.flight import RECORDER
    tls = RECORDER.snapshot(limit=limit)
    covs = [b["coverage"] for b in map(attribution.phase_breakdown, tls)
            if b.get("coverage") is not None]
    agg = attribution.aggregate(tls, group_by=group_by)
    out = {"attr_requests": len(tls),
           "attr_phase_table": agg}
    if covs:
        out["attr_coverage_mean"] = round(sum(covs) / len(covs), 4)
        out["attr_coverage_min"] = round(min(covs), 4)
    return out


# -- prefix / chunked-prefill mode --------------------------------------------


def run_prefix_bench(shared_prefix=16, waves=10, long_prompts=3,
                     prompt_len=64, chunk_tokens=8, followers=8,
                     prefill_delay=0.002, cache_dir=None,
                     attribution=False):
    """The chunked-prefill + prefix-reuse acceptance probe (ISSUE 14).

    Phase A — head-of-line blocking: a short request submitted behind
    ``long_prompts`` long prefills, monolithic vs chunked, on the
    toydecode stand-in with a pinned per-prompt-token prefill cost (the
    ``sleep:`` philosophy — scheduling is what's measured, not XLA).
    The short request's TTFT p99 must drop >= 3x when long prefills are
    chunked and interleaved with decode.

    Phase B — prefix reuse: one seed generation publishes its prompt
    blocks, then ``followers`` sequences sharing a ``shared_prefix``-
    token system prompt attach to them; reports the reused-block
    fraction (> 0.5 acceptance) and the bitwise oracle check.
    """
    from veles_tpu.serving import DecodeScheduler
    from veles_tpu.serving.toydecode import ToyDecodeModel

    if cache_dir:
        from veles_tpu.config import root
        root.common.compile_cache.dir = cache_dir
    if attribution:
        # every submission gets its own trace context so the flight
        # recorder opens a timeline per request; the phase-share table
        # rides the bench JSON (attr_* keys)
        from veles_tpu.observability.flight import RECORDER
        RECORDER.reset()
        RECORDER.configure(enabled=True)

    def _submit(scheduler, prompt, n):
        if not attribution:
            return scheduler.submit(prompt, n)
        from veles_tpu.observability import trace as _trace
        with _trace.span_context():
            return scheduler.submit(prompt, n)

    out = {"prefix_shared_tokens": shared_prefix,
           "prefix_chunk_tokens": chunk_tokens,
           "prefix_long_prompts": long_prompts,
           "prefix_prompt_len": prompt_len,
           "prefix_waves": waves}

    # -- phase A: short-request TTFT behind long prefills ---------------------
    model = ToyDecodeModel(vocab=97, prefill_delay=prefill_delay)
    rng = numpy.random.RandomState(7)
    long_reqs = [rng.randint(1, 90, prompt_len).tolist()
                 for _ in range(long_prompts)]
    short_req = [3, 1, 4, 1]

    def ttft_run(chunk):
        scheduler = DecodeScheduler(
            model, max_batch=long_prompts + 1, block_size=4,
            max_prompt_len=prompt_len, max_new_tokens=8,
            queue_limit=256,
            prefill_chunk_tokens=chunk,
            name="prefix_chunk%s" % (chunk or 0))
        ttfts = []
        try:
            warm = scheduler.stats()["compiles"]
            for _ in range(max(1, waves)):
                futures = [_submit(scheduler, p, 8)
                           for p in long_reqs]
                short = _submit(scheduler, short_req, 8)
                ttfts.append(short.result(120)["ttft_s"])
                for f in futures:
                    f.result(120)
            post = scheduler.stats()["compiles"] - warm
        finally:
            scheduler.close(drain=True)
        ttfts.sort()
        pick = lambda q: ttfts[min(len(ttfts) - 1,  # noqa: E731
                                   int(q * len(ttfts)))]
        return pick(0.50), pick(0.99), post

    mono_p50, mono_p99, _ = ttft_run(None)
    chunk_p50, chunk_p99, chunk_post = ttft_run(chunk_tokens)
    out["prefix_ttft_p50_monolithic_ms"] = round(mono_p50 * 1e3, 2)
    out["prefix_ttft_p99_monolithic_ms"] = round(mono_p99 * 1e3, 2)
    out["prefix_ttft_p50_chunked_ms"] = round(chunk_p50 * 1e3, 2)
    out["prefix_ttft_p99_chunked_ms"] = round(chunk_p99 * 1e3, 2)
    out["prefix_ttft_p99_speedup"] = round(mono_p99 / chunk_p99, 2) \
        if chunk_p99 else None
    out["prefix_chunked_post_warmup_compiles"] = chunk_post

    # -- phase B: shared-prefix block reuse -----------------------------------
    model2 = ToyDecodeModel(vocab=97)
    oracle = model2.generate_reference
    prefix = [(11 * i + 5) % 89 + 1 for i in range(shared_prefix)]
    block_size = 4
    scheduler = DecodeScheduler(
        model2, max_batch=4, block_size=block_size,
        max_prompt_len=shared_prefix + 8, max_new_tokens=8,
        queue_limit=256, prefix_caching=True,
        prefill_chunk_tokens=chunk_tokens, name="prefix_reuse")
    try:
        warm_compiles = scheduler.stats()["compiles"]
        seed_prompt = prefix + [91]
        assert _submit(scheduler, seed_prompt, 8).result(120)["tokens"] \
            == oracle(seed_prompt, 8)
        mismatches = 0
        fut = [(prefix + [40 + i, 41 + i, 42 + i],
                _submit(scheduler,
                        prefix + [40 + i, 41 + i, 42 + i], 8))
               for i in range(followers)]
        for prompt, f in fut:
            if f.result(120)["tokens"] != oracle(prompt, 8):
                mismatches += 1
        stats = scheduler.stats()
    finally:
        scheduler.close(drain=True)
    blocks_per_follower = -(-(shared_prefix + 3) // block_size)
    out["prefix_followers"] = followers
    out["prefix_hits"] = stats["prefix_hits"]
    out["prefix_dedup_blocks"] = stats["dedup_blocks"]
    out["prefix_published_blocks"] = stats["published_blocks"]
    out["prefix_reused_fraction"] = round(
        stats["dedup_blocks"] / (followers * blocks_per_follower), 3)
    out["prefix_token_mismatches"] = mismatches
    out["prefix_tokens_match"] = mismatches == 0
    out["prefix_compiles"] = stats["compiles"]
    out["prefix_post_warmup_compiles"] = (stats["compiles"]
                                          - warm_compiles)
    if attribution:
        out.update(attribution_summary())
    return out


# -- speculative decoding mode ------------------------------------------------


def run_spec_bench(depths=(1, 2, 3, 4), agreement=0.8, n_requests=24,
                   max_prompt_len=8, max_new_tokens=16,
                   step_delay=0.002, rounds=2, cache_dir=None):
    """The speculative-decoding acceptance sweep (ISSUE 15): the SAME
    mixed request set served by the SAME toydecode model (pinned
    per-verify-pass host cost, tunable drafter agreement) plain vs
    draft-and-verify at each candidate depth.  Every emitted sequence
    is first checked bitwise against the pure-host oracle — the
    speedup table only counts if the tokens are identical; then each
    depth's tok/s is measured interleaved with the plain baseline so
    machine-load drift cancels out of the ratio.  The tok/s-vs-depth
    curve crosses over where the acceptance rate stops paying for the
    extra verify width; ``spec_best_depth`` is the measured knee."""
    from veles_tpu.serving import DecodeScheduler
    from veles_tpu.serving.toydecode import ToyDecodeModel

    if cache_dir:
        from veles_tpu.config import root
        root.common.compile_cache.dir = cache_dir
    model = ToyDecodeModel(vocab=31, step_delay=step_delay,
                           draft_agreement=agreement)
    requests = _decode_requests(n_requests, max_prompt_len,
                                max_new_tokens, model.vocab)
    oracle = [model.generate_reference(p, n) for p, n in requests]

    def build(depth):
        return DecodeScheduler(
            model, max_batch=4, block_size=4,
            max_prompt_len=max_prompt_len,
            max_new_tokens=max_new_tokens, queue_limit=4096,
            spec_depth=depth, name="spec_bench_d%s" % (depth or 0))

    out = {"spec_requests": n_requests, "spec_agreement": agreement,
           "spec_step_delay_s": step_delay,
           "spec_max_new_tokens": max_new_tokens,
           "spec_depths": [int(d) for d in depths]}
    schedulers = {0: build(None)}       # 0 = the plain scheduler
    for d in depths:
        schedulers[int(d)] = build(int(d))
    try:
        # correctness first (also the untimed warm pass): every
        # sequence from every variant must match the oracle bitwise
        mismatches = 0
        for s in schedulers.values():
            _tok, _dt, results = _run_continuous(s, requests)
            mismatches += sum(1 for r, want in zip(results, oracle)
                              if r["tokens"] != want)
        out["spec_token_mismatches"] = mismatches
        out["spec_tokens_match"] = mismatches == 0
        warm = {d: s.stats()["compiles"] for d, s in schedulers.items()}
        acc = {d: {"tokens": 0, "t": 0.0} for d in schedulers}
        for _ in range(max(1, rounds)):    # interleaved: drift cancels
            for d, s in schedulers.items():
                tok, dt, _res = _run_continuous(s, requests)
                acc[d]["tokens"] += tok
                acc[d]["t"] += dt
        plain = acc[0]["tokens"] / acc[0]["t"]
        out["spec_plain_tok_s"] = round(plain, 1)
        best_depth, best = None, 0.0
        for d in sorted(set(int(d) for d in depths)):
            rate = acc[d]["tokens"] / acc[d]["t"]
            out["spec_tok_s_depth%d" % d] = round(rate, 1)
            out["spec_acceptance_depth%d" % d] = \
                schedulers[d].stats()["acceptance_rate"]
            if rate > best:
                best_depth, best = d, rate
        out["spec_best_depth"] = best_depth
        out["spec_best_tok_s"] = round(best, 1)
        out["spec_best_speedup"] = round(best / plain, 2) \
            if plain else None
        out["spec_post_warmup_compiles"] = sum(
            s.stats()["compiles"] - warm[d]
            for d, s in schedulers.items())
    finally:
        for s in schedulers.values():
            s.close(drain=True)
    return out


# -- quantized serving mode ---------------------------------------------------


def run_quant_bench(kv_dtypes=("f32", "int8"), pool_bytes=4096,
                    n_requests=48, max_prompt_len=8, max_new_tokens=16,
                    block_size=8, step_delay=0.002, rounds=2,
                    cache_dir=None):
    """The quantized-serving sweep (ISSUE 18): the SAME request mix
    served at each candidate KV precision under a FIXED device-byte
    budget for the pools.  What int8 pools buy is capacity — the same
    bytes hold ~2-4x the blocks, so more sequences decode concurrently
    instead of queueing — and with a pinned per-STEP host cost (batch
    decode's defining property: one step serves every live row), the
    capacity win is directly a tok/s win.  Every emitted sequence is
    checked bitwise against the pure-host oracle (the toy model stores
    token ids, losslessly int8-representable), and the flagship logit
    RMSE of each precision rides along so the capacity table can never
    hide an accuracy regression."""
    import jax
    import numpy
    from veles_tpu.autotune.probe import _decode_logit_rmse
    from veles_tpu.serving import DecodeScheduler
    from veles_tpu.serving.toydecode import ToyDecodeModel
    from veles_tpu.znicz.paged_attention import required_blocks
    from veles_tpu.znicz.samples.flagship import FlagshipDecodeModel

    if cache_dir:
        from veles_tpu.config import root
        root.common.compile_cache.dir = cache_dir
    model = ToyDecodeModel(vocab=64, step_delay=step_delay)
    requests = _decode_requests(n_requests, max_prompt_len,
                                max_new_tokens, model.vocab)
    oracle = [model.generate_reference(p, n) for p, n in requests]
    flagship = FlagshipDecodeModel(stages=2, experts=2, d=16, heads=2,
                                   hidden=32, vocab=32, seed=0)
    per_seq = required_blocks(max_prompt_len + max_new_tokens,
                              block_size)

    def block_bytes(kvd):
        pools = model.make_pools(1, block_size, kv_dtype=kvd)
        return sum(int(numpy.prod(leaf.shape[1:])) * leaf.dtype.itemsize
                   for leaf in jax.tree_util.tree_leaves(pools))

    out = {"quant_kv_dtypes": [str(d) for d in kv_dtypes],
           "quant_pool_bytes": int(pool_bytes),
           "quant_requests": n_requests,
           "quant_step_delay_s": step_delay,
           "quant_block_size": block_size}
    schedulers, sessions = {}, {}
    for kvd in kv_dtypes:
        bb = block_bytes(kvd)
        num_blocks = max(int(pool_bytes) // bb, per_seq + 1)
        max_sessions = max((num_blocks - 1) // per_seq, 1)
        sessions[kvd] = max_sessions
        out["quant_block_bytes_%s" % kvd] = bb
        out["quant_num_blocks_%s" % kvd] = num_blocks
        out["quant_max_sessions_%s" % kvd] = max_sessions
        out["quant_logit_rmse_%s" % kvd] = round(
            _decode_logit_rmse(flagship, kvd, [3, 1, 2],
                               max_new_tokens), 6)
        schedulers[kvd] = DecodeScheduler(
            model, max_batch=min(max_sessions, 64),
            block_size=block_size, num_blocks=num_blocks,
            max_prompt_len=max_prompt_len,
            max_new_tokens=max_new_tokens, queue_limit=4096,
            kv_dtype=kvd, name="quant_bench_%s" % kvd)
    try:
        # correctness first (also the untimed warm pass): every
        # sequence from every precision must match the oracle bitwise
        mismatches = 0
        for s in schedulers.values():
            _tok, _dt, results = _run_continuous(s, requests)
            mismatches += sum(1 for r, want in zip(results, oracle)
                              if r["tokens"] != want)
        out["quant_token_mismatches"] = mismatches
        out["quant_tokens_match"] = mismatches == 0
        warm = {d: s.stats()["compiles"]
                for d, s in schedulers.items()}
        acc = {d: {"tokens": 0, "t": 0.0} for d in schedulers}
        for _ in range(max(1, rounds)):    # interleaved: drift cancels
            for d, s in schedulers.items():
                tok, dt, _res = _run_continuous(s, requests)
                acc[d]["tokens"] += tok
                acc[d]["t"] += dt
        for d in schedulers:
            out["quant_tok_s_%s" % d] = round(
                acc[d]["tokens"] / acc[d]["t"], 1)
        if "f32" in schedulers and "int8" in schedulers:
            out["quant_session_ratio"] = round(
                sessions["int8"] / sessions["f32"], 2)
            f32_rate = acc["f32"]["tokens"] / acc["f32"]["t"]
            int8_rate = acc["int8"]["tokens"] / acc["int8"]["t"]
            out["quant_speedup"] = round(int8_rate / f32_rate, 2) \
                if f32_rate else None
        out["quant_post_warmup_compiles"] = sum(
            s.stats()["compiles"] - warm[d]
            for d, s in schedulers.items())
    finally:
        for s in schedulers.values():
            s.close(drain=True)
    return out


# -- fleet load mode ----------------------------------------------------------
#
# The multi-replica counterpart (ISSUE 7): the SAME open/closed-loop
# generators above, pointed at a FleetRouter in front of N replica
# subprocesses, measuring the three fleet acceptance numbers —
#
# - ``fleet_scaling_efficiency``: closed-loop req/s with all N replicas
#   admitted vs ONE (the other N-1 quiesced at the router, so both
#   windows share processes, warm caches, and machine state);
# - kill drill: SIGKILL one replica under an open-loop load — failed
#   (non-429) responses must stay 0 while the supervisor respawns it
#   warm (``fleet_respawn_compiles == 0`` off the shared compile
#   cache);
# - rollout drill: a rolling model update under the same load — the
#   error count over the rollout window is the zero-downtime evidence.


def _http_status_open_loop(port, offered_rps, seconds, sizes,
                           sample_shape, route="/api/mnist",
                           headers=None, shed_statuses=(429,)):
    """Paced open loop that records STATUS CLASSES: (ok, shed,
    expired_504, failed) — the fleet drills need "non-backpressure
    failures == 0", which the closed-loop helper's single error bucket
    cannot express.  ``headers`` rides on every request (the chaos
    drill sends ``X-Deadline-Ms``); ``shed_statuses`` says which codes
    count as backpressure rather than failure."""
    bodies = {bs: json.dumps({"input": numpy.random.RandomState(bs)
                              .uniform(-1, 1, (bs,) + tuple(sample_shape))
                              .round(4).tolist()}).encode()
              for bs in sizes}
    req_headers = {"Content-Type": "application/json", **(headers or {})}
    lock = threading.Lock()
    out = {"ok": 0, "shed": 0, "expired": 0, "failed": 0,
           "latencies": []}

    def fire(body):
        t0 = time.perf_counter()
        try:
            conn = http.client.HTTPConnection("127.0.0.1", port,
                                              timeout=30)
            conn.request("POST", route, body, req_headers)
            status = conn.getresponse()
            status.read()
            code = status.status
            conn.close()
        except Exception:
            code = -1
        with lock:
            if code == 200:
                out["ok"] += 1
                out["latencies"].append(time.perf_counter() - t0)
            elif code in shed_statuses:
                out["shed"] += 1
            elif code == 504:
                out["expired"] += 1
            else:
                out["failed"] += 1

    threads = []
    start = time.perf_counter()
    n_arrivals = max(1, int(offered_rps * seconds))
    for k in range(n_arrivals):
        due = start + k / offered_rps
        delay = due - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        t = threading.Thread(target=fire,
                             args=(bodies[sizes[k % len(sizes)]],))
        t.start()
        threads.append(t)
    for t in threads:
        t.join()
    out["elapsed"] = time.perf_counter() - start
    return out


def run_fleet_bench(replicas=3, clients=None, seconds=2.0,
                    sizes=DEFAULT_SIZES, package=None, max_batch=16,
                    offered_rps=60.0, drill_seconds=4.0,
                    cache_dir=None, row_latency=0.01):
    """Replica scaling + kill/rollout drills through the router;
    returns the result dict (``fleet_*`` keys ride into the bench
    JSON).

    Scaling is measured on the ``sleep:`` stand-in model (a fixed
    device-time-per-row twin, see fleet/replica.py): on a small shared
    CPU host the real MNIST forward is microseconds, so one replica's
    batching amortization beats process parallelism and — on a
    single-core box — CPU-bound work cannot scale across replicas BY
    CONSTRUCTION.  The drills (SIGKILL failover, rolling update, warm
    respawn compiles) run against the real exported package, where the
    compile-cache and hot-load machinery actually engage."""
    import shutil
    import signal
    from veles_tpu.fleet import Fleet

    tmp = None
    if package is None:
        tmp = tempfile.mkdtemp(prefix="fleet_bench_")
        package = build_mnist_package(os.path.join(tmp, "mnist_pkg.zip"))
    if cache_dir is None:
        cache_dir = fixed_cache_dir("veles_executables")
    from veles_tpu.export.loader import PackageLoader
    sample_shape = tuple(PackageLoader(package)
                         .model_metadata["input"]["sample_shape"])
    lat_model = "sleep:%s:4" % row_latency
    clients = clients or 4 * replicas

    out = {"fleet_replicas": replicas, "fleet_clients": clients,
           "fleet_max_batch": max_batch,
           "fleet_scaling_model": lat_model}
    t0 = time.perf_counter()
    fleet = Fleet({"mnist": package, "lat": lat_model},
                  replicas=replicas, max_batch=max_batch,
                  cache_dir=cache_dir, poll_interval=0.1,
                  backoff={"base": 0.2, "factor": 2.0, "cap": 5.0,
                           "max_restarts": 10})
    fleet.start(ready_timeout=300)
    out["fleet_start_s"] = round(time.perf_counter() - t0, 2)
    rids = fleet.router.replica_ids()
    try:
        # -- scaling: one admitted replica vs all, interleaved ---------------
        lat_sizes, lat_shape = (1,), (4,)   # one row per request

        def window(n_admit):
            for rid in rids:
                fleet.router.set_admitting(rid, rid in rids[:n_admit])
            _http_closed_loop(fleet.port, 2, 0.2, lat_sizes, lat_shape,
                              route="/api/lat")            # warm
            return _http_closed_loop(fleet.port, clients,
                                     seconds, lat_sizes, lat_shape,
                                     route="/api/lat")
        single = {"n": 0, "t": 0.0}
        full = {"n": 0, "t": 0.0}
        for _ in range(2):                  # interleaved: drift cancels
            rps, lat, err = window(1)
            single["n"] += rps * seconds
            single["t"] += seconds
            rps, lat, err = window(len(rids))
            full["n"] += rps * seconds
            full["t"] += seconds
        for rid in rids:
            fleet.router.set_admitting(rid, True)
        single_rps = single["n"] / single["t"]
        fleet_rps = full["n"] / full["t"]
        out["fleet_single_rps"] = round(single_rps, 1)
        out["fleet_rps"] = round(fleet_rps, 1)
        out["fleet_speedup_vs_single"] = round(fleet_rps / single_rps,
                                               2) if single_rps else None
        out["fleet_scaling_efficiency"] = round(
            fleet_rps / (replicas * single_rps), 3) if single_rps \
            else None

        # -- kill drill: SIGKILL one replica under open-loop load ------------
        victim = rids[-1]
        drill = {}

        def run_drill():
            drill.update(_http_status_open_loop(
                fleet.port, offered_rps, drill_seconds, sizes,
                sample_shape))
        loader = threading.Thread(target=run_drill)
        loader.start()
        time.sleep(drill_seconds * 0.25)
        t_kill = time.perf_counter()
        fleet.supervisor.kill(victim, signal.SIGKILL)
        # recovery = kill → the router has SEEN the death and then
        # reports the respawned replica ready again (reading ready
        # before the down transition would clock a stale 0s)
        seen_down = False
        recovered = None
        while time.perf_counter() - t_kill < 120:
            rep = fleet.router.replica(victim)
            up = rep is not None and rep.up and rep.ready
            if not seen_down:
                seen_down = not up
            elif up:
                recovered = time.perf_counter() - t_kill
                break
            time.sleep(0.02)
        loader.join()
        out["fleet_kill_ok"] = drill["ok"]
        out["fleet_kill_shed"] = drill["shed"]
        out["fleet_kill_failed"] = drill["failed"]
        out["fleet_kill_recovery_s"] = round(recovered, 2) \
            if recovered else None
        # the respawned replica's compile counters: the warm-spawn proof
        met = fleet.router.merged_metrics()
        respawned = (met["replicas"].get(victim) or {}).get("mnist") or {}
        out["fleet_respawn_compiles"] = respawned.get("compiles")
        out["fleet_respawn_cache_hits"] = respawned.get("cache_hits")
        out["fleet_retries"] = sum(
            r["retries"] for r in met["router"]["replicas"].values())

        # -- rollout drill: rolling update under the same load ---------------
        drill2 = {}

        def run_drill2():
            drill2.update(_http_status_open_loop(
                fleet.port, offered_rps, drill_seconds, sizes,
                sample_shape))
        loader = threading.Thread(target=run_drill2)
        loader.start()
        time.sleep(drill_seconds * 0.1)
        rollout = fleet.rolling_update("mnist", package, version="v2")
        loader.join()
        out["fleet_rollout_s"] = rollout["seconds"]
        out["fleet_rollout_updated"] = len(rollout["updated"])
        out["fleet_rollout_ok"] = drill2["ok"]
        out["fleet_rollout_shed"] = drill2["shed"]
        out["fleet_rollout_failed"] = drill2["failed"]
        out["fleet_rollout_error_rate"] = round(
            drill2["failed"] / max(drill2["ok"] + drill2["shed"]
                                   + drill2["failed"], 1), 4)
    finally:
        fleet.stop()
        if tmp:
            shutil.rmtree(tmp, ignore_errors=True)
    return out


def run_fleet_prefix_bench(replicas=2, users=None, seconds=5.0,
                           offered_rps=30.0, num_blocks=40,
                           cache_dir=None):
    """Cache-aware routing vs least-loaded on a multi-replica
    shared-prefix decode workload (ISSUE 16 acceptance).

    ``users`` personas each own a distinct system prefix; requests
    arrive open-loop, round-robin across personas.  The HBM pool is
    sized so ONE replica cannot hold every persona's chains: least-
    loaded routing duplicates the working set on every replica and
    thrashes, while cache-aware routing (the ``X-Veles-Prefix-Keys``
    header against the router's prefix directory) partitions personas
    across replicas so each set fits.  Both phases run a FRESH fleet
    over the same compile cache; the bar is affinity beating baseline
    on BOTH the prefix-hit rate and TTFT p99."""
    from veles_tpu.fleet import Fleet
    from veles_tpu.kvtier import PREFIX_HEADER, prefix_key_header
    from veles_tpu.serving.toydecode import ToyDecodeModel

    users = users or 12 * replicas
    block = 4
    spec = ("toydecode:vocab=97,pdelay=0.002,max_batch=4,block=%d,"
            "max_prompt=16,max_new=8,chunk=8,prefix=1,num_blocks=%d,"
            "tier_host=%d" % (block, num_blocks, 32 << 20))
    if cache_dir is None:
        cache_dir = fixed_cache_dir("veles_executables")
    # distinct 8-token system prefixes (2 full blocks each)
    prefixes = [[(7 * u + j) % 97 for j in range(8)]
                for u in range(users)]
    prefix_headers = [prefix_key_header(p, block) for p in prefixes]
    oracle_model = ToyDecodeModel(vocab=97)
    oracle_memo = {}

    def oracle(prompt, n):
        key = (tuple(prompt), n)
        if key not in oracle_memo:
            oracle_memo[key] = oracle_model.generate_reference(prompt, n)
        return oracle_memo[key]

    def phase(with_header):
        fleet = Fleet({"kv": spec}, replicas=replicas,
                      cache_dir=cache_dir, poll_interval=0.1,
                      backoff={"base": 0.2, "factor": 2.0, "cap": 5.0,
                               "max_restarts": 10})
        fleet.start(ready_timeout=300)
        res = {"ok": 0, "shed": 0, "failed": 0, "mismatch": 0,
               "ttfts": []}
        lock = threading.Lock()

        def fire(k):
            u = k % users
            prompt = prefixes[u] + [10 + (k // users) % 5]
            try:
                conn = http.client.HTTPConnection(
                    "127.0.0.1", fleet.port, timeout=30)
                headers = {"Content-Type": "application/json"}
                if with_header:
                    headers[PREFIX_HEADER] = prefix_headers[u]
                conn.request("POST", "/api/kv/generate",
                             json.dumps({"prompt": prompt,
                                         "max_new_tokens": 6}).encode(),
                             headers)
                resp = conn.getresponse()
                body = json.loads(resp.read() or b"{}")
                status = resp.status
                conn.close()
            except Exception:
                status, body = -1, {}
            with lock:
                if status == 200:
                    if body.get("tokens") == oracle(prompt, 6):
                        res["ok"] += 1
                        res["ttfts"].append(body.get("ttft_s", 0.0))
                    else:
                        res["mismatch"] += 1
                elif status in (429, 503):
                    res["shed"] += 1
                else:
                    res["failed"] += 1

        threads = []
        start = time.perf_counter()
        for k in range(max(1, int(offered_rps * seconds))):
            due = start + k / offered_rps
            delay = due - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            t = threading.Thread(target=fire, args=(k,))
            t.start()
            threads.append(t)
        for t in threads:
            t.join()
        met = fleet.router.merged_metrics()
        res["prefix_hits"] = sum(
            (rep or {}).get("kv", {}).get("prefix_hits", 0)
            for rep in met["replicas"].values())
        res["affinity_hits"] = met["router"]["affinity_hits"]
        res["affinity_fallbacks"] = met["router"]["affinity_fallbacks"]
        fleet.stop()
        return res

    out = {"fp_replicas": replicas, "fp_users": users,
           "fp_offered_rps": offered_rps, "fp_seconds": seconds,
           "fp_num_blocks": num_blocks}
    for mode, res in (("baseline", phase(False)),
                      ("affinity", phase(True))):
        q = _quantiles_ms(res["ttfts"])
        served = max(res["ok"], 1)
        out["fp_%s_ok" % mode] = res["ok"]
        out["fp_%s_shed" % mode] = res["shed"]
        out["fp_%s_failed" % mode] = res["failed"]
        out["fp_%s_mismatch" % mode] = res["mismatch"]
        out["fp_%s_prefix_hits" % mode] = res["prefix_hits"]
        out["fp_%s_hit_rate" % mode] = round(
            res["prefix_hits"] / served, 4)
        out["fp_%s_ttft_p50_ms" % mode] = q.get("p50_ms")
        out["fp_%s_ttft_p99_ms" % mode] = q.get("p99_ms")
        out["fp_%s_affinity_hits" % mode] = res["affinity_hits"]
        out["fp_%s_affinity_fallbacks" % mode] = \
            res["affinity_fallbacks"]
    base_p99 = out.get("fp_baseline_ttft_p99_ms")
    aff_p99 = out.get("fp_affinity_ttft_p99_ms")
    out["fleet_prefix_hit_rate_gain"] = round(
        out["fp_affinity_hit_rate"] - out["fp_baseline_hit_rate"], 4)
    out["fleet_prefix_ttft_p99_speedup"] = round(
        base_p99 / aff_p99, 2) if base_p99 and aff_p99 else None
    return out


def _post_json(port, route, payload, timeout=30):
    """One JSON POST to the local router; → (status, parsed body)."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("POST", route, json.dumps(payload).encode(),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        body = resp.read()
        status = resp.status
    finally:
        conn.close()
    try:
        return status, json.loads(body or b"{}")
    except ValueError:
        return status, {}


# the chaos fleet's decode model: prefix caching + chunked prefill ON,
# so the fault run also exercises the deduped-pool/chunk-queue paths
CHAOS_KV_SPEC = ("toydecode:vocab=97,delay=0.0,max_batch=4,block=4,"
                 "max_prompt=16,max_new=8,chunk=4,prefix=1")


def run_chaos_bench(replicas=3, package=None, offered_rps=40.0,
                    drill_seconds=10.0, sizes=DEFAULT_SIZES,
                    max_batch=16, cache_dir=None):
    """The seeded chaos drill (ISSUE 12 acceptance) against the REAL
    exported package: a deterministic FaultPlan per replica — SIGKILL,
    response truncation, connection black-hole, SIGSTOP freeze — under
    a deadline-carrying open loop.  The bar: ``chaos_failed == 0``
    (every response is 200, backpressure, or a deadline 504), plus the
    kill→ready-again recovery seconds in the bench JSON.

    Every replica also hosts a prefix-caching decode model
    (``CHAOS_KV_SPEC``) fed shared-prefix generate traffic through the
    same fault window; after the drill each surviving pool is fetched
    via ``GET /api/kv/kv`` and checked with tools/kv_inspect — the
    ``chaos_kv_violations`` list must stay empty and every 200 response
    must match the host oracle bitwise."""
    import shutil
    from veles_tpu.fleet import Fleet
    from veles_tpu.serving.toydecode import ToyDecodeModel
    from tools import kv_inspect

    tmp = None
    if package is None:
        tmp = tempfile.mkdtemp(prefix="chaos_bench_")
        package = build_mnist_package(os.path.join(tmp, "mnist_pkg.zip"))
    if cache_dir is None:
        cache_dir = fixed_cache_dir("veles_executables")
    from veles_tpu.export.loader import PackageLoader
    sample_shape = tuple(PackageLoader(package)
                         .model_metadata["input"]["sample_shape"])

    # the script: every fault at a fixed data-request ordinal, so the
    # drill replays identically run after run
    plans = {
        "r0": {"seed": 1, "rules": [
            {"at": 15, "action": "sigkill"}]},
        "r1": {"seed": 2, "rules": [
            {"every": 11, "action": "truncate", "bytes": 24},
            {"at": 40, "action": "sigstop", "resume_after": 2.0}]},
        "r2": {"seed": 3, "rules": [
            {"at": 9, "action": "blackhole", "seconds": 2.0}]},
    }
    out = {"chaos_replicas": replicas,
           "chaos_offered_rps": offered_rps,
           "chaos_seconds": drill_seconds}
    t0 = time.perf_counter()
    fleet = Fleet({"mnist": package, "kv": CHAOS_KV_SPEC},
                  replicas=replicas,
                  max_batch=max_batch, cache_dir=cache_dir,
                  poll_interval=0.1, fault_plans=plans,
                  backoff={"base": 0.2, "factor": 2.0, "cap": 5.0,
                           "max_restarts": 10})
    fleet.start(ready_timeout=300)
    out["chaos_start_s"] = round(time.perf_counter() - t0, 2)
    try:
        # shared-prefix decode traffic riding the same fault window:
        # availability may dip (that is the drill), correctness may not
        kv_out = {"ok": 0, "shed": 0, "failed": 0, "mismatch": 0}
        kv_stop = threading.Event()
        kv_oracle = ToyDecodeModel(vocab=97).generate_reference

        def kv_traffic():
            prefix = list(range(1, 9))   # one system prompt, many tails
            k = 0
            while not kv_stop.is_set():
                prompt = prefix + [10 + (k % 5)]
                k += 1
                try:
                    status, body = _post_json(
                        fleet.port, "/api/kv/generate",
                        {"prompt": prompt, "max_new_tokens": 8})
                except Exception:
                    status, body = -1, {}
                if status == 200:
                    if body.get("tokens") == kv_oracle(prompt, 8):
                        kv_out["ok"] += 1
                    else:
                        kv_out["mismatch"] += 1
                elif status in (429, 503, 504):
                    kv_out["shed"] += 1
                else:
                    kv_out["failed"] += 1
                if kv_stop.wait(0.25):
                    break
        kv_thread = threading.Thread(target=kv_traffic)
        kv_thread.start()
        # sample replica state through the drill: recovery = the first
        # down transition of the SIGKILLed replica → ready again
        down_at = {}
        recovery = {}
        sampling = threading.Event()

        def sample():
            while not sampling.wait(0.02):
                now = time.perf_counter()
                for rid in fleet.router.replica_ids():
                    rep = fleet.router.replica(rid)
                    alive = rep is not None and rep.up and rep.ready
                    if not alive and rid not in down_at:
                        down_at[rid] = now
                    elif alive and rid in down_at \
                            and rid not in recovery:
                        recovery[rid] = now - down_at[rid]
        sampler = threading.Thread(target=sample)
        sampler.start()
        drill = _http_status_open_loop(
            fleet.port, offered_rps, drill_seconds, sizes,
            sample_shape, headers={"X-Deadline-Ms": "15000"},
            shed_statuses=(429, 503))
        # let the killed replica finish respawning before the verdict
        t_wait = time.perf_counter()
        while time.perf_counter() - t_wait < 120:
            if fleet.router.ready_count() == replicas:
                break
            time.sleep(0.1)
        sampling.set()
        sampler.join()
        kv_stop.set()
        kv_thread.join()

        # pool integrity on every surviving replica, straight at the
        # replica ports (the same sweep `kv_inspect --verify` runs)
        kv_violations = []
        kv_pools = kv_hits = kv_dedup = 0
        for rid in fleet.router.replica_ids():
            rep = fleet.router.replica(rid)
            if rep is None or not (rep.up and rep.ready):
                continue
            base = "http://%s:%d" % (rep.host, rep.port)
            try:
                dump = kv_inspect.fetch_dump(base, "kv")
            except Exception as e:
                kv_violations.append("%s: kv dump unreachable (%s)"
                                     % (rid, e))
                continue
            kv_pools += 1
            kv_hits += dump.get("prefix_hits", 0)
            kv_dedup += dump.get("dedup_blocks", 0)
            kv_violations.extend("%s: %s" % (rid, v)
                                 for v in kv_inspect.verify_dump(dump))
        out["chaos_kv_ok"] = kv_out["ok"]
        out["chaos_kv_shed"] = kv_out["shed"]
        out["chaos_kv_failed"] = kv_out["failed"]
        out["chaos_kv_mismatch"] = kv_out["mismatch"]
        out["chaos_kv_pools_checked"] = kv_pools
        out["chaos_kv_prefix_hits"] = kv_hits
        out["chaos_kv_dedup_blocks"] = kv_dedup
        out["chaos_kv_violations"] = kv_violations
        out["chaos_ok"] = drill["ok"]
        out["chaos_shed"] = drill["shed"]
        out["chaos_expired"] = drill["expired"]
        out["chaos_failed"] = drill["failed"]
        out["chaos_p99_ms"] = _quantiles_ms(
            drill["latencies"]).get("p99_ms")
        out["chaos_kill_recovery_s"] = round(recovery["r0"], 2) \
            if "r0" in recovery else None
        met = fleet.router.merged_metrics()
        reps = met["router"]["replicas"]
        out["chaos_truncated"] = sum(r["truncated"] for r in
                                     reps.values())
        out["chaos_aborted"] = sum(r["aborted"] for r in reps.values())
        out["chaos_retries"] = sum(r["retries"] for r in reps.values())
        out["chaos_breaker_trips"] = sum(r["breaker_trips"] for r in
                                         reps.values())
        out["chaos_restarts"] = sum(
            v["restarts"] for v in met["supervisor"].values())
        out["chaos_ready_after"] = fleet.router.ready_count()
    finally:
        fleet.stop()
        if tmp:
            shutil.rmtree(tmp, ignore_errors=True)
    return out


def main(argv=None):
    p = argparse.ArgumentParser(
        prog="serve_bench",
        description="Inference-serving load generator (closed + open "
                    "loop) for the veles_tpu.serving subsystem.")
    p.add_argument("--package", default=None,
                   help="exported package zip (default: build an "
                        "initialized MNIST package in a temp dir)")
    p.add_argument("--clients", type=int, default=8)
    p.add_argument("--seconds", type=float, default=2.0,
                   help="closed-loop measurement window per path")
    p.add_argument("--batch-sizes", default="1,2,3,5,8",
                   help="comma list of request batch sizes to mix")
    p.add_argument("--max-batch", type=int, default=64)
    p.add_argument("--transport", default="both",
                   choices=("inproc", "http", "both"),
                   help="inproc: scheduler vs seed dispatch paths only; "
                        "http: also the full server end to end")
    p.add_argument("--smoke", action="store_true",
                   help="short windows (~1 s each), inproc only — the "
                        "tier-1 regression mode")
    p.add_argument("--sustained", action="store_true",
                   help="longer windows + paced open-loop arrivals "
                        "(the slow-marked load test)")
    p.add_argument("--offered-rps", type=float, default=None,
                   help="open-loop arrival rate (default in --sustained: "
                        "half the measured closed-loop serve_rps)")
    p.add_argument("--json", action="store_true",
                   help="print only the final JSON line")
    p.add_argument("--decode", action="store_true",
                   help="token-level decode load mode: continuous vs "
                        "static-gang batching on the flagship decode "
                        "model (tok/s, per-token tails, TTFT)")
    p.add_argument("--decode-max-batch", type=int, default=8)
    p.add_argument("--decode-block-size", type=int, default=8)
    p.add_argument("--decode-max-prompt", type=int, default=16)
    p.add_argument("--decode-max-new", type=int, default=16)
    p.add_argument("--decode-requests", type=int, default=None)
    p.add_argument("--shared-prefix", type=int, default=None,
                   metavar="N",
                   help="prefix/chunked-prefill mode: short-request "
                        "TTFT behind long prefills (monolithic vs "
                        "chunked) plus block dedupe across sequences "
                        "sharing an N-token system prompt")
    p.add_argument("--prefix-waves", type=int, default=10,
                   help="head-of-line waves per variant "
                        "(--shared-prefix mode)")
    p.add_argument("--spec-depth", default=None, metavar="K[,K2,...]",
                   help="speculative decoding sweep: plain decode vs "
                        "draft-and-verify at each listed depth on the "
                        "toydecode stand-in (pinned per-verify-pass "
                        "host cost, tunable drafter agreement)")
    p.add_argument("--spec-agree", type=float, default=0.8,
                   help="drafter agreement rate for the --spec-depth "
                        "sweep (0..1; the acceptance-rate dial)")
    p.add_argument("--kv-dtype", default=None, metavar="D[,D2,...]",
                   help="quantized serving sweep: the same request mix "
                        "at each listed KV precision (f32,int8) under "
                        "a fixed pool byte budget — capacity, tok/s "
                        "and flagship logit RMSE per precision")
    p.add_argument("--pool-bytes", type=int, default=4096,
                   help="device byte budget for the KV pools in the "
                        "--kv-dtype sweep (both precisions get the "
                        "same budget; int8 fits more blocks in it)")
    p.add_argument("--cache-dir", default=None,
                   help="persistent executable cache dir (decode mode; "
                        "run twice to prove the zero-recompile warm "
                        "restart; fleet mode: shared by every replica)")
    p.add_argument("--fleet", type=int, default=None, metavar="N",
                   help="fleet load mode: N replica subprocesses behind "
                        "the FleetRouter — replica-scaling efficiency "
                        "plus SIGKILL and rolling-update drills under "
                        "open-loop load")
    p.add_argument("--drill-seconds", type=float, default=4.0,
                   help="open-loop window for each fleet drill")
    p.add_argument("--fleet-prefix", type=int, default=None,
                   metavar="N",
                   help="cache-aware-routing mode: N replicas serving "
                        "a multi-persona shared-prefix decode workload "
                        "twice — least-loaded vs X-Veles-Prefix-Keys "
                        "affinity — comparing prefix-hit rate and "
                        "TTFT p99")
    p.add_argument("--flight-overhead", action="store_true",
                   help="flight-recorder overhead gate: recorder-on "
                        "vs recorder-off decode tok/s interleaved, "
                        "plus one organically captured anomaly "
                        "timeline (ISSUE 17: overhead < 2%%)")
    p.add_argument("--attribution", action="store_true",
                   help="with --shared-prefix: trace every request "
                        "and append the flight-recorder phase-share "
                        "table (attr_* keys) to the bench JSON")
    p.add_argument("--chaos", type=int, default=None, metavar="N",
                   help="chaos drill mode: N replicas with scripted "
                        "fault plans (SIGKILL, truncation, black-hole, "
                        "SIGSTOP) under a deadline-carrying open loop "
                        "— the zero-failed-responses acceptance drill")
    args = p.parse_args(argv)

    if args.flight_overhead:
        out = run_flight_bench(
            seconds=args.seconds, n_requests=args.decode_requests,
            cache_dir=args.cache_dir)
        line = {"metric": "flight_overhead_pct",
                "value": out.get("flight_overhead_pct"), "unit": "%"}
        line.update(out)
        if not args.json:
            print("flight bench: %s tok/s recorder-on vs %s off "
                  "(overhead %s%%); %s anomalies captured (%s), %s "
                  "persisted record(s), attribution coverage %s"
                  % (out.get("flight_on_tok_s"),
                     out.get("flight_off_tok_s"),
                     out.get("flight_overhead_pct"),
                     out.get("flight_anomalies_captured"),
                     ",".join(out.get("flight_anomaly_reasons") or [])
                     or "-",
                     out.get("flight_persisted_records"),
                     out.get("flight_attr_coverage_mean")),
                  file=sys.stderr)
        print(json.dumps(line))
        return 0

    if args.chaos:
        out = run_chaos_bench(
            replicas=args.chaos, package=args.package,
            offered_rps=args.offered_rps or 40.0,
            drill_seconds=max(args.drill_seconds, 10.0),
            max_batch=min(args.max_batch, 16),
            cache_dir=args.cache_dir)
        line = {"metric": "chaos_failed",
                "value": out.get("chaos_failed"), "unit": "responses"}
        line.update(out)
        if not args.json:
            print("chaos drill: ok=%s shed=%s expired=%s FAILED=%s; "
                  "kill recovery %ss, %s truncated / %s retried / %s "
                  "breaker trips, %s restarts"
                  % (out.get("chaos_ok"), out.get("chaos_shed"),
                     out.get("chaos_expired"), out.get("chaos_failed"),
                     out.get("chaos_kill_recovery_s"),
                     out.get("chaos_truncated"),
                     out.get("chaos_retries"),
                     out.get("chaos_breaker_trips"),
                     out.get("chaos_restarts")), file=sys.stderr)
            print("chaos kv: ok=%s shed=%s failed=%s MISMATCH=%s; "
                  "%s pool(s) checked, %s prefix hits / %s blocks "
                  "dedup'd, violations=%s"
                  % (out.get("chaos_kv_ok"), out.get("chaos_kv_shed"),
                     out.get("chaos_kv_failed"),
                     out.get("chaos_kv_mismatch"),
                     out.get("chaos_kv_pools_checked"),
                     out.get("chaos_kv_prefix_hits"),
                     out.get("chaos_kv_dedup_blocks"),
                     out.get("chaos_kv_violations") or "none"),
                  file=sys.stderr)
        print(json.dumps(line))
        return 0

    if args.fleet_prefix:
        out = run_fleet_prefix_bench(
            replicas=args.fleet_prefix,
            seconds=args.seconds if args.seconds != 2.0 else 5.0,
            offered_rps=args.offered_rps or 30.0,
            cache_dir=args.cache_dir)
        line = {"metric": "fleet_prefix_ttft_p99_speedup",
                "value": out.get("fleet_prefix_ttft_p99_speedup"),
                "unit": "x"}
        line.update(out)
        if not args.json:
            print("fleet prefix bench: hit rate %s (affinity) vs %s "
                  "(least-loaded), TTFT p99 %s ms vs %s ms (%sx); "
                  "affinity hits=%s fallbacks=%s; failed=%s/%s "
                  "mismatch=%s/%s"
                  % (out.get("fp_affinity_hit_rate"),
                     out.get("fp_baseline_hit_rate"),
                     out.get("fp_affinity_ttft_p99_ms"),
                     out.get("fp_baseline_ttft_p99_ms"),
                     out.get("fleet_prefix_ttft_p99_speedup"),
                     out.get("fp_affinity_affinity_hits"),
                     out.get("fp_affinity_affinity_fallbacks"),
                     out.get("fp_affinity_failed"),
                     out.get("fp_baseline_failed"),
                     out.get("fp_affinity_mismatch"),
                     out.get("fp_baseline_mismatch")), file=sys.stderr)
        print(json.dumps(line))
        return 0

    if args.fleet:
        out = run_fleet_bench(
            replicas=args.fleet, clients=args.clients,
            seconds=args.seconds, package=args.package,
            max_batch=min(args.max_batch, 16),
            offered_rps=args.offered_rps or 60.0,
            drill_seconds=args.drill_seconds, cache_dir=args.cache_dir)
        line = {"metric": "fleet_rps", "value": out.get("fleet_rps"),
                "unit": "req/s"}
        line.update(out)
        if not args.json:
            print("fleet bench: %s req/s on %d replicas vs %s single "
                  "(efficiency %s); kill drill failed=%s recovery=%ss "
                  "respawn compiles=%s; rollout failed=%s in %ss"
                  % (out.get("fleet_rps"), args.fleet,
                     out.get("fleet_single_rps"),
                     out.get("fleet_scaling_efficiency"),
                     out.get("fleet_kill_failed"),
                     out.get("fleet_kill_recovery_s"),
                     out.get("fleet_respawn_compiles"),
                     out.get("fleet_rollout_failed"),
                     out.get("fleet_rollout_s")), file=sys.stderr)
        print(json.dumps(line))
        return 0

    if args.kv_dtype:
        out = run_quant_bench(
            kv_dtypes=tuple(d.strip() for d in
                            args.kv_dtype.split(",") if d.strip()),
            pool_bytes=args.pool_bytes, cache_dir=args.cache_dir)
        line = {"metric": "quant_session_ratio",
                "value": out.get("quant_session_ratio"), "unit": "x"}
        line.update(out)
        if not args.json:
            cols = ", ".join(
                "%s %s tok/s (%s sessions, rmse %s)"
                % (d, out.get("quant_tok_s_%s" % d),
                   out.get("quant_max_sessions_%s" % d),
                   out.get("quant_logit_rmse_%s" % d))
                for d in out["quant_kv_dtypes"])
            print("quant bench: %s at %d pool bytes; session ratio "
                  "%sx, speedup %sx, oracle match=%s, %s post-warmup "
                  "compiles"
                  % (cols, out["quant_pool_bytes"],
                     out.get("quant_session_ratio"),
                     out.get("quant_speedup"),
                     out.get("quant_tokens_match"),
                     out.get("quant_post_warmup_compiles")),
                  file=sys.stderr)
        print(json.dumps(line))
        return 0

    if args.spec_depth:
        out = run_spec_bench(
            depths=tuple(int(d) for d in args.spec_depth.split(",")),
            agreement=args.spec_agree, cache_dir=args.cache_dir)
        line = {"metric": "spec_best_speedup",
                "value": out.get("spec_best_speedup"), "unit": "x"}
        line.update(out)
        if not args.json:
            depth_cols = ", ".join(
                "d%d %s tok/s (acc %s)"
                % (d, out.get("spec_tok_s_depth%d" % d),
                   out.get("spec_acceptance_depth%d" % d))
                for d in out["spec_depths"])
            print("spec bench: plain %s tok/s vs %s; best depth %s = "
                  "%sx at agreement %s, oracle match=%s, %s "
                  "post-warmup compiles"
                  % (out.get("spec_plain_tok_s"), depth_cols,
                     out.get("spec_best_depth"),
                     out.get("spec_best_speedup"),
                     out.get("spec_agreement"),
                     out.get("spec_tokens_match"),
                     out.get("spec_post_warmup_compiles")),
                  file=sys.stderr)
        print(json.dumps(line))
        return 0

    if args.shared_prefix:
        out = run_prefix_bench(shared_prefix=args.shared_prefix,
                               waves=args.prefix_waves,
                               cache_dir=args.cache_dir,
                               attribution=args.attribution)
        line = {"metric": "prefix_ttft_p99_speedup",
                "value": out.get("prefix_ttft_p99_speedup"),
                "unit": "x"}
        line.update(out)
        if not args.json:
            print("prefix bench: short-request TTFT p99 %s ms "
                  "monolithic vs %s ms chunked (%sx); %s%% of follower "
                  "blocks reused (%s hits, %s dedup'd), oracle match=%s,"
                  " %s post-warmup compiles"
                  % (out.get("prefix_ttft_p99_monolithic_ms"),
                     out.get("prefix_ttft_p99_chunked_ms"),
                     out.get("prefix_ttft_p99_speedup"),
                     round(100 * out.get("prefix_reused_fraction", 0)),
                     out.get("prefix_hits"),
                     out.get("prefix_dedup_blocks"),
                     out.get("prefix_tokens_match"),
                     out.get("prefix_post_warmup_compiles")),
                  file=sys.stderr)
        print(json.dumps(line))
        return 0

    if args.decode:
        out = run_decode_bench(
            seconds=args.seconds, n_requests=args.decode_requests,
            max_batch=args.decode_max_batch,
            block_size=args.decode_block_size,
            max_prompt_len=args.decode_max_prompt,
            max_new_tokens=args.decode_max_new,
            offered_rps=args.offered_rps, cache_dir=args.cache_dir)
        line = {"metric": "decode_tok_s",
                "value": out.get("decode_tok_s"), "unit": "tok/s"}
        line.update(out)
        if not args.json:
            print("decode bench: %s tok/s continuous vs %s tok/s "
                  "static gangs (%sx), token p99 %s ms, ttft p50 %s "
                  "ms, %s post-warmup compiles"
                  % (out.get("decode_tok_s"),
                     out.get("decode_static_tok_s"),
                     out.get("decode_vs_static_speedup"),
                     out.get("decode_token_p99_ms"),
                     out.get("decode_ttft_p50_ms"),
                     out.get("decode_post_warmup_compiles")),
                  file=sys.stderr)
        print(json.dumps(line))
        return 0

    kwargs = dict(
        package=args.package, clients=args.clients,
        seconds=args.seconds, max_batch=args.max_batch,
        sizes=tuple(int(s) for s in args.batch_sizes.split(",")),
        transport=args.transport, offered_rps=args.offered_rps)
    if args.smoke:
        kwargs.update(seconds=min(args.seconds, 1.0), transport="inproc")
    if args.sustained:
        kwargs.update(seconds=max(args.seconds, 4.0), transport="both")
        if kwargs["offered_rps"] is None:
            kwargs["offered_rps"] = 200.0
        kwargs["open_seconds"] = max(args.seconds, 4.0)

    out = run_bench(**kwargs)
    line = {"metric": "serve_rps", "value": out.get("serve_rps"),
            "unit": "req/s"}
    line.update(out)
    if not args.json:
        print("serving bench: %s req/s bucketed vs %s req/s seed "
              "per-request path (%sx), batch fill %s, "
              "%s compiles (all warmup)"
              % (out.get("serve_rps"), out.get("per_request_rps"),
                 out.get("serve_speedup_vs_per_request"),
                 out.get("batch_fill"), out.get("compiles")),
              file=sys.stderr)
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
