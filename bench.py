"""Benchmark harness: north-star model training throughput on the real chip.

Prints ONE JSON line: {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}.

Primary metric (BASELINE.json): **ImageNet AlexNet images/sec/chip** —
synthetic ImageNet-shaped data resident in HBM, batch 128, f32, measured on
the **epoch-scan path** (``znicz/scan_step.py``): every dispatch carries
``steps_per_dispatch`` fused train steps inside one ``lax.scan``, so the
number reflects chip compute, not per-launch host dispatch (its cost on
the present chip: not measured).  The per-launch path is reported
alongside as
``alexnet_step_images_per_sec`` so dispatch overhead stays visible — as of
ISSUE 3 that number runs with the async prefetching input pipeline ON
(``loader/prefetch.py``), with ``alexnet_step_sync_images_per_sec``,
``alexnet_step_prefetch_speedup`` and the fenced profiler's
``alexnet_step[_sync]_data_wait_pct`` recording the prefetch-off
comparison in the same run.

``vs_baseline`` compares against the reference's CUDA backend era:
published Caffe/cuDNN-v1 AlexNet training throughput on the GTX TITAN /
K40 class hardware the reference targeted (devices/device_infos.json ships
a GTX TITAN autotune entry) was ~230-260 images/sec; we use a generous
500 img/s anchor so vs_baseline understates rather than overstates the win.

Also reported in the same JSON line:
- ``f32_model_tflops_per_sec`` / ``bf16_model_tflops_per_sec`` +
  ``*_mfu_vs_bf16_peak`` — achieved model FLOP/s against the v5e
  bf16 peak of the device the liveness stage found (``PEAK_BF16_FLOPS``,
  keyed by ``device_kind``; an unknown kind is an error).  FLOPs per step
  come from XLA's own ``cost_analysis()`` of the compiled per-minibatch
  step; when that fails the stage fails.
- ``bf16_speedup_vs_f32`` — the mixed-precision gain on the scan path.
- ``pallas_lrn_speedup`` — epoch-scan throughput with the Pallas LRN
  kernel pair enabled vs the default MXU banded-matmul formula (records
  the hand-kernel delta on the real chip once per round; round-4
  measurement: the gridded kernel compiles in ~18 s but the pallas_call
  boundary blocks XLA fusion, so the pure-XLA MXU path stays default).
- ``flash_attention_speedup`` — train-shaped (fwd+bwd) Pallas flash
  attention vs the XLA oracle at B2 T2048 H8 D64, interleaved — the
  hand-kernel-beats-XLA delta, recorded on the real chip each round.
- ``window_attention_speedup`` — sliding-window (banded-grid) flash
  vs full-causal flash, train-shaped at B1 T16384 W512 — the O(T*W)
  band's recorded delta (grows linearly in T/W; docs/PERF.md).
- ``flagship_tokens_per_sec`` — the modern-model path: one-chip
  train-step throughput of the flagship MoE transformer (all stages,
  all experts, single-device ``flagship_reference`` formulation; the
  composed multi-device shard_map program is the multichip dryrun's
  job — a pipeline needs >1 device to exist).
- ``precise_gemm`` — on-chip cost of the compensated GEMM levels
  ({l0_tflops, l1_overhead, l2_overhead, l0_vs_xla_default}); the
  reference charged +9 %/+90 % for levels 1/2, on the MXU the block
  compensation is ~free (round-4 measurement: 0.99x/1.01x).
- ``mnist_anchor_images_per_sec`` + ``mnist_vs_anchor`` — the round-1
  MNIST-FC epoch-scan anchor (1.127M img/s, the value the DRIVER
  recorded in round 1), kept as a regression canary for the
  dispatch/scan path.
- ``serve_rps`` + ``serve_speedup_vs_per_request`` + ``serve_p99_ms`` +
  ``serve_batch_fill`` — the inference-serving path
  (tools/serve_bench.py): closed-loop req/s of the bucketed
  dynamic-batching scheduler (veles_tpu.serving) vs the seed
  per-request dispatch on the same exported MNIST package, with
  ``serve_post_warmup_compiles`` recording the zero-recompile
  guarantee.
- ``decode_tok_s`` + ``decode_vs_static_speedup`` +
  ``decode_token_p99_ms`` + ``decode_ttft_p50_ms`` +
  ``decode_post_warmup_compiles`` + ``decode_warm_compiles`` — the
  token-level decode path (ISSUE 6): continuous batching over the
  paged KV cache vs request-granularity gangs on the SAME flagship
  decode executables (tools/serve_bench.py --decode), run cold then
  warm in fresh subprocesses so ``decode_warm_compiles == 0`` proves
  the zero-recompile restart via the compile-cache manifest.
- ``fleet_rps`` + ``fleet_scaling_efficiency`` +
  ``fleet_kill_{failed,recovery_s}`` + ``fleet_respawn_compiles`` +
  ``fleet_rollout_{failed,s}`` — the multi-replica serving fleet
  (ISSUE 7, tools/serve_bench.py --fleet): closed-loop req/s of N
  replicas behind the least-loaded router vs one admitted replica,
  plus the SIGKILL and rolling-update drills under open-loop load
  (zero non-429 failures = the zero-downtime evidence; respawn
  ``compiles == 0`` = the warm-spawn evidence).
- ``graph_nonstd_speedup`` + ``graph_nonstd_{interpreted,traced}_ips`` +
  ``graph_std_traced_vs_fused`` + ``graph_std_traced_vs_interpreted`` +
  ``graph_{cold,warm}_compiles`` — whole-workflow compilation (ISSUE 8,
  tools/graph_bench.py): a deliberately non-standard two-branch DAG
  (not expressible by ``FusedTrainStep``) interpreted vs traced into
  one compiled program per step (acceptance >= 1.5x), the standard
  MNIST topology traced vs the hand-fused step (no-regression proof),
  and a cold→warm traced-restart pair over one compile-cache dir
  (``graph_warm_compiles == 0`` = the zero-recompile evidence).
- ``snapshot_stall_speedup`` + ``snapshot_stall_{sync,async}_ms`` +
  ``snapshot_write_gz{9,6}_ms`` — the checkpointing path (ISSUE 4):
  per-snapshot training-thread stall on the MNIST step loop with the
  async capture/write split on vs off (interleaved windows; acceptance
  >= 5x), and the synchronous durable-write time at gzip level 9 (the
  old default) vs 6 (the new one).
- ``spread`` — {name: [min_s, median_s, n]} per timed region, so
  contention claims are checkable from the JSON alone.

Execution design: the parent process is a JAX-FREE orchestrator — a
chip belongs to one process at a time, so the parent never touches it —
and every stage runs as a killable subprocess with its own timeout under
a global wall-clock budget (``VELES_BENCH_BUDGET``, default 1700 s), in
HEADLINE-FIRST order behind a liveness gate that fails unless JAX's
platform is ``tpu``.  A run in which any stage failed exits non-zero.
Compile caches and the tuning store live under ``backends.cache_root()``
(``$JAX_COMPILATION_CACHE_DIR``, else the checkout's ``.cache/``).
"""

import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

# jax-free imports: the orchestrator parent must stay off the chip
from veles_tpu.autotune.runner import run_isolated  # noqa: E402
from veles_tpu.backends import cache_dir  # noqa: E402

# Generous estimate of reference-era CUDA AlexNet training throughput
# (GTX TITAN / K40, Caffe-class kernels): see module docstring.
ALEXNET_BASELINE = 500.0
# images/sec the DRIVER recorded for the MNIST-FC scan bench on one v5e
# chip in round 1 (the driver's record; the 1.45M sometimes quoted was
# an ad-hoc quiet-window measurement, not a recorded baseline — ratios
# against it conflated contention with regression)
MNIST_ANCHOR = 1_127_292.0
# bf16 peak FLOP/s by ``jax.devices()[0].device_kind`` (f32 matmuls run
# at a fraction of it).  Source: Google Cloud documentation, "TPU v5e":
# 197 TFLOP/s bf16 per chip.  A device that is not here is an error, not
# a default.
PEAK_BF16_FLOPS = {"TPU v5 lite": 197e12}


def peak_bf16_flops(device_kind):
    try:
        return PEAK_BF16_FLOPS[device_kind]
    except KeyError:
        raise SystemExit(
            "bench: no published peak for device_kind %r (have: %s); add "
            "it to PEAK_BF16_FLOPS with its source before reporting MFU"
            % (device_kind, ", ".join(sorted(PEAK_BF16_FLOPS))))


# where every stage keeps its executable cache and the round keeps its
# tuning stores: fixed paths, so a second run finds what the first built
EXECUTABLE_CACHE = cache_dir("veles_executables")
AUTOTUNE_STORE = cache_dir("veles_autotune")

# shared by every AlexNet stage and the MFU math.  Round-5 interleaved
# sweep at 32 epochs/dispatch: b256 beats b128 by ~14 % at equal
# dispatch depth (10,441 vs ~9,900 img/s headline) and b512 adds only
# +1.7 % — 256 is the knee (the old "256 does not beat 128" note was a
# depth-8 measurement)
BATCH = int(os.environ.get("VELES_BENCH_BATCH", 256))
SPREAD = {}
_T0 = time.perf_counter()


def _stamp(msg):
    """Stage progress to stderr: a first compile can take minutes — a
    silent bench is undebuggable."""
    print("bench [%7.1fs] %s" % (time.perf_counter() - _T0, msg),
          file=sys.stderr, flush=True)


def _record(name, times):
    SPREAD[name] = [round(min(times), 4),
                    round(statistics.median(times), 4), len(times)]
    return min(times)


def _sync(step):
    """Wait for the last step: JAX returns before the device finishes."""
    import jax
    jax.block_until_ready(step._params_)


def _xla_flops_per_step(step, wf, batch):
    """FLOPs per fused train step from XLA's cost model of the compiled
    step.  No second source: a count the compiler did not give would
    put a made-up denominator under the MFU."""
    cost = step._train_step_g_.lower(
        step._data_dev_, step._y_dev_, step._params_, step._opt_,
        step._macc_, wf.loader._padded_indices_, batch,
        7, 1.0).compile().cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    flops = float(cost.get("flops", 0.0))
    if flops <= 0:
        raise RuntimeError("cost_analysis of the fused train step "
                           "reports no flops: %r" % (cost,))
    return flops, "xla_cost_analysis"


def _make_alexnet(batch, compute_dtype=None, epoch_scan=False,
                  use_pallas_lrn=False, prefetch_depth=None):
    from veles_tpu.backends import Device
    from veles_tpu.config import root
    from veles_tpu.prng import RandomGenerator
    from veles_tpu.znicz.samples import alexnet

    # restore UNSET as unset: the knob is tri-state (None = per-unit
    # AUTO, nn_units.resolve_use_pallas) — writing False back would
    # force-off attention AUTO for the rest of the process
    prior = root.common.engine.get("use_pallas", None)
    if use_pallas_lrn:
        root.common.engine.use_pallas = True
    try:
        trainer = {"compute_dtype": compute_dtype} if compute_dtype else {}
        loader_cfg = {"minibatch_size": batch, "n_train": 8 * batch,
                      "n_valid": batch, "prng": RandomGenerator().seed(3)}
        if prefetch_depth is not None:
            loader_cfg["prefetch_depth"] = prefetch_depth
        wf = alexnet.create_workflow(
            loader=loader_cfg,
            decision={"max_epochs": 10 ** 9, "silent": True},
            trainer=trainer, epoch_scan=epoch_scan)
        wf.initialize(device=Device(backend="tpu"))
    finally:
        if prior is None:
            if use_pallas_lrn:
                delattr(root.common.engine, "use_pallas")
        else:
            root.common.engine.use_pallas = prior
    return wf


def bench_alexnet_scan(batch=128, epochs_per_dispatch=32, repeats=5,
                       compute_dtype=None, use_pallas_lrn=False,
                       name="alexnet_f32"):
    """AlexNet epoch-scan throughput: ``8 * epochs_per_dispatch`` fused
    train steps ride ONE ``lax.scan`` dispatch (n_train = 8*batch), so
    per-launch host dispatch and the per-dispatch metric flush are
    amortized ~256x and the timing is chip-bound.  Scan-depth sweep in round 5
    (another JAX, another machine; interleaved per-epoch minima): 4->8
    +17 %, 8->16 +12 %, 16->32 +7 %, 32->64 +3 % — 32 captured most of
    the curve there; on the present chip: not measured (batch: see the
    BATCH constant's sweep note)."""
    _stamp("building %s (epoch-scan)" % name)
    wf = _make_alexnet(batch, compute_dtype=compute_dtype, epoch_scan=True,
                       use_pallas_lrn=use_pallas_lrn)
    step = wf.fused_step
    _stamp("%s: compiling + warmup" % name)
    step.train_epochs(epochs_per_dispatch)  # compile
    step.train_epochs(epochs_per_dispatch)
    _sync(step)
    times = []
    images = 8 * batch * epochs_per_dispatch
    for _ in range(repeats):
        t0 = time.perf_counter()
        step.train_epochs(epochs_per_dispatch)
        _sync(step)
        times.append(time.perf_counter() - t0)
    _stamp("%s: measured" % name)
    # return only the rate: holding wf alive would keep its HBM-resident
    # synthetic dataset allocated through the subsequent benches
    return images / _record(name, times)


def bench_alexnet_step(batch=128, steps=16, repeats=5, prof_steps=12,
                       prefetch_depth=2):
    """AlexNet per-launch-path throughput (dispatch-overhead diagnostic)
    with the async input pipeline OFF vs ON (ISSUE 3): interleaved A/B
    windows of the same step loop, synchronous serving vs a
    MinibatchPrefetcher at ``prefetch_depth``, plus fenced StepProfiler
    windows recording each mode's data_wait share of step time — the
    win the prefetcher claims must be visible in this JSON.  Also runs
    the FLOPs-per-step probe for MFU accounting."""
    from veles_tpu import loader as loader_mod
    _stamp("building alexnet_step (per-launch, prefetch A/B)")
    wf = _make_alexnet(batch, prefetch_depth=0)
    step = wf.fused_step

    def run_steps(n):
        done = 0
        while done < n:
            wf.loader.run()
            if wf.loader.minibatch_class == loader_mod.TRAIN:
                step.run()
                done += 1
        _sync(step)

    def attach():
        return wf.attach_prefetcher(depth=prefetch_depth,
                                    stage_to_device=True)

    run_steps(2)                 # compile + warmup (sync variant)
    pf = attach()
    run_steps(2)                 # warm the device-staged idx/size/seed
    pf.detach()                  # variant too (its own jit signature)
    sync_times, pre_times = [], []
    for _ in range(repeats):     # interleaved windows: shared-chip
        t0 = time.perf_counter()  # contention drift cancels
        run_steps(steps)
        sync_times.append(time.perf_counter() - t0)
        pf = attach()
        t0 = time.perf_counter()
        run_steps(steps)
        pre_times.append(time.perf_counter() - t0)
        pf.detach()
    ips_sync = batch * steps / _record("alexnet_step_sync", sync_times)
    ips_pre = batch * steps / _record("alexnet_step", pre_times)

    def data_wait_pct(prefetch):
        """Fenced profiler window: data_wait share of step time."""
        pf = attach() if prefetch else None
        prof = wf.attach_profiler()   # AFTER the prefetcher: data_wait
        run_steps(prof_steps)         # = time blocked on the queue
        prof.detach()
        if pf is not None:
            pf.detach()
        return (prof.summary().get("phase_pct") or {}).get("data_wait")

    dw_sync = data_wait_pct(False)
    dw_pre = data_wait_pct(True)
    flops_per_step, flops_source = _xla_flops_per_step(step, wf, batch)
    _stamp("alexnet_step: measured (prefetch %.2fx, data_wait "
           "%s%% -> %s%%; flops via %s)"
           % (ips_pre / ips_sync, dw_sync, dw_pre, flops_source))
    return {"alexnet_step_images_per_sec": round(ips_pre, 1),
            "alexnet_step_sync_images_per_sec": round(ips_sync, 1),
            "alexnet_step_prefetch_speedup": round(ips_pre / ips_sync, 3),
            "alexnet_step_data_wait_pct": dw_pre,
            "alexnet_step_sync_data_wait_pct": dw_sync,
            "flops_per_step": flops_per_step,
            "flops_source": flops_source}


def bench_mnist(batch=512, epochs=24, n_train=16384, repeats=10):
    """MNIST-FC bulk epoch-scan throughput (dispatch-path canary).

    ``epochs=24`` matches the round-1 anchor's block size — round 2/3
    briefly measured 12-epoch blocks, under-amortizing the per-block
    flush and reading ~40% low against the anchor."""
    from veles_tpu.backends import Device
    from veles_tpu.prng import RandomGenerator
    from veles_tpu.znicz.samples import mnist

    _stamp("building mnist canary")
    wf = mnist.create_workflow(
        # use_fixture=False: the canary must stay on the SYNTHETIC twin
        # — the committed digits fixture caps at 12000 train rows, which
        # would silently shrink the 16384-row epochs the round-1 anchor
        # was measured on (and break the img/s accounting)
        loader={"minibatch_size": batch, "n_train": n_train,
                "n_valid": batch, "use_fixture": False,
                "prng": RandomGenerator().seed(3)},
        decision={"max_epochs": 10 ** 9, "silent": True},
        epoch_scan=True)
    wf.initialize(device=Device(backend="tpu"))
    from veles_tpu import loader as loader_mod
    actual_train = wf.loader.class_lengths[loader_mod.TRAIN]
    # if/raise, not assert (python -O would strip it), and provenance,
    # not just row count (real IDX files in the datasets dir would
    # still outrank use_fixture=False): anchor comparability must fail
    # LOUDLY, never silently
    if actual_train != n_train or wf.loader.provenance != "synthetic":
        raise RuntimeError(
            "canary dataset is %r with %d train rows; the round-1 "
            "anchor needs the synthetic twin with %d"
            % (wf.loader.provenance, actual_train, n_train))
    step = wf.fused_step
    # warmup with the SAME epoch-block size: a different scan length would
    # recompile inside the timed region
    step.train_epochs(epochs)
    _sync(step)
    times = []
    for _ in range(repeats):  # many SHORT blocks: more, smaller samples
        # give the min a chance to land in a quiet window of the host
        t0 = time.perf_counter()
        step.train_epochs(epochs)
        _sync(step)
        times.append(time.perf_counter() - t0)
    return n_train * epochs / _record("mnist", times)


def _last_json_line(text):
    """The last parseable JSON object line in ``text`` (or None)."""
    for raw in reversed(text.strip().splitlines()):
        raw = raw.strip()
        if not raw.startswith("{"):
            continue
        try:
            return json.loads(raw)
        except ValueError:
            continue
    return None


def _stage_subprocess(stage, timeout):
    """EVERY bench stage runs in a KILLABLE subprocess: a chip belongs
    to one process at a time, so sequential children each own it in
    turn while the parent stays JAX-free, and a stage that hangs costs
    its own timeout, never the run — ``run_isolated`` kills the child's
    whole process group, so no grandchild is left holding the chip
    while the next stage starts.  Returns
    (line_dict_or_None, error_or_None)."""
    rc, stdout, stderr, timed_out = run_isolated(
        [sys.executable, os.path.abspath(__file__), "--stage", stage],
        timeout, cwd=os.path.dirname(os.path.abspath(__file__)))
    line = _last_json_line(stdout)
    if timed_out:
        return line, "stage %s timeout after %ds" % (stage, timeout)
    if line is None:
        return None, "stage %s exit %d, no JSON: %s" % (
            stage, rc, stderr[-500:])
    if rc:
        # keep BOTH the child's own error field and its stderr tail —
        # a crash after the result line printed is otherwise blank
        return line, "stage %s exit %d (partial kept): %s | stderr: %s" % (
            stage, rc, line.get("error", "")[:300], stderr[-300:])
    return line, None


def bench_precise_gemm(n=4096, reps=8, repeats=6):
    """On-chip overhead of the compensated GEMM levels (znicz/gemm.py)
    vs its own level-0 blocking and vs XLA's stock matmul — the TPU
    answer to the reference's published +9 % / +90 % level-1/2 cost
    (/root/reference/veles/config.py:245-248).  ``reps`` chained matmuls
    ride one dispatch (data dependency) so host dispatch amortizes; the
    D2H read of one element waits for the chain."""
    import numpy
    import jax
    import jax.numpy as jnp
    from veles_tpu.znicz.gemm import precise_matmul
    _stamp("precise-gemm stage")
    rng = numpy.random.RandomState(0)
    a = jnp.asarray(rng.standard_normal((n, n)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((n, n)), jnp.float32)

    def chain(f):
        def g(a, b):
            y = f(a, b)
            for _ in range(reps - 1):
                y = f(a, y / jnp.float32(n))
            return y
        return jax.jit(g)

    fns = {"xla_default": lambda a, b: jnp.dot(a, b)}
    for lvl in (0, 1, 2):
        fns["level%d" % lvl] = \
            lambda a, b, l=lvl: precise_matmul(a, b, l, False)
    res = {}
    for name, f in fns.items():
        g = chain(f)
        y = g(a, b)
        numpy.asarray(y[0, 0])  # compile + flush
        times = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            y = g(a, b)
            numpy.asarray(y[0, 0])
            times.append((time.perf_counter() - t0) / reps)
        _record("gemm_" + name, times)
        # ratios use the MEDIAN: on the shared chip a freak-fast or
        # freak-slow min would make overhead ratios meaningless
        res[name] = statistics.median(times)
    return {
        "l0_tflops": round(2 * n ** 3 / res["level0"] / 1e12, 2),
        "l1_overhead": round(res["level1"] / res["level0"], 3),
        "l2_overhead": round(res["level2"] / res["level0"], 3),
        "l0_vs_xla_default": round(res["level0"] / res["xla_default"],
                                   3),
        "config": _autotune_provenance(
            "precise_gemm", {"m": n, "k": n, "n": n, "level": 1}),
    }


def bench_flash_attention(b=2, t=2048, h=8, d=64, reps=8, chain=4):
    """Train-shaped (full fwd+bwd, grads wrt q/k/v on both sides — see
    tools.ab_flash_attention.train_shaped for the DCE-fairness
    rationale) interleaved A/B: the Pallas flash kernel pair vs the
    XLA oracle that materializes [B, H, T, T]
    (znicz/flash_attention.py vs parallel/ring.py:27) — records the
    hand-kernel-beats-XLA delta on the real chip each round (round 5,
    another JAX: train 1.1-1.6x at T=1k-4k, fwd >= parity,
    docs/PERF.md; on the present chip: not measured).  ``chain``
    dependent steps per dispatch amortize host dispatch."""
    import numpy
    import jax.numpy as jnp
    from tools.ab_flash_attention import time_pair, train_shaped
    from veles_tpu.parallel.ring import attention_reference
    from veles_tpu.znicz.flash_attention import flash_attention
    _stamp("flash-attention stage")
    rng = numpy.random.RandomState(0)
    q, k, v = (jnp.asarray(rng.standard_normal((b, t, h, d)) * 0.5,
                           jnp.float32) for _ in range(3))

    fa = train_shaped(lambda q, k, v: flash_attention(q, k, v, True),
                      chain)
    fo = train_shaped(lambda q, k, v: attention_reference(
        q, k, v, causal=True), chain)
    ta, to = time_pair(fa, fo, (q, k, v), reps=reps, chain=chain)
    _record("flash_train", ta)
    _record("attn_oracle_train", to)
    return {"flash_attention_train_s": round(min(ta), 5),
            "attention_oracle_train_s": round(min(to), 5),
            "flash_attention_shape": [b, t, h, d],
            "flash_attention_config": _autotune_provenance(
                "flash_attention", {"t": t, "d": d, "causal": True})}


def bench_window_attention(b=1, t=16384, h=8, d=64, w=512, reps=6,
                           chain=2):
    """Sliding-window (banded-grid) flash vs full-causal flash,
    train-shaped and interleaved: records the O(T*W) band's delta on
    the real chip.  T must be long enough that the step is
    compute-bound, not dispatch-bound: at T=4096 both variants ride
    under the launch latency and the ratio collapses to ~1.04x
    (measured) — T=16k records 2.04x clean-sync, and the advantage
    grows linearly in T/W (3.2x at T=32k, docs/PERF.md)."""
    import numpy
    import jax.numpy as jnp
    from tools.ab_flash_attention import time_pair, train_shaped
    from veles_tpu.znicz.flash_attention import flash_attention
    _stamp("window-attention stage")
    rng = numpy.random.RandomState(0)
    q, k, v = (jnp.asarray(rng.standard_normal((b, t, h, d)) * 0.5,
                           jnp.float32) for _ in range(3))
    fw = train_shaped(lambda q, k, v: flash_attention(
        q, k, v, True, window=w), chain)
    ff = train_shaped(lambda q, k, v: flash_attention(
        q, k, v, True), chain)
    tw, tf = time_pair(fw, ff, (q, k, v), reps=reps, chain=chain)
    _record("window_train", tw)
    _record("full_causal_train", tf)
    return {"window_attention_train_s": round(min(tw), 5),
            "full_causal_train_s": round(min(tf), 5),
            "window_attention_shape": [b, t, h, d, w],
            "window_attention_config": _autotune_provenance(
                "window_attention",
                {"t": t, "d": d, "causal": True, "window": w})}


def bench_flagship(stages=4, experts=4, d=256, heads=8, hidden=1024,
                   b=8, t=1024, steps_per_dispatch=8, repeats=5):
    """Tokens/sec of a full flagship MoE-transformer SGD step
    (znicz/samples/flagship.py) on ONE chip, via the single-device
    ``flagship_reference`` formulation: ALL ``stages`` blocks and ALL
    ``experts`` run sequentially (a 1-device mesh through the sharded
    path would silently execute only stage 0 / expert 0 — review
    catch; the composed shard_map program is what the multichip
    dryrun validates, a pipeline needs >1 device to exist).
    ``steps_per_dispatch`` fused SGD steps ride one lax.scan dispatch
    (same amortization story as the AlexNet scan)."""
    import numpy
    import jax
    import jax.numpy as jnp
    from jax import lax
    from veles_tpu.znicz.samples.flagship import (flagship_reference,
                                                  init_params)
    _stamp("flagship stage")
    params = init_params(stages=stages, experts=experts, d=d,
                         heads=heads, hidden=hidden)
    rng = numpy.random.RandomState(0)
    x = jnp.asarray(rng.standard_normal((b, t, d)) * 0.5, jnp.float32)
    tgt = jnp.asarray(rng.standard_normal((b, t, d)) * 0.5, jnp.float32)

    def loss_fn(p):
        y = flagship_reference(p, x, heads=heads, microbatches=2)
        return ((y - tgt) ** 2).mean()

    def many(params):
        def body(p, _):
            loss, g = jax.value_and_grad(loss_fn)(p)
            return (jax.tree.map(lambda w, gw: w - 0.05 * gw, p, g),
                    loss)
        _, losses = lax.scan(body, params, None,
                             length=steps_per_dispatch)
        return losses[-1]

    f = jax.jit(many)
    loss = float(f(params))
    assert loss == loss, "NaN loss from flagship bench"
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        float(f(params))
        times.append(time.perf_counter() - t0)
    tokens = b * t * steps_per_dispatch
    return {"flagship_tokens_per_sec":
            round(tokens / _record("flagship", times), 1),
            "flagship_config": {"stages": stages, "experts": experts,
                                "d": d, "heads": heads,
                                "hidden": hidden, "batch": b, "t": t}}


def bench_serving(clients=8, seconds=2.0):
    """Inference-serving throughput (tools/serve_bench.py): the bucketed
    dynamic-batching scheduler vs the seed per-request path, closed-loop
    with ``clients`` concurrent clients and mixed batch sizes on an
    exported MNIST package.  Keys land in the record as ``serve_rps``,
    ``serve_speedup_vs_per_request``, ``serve_p99_ms``,
    ``serve_batch_fill`` — the serving-side counterpart of the training
    MFU numbers."""
    _stamp("serving stage")
    from tools.serve_bench import run_bench
    out = run_bench(clients=clients, seconds=seconds, transport="inproc")
    return {"serve_rps": out.get("serve_rps"),
            "serve_speedup_vs_per_request":
                out.get("serve_speedup_vs_per_request"),
            "serve_p50_ms": out.get("serve_p50_ms"),
            "serve_p99_ms": out.get("serve_p99_ms"),
            "serve_batch_fill": out.get("batch_fill"),
            "serve_post_warmup_compiles":
                out.get("post_warmup_compiles"),
            "serve_time_to_first_response_s":
                out.get("serve_time_to_first_response_s"),
            "serve_bucket_config": _autotune_provenance(
                "serving.bucket_ladder", {"max_batch": 64})}


def bench_cold_start(max_batch=16, probe_timeout=150):
    """Process-start -> first-inference / first-train-step with the
    persistent executable cache (veles_tpu.compilecache) off, cold and
    warm (ISSUE 5 acceptance: the second start's serving warmup path
    >= 2x faster cache-on vs cache-off).  Each probe is a FRESH
    subprocess (tools/cold_start.py) — compilation caches only matter
    across process lifetimes, so in-process timing would be fiction."""
    import subprocess
    import tempfile
    _stamp("cold-start stage: building package")
    repo = os.path.dirname(os.path.abspath(__file__))
    package = os.path.join(tempfile.mkdtemp(prefix="veles-cold-start-"),
                           "mnist_pkg.zip")
    # built in a child of its own: this process must not hold the chip
    # while the probes below need it
    subprocess.run(
        [sys.executable, "-c",
         "import sys; from tools.serve_bench import build_mnist_package; "
         "build_mnist_package(sys.argv[1])", package],
        cwd=repo, check=True, timeout=probe_timeout)
    tool = os.path.join(repo, "tools", "cold_start.py")

    def probe(phase, cached):
        argv = [sys.executable, tool, "--phase", phase,
                "--max-batch", str(max_batch)]
        if phase == "serving":
            argv += ["--package", package]
        if cached:
            argv += ["--cache-dir", EXECUTABLE_CACHE]
        proc = subprocess.run(argv, capture_output=True,
                              timeout=probe_timeout)
        line = _last_json_line(proc.stdout.decode())
        if line is None:
            raise RuntimeError("cold_start probe %s/%s failed: %s"
                               % (phase, cached,
                                  proc.stderr.decode()[-400:]))
        _stamp("cold-start %s cached=%s: total %.2fs warmup %.2fs"
               % (phase, cached, line.get("total_s", -1),
                  line.get("warmup_s") or line.get("first_step_s", -1)))
        return line

    out = {}
    serve_off = probe("serving", False)
    serve_cold = probe("serving", True)     # populates the cache
    serve_warm = probe("serving", True)     # the restart being measured
    out["cold_start_serving_off_warmup_s"] = serve_off["warmup_s"]
    out["cold_start_serving_cold_warmup_s"] = serve_cold["warmup_s"]
    out["cold_start_serving_warm_warmup_s"] = serve_warm["warmup_s"]
    out["cold_start_serving_off_total_s"] = serve_off["total_s"]
    out["cold_start_serving_warm_total_s"] = serve_warm["total_s"]
    out["cold_start_serving_warm_compiles"] = serve_warm["compiles"]
    out["cold_start_serving_warm_cache_hits"] = serve_warm["cache_hits"]
    if serve_warm["warmup_s"]:
        out["cold_start_serving_warmup_speedup"] = round(
            serve_off["warmup_s"] / serve_warm["warmup_s"], 2)
    train_off = probe("train", False)
    probe("train", True)                    # populate
    train_warm = probe("train", True)
    out["cold_start_train_off_first_step_s"] = train_off["first_step_s"]
    out["cold_start_train_warm_first_step_s"] = \
        train_warm["first_step_s"]
    if train_warm["first_step_s"]:
        out["cold_start_train_first_step_speedup"] = round(
            train_off["first_step_s"] / train_warm["first_step_s"], 2)
    return out


def bench_decode(probe_timeout=240):
    """Token-level continuous batching vs request-granularity gangs on
    the flagship decode path (ISSUE 6 acceptance: higher sustained
    tok/s on the same mixed prompt/output-length traffic, zero
    steady-state recompiles, proven across a warm restart).  Each probe
    is a FRESH subprocess running ``tools/serve_bench.py --decode``
    (the cold_start pattern): the first populates the executable cache,
    the second IS the warm restart being measured."""
    import subprocess
    _stamp("decode stage")
    tool = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "tools", "serve_bench.py")

    def probe(tag):
        argv = [sys.executable, tool, "--decode", "--seconds", "2",
                "--decode-requests", "64", "--json",
                "--cache-dir", EXECUTABLE_CACHE]
        proc = subprocess.run(argv, capture_output=True,
                              timeout=probe_timeout)
        line = _last_json_line(proc.stdout.decode())
        if line is None:
            raise RuntimeError("decode probe (%s) failed: %s"
                               % (tag, proc.stderr.decode()[-400:]))
        _stamp("decode %s: %.1f tok/s (%.2fx vs static), warmup %.2fs,"
               " %s compiles" % (tag, line.get("decode_tok_s") or -1,
                                 line.get("decode_vs_static_speedup")
                                 or -1, line.get("decode_warmup_s", -1),
                                 line.get("decode_compiles")))
        return line

    cold = probe("cold")
    warm = probe("warm")        # the restart: manifest + cache replay
    out = {"decode_tok_s": warm.get("decode_tok_s"),
           "decode_static_tok_s": warm.get("decode_static_tok_s"),
           "decode_vs_static_speedup":
               warm.get("decode_vs_static_speedup"),
           "decode_token_p50_ms": warm.get("decode_token_p50_ms"),
           "decode_token_p99_ms": warm.get("decode_token_p99_ms"),
           "decode_ttft_p50_ms": warm.get("decode_ttft_p50_ms"),
           "decode_row_fill": warm.get("decode_row_fill"),
           "decode_post_warmup_compiles":
               warm.get("decode_post_warmup_compiles"),
           "decode_cold_warmup_s": cold.get("decode_warmup_s"),
           "decode_warm_warmup_s": warm.get("decode_warmup_s"),
           "decode_warm_compiles": warm.get("decode_compiles"),
           "decode_warm_cache_hits": warm.get("decode_cache_hits"),
           "decode_config": _autotune_provenance(
               "serving.decode", {"max_context": 32})}
    return out


def bench_prefix_reuse(probe_timeout=300):
    """Chunked prefill + prefix-aware KV reuse (ISSUE 14 acceptance:
    short-request TTFT p99 >= 3x better when long prefills are chunked
    and interleaved with decode, > 50% of blocks reused across
    sequences sharing a system prompt with bitwise-oracle tokens, and
    zero steady-state recompiles across a warm restart — the chunk
    executable rides the same manifest as the decode step).  Cold/warm
    probe pair like the decode stage: two fresh subprocesses sharing
    one cache dir, the second IS the restart."""
    import subprocess
    _stamp("prefix-reuse stage")
    tool = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "tools", "serve_bench.py")

    def probe(tag):
        argv = [sys.executable, tool, "--shared-prefix", "16",
                "--prefix-waves", "8", "--json",
                "--cache-dir", EXECUTABLE_CACHE]
        proc = subprocess.run(argv, capture_output=True,
                              timeout=probe_timeout)
        line = _last_json_line(proc.stdout.decode())
        if line is None:
            raise RuntimeError("prefix probe (%s) failed: %s"
                               % (tag, proc.stderr.decode()[-400:]))
        _stamp("prefix %s: ttft p99 %s ms mono vs %s ms chunked (%sx), "
               "reuse %s, %s post-warmup compiles"
               % (tag, line.get("prefix_ttft_p99_monolithic_ms"),
                  line.get("prefix_ttft_p99_chunked_ms"),
                  line.get("prefix_ttft_p99_speedup"),
                  line.get("prefix_reused_fraction"),
                  line.get("prefix_post_warmup_compiles")))
        return line

    cold = probe("cold")
    warm = probe("warm")        # the restart: manifest + cache replay
    keys = ("prefix_ttft_p50_monolithic_ms",
            "prefix_ttft_p99_monolithic_ms",
            "prefix_ttft_p50_chunked_ms", "prefix_ttft_p99_chunked_ms",
            "prefix_ttft_p99_speedup", "prefix_reused_fraction",
            "prefix_hits", "prefix_dedup_blocks",
            "prefix_published_blocks", "prefix_tokens_match",
            "prefix_post_warmup_compiles",
            "prefix_chunked_post_warmup_compiles")
    out = {k: warm.get(k) for k in keys}
    out["prefix_cold_compiles"] = cold.get("prefix_compiles")
    out["prefix_warm_compiles"] = warm.get("prefix_compiles")
    out["prefix_config"] = _autotune_provenance(
        "serving.prefill_chunk", {"max_prompt_len": 64})
    return out


def bench_speculative(probe_timeout=300):
    """Speculative decoding: draft-and-verify through the multi-token
    verify entry of the paged-attention path (ISSUE 15 acceptance:
    every emitted sequence bitwise-equal to the plain-decode oracle,
    tok/s beating the plain scheduler above the measured acceptance
    threshold, zero steady-state recompiles across a warm restart
    including the @draft/@verify executables).  Cold/warm probe pair
    like the decode stage: two fresh subprocesses sharing one cache
    dir, the second IS the restart; a third probe at a low drafter
    agreement rate records the other side of the acceptance crossover
    (where rejected drafts stop paying for the verify width)."""
    import subprocess
    _stamp("speculative stage")
    tool = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "tools", "serve_bench.py")

    def probe(tag, agree):
        argv = [sys.executable, tool, "--spec-depth", "1,2,3,4",
                "--spec-agree", str(agree), "--json",
                "--cache-dir", EXECUTABLE_CACHE]
        proc = subprocess.run(argv, capture_output=True,
                              timeout=probe_timeout)
        line = _last_json_line(proc.stdout.decode())
        if line is None:
            raise RuntimeError("spec probe (%s) failed: %s"
                               % (tag, proc.stderr.decode()[-400:]))
        _stamp("spec %s (agree %s): best depth %s = %sx vs plain, "
               "match=%s, %s post-warmup compiles"
               % (tag, agree, line.get("spec_best_depth"),
                  line.get("spec_best_speedup"),
                  line.get("spec_tokens_match"),
                  line.get("spec_post_warmup_compiles")))
        return line

    cold = probe("cold", 0.9)
    warm = probe("warm", 0.9)   # the restart: manifest + cache replay
    low = probe("low_agree", 0.3)
    keys = ("spec_plain_tok_s", "spec_best_depth", "spec_best_tok_s",
            "spec_best_speedup", "spec_tokens_match",
            "spec_token_mismatches", "spec_post_warmup_compiles")
    out = {k: warm.get(k) for k in keys}
    for d in warm.get("spec_depths") or []:
        for k in ("spec_tok_s_depth%d" % d,
                  "spec_acceptance_depth%d" % d):
            out[k] = warm.get(k)
    out["spec_cold_best_speedup"] = cold.get("spec_best_speedup")
    out["spec_low_agree_speedup"] = low.get("spec_best_speedup")
    out["spec_low_agree_tokens_match"] = low.get("spec_tokens_match")
    # the acceptance crossover: high agreement must beat plain, and the
    # low-agreement sweep must land strictly below the high one
    out["spec_crossover_observed"] = bool(
        (warm.get("spec_best_speedup") or 0) > 1.0
        and (low.get("spec_best_speedup") or 1e9)
        < (warm.get("spec_best_speedup") or 0))
    out["spec_config"] = _autotune_provenance(
        "serving.spec_depth", {"max_new_tokens": 16})
    return out


def bench_quantized(probe_timeout=300):
    """Quantized serving (ISSUE 18 acceptance: int8 KV pools hold
    >= 2x the concurrent sessions of f32 at a FIXED pool byte budget
    and beat its decode tok/s, with flagship logit RMSE <= 1e-2 and
    every emitted sequence bitwise-equal to the oracle; warm restart
    of the int8 config compiles nothing including the dtype-tagged
    executables).  Cold/warm probe pair like the decode stage: two
    fresh subprocesses sharing one cache dir, the second IS the
    restart."""
    import subprocess
    _stamp("quantized stage")
    tool = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "tools", "serve_bench.py")

    def probe(tag):
        argv = [sys.executable, tool, "--kv-dtype", "f32,int8",
                "--json", "--cache-dir", EXECUTABLE_CACHE]
        proc = subprocess.run(argv, capture_output=True,
                              timeout=probe_timeout)
        line = _last_json_line(proc.stdout.decode())
        if line is None:
            raise RuntimeError("quant probe (%s) failed: %s"
                               % (tag, proc.stderr.decode()[-400:]))
        _stamp("quant %s: sessions %sx, tok/s %sx, rmse %s, "
               "match=%s, %s post-warmup compiles"
               % (tag, line.get("quant_session_ratio"),
                  line.get("quant_speedup"),
                  line.get("quant_logit_rmse_int8"),
                  line.get("quant_tokens_match"),
                  line.get("quant_post_warmup_compiles")))
        return line

    cold = probe("cold")
    warm = probe("warm")        # the restart: manifest + cache replay
    keys = ("quant_pool_bytes", "quant_block_bytes_f32",
            "quant_block_bytes_int8", "quant_max_sessions_f32",
            "quant_max_sessions_int8", "quant_session_ratio",
            "quant_tok_s_f32", "quant_tok_s_int8", "quant_speedup",
            "quant_logit_rmse_int8", "quant_tokens_match",
            "quant_token_mismatches", "quant_post_warmup_compiles")
    out = {k: warm.get(k) for k in keys}
    out["quant_cold_session_ratio"] = cold.get("quant_session_ratio")
    out["quant_gate_passed"] = bool(
        (warm.get("quant_session_ratio") or 0) >= 2.0
        and (warm.get("quant_speedup") or 0) > 1.0
        and (warm.get("quant_logit_rmse_int8") or 1e9) <= 1e-2
        and warm.get("quant_tokens_match"))
    out["quant_config"] = _autotune_provenance(
        "serving.kv_dtype", {"max_context": 64})
    return out


def bench_flight_recorder(probe_timeout=420):
    """Flight-recorder overhead gate (ISSUE 17 acceptance: recorder-on
    decode tok/s within 2% of recorder-off, every anomalous request
    leaving a persisted timeline, attribution phase shares covering
    >= 95% of wall-clock TTFT).  Two fresh subprocesses: the overhead
    probe interleaves recorder-on/off windows of the flagship decode
    workload and captures one organic p99 anomaly; the attribution
    probe reruns the shared-prefix bench with per-request tracing and
    reports phase-share coverage."""
    import subprocess
    _stamp("flight-recorder stage")
    tool = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "tools", "serve_bench.py")

    def probe(tag, argv):
        proc = subprocess.run(
            [sys.executable, tool] + argv +
            ["--json", "--cache-dir", EXECUTABLE_CACHE],
            capture_output=True, timeout=probe_timeout)
        line = _last_json_line(proc.stdout.decode())
        if line is None:
            raise RuntimeError("flight probe (%s) failed: %s"
                               % (tag, proc.stderr.decode()[-400:]))
        return line

    over = probe("overhead", ["--flight-overhead", "--seconds", "2"])
    _stamp("flight overhead: %s tok/s on vs %s off (%s%%), %s "
           "anomalies, %s persisted"
           % (over.get("flight_on_tok_s"), over.get("flight_off_tok_s"),
              over.get("flight_overhead_pct"),
              over.get("flight_anomalies_captured"),
              over.get("flight_persisted_records")))
    attr = probe("attribution", ["--shared-prefix", "16",
                                 "--prefix-waves", "4",
                                 "--attribution"])
    _stamp("flight attribution: %s request(s), coverage mean %s / "
           "min %s" % (attr.get("attr_requests"),
                       attr.get("attr_coverage_mean"),
                       attr.get("attr_coverage_min")))
    out = {k: over.get(k) for k in (
        "flight_on_tok_s", "flight_off_tok_s", "flight_overhead_pct",
        "flight_anomalies_captured", "flight_anomaly_reasons",
        "flight_persisted_records", "flight_requests")}
    anomaly = over.get("flight_anomaly_timeline") or {}
    out["flight_anomaly_status"] = anomaly.get("status")
    out["flight_anomaly_events"] = len(anomaly.get("events") or ())
    out["flight_overhead_ok"] = (
        over.get("flight_overhead_pct") is not None
        and over["flight_overhead_pct"] < 2.0)
    out["flight_attr_requests"] = attr.get("attr_requests")
    out["flight_attr_coverage_mean"] = attr.get("attr_coverage_mean")
    out["flight_attr_coverage_min"] = attr.get("attr_coverage_min")
    return out


def bench_fleet(replicas=3, probe_timeout=360):
    """Multi-replica serving fleet (ISSUE 7 acceptance: >= 0.8
    replica-scaling efficiency on the open-loop serve_bench load, a
    SIGKILL mid-load with zero failed non-429 responses and a warm
    (compiles == 0) respawn, and a zero-downtime rolling update).  The
    whole fleet runs in ONE fresh subprocess driving
    ``tools/serve_bench.py --fleet N`` — the replicas are its
    grandchildren, so a wedged replica dies with the stage instead of
    leaking."""
    import subprocess
    _stamp("fleet stage")
    tool = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "tools", "serve_bench.py")
    argv = [sys.executable, tool, "--fleet", str(replicas),
            "--seconds", "2", "--json", "--cache-dir", EXECUTABLE_CACHE]
    proc = subprocess.run(argv, capture_output=True,
                          timeout=probe_timeout)
    line = _last_json_line(proc.stdout.decode())
    if line is None:
        raise RuntimeError("fleet probe failed: %s"
                           % proc.stderr.decode()[-400:])
    _stamp("fleet: %s req/s on %d replicas (efficiency %s), kill "
           "failed=%s recovery=%ss respawn compiles=%s, rollout "
           "failed=%s"
           % (line.get("fleet_rps"), replicas,
              line.get("fleet_scaling_efficiency"),
              line.get("fleet_kill_failed"),
              line.get("fleet_kill_recovery_s"),
              line.get("fleet_respawn_compiles"),
              line.get("fleet_rollout_failed")))
    keys = ("fleet_replicas", "fleet_rps", "fleet_single_rps",
            "fleet_speedup_vs_single", "fleet_scaling_efficiency",
            "fleet_start_s", "fleet_kill_ok", "fleet_kill_shed",
            "fleet_kill_failed", "fleet_kill_recovery_s",
            "fleet_respawn_compiles", "fleet_respawn_cache_hits",
            "fleet_retries", "fleet_rollout_s", "fleet_rollout_ok",
            "fleet_rollout_shed", "fleet_rollout_failed",
            "fleet_rollout_error_rate")
    return {k: line.get(k) for k in keys}


def bench_fleet_prefix(replicas=2, probe_timeout=400):
    """Cache-aware routing vs least-loaded (ISSUE 16 acceptance:
    affinity routing on the ``X-Veles-Prefix-Keys`` header beats
    least-loaded dispatch on BOTH prefix-hit rate and TTFT p99 over a
    multi-persona shared-prefix decode workload whose working set
    exceeds one replica's HBM pool).  One fresh subprocess
    (``tools/serve_bench.py --fleet-prefix N``) owns both fleets."""
    import subprocess
    _stamp("fleet-prefix stage")
    tool = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "tools", "serve_bench.py")
    argv = [sys.executable, tool, "--fleet-prefix", str(replicas),
            "--json", "--cache-dir", EXECUTABLE_CACHE]
    proc = subprocess.run(argv, capture_output=True,
                          timeout=probe_timeout)
    line = _last_json_line(proc.stdout.decode())
    if line is None:
        raise RuntimeError("fleet-prefix probe failed: %s"
                           % proc.stderr.decode()[-400:])
    _stamp("fleet-prefix: hit rate %s vs %s, TTFT p99 %s ms vs %s ms "
           "(%sx), failed=%s/%s mismatch=%s/%s"
           % (line.get("fp_affinity_hit_rate"),
              line.get("fp_baseline_hit_rate"),
              line.get("fp_affinity_ttft_p99_ms"),
              line.get("fp_baseline_ttft_p99_ms"),
              line.get("fleet_prefix_ttft_p99_speedup"),
              line.get("fp_affinity_failed"),
              line.get("fp_baseline_failed"),
              line.get("fp_affinity_mismatch"),
              line.get("fp_baseline_mismatch")))
    keys = ("fp_replicas", "fp_users", "fp_offered_rps", "fp_seconds",
            "fp_num_blocks", "fp_baseline_ok", "fp_baseline_failed",
            "fp_baseline_mismatch", "fp_baseline_hit_rate",
            "fp_baseline_ttft_p50_ms", "fp_baseline_ttft_p99_ms",
            "fp_affinity_ok", "fp_affinity_failed",
            "fp_affinity_mismatch", "fp_affinity_hit_rate",
            "fp_affinity_ttft_p50_ms", "fp_affinity_ttft_p99_ms",
            "fp_affinity_affinity_hits", "fp_affinity_affinity_fallbacks",
            "fleet_prefix_hit_rate_gain", "fleet_prefix_ttft_p99_speedup")
    return {k: line.get(k) for k in keys}


def bench_chaos(replicas=3, probe_timeout=400):
    """Seeded chaos drill on the real-package fleet (ISSUE 12
    acceptance: SIGKILL + black-hole + truncation + SIGSTOP under a
    deadline-carrying open loop with ZERO failed non-backpressure,
    non-504 responses, bounded kill recovery).  One fresh subprocess
    (``tools/serve_bench.py --chaos N``) owns the router and the
    fault-injected replica grandchildren, so a wedged drill dies with
    the stage instead of leaking."""
    import subprocess
    _stamp("chaos stage")
    tool = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "tools", "serve_bench.py")
    argv = [sys.executable, tool, "--chaos", str(replicas),
            "--json", "--cache-dir", EXECUTABLE_CACHE]
    proc = subprocess.run(argv, capture_output=True,
                          timeout=probe_timeout)
    line = _last_json_line(proc.stdout.decode())
    if line is None:
        raise RuntimeError("chaos probe failed: %s"
                           % proc.stderr.decode()[-400:])
    _stamp("chaos: ok=%s shed=%s expired=%s failed=%s, kill recovery "
           "%ss, %s truncated / %s retried / %s breaker trips"
           % (line.get("chaos_ok"), line.get("chaos_shed"),
              line.get("chaos_expired"), line.get("chaos_failed"),
              line.get("chaos_kill_recovery_s"),
              line.get("chaos_truncated"), line.get("chaos_retries"),
              line.get("chaos_breaker_trips")))
    keys = ("chaos_replicas", "chaos_offered_rps", "chaos_seconds",
            "chaos_start_s", "chaos_ok", "chaos_shed", "chaos_expired",
            "chaos_failed", "chaos_p99_ms", "chaos_kill_recovery_s",
            "chaos_truncated", "chaos_aborted", "chaos_retries",
            "chaos_breaker_trips", "chaos_restarts",
            "chaos_ready_after")
    return {k: line.get(k) for k in keys}


def bench_graph_compile(probe_timeout=150):
    """Whole-workflow compilation (ISSUE 8 acceptance: a non-standard
    two-branch workflow traced >= 1.5x its interpreted throughput, the
    standard MNIST topology traced >= the hand-fused step, and a warm
    restart of a traced workflow doing ZERO XLA compiles).  Each probe
    is a FRESH subprocess (tools/graph_bench.py); the warm pair shares
    one cache dir — the second process IS the restart being measured."""
    import subprocess
    _stamp("graph-compile stage")
    tool = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "tools", "graph_bench.py")

    def probe(name, *extra):
        argv = [sys.executable, tool, "--probe", name] + list(extra)
        proc = subprocess.run(argv, capture_output=True,
                              timeout=probe_timeout)
        line = _last_json_line(proc.stdout.decode())
        if line is None:
            raise RuntimeError("graph_bench probe %s failed: %s"
                               % (name, proc.stderr.decode()[-400:]))
        return line

    out = {}
    out.update(probe("nonstd"))
    _stamp("graph-compile nonstd: %sx traced vs interpreted (bitwise=%s)"
           % (out.get("graph_nonstd_speedup"),
              out.get("graph_nonstd_bitwise_n_err")))
    out.update(probe("std"))
    _stamp("graph-compile std: traced/fused %s traced/interpreted %s"
           % (out.get("graph_std_traced_vs_fused"),
              out.get("graph_std_traced_vs_interpreted")))
    cold = probe("warm", "--cache-dir", EXECUTABLE_CACHE)
    warm = probe("warm", "--cache-dir", EXECUTABLE_CACHE)
    out["graph_cold_compiles"] = cold["graph_compiles"]
    out["graph_warm_compiles"] = warm["graph_compiles"]
    out["graph_warm_cache_hits"] = warm["graph_cache_hits"]
    _stamp("graph-compile warm restart: compiles %s (cold %s), hits %s"
           % (warm["graph_compiles"], cold["graph_compiles"],
              warm["graph_cache_hits"]))
    return out


def bench_observability(batch=512, steps=64, repeats=5):
    """Tracing+metrics overhead on the MNIST per-step loop (ISSUE 2
    acceptance: < 5%): the SAME per-launch step loop timed bare, then
    with the full observability stack on — JSONL event tracing, the
    process-global metrics registry, and the StepProfiler with its
    block_until_ready fencing.  Interleaved A/B windows so shared-chip
    contention drift cancels instead of biasing the ratio; the overhead
    ratio uses per-window minima."""
    import tempfile
    from veles_tpu import loader as loader_mod
    from veles_tpu.backends import Device
    from veles_tpu.config import root
    from veles_tpu.prng import RandomGenerator
    from veles_tpu.znicz.samples import mnist as mnist_sample

    _stamp("observability stage: building mnist step loop")
    wf = mnist_sample.create_workflow(
        loader={"minibatch_size": batch, "n_train": 8 * batch,
                "n_valid": batch, "use_fixture": False,
                "prng": RandomGenerator().seed(3)},
        decision={"max_epochs": 10 ** 9, "silent": True})
    wf.initialize(device=Device(backend="tpu"))
    step = wf.fused_step

    def run_steps(n):
        done = 0
        while done < n:
            wf.loader.run()
            if wf.loader.minibatch_class == loader_mod.TRAIN:
                step.run()
                done += 1
        _sync(step)

    run_steps(steps)  # compile + warmup
    run_steps(steps)

    trace_file = tempfile.NamedTemporaryFile(
        prefix="veles-obs-bench-", suffix=".jsonl", delete=False)
    trace_file.close()
    off_times, on_times = [], []
    profiler = None
    try:
        for _ in range(repeats):
            # bare window
            t0 = time.perf_counter()
            run_steps(steps)
            off_times.append(time.perf_counter() - t0)
            # instrumented window: tracing + registry + profiler
            root.common.trace.enabled = True
            root.common.trace.file = trace_file.name
            profiler = wf.attach_profiler()
            t0 = time.perf_counter()
            run_steps(steps)
            on_times.append(time.perf_counter() - t0)
            profiler.detach()
            root.common.trace.enabled = False
    finally:
        root.common.trace.enabled = False
        root.common.trace.file = None
        from veles_tpu.logger import events
        events.reset()
    t_off = _record("obs_off", off_times)
    t_on = _record("obs_on", on_times)
    overhead = t_on / t_off - 1.0
    out = {"observability_overhead_pct": round(100 * overhead, 2),
           "observability_steps_per_sec_off": round(steps / t_off, 1),
           "observability_steps_per_sec_on": round(steps / t_on, 1)}
    if profiler is not None:
        out["observability_recompiles"] = profiler.recompiles
        if profiler.steps:
            total = (profiler.data_wait_s + profiler.host_s +
                     profiler.device_s)
            out["observability_phase_split"] = {
                "data_wait": round(profiler.data_wait_s / total, 4),
                "host": round(profiler.host_s / total, 4),
                "device": round(profiler.device_s / total, 4),
            } if total else None
    try:
        with open(trace_file.name) as f:
            out["observability_trace_events"] = sum(1 for _ in f)
        os.unlink(trace_file.name)
    except OSError:
        pass
    _stamp("observability stage: measured (%.2f%% overhead)"
           % (100 * overhead))
    return out


def bench_snapshot(batch=512, steps=8, snaps=5, repeats=4):
    """Per-snapshot training-thread stall, synchronous vs asynchronous
    write (ISSUE 4 acceptance: >= 5x): the MNIST per-step loop with a
    SnapshotterToFile driven explicitly, interleaved A/B windows (same
    methodology as the observability stage) timing ONLY the export()
    call — the stall the step loop actually eats.  The async window's
    writer backlog drains untimed between windows so writer CPU never
    leaks into the other mode's window.  Also records the
    compression-level satellite: the synchronous durable-write time at
    gzip level 9 (the old hardcoded default) vs level 6 (the new one),
    interleaved the same way."""
    import shutil
    import tempfile
    from veles_tpu import loader as loader_mod
    from veles_tpu.backends import Device
    from veles_tpu.config import root
    from veles_tpu.prng import RandomGenerator
    from veles_tpu.snapshotter import SnapshotterToFile
    from veles_tpu.znicz.samples import mnist as mnist_sample

    _stamp("snapshot stage: building mnist step loop")
    wf = mnist_sample.create_workflow(
        loader={"minibatch_size": batch, "n_train": 8 * batch,
                "n_valid": batch, "use_fixture": False,
                "prng": RandomGenerator().seed(3)},
        decision={"max_epochs": 10 ** 9, "silent": True})
    wf.initialize(device=Device(backend="tpu"))
    step = wf.fused_step

    def run_steps(n):
        done = 0
        while done < n:
            wf.loader.run()
            if wf.loader.minibatch_class == loader_mod.TRAIN:
                step.run()
                done += 1
        _sync(step)

    run_steps(steps)  # compile + warmup
    snapdir = tempfile.mkdtemp(prefix="veles-snap-bench-")
    snap = SnapshotterToFile(wf, prefix="bench", directory=snapdir,
                             time_interval=0, compression="gz")

    def window(async_on, level=6):
        snap.async_write = async_on
        snap.compression_level = level
        stalls = []
        for _ in range(snaps):
            run_steps(steps)
            t0 = time.perf_counter()
            snap._counter += 1     # unique filenames; run()'s job
            snap.export()
            stalls.append(time.perf_counter() - t0)
        snap.flush()               # untimed backlog drain
        return stalls

    try:
        window(True)               # warm both paths (capture + writer)
        window(False)
        sync_t, async_t, gz9_t, gz6_t = [], [], [], []
        for _ in range(repeats):   # interleaved: contention drift cancels
            sync_t += window(False)
            async_t += window(True)
        for _ in range(2):         # compression-level satellite (sync:
            gz9_t += window(False, level=9)   # the stall IS the write)
            gz6_t += window(False, level=6)
        failure = snap._get_writer().take_failure()
        if failure is not None:
            raise failure
        stats = snap.writer_stats() or {}
    finally:
        snap.stop()
        wf.del_ref(snap)
        shutil.rmtree(snapdir, ignore_errors=True)
    _record("snapshot_stall_sync", sync_t)
    _record("snapshot_stall_async", async_t)
    _record("snapshot_write_gz9", gz9_t)
    _record("snapshot_write_gz6", gz6_t)
    med = statistics.median
    out = {"snapshot_stall_sync_ms": round(med(sync_t) * 1e3, 3),
           "snapshot_stall_async_ms": round(med(async_t) * 1e3, 3),
           "snapshot_stall_speedup": round(med(sync_t) / med(async_t), 2),
           "snapshot_write_gz9_ms": round(med(gz9_t) * 1e3, 3),
           "snapshot_write_gz6_ms": round(med(gz6_t) * 1e3, 3),
           "snapshot_gz6_write_speedup": round(med(gz9_t) / med(gz6_t),
                                               2),
           "snapshot_writer_coalesced": stats.get("coalesced"),
           "snapshot_writer_written": stats.get("written")}
    _stamp("snapshot stage: measured (stall %.1fx, gz9->gz6 %.1fx)"
           % (out["snapshot_stall_speedup"],
              out["snapshot_gz6_write_speedup"]))
    return out


def bench_checkpoint(batch=512, steps=8, snaps=4, repeats=3):
    """Sharded content-addressed checkpoints vs the pickle monolith
    (ISSUE 10): per-checkpoint training-thread stall (async capture on
    both paths), full restore wall time, and the dedupe ratio — bytes a
    re-export of UNCHANGED state writes (shards: zero; pickle: the whole
    blob, every time).  Same interleaved-window methodology as the
    snapshot stage, one fresh subprocess."""
    import shutil
    import tempfile
    from veles_tpu import loader as loader_mod
    from veles_tpu.backends import Device
    from veles_tpu.checkpoint import SnapshotterToShards
    from veles_tpu.prng import RandomGenerator
    from veles_tpu.snapshotter import SnapshotterToFile, restore
    from veles_tpu.znicz.samples import mnist as mnist_sample

    _stamp("checkpoint stage: building mnist step loop")
    wf = mnist_sample.create_workflow(
        loader={"minibatch_size": batch, "n_train": 8 * batch,
                "n_valid": batch, "use_fixture": False,
                "prng": RandomGenerator().seed(3)},
        decision={"max_epochs": 10 ** 9, "silent": True})
    wf.initialize(device=Device(backend="tpu"))
    step = wf.fused_step

    def run_steps(n):
        done = 0
        while done < n:
            wf.loader.run()
            if wf.loader.minibatch_class == loader_mod.TRAIN:
                step.run()
                done += 1
        _sync(step)

    run_steps(steps)  # compile + warmup
    pickle_dir = tempfile.mkdtemp(prefix="veles-ckpt-bench-p-")
    shards_dir = tempfile.mkdtemp(prefix="veles-ckpt-bench-s-")
    pick = SnapshotterToFile(wf, prefix="bench", directory=pickle_dir,
                             time_interval=0, compression="gz")
    shrd = SnapshotterToShards(wf, prefix="bench", directory=shards_dir,
                               time_interval=0)

    def window(snap):
        stalls = []
        for _ in range(snaps):
            run_steps(steps)
            t0 = time.perf_counter()
            snap._counter += 1
            snap.export()
            stalls.append(time.perf_counter() - t0)
        snap.flush()               # untimed backlog drain
        return stalls

    out = {}
    try:
        window(shrd)               # warm both paths (capture + writer)
        window(pick)
        pickle_t, shards_t = [], []
        for _ in range(repeats):   # interleaved: contention drift cancels
            pickle_t += window(pick)
            shards_t += window(shrd)
        for snap in (pick, shrd):
            failure = snap._get_writer().take_failure()
            if failure is not None:
                raise failure

        # dedupe: re-export with NOTHING trained in between
        shrd._counter += 1
        shrd.export()
        shrd.flush()
        trained = dict(shrd._last_write_stats_)
        shrd._counter += 1
        shrd.export()
        shrd.flush()
        unchanged = dict(shrd._last_write_stats_)

        # restore wall time, whole workflow, newest checkpoint each
        t0 = time.perf_counter()
        restore(os.path.join(pickle_dir, "bench_current"))
        pickle_restore = time.perf_counter() - t0
        t0 = time.perf_counter()
        restore(os.path.join(shards_dir, "bench_current"))
        shards_restore = time.perf_counter() - t0

        med = statistics.median
        _record("checkpoint_stall_pickle", pickle_t)
        _record("checkpoint_stall_shards", shards_t)
        out = {"checkpoint_stall_pickle_ms":
               round(med(pickle_t) * 1e3, 3),
               "checkpoint_stall_shards_ms":
               round(med(shards_t) * 1e3, 3),
               "checkpoint_restore_pickle_s": round(pickle_restore, 3),
               "checkpoint_restore_shards_s": round(shards_restore, 3),
               "checkpoint_tensor_bytes": trained.get("bytes_total"),
               "checkpoint_unchanged_rewrite_bytes":
               unchanged.get("bytes_written"),
               "checkpoint_dedupe_saved_bytes":
               (unchanged.get("bytes_total", 0) -
                unchanged.get("bytes_written", 0))}
    finally:
        pick.stop()
        shrd.stop()
        wf.del_ref(pick)
        wf.del_ref(shrd)
        shutil.rmtree(pickle_dir, ignore_errors=True)
        shutil.rmtree(shards_dir, ignore_errors=True)
    _stamp("checkpoint stage: measured (unchanged re-export writes %s "
           "of %s tensor bytes)"
           % (out.get("checkpoint_unchanged_rewrite_bytes"),
              out.get("checkpoint_tensor_bytes")))
    return out


def _autotune_provenance(site, ctx, default=None):
    """What the tuning store resolved for this stage's kernel shape:
    flat config + ``config_source: "tuned"|"default"`` — every kernel
    metric names the config that produced it (ISSUE 13 satellite).
    Provenance must never fail a measurement."""
    try:
        from veles_tpu.autotune import describe
        from veles_tpu.autotune.space import site as _site
        sp = _site(site)
        return describe(site, sp.shape_class(ctx),
                        default if default is not None
                        else dict(sp.default))
    except Exception as exc:            # noqa: BLE001
        return {"config_source": "error: %s" % exc}


def bench_autotune(probe_timeout=90):
    """Persistent kernel/serving config tuning (ISSUE 13).

    (a) CPU end-to-end roundtrip across TWO fresh processes: the first
    tunes a tiny LRN site into a scratch store (every candidate its own
    gated subprocess), the second resolves the persisted winner off
    disk — asserting source == "tuned", the exact stored config, and a
    byte-untouched store (zero re-measurement on warm restart).

    (b) on-device tuning of the shapes the LATER stages dispatch (the
    AlexNet LRN classes, the paged decode kernel, the serving bucket
    ladder) into the shared ``$VELES_AUTOTUNE_DIR`` the orchestrator
    exports to every stage child — so ``pallas_lrn`` & co. resolve
    measured winners instead of hand-picks.  Budget-aware: sites are
    skipped, never truncated mid-measurement.  This process stays off
    JAX throughout: every probe is a child that needs the chip."""
    import subprocess
    _stamp("autotune stage")
    stage_t0 = time.perf_counter()
    repo = os.path.dirname(os.path.abspath(__file__))
    tool = os.path.join(repo, "tools", "autotune.py")
    out = {}

    # -- (a) cross-process roundtrip: tune, restart, resolve ----------
    scratch = cache_dir("veles_autotune_roundtrip")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("VELES_AUTOTUNE_DIR", None)   # the roundtrip owns its store
    t0 = time.perf_counter()
    p1 = subprocess.run(
        [sys.executable, tool, "tune", "--dir", scratch, "--site",
         "lrn", "--ctx", json.dumps({"rows": 256, "c": 32, "n": 5}),
         "--json", "--timeout", "60"],
        capture_output=True, timeout=max(4 * probe_timeout, 300),
        env=env, cwd=repo)
    tune_s = time.perf_counter() - t0
    try:
        winner = json.loads(p1.stdout.decode())["tuned"][0]
    except (ValueError, LookupError):
        raise RuntimeError("autotune roundtrip tune failed: %s"
                           % p1.stderr.decode()[-400:])

    def _store_state():
        return sorted(
            (f, os.path.getmtime(os.path.join(scratch, f)),
             os.path.getsize(os.path.join(scratch, f)))
            for f in os.listdir(scratch))

    before = _store_state()
    p2 = subprocess.run(
        [sys.executable, tool, "resolve", "--dir", scratch, "--site",
         "lrn", "--shape", winner["shape_class"]],
        capture_output=True, timeout=probe_timeout, env=env, cwd=repo)
    res = _last_json_line(p2.stdout.decode()) or {}
    untouched = _store_state() == before
    ok = (res.get("config_source") == "tuned"
          and res.get("config") == winner["config"] and untouched)
    out["autotune_roundtrip_ok"] = bool(ok)
    out["autotune_roundtrip_speedup"] = winner.get("speedup")
    out["autotune_roundtrip_winner"] = winner.get("config")
    out["autotune_roundtrip_tune_s"] = round(tune_s, 2)
    if not ok:
        out["autotune_roundtrip_detail"] = (
            "source=%r config_equal=%r store_untouched=%r"
            % (res.get("config_source"),
               res.get("config") == winner.get("config"), untouched))

    # -- (b) tune what the later kernel stages will dispatch ----------
    tune_dir = os.environ.get("VELES_AUTOTUNE_DIR")
    if not tune_dir:
        return out
    from veles_tpu.autotune.runner import tune_site
    from veles_tpu.autotune.store import TuningStore
    store = TuningStore(tune_dir)
    budget = dict(STAGE_PLAN)["autotune"] - 90
    # LRN first (it feeds the pallas_lrn_speedup acceptance); the
    # serving ladder and the paged decode kernel after; the second LRN
    # class last (same kernel, diminishing returns if budget is tight)
    plan = [
        ("lrn", {"rows": 2048, "c": 96, "n": 5}),
        ("serving.bucket_ladder", {"max_batch": 16, "dim": 64,
                                   "requests": 48}),
        ("paged_attention", {"batch": 2, "heads": 2, "d": 16,
                             "length": 48}),
        ("lrn", {"rows": 2048, "c": 256, "n": 5}),
    ]
    tuned, skipped = {}, []
    for site_name, ctx in plan:
        left = budget - (time.perf_counter() - stage_t0)
        if left < 2.5 * probe_timeout:
            skipped.append(site_name)
            continue
        try:
            rec = tune_site(site_name, ctx or None, store=store,
                            timeout=probe_timeout, log_fn=_stamp)
        except Exception as exc:        # noqa: BLE001 — keep tuning
            tuned["%s!error" % site_name] = str(exc)[:200]
            continue
        if rec is not None:
            tuned["%s/%s" % (site_name, rec["shape_class"])] = {
                "config": rec["config"],
                "speedup": rec["speedup"], "gate": rec["gate"]}
    out["autotune_tuned"] = tuned
    if skipped:
        out["autotune_skipped"] = skipped
    return out


def bench_liveness():
    """Stage 0 gate: the platform must be ``tpu`` and one tiny jitted
    matmul must come back right.  If THIS fails there is no chip to
    measure, and the orchestrator reports immediately instead of burning
    its budget stage by stage (or timing the CPU under a device's
    name)."""
    import numpy
    import jax
    import jax.numpy as jnp
    _stamp("liveness probe")
    t0 = time.perf_counter()
    device = jax.devices()[0]
    if device.platform != "tpu":
        raise SystemExit("bench: JAX's platform is %r, not 'tpu': "
                         "nothing to measure" % device.platform)
    x = jnp.ones((512, 512), jnp.float32)
    v = float(numpy.asarray(jax.jit(lambda a: a @ a)(x)[0, 0]))
    if v != 512.0:
        raise SystemExit("bench: liveness matmul produced %r" % v)
    return {"liveness_s": round(time.perf_counter() - t0, 1),
            "platform": device.platform,
            "device_kind": device.device_kind,
            "device_count": len(jax.devices())}


def _stage_main(stage):
    """Subprocess entry: run one isolated stage, print its JSON line."""
    if stage == "liveness":
        out = bench_liveness()
    elif stage == "alexnet_f32":
        ips = bench_alexnet_scan(batch=BATCH)
        out = {"alexnet_f32_images_per_sec": round(ips, 1)}
    elif stage == "alexnet_bf16":
        ips = bench_alexnet_scan(batch=BATCH, compute_dtype="bfloat16",
                                 name="alexnet_bf16")
        out = {"alexnet_bf16_images_per_sec": round(ips, 1)}
    elif stage == "alexnet_step":
        out = bench_alexnet_step(batch=BATCH)
    elif stage == "mnist":
        out = {"mnist_anchor_images_per_sec": round(bench_mnist(), 1)}
    elif stage == "flash_attention":
        out = bench_flash_attention()
    elif stage == "flagship":
        out = bench_flagship()
    elif stage == "window_attention":
        out = bench_window_attention()
    elif stage == "pallas_lrn":
        ips = bench_alexnet_scan(batch=BATCH, use_pallas_lrn=True,
                                 repeats=3, name="alexnet_pallas_lrn")
        out = {"pallas_lrn_images_per_sec": round(ips, 1),
               "pallas_lrn_config": {
                   cls: _autotune_provenance(
                       "lrn", {"c": c, "n": 5, "rows": 2048})
                   for cls, c in (("c96_n5", 96), ("c256_n5", 256))}}
    elif stage == "autotune":
        out = bench_autotune()
    elif stage == "precise_gemm":
        out = {"precise_gemm": bench_precise_gemm()}
    elif stage == "serving":
        out = bench_serving()
    elif stage == "observability":
        out = bench_observability()
    elif stage == "snapshot":
        out = bench_snapshot()
    elif stage == "checkpoint":
        out = bench_checkpoint()
    elif stage == "cold_start":
        out = bench_cold_start()
    elif stage == "decode":
        out = bench_decode()
    elif stage == "prefix_reuse":
        out = bench_prefix_reuse()
    elif stage == "speculative":
        out = bench_speculative()
    elif stage == "quantized":
        out = bench_quantized()
    elif stage == "flight_recorder":
        out = bench_flight_recorder()
    elif stage == "fleet":
        out = bench_fleet()
    elif stage == "fleet_prefix":
        out = bench_fleet_prefix()
    elif stage == "chaos":
        out = bench_chaos()
    elif stage == "graph_compile":
        out = bench_graph_compile()
    else:
        raise SystemExit("unknown stage %r" % stage)
    out["spread"] = SPREAD
    print(json.dumps(out))


# (stage, per-stage timeout [s]) in run order: the liveness gate, then
# the HEADLINE scan stages, then diagnostics, then the optional
# hand-kernel stages LAST, so a budget that runs out costs the optional
# tail and not the headline.  The global budget below bounds the sum.
STAGE_PLAN = [
    ("liveness", 180),
    ("alexnet_f32", 1200),
    ("alexnet_bf16", 900),
    ("alexnet_step", 600),
    ("mnist", 600),
    # flash compiles TWO chain-unrolled train jits; a contended first
    # compile can take minutes — don't let the cap kill the round's
    # hand-kernel metric mid-compile
    ("flash_attention", 420),
    # the tuner runs BEFORE the kernel stages it feeds: winners land in
    # the shared $VELES_AUTOTUNE_DIR, so pallas_lrn below dispatches
    # measured configs.  Also proves the cross-process roundtrip on CPU
    # (tune in one process, resolve untouched in a second)
    ("autotune", 420),
    # pallas_lrn runs the SAME 32-epoch scan depth as the headline (a
    # mixed-depth ratio would understate the kernel by the ~19 %
    # dispatch amortization), so its compile+timed block needs more cap
    ("pallas_lrn", 420),
    ("precise_gemm", 300),
    # trailing bonus metrics: the modern-model (MoE transformer) path
    # and the sliding-window band; skipped harmlessly when the budget
    # is exhausted
    ("flagship", 420),
    ("window_attention", 420),
    # the serving-path number (bucketed scheduler vs seed per-request
    # dispatch) — cheap, but still optional-tail so a tight budget
    # never trades a headline training stage for it
    ("serving", 300),
    # tracing+metrics+profiler overhead on the MNIST step loop (must
    # stay < 5%; ISSUE 2 acceptance) — optional tail like serving
    ("observability", 300),
    # per-snapshot step-loop stall, sync vs async write + the gz9->gz6
    # compression-level delta (ISSUE 4 acceptance: stall >= 5x)
    ("snapshot", 300),
    # sharded content-addressed checkpoints vs the pickle monolith
    # (ISSUE 10): save stall, restore wall time, dedupe bytes on an
    # unchanged re-export (shards must write ~zero) — fresh subprocess
    ("checkpoint", 420),
    # process-restart cost with the persistent executable cache off /
    # cold / warm (ISSUE 5 acceptance: warm serving warmup >= 2x) —
    # six fresh subprocesses, each its own import+compile, so this
    # stage needs real wall clock despite doing almost no device work
    ("cold_start", 420),
    # token-level continuous batching vs request-granularity gangs on
    # the flagship decode path (ISSUE 6 acceptance: tok/s up, zero
    # steady-state recompiles across a warm restart) — two fresh
    # subprocesses (cold populates the cache, warm IS the restart)
    ("decode", 420),
    # chunked prefill + prefix-aware KV reuse (ISSUE 14): short-request
    # TTFT p99 >= 3x under head-of-line long prefills, > 50% block
    # dedupe across shared-system-prompt sequences with oracle-bitwise
    # tokens, warm restart compiles == 0 including the chunk executable
    ("prefix_reuse", 300),
    # speculative decoding (ISSUE 15): plain vs draft-and-verify tok/s
    # at each depth with a tunable drafter agreement rate — bitwise
    # oracle tokens, the acceptance crossover (high agreement wins,
    # low agreement loses), warm restart compiles == 0 including the
    # @draft/@verify executables; three fresh subprocesses over one
    # cache dir
    ("speculative", 360),
    # quantized serving (ISSUE 18): int8 KV pools vs f32 at a fixed
    # pool byte budget — >= 2x concurrent sessions, improved tok/s,
    # flagship logit RMSE <= 1e-2, bitwise oracle tokens, warm restart
    # compiles == 0 including the dtype-tagged executables; two fresh
    # subprocesses over one cache dir
    ("quantized", 420),
    # flight-recorder overhead gate (ISSUE 17): recorder-on vs
    # recorder-off decode tok/s interleaved (< 2% acceptance), one
    # organically captured p99-anomaly timeline, and the shared-prefix
    # attribution coverage (phase shares >= 95% of wall-clock TTFT);
    # two fresh subprocesses over one cache dir
    ("flight_recorder", 420),
    # multi-replica serving fleet: scaling efficiency, SIGKILL
    # kill-recovery (zero non-429 failures, warm compiles==0 respawn)
    # and rolling-update error rate (ISSUE 7) — one fresh subprocess
    # owning router + N replica grandchildren under a hard cap
    ("fleet", 420),
    # cache-aware routing vs least-loaded (ISSUE 16): two fresh fleets
    # serving a shared-prefix persona workload — affinity must beat
    # baseline on prefix-hit rate AND TTFT p99; one fresh subprocess
    ("fleet_prefix", 420),
    # seeded chaos drill (ISSUE 12): scripted SIGKILL / black-hole /
    # truncation / SIGSTOP against the real-package fleet under a
    # deadline-carrying open loop — zero failed (non-backpressure,
    # non-504) responses and the kill-recovery seconds; one fresh
    # subprocess owning the fault-injected replica grandchildren
    ("chaos", 420),
    # whole-workflow compilation (ISSUE 8): the non-standard two-branch
    # DAG interpreted vs traced (>= 1.5x acceptance), the standard MNIST
    # topology traced vs hand-fused (no-regression proof), and the
    # cold/warm traced-restart pair over one cache dir (warm compiles
    # == 0) — four fresh subprocesses a la decode/fleet
    ("graph_compile", 420),
]


def _orchestrate():
    """JAX-free parent: run every stage as a killable subprocess under a
    global wall-clock budget, then print the ONE schema-whole JSON line
    from whatever completed."""
    # the final JSON line must print inside the budget, even if that
    # means skipping the trailing optional stages
    budget = float(os.environ.get("VELES_BENCH_BUDGET", 1700))
    deadline = time.perf_counter() + budget
    # one shared tuning store for the whole round: the autotune stage
    # writes winners here, every later stage child inherits the env and
    # dispatches them
    os.environ.setdefault("VELES_AUTOTUNE_DIR", AUTOTUNE_STORE)
    results, errors = {}, {}
    for stage, cap in STAGE_PLAN:
        remaining = deadline - time.perf_counter()
        if remaining < 90:
            errors[stage] = "skipped: bench budget exhausted"
            _stamp("%s skipped (budget exhausted)" % stage)
            continue
        timeout = min(cap, remaining)
        _stamp("stage %s (subprocess, timeout %ds)" % (stage, timeout))
        line, err = _stage_subprocess(stage, timeout)
        if err:
            errors[stage] = err
            print("bench: %s" % err, file=sys.stderr)
        if line:
            SPREAD.update(line.pop("spread", {}) or {})
            results.update({k: v for k, v in line.items()
                            if v is not None})
        if stage == "liveness" and "liveness_s" not in results:
            # the gate itself failed: report NOW, don't burn the budget
            print(json.dumps({
                "metric": "alexnet_train_images_per_sec_per_chip",
                "value": None, "unit": "images/sec/chip",
                "vs_baseline": None, "spread": SPREAD,
                "error": "no TPU (liveness probe failed): %s"
                         % errors.get(stage)}), flush=True)
            sys.exit(2)

    scan_ips = results.pop("alexnet_f32_images_per_sec", None)
    line = {"metric": "alexnet_train_images_per_sec_per_chip",
            "value": scan_ips, "unit": "images/sec/chip",
            "vs_baseline": round(scan_ips / ALEXNET_BASELINE, 3)
            if scan_ips else None}
    line.update(results)
    bf16_ips = results.get("alexnet_bf16_images_per_sec")
    if bf16_ips:
        line["bf16_vs_baseline"] = round(bf16_ips / ALEXNET_BASELINE, 3)
        if scan_ips:
            line["bf16_speedup_vs_f32"] = round(bf16_ips / scan_ips, 3)
    flops_per_step = line.pop("flops_per_step", None)
    if flops_per_step:
        fpi = flops_per_step / BATCH
        line["flops_per_image"] = round(fpi / 1e9, 3)
        for tag, ips in (("f32", scan_ips), ("bf16", bf16_ips)):
            if ips:
                line["%s_model_tflops_per_sec" % tag] = round(
                    fpi * ips / 1e12, 2)
                line["%s_mfu_vs_bf16_peak" % tag] = round(
                    fpi * ips / peak_bf16_flops(line["device_kind"]), 4)
    mnist_ips = line.get("mnist_anchor_images_per_sec")
    if mnist_ips:
        line["mnist_vs_anchor"] = round(mnist_ips / MNIST_ANCHOR, 3)
    # keep the RAW pallas number in the record (round-over-round
    # comparability) and derive the speedup beside it when possible
    lrn_ips = line.get("pallas_lrn_images_per_sec")
    if lrn_ips and scan_ips:
        line["pallas_lrn_speedup"] = round(lrn_ips / scan_ips, 3)
    fl, orc = (line.get("flash_attention_train_s"),
               line.get("attention_oracle_train_s"))
    if fl and orc:
        line["flash_attention_speedup"] = round(orc / fl, 3)
    wt, fc = (line.get("window_attention_train_s"),
              line.get("full_causal_train_s"))
    if wt and fc:
        line["window_attention_speedup"] = round(fc / wt, 3)
    if errors:
        line["stage_errors"] = errors
    line["spread"] = SPREAD
    print(json.dumps(line), flush=True)
    # a stage that failed or was skipped is a failed run, whatever else
    # was measured: the record says which, the exit code says so
    return 1 if errors else 0


if __name__ == "__main__":
    if "--stage" in sys.argv:
        _stage_main(sys.argv[sys.argv.index("--stage") + 1])
        sys.exit(0)
    sys.exit(_orchestrate())
