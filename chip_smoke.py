"""chip_smoke.py: the quickest proof that the system starts, and computes
right, on the TPU, through the entry points its users call.

    python chip_smoke.py              # one chip: trainer, kernels, decode
                                      # server, export -> serve
    python chip_smoke.py --multichip  # four chips: the sharded train steps
                                      # and ring flash attention, each
                                      # against one chip, and nothing else

One process does everything (a chip belongs to one process at a time).
Each phase prints one line; the LAST line of standard output is one JSON
object, ``{"ok": true, "device": {"platform": "tpu", "kind": ...,
"count": N}}``.  With no TPU the script stops before the first phase and
exits 2; a phase that fails makes the exit code 1 and ``"ok": false``.
Sizes are the constants below (full widths of models the repo supports;
depth, data and step counts cut), not options.  The seconds it prints
are smoke timings of one cold run, not metrics.
"""

import argparse
import gc
import json
import math
import os
import sys
import tempfile
import time
import traceback
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

SEED = 1234
BACKEND = "tpu"

#: the ImageNet AlexNet sample at its full width (samples/alexnet.py:
#: 227x227x3, 1000 classes), batch 256 as the benchmark's cells run it;
#: the synthetic dataset is cut to eight train minibatches and the run to
#: a few epochs
ALEXNET = {"batch": 256, "side": 227, "n_classes": 1000, "n_train": 2048,
           "n_valid": 256, "epochs": 3}
#: (--mode, --compute-dtype): per-step fused steps with the prefetcher on,
#: and the epoch-scan dispatch, each in f32 and in bf16 compute
TRAINER_RUNS = (("fused", "float32"), ("scan", "float32"),
                ("fused", "bfloat16"), ("scan", "bfloat16"))

#: the widest flagship the repo runs (the old record's 249,902 tok/s)
#: behind the README "Decode quickstart" geometry
FLAGSHIP = {"stages": 4, "experts": 4, "d": 256, "heads": 8,
            "hidden": 1024, "vocab": 1024}
DECODE_GEOMETRY = {"max_batch": 16, "block_size": 16,
                   "max_prompt_len": 128, "max_new_tokens": 128}
#: (prompt tokens, new tokens) per request; sent concurrently
DECODE_REQUESTS = ((3, 4), (17, 8), (64, 16), (128, 128), (40, 64),
                   (100, 32), (9, 100), (128, 1))
#: requests short enough to ALSO replay through generate_reference itself
#: (a growing T is a new shape, and so a new compile, per token)
ORACLE_MAX_NEW = 8
#: Server and reference are compared at ``--precision-level 2`` (f32
#: matmuls as six bf16 MXU passes), on both sides.  At level 0 the
#: comparison decides nothing: the random-weight flagship is chaotic
#: (logit std ~200, top-1 expert routing), so one-pass bf16 rounding,
#: which differs between two programs of different shape, flips routing
#: decisions and moves logits by whole standard deviations — first chip
#: run of PR 21, level 0: 304 of 353 tokens equal, the others nowhere
#: near a tie.  Kernels, paging and scheduling are the same at either
#: level; only XLA's matmul passes change.
DECODE_PRECISION_LEVEL = 2
#: A served token may differ from the reference's argmax only where the
#: reference itself is this close to a tie, as a share of the logits'
#: standard deviation (ROADMAP D13: bit equality with the reference is
#: not a kernel's contract on a real chip; the paged kernel sums on the
#: VPU, the dense reference on the MXU, and the orders differ).
TOKEN_MARGIN_TOLERANCE = 1e-3

#: kernel-vs-reference bounds on the chip, for O(1) operands.  f32 dots
#: inside the flash kernels and in the XLA reference both round operands
#: to bf16 on the MXU (relative 2^-9 per product); the paged kernels and
#: their references multiply on the VPU in f32
FLASH_TOLERANCE = 2e-2
FLASH_WINDOW = 512
PAGED_TOLERANCE = 1e-4

MNIST = {"epochs": 1, "max_batch": 64, "requests": (1, 3, 8, 64)}
#: share of the served predictions that must be the labels, on the
#: validation samples the largest request sends (chance is 0.1)
MNIST_MIN_ACCURACY = 0.7

#: --multichip: three steps of the same step over each layout
MULTICHIP_STEPS = 3
MULTICHIP_MESHES = ("data=4", "data=2,model=2")
#: the same seed gives the same minibatches, weights and dropout masks;
#: what differs across layouts is the order of f32 sums (and the
#: all-reduce), so losses near ln(1000) agree far inside this
MULTICHIP_LOSS_TOLERANCE = 2e-3
RING = {"batch": 1, "t": 2048, "heads": 8, "d": 64}


class SmokeFailure(Exception):
    """A check that makes going on with the phase pointless."""


class Phase:
    """One phase's checks: ``require`` stops the phase, ``check`` records
    and goes on (so a CPU rehearsal runs the whole control flow and fails
    on the device checks alone)."""

    def __init__(self):
        self.passed, self.failed = [], []

    def check(self, ok, what):
        (self.passed if ok else self.failed).append(what)
        return bool(ok)

    def require(self, ok, what):
        if not self.check(ok, what):
            raise SmokeFailure(what)


def _on_tpu(tree):
    """Every array of ``tree`` lives on a TPU device.  Hard-wired, like
    every device check here: whatever backend a rehearsal steers the
    entry points to, only a TPU can make this script say ok."""
    import jax
    return all(d.platform == "tpu"
               for leaf in jax.tree_util.tree_leaves(tree)
               for d in leaf.devices())


# -- A1: the trainer ----------------------------------------------------------

def _alexnet_cli(mode, dtype, backend, extra=()):
    """The ImageNet AlexNet sample through the command line's own driver
    (``python -m veles_tpu veles_tpu/znicz/samples/alexnet.py ...``),
    stopped after initialize so the caller can look before it runs."""
    from veles_tpu.__main__ import Main
    a = ALEXNET
    main = Main([
        os.path.join(REPO, "veles_tpu", "znicz", "samples", "alexnet.py"),
        "root.alexnet.loader.minibatch_size=%d" % a["batch"],
        "root.alexnet.loader.side=%d" % a["side"],
        "root.alexnet.loader.n_classes=%d" % a["n_classes"],
        "root.alexnet.loader.n_train=%d" % a["n_train"],
        "root.alexnet.loader.n_valid=%d" % a["n_valid"],
        "root.alexnet.decision.max_epochs=%d" % a["epochs"],
        "root.alexnet.decision.silent=True",
        "-a", backend, "--mode", mode, "--compute-dtype", dtype,
        "--random-seed", str(SEED), "--dry-run", "init"] + list(extra))
    rc = main.run()
    if rc:
        raise SmokeFailure("CLI driver returned %r at --dry-run init" % rc)
    return main


def _fixed_minibatch_loss(wf):
    """Eval-mode loss (no dropout) of the first ``batch`` TRAIN samples
    under the step's current parameters, through the step's own
    evaluation executable."""
    import numpy
    from veles_tpu import loader as loader_mod
    step, ld = wf.fused_step, wf.loader
    b = ld.max_minibatch_size
    first_train = ld.class_end_offsets[loader_mod.VALID]
    idx = numpy.arange(first_train, first_train + b, dtype=ld.INDEX_DTYPE)
    if hasattr(step, "_eval_scan_"):
        _, losses = step._eval_scan_(
            step._data_dev_, step._y_dev_, step._params_,
            step._macc_init(), idx[None], numpy.full(1, b, numpy.int32))
        return float(losses[0])
    _, loss, _ = step._eval_step_g_(
        step._data_dev_, step._y_dev_, step._params_, step._macc_init(),
        idx, numpy.int32(b))
    return float(loss)


def _train_and_check(phase, main, tag):
    """Run the initialized workflow to its end through the Launcher and
    check what the trainer phase promises."""
    import jax
    import jax.numpy as jnp
    wf, step = main.workflow, main.workflow.fused_step
    before_params = jax.tree.map(jnp.array, step._params_)
    before = _fixed_minibatch_loss(wf)
    main.launcher.run()
    jax.block_until_ready(step._params_)
    after = _fixed_minibatch_loss(wf)
    phase.require(wf.is_finished, "%s: workflow ran to its end point" % tag)
    phase.require(math.isfinite(before) and math.isfinite(after),
                  "%s: finite loss (%r -> %r)" % (tag, before, after))
    moved = jax.tree.map(lambda a, b: bool(jnp.any(a != b)),
                         before_params, step._params_)
    phase.require(all(jax.tree_util.tree_leaves(moved)),
                  "%s: every parameter array changed" % tag)
    phase.check(after < before,
                "%s: loss on a fixed minibatch fell (%.6f -> %.6f)"
                % (tag, before, after))
    phase.check(_on_tpu((step._params_, step._opt_, step._macc_,
                         step._data_dev_, step._y_dev_)),
                "%s: every array of the step on the TPU device" % tag)


def phase_trainer(phase, backend):
    a = ALEXNET
    for mode, dtype in TRAINER_RUNS:
        tag = "%s/%s" % (mode, dtype)
        main = _alexnet_cli(mode, dtype, backend)
        wf = main.workflow
        per_epoch = a["n_train"] // a["batch"]
        if mode == "fused":
            phase.require(getattr(wf, "prefetcher_", None) is not None,
                          "%s: %d per-step fused steps, prefetcher on"
                          % (tag, a["epochs"] * per_epoch))
        else:
            phase.passed.append("%s: %d epoch-scan dispatch(es) of %d "
                                "steps" % (tag, a["epochs"], per_epoch))
        _train_and_check(phase, main, tag)
        main.launcher.stop()
        del main, wf
        gc.collect()    # let go of this run's device arrays


# -- C: the kernels, each against its reference -------------------------------

def _max_err(a, b):
    import jax.numpy as jnp
    return float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                 - b.astype(jnp.float32))))


def _compiled(fn, *args):
    """``fn(*args)`` through one compile -> (result, whether the compiled
    program contains a Mosaic kernel)."""
    import jax
    exe = jax.jit(fn).lower(*args).compile()
    return exe(*args), "tpu_custom_call" in exe.as_text()


def phase_kernels(phase, backend):
    import jax
    import jax.numpy as jnp
    import numpy
    from veles_tpu.parallel.ring import attention_reference
    from veles_tpu.znicz import gemm, lrn, paged_attention as pa
    from veles_tpu.znicz.flash_attention import flash_attention
    rng = numpy.random.RandomState(SEED)

    # flash attention, forward and backward, plain and sliding-window
    r = RING
    q, k, v = (jnp.asarray(rng.standard_normal(
        (2, r["t"], r["heads"], r["d"])) * 0.5, jnp.float32)
        for _ in range(3))
    for window in (None, FLASH_WINDOW):
        def loss(attend, q, k, v):
            return jnp.sum(attend(q, k, v) ** 2)

        def flash(q, k, v, window=window):
            return flash_attention(q, k, v, causal=True, window=window)

        def oracle(q, k, v, window=window):
            with jax.default_matmul_precision("highest"):
                return attention_reference(q, k, v, causal=True,
                                           window=window)
        tag = "flash_attention T%d D%d window=%s" % (r["t"], r["d"],
                                                     window)
        out, fwd_kernel = _compiled(flash, q, k, v)
        err = _max_err(out, jax.jit(oracle)(q, k, v))
        phase.require(err < FLASH_TOLERANCE,
                      "%s forward within %g of the oracle (max err %.2e)"
                      % (tag, FLASH_TOLERANCE, err))
        grads, bwd_kernel = _compiled(
            jax.grad(lambda *a: loss(flash, *a), (0, 1, 2)), q, k, v)
        ref = jax.jit(jax.grad(lambda *a: loss(oracle, *a), (0, 1, 2)))
        gerr = max(_max_err(a, b) / max(float(jnp.max(jnp.abs(b))), 1e-6)
                   for a, b in zip(grads, ref(q, k, v)))
        phase.require(gerr < FLASH_TOLERANCE,
                      "%s gradients within %g of the oracle's, relative "
                      "to their max (%.2e)" % (tag, FLASH_TOLERANCE, gerr))
        phase.check(fwd_kernel and bwd_kernel,
                    "%s: tpu_custom_call in the compiled forward and "
                    "backward" % tag)

    # the paged kernels at the smoke server's geometry: decode, prefill
    # chunk and speculative verify, over f32 and int8 pools
    g, f = DECODE_GEOMETRY, FLAGSHIP
    heads, hd = f["heads"], f["d"] // f["heads"]
    bs = g["block_size"]
    nb = (g["max_prompt_len"] + g["max_new_tokens"]) // bs
    batch = g["max_batch"]
    n_pool = batch * nb + 1
    kp, vp = (jnp.asarray(rng.standard_normal((n_pool, bs, heads, hd)),
                          jnp.float32) for _ in range(2))
    table = jnp.asarray(1 + rng.permutation(batch * nb).reshape(batch, nb),
                        jnp.int32)
    context, span = nb * bs, 4
    lengths = jnp.asarray(rng.randint(0, context - span, batch), jnp.int32)
    lengths = lengths.at[0].set(0).at[1].set(context - span)
    chunk = min(32, context // 4)       # a prefill chunk, partly padding
    start, length = context // 4, context // 4 + chunk - 2
    for kv in ("f32", "int8"):
        if kv == "int8":
            (kq, ks), (vq, vs) = pa.quantize_pool(kp), pa.quantize_pool(vp)
            pools, scales = (kq, vq), {"k_scales": ks, "v_scales": vs}
        else:
            pools, scales = (kp, vp), {}
        q1 = jnp.asarray(rng.standard_normal((batch, heads, hd)),
                         jnp.float32)
        qc = jnp.asarray(rng.standard_normal((chunk, heads, hd)),
                         jnp.float32)
        qs = jnp.asarray(rng.standard_normal((batch, span, heads, hd)),
                         jnp.float32)
        cases = (
            ("paged_attention", pa.paged_attention,
             pa.paged_attention_reference, (q1, *pools, table, lengths)),
            ("paged_prefill_attention", pa.paged_prefill_attention,
             pa.paged_prefill_attention_reference,
             (qc, *pools, table[2], jnp.int32(start), jnp.int32(length))),
            ("paged_verify_attention", pa.paged_verify_attention,
             pa.paged_verify_attention_reference,
             (qs, *pools, table, lengths)))
        for name, kernel, reference, args in cases:
            def run(*a, fn=kernel):
                return fn(*a, **scales)

            def ref(*a, fn=reference):
                return fn(*a, **scales)
            out, has_kernel = _compiled(run, *args)
            err = _max_err(out, jax.jit(ref)(*args))
            phase.require(err < PAGED_TOLERANCE,
                          "%s/%s within %g of its reference (max err "
                          "%.2e)" % (name, kv, PAGED_TOLERANCE, err))
            phase.check(has_kernel,
                        "%s/%s: tpu_custom_call in the compiled program"
                        % (name, kv))

    # the GEMM and LRN hand kernels
    a, b = (jnp.asarray(rng.standard_normal((512, 512)), jnp.float32)
            for _ in range(2))
    exact = numpy.asarray(a, numpy.float64) @ numpy.asarray(b,
                                                            numpy.float64)
    for level in (0, 1, 2):
        err = float(numpy.max(numpy.abs(numpy.asarray(
            gemm.precise_matmul(a, b, level)) - exact)))
        phase.require(err < 1e-3, "precise_matmul level %d within 1e-3 of "
                      "float64 (max err %.2e)" % (level, err))
    wq, ws = gemm.quantize_weight(b)
    err = _max_err(gemm.quantized_matmul(a, wq, ws),
                   gemm.quantized_matmul_reference(a, wq, ws))
    phase.require(err < 1e-2, "quantized_matmul int8 within 1e-2 of its "
                  "reference (max err %.2e)" % err)
    x = jnp.asarray(rng.uniform(-1, 1, (8, 27, 27, 96)), jnp.float32)
    args = (5, 1e-4, 0.75, 2.0)
    err = _max_err(lrn.pallas_lrn(x, *args), lrn.lrn_mxu(x, *args))
    gerr = _max_err(
        jax.grad(lambda x: jnp.sum(lrn.pallas_lrn(x, *args) ** 2))(x),
        jax.grad(lambda x: jnp.sum(lrn.lrn_mxu(x, *args) ** 2))(x))
    phase.require(err < 1e-3 and gerr < 1e-3,
                  "pallas_lrn forward and gradient within 1e-3 of the "
                  "MXU-band form (max err %.2e / %.2e)" % (err, gerr))


# -- A2: the decode server ----------------------------------------------------

def _http_json(url, payload=None, timeout=600):
    data = None if payload is None else json.dumps(payload).encode()
    request = urllib.request.Request(
        url, data=data, headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(request, timeout=timeout) as response:
        return json.loads(response.read())


def phase_decode_server(phase, backend):
    from veles_tpu.backends import Device
    Device(backend=backend, precision_level=DECODE_PRECISION_LEVEL)
    try:
        _serve_and_compare(phase)
    finally:
        Device(backend=backend, precision_level=0)


def _serve_and_compare(phase):
    import concurrent.futures
    import jax
    import jax.numpy as jnp
    import numpy
    from veles_tpu.serving import InferenceServer
    from veles_tpu.znicz.samples.flagship import (FlagshipDecodeModel,
                                                  generate_reference,
                                                  reference_logits)
    f, g = FLAGSHIP, DECODE_GEOMETRY
    model = FlagshipDecodeModel(seed=SEED, **f)
    rng = numpy.random.RandomState(SEED)
    prompts = [[int(t) for t in rng.randint(0, f["vocab"], n)]
               for n, _ in DECODE_REQUESTS]
    server = InferenceServer({"flagship": model}, port=0, **g)
    try:
        scheduler = server.registry.get("flagship").scheduler
        stats = scheduler.stats()
        phase.require(stats["ready"] and stats["compiles"]
                      == 1 + len(stats["buckets"]),
                      "decode step and all %d prefill buckets warm before "
                      "the socket opened" % len(stats["buckets"]))
        phase.check("tpu_custom_call" in scheduler._decode_exe.as_text(),
                    "tpu_custom_call (compiled paged_attention) in the "
                    "decode step the server runs")
        phase.check(_on_tpu((scheduler._k_pools, scheduler._v_pools,
                             model.params)),
                    "KV pools and weights on the TPU device")
        url = "%s/api/flagship/generate" % server.url
        with concurrent.futures.ThreadPoolExecutor(
                len(prompts)) as pool:
            answers = list(pool.map(
                lambda job: _http_json(url, {"prompt": job[0],
                                             "max_new_tokens": job[1][1]}),
                zip(prompts, DECODE_REQUESTS)))
        kv = _http_json("%s/api/flagship/kv" % server.url)
        phase.require(kv["integrity"] == [],
                      "GET /api/flagship/kv: integrity list empty")
        phase.require(scheduler.stats()["post_warmup_compiles"] == 0,
                      "no compile after warm-up")
    finally:
        server.stop()
    server._thread.join(30)
    scheduler._worker.join(30)
    phase.require(not server._thread.is_alive()
                  and not scheduler._worker.is_alive(),
                  "stop(): listener and decode worker gone")
    for (n, new), answer in zip(DECODE_REQUESTS, answers):
        phase.require(len(answer["tokens"]) == new,
                      "request (%d prompt, %d new): %d tokens back"
                      % (n, new, len(answer["tokens"])))

    # the reference, in this process, on this device: one teacher-forced
    # dense forward over every request's prompt + served tokens, padded
    # to the longest (causal: a row never sees its padding)
    t_max = g["max_prompt_len"] + g["max_new_tokens"]
    padded = numpy.zeros((len(prompts), t_max), numpy.int32)
    for i, (prompt, answer) in enumerate(zip(prompts, answers)):
        seq = prompt + answer["tokens"]
        padded[i, :len(seq)] = seq
    # (weights as an argument: closed over, they would be baked into the
    # executable as constants, as they are in the server's own)
    logits = numpy.asarray(jax.jit(jax.vmap(
        lambda params, t: reference_logits(params, t, heads=f["heads"]),
        in_axes=(None, 0)))(model.params, jnp.asarray(padded)))
    phase.require(numpy.isfinite(logits).all(), "reference logits finite")
    logit_std = float(logits.std())
    differing, worst = 0, 0.0
    for i, ((n, new), answer) in enumerate(zip(DECODE_REQUESTS, answers)):
        for j, token in enumerate(answer["tokens"]):
            row = logits[i, n + j - 1]
            if int(row.argmax()) != token:
                differing += 1
                margin = float(row.max() - row[token])
                worst = max(worst, margin)
                print("  request %d (%d prompt): token %d is %d, the "
                      "reference's argmax is %d; its margin over the "
                      "served token is %.4f (top-2 margin %.4f)"
                      % (i, n, j, token, int(row.argmax()), margin,
                         float(numpy.diff(numpy.sort(row)[-2:])[0])))
    total = sum(new for _, new in DECODE_REQUESTS)
    tolerance = TOKEN_MARGIN_TOLERANCE * logit_std
    phase.require(worst <= tolerance,
                  "%d/%d served tokens are the teacher-forced reference's "
                  "argmax at precision level %d; the rest sit within %.4f "
                  "of it (tolerance %g x logit_std %.1f = %.4f)"
                  % (total - differing, total, DECODE_PRECISION_LEVEL,
                     worst, TOKEN_MARGIN_TOLERANCE, logit_std, tolerance))
    # and the oracle itself, free-running, on the short requests
    for i, (prompt, (n, new), answer) in enumerate(
            zip(prompts, DECODE_REQUESTS, answers)):
        if new > ORACLE_MAX_NEW:
            continue
        oracle = generate_reference(model.params, prompt, new,
                                    heads=f["heads"])
        same = 0
        while same < new and oracle[same] == answer["tokens"][same]:
            same += 1
        # past a near-tie the two histories differ, and so may every later
        # token: what is bounded is the tie at the first difference
        gap = 0.0 if same == new else abs(float(
            logits[i, n + same - 1][oracle[same]]
            - logits[i, n + same - 1][answer["tokens"][same]]))
        phase.require(gap <= tolerance,
                      "generate_reference (%d prompt, %d new): %d/%d "
                      "tokens equal%s" % (n, new, same, new,
                                          "" if same == new else
                                          ", then a %.4f near-tie" % gap))


# -- A3: export -> serve ------------------------------------------------------

def phase_export_serve(phase, backend):
    import jax
    import numpy
    from veles_tpu.__main__ import Main
    from veles_tpu.export import export_model
    from veles_tpu.export.model import forward_fn
    from veles_tpu.serving import InferenceServer
    from veles_tpu.serving.scheduler import bucket_sizes
    main = Main([
        os.path.join(REPO, "veles_tpu", "znicz", "samples", "mnist.py"),
        "root.mnist.decision.max_epochs=%d" % MNIST["epochs"],
        "root.mnist.decision.silent=True",
        "-a", backend, "--compute-dtype", "float32",
        "--random-seed", str(SEED)])
    phase.require(main.run() == 0, "MNIST sample trained %d epoch(s) "
                  "through the CLI driver" % MNIST["epochs"])
    wf = main.workflow
    phase.check(_on_tpu(wf.fused_step._params_),
                "trained parameters on the TPU device")
    samples = numpy.asarray(wf.loader.original_data.map_read()
                            [:max(MNIST["requests"])], numpy.float32)
    # dense class indices, as the network's outputs are numbered
    labels = numpy.asarray(wf.loader._dense_labels
                           [:max(MNIST["requests"])])
    reference = numpy.asarray(jax.jit(forward_fn(wf.forwards))(
        [fwd.params for fwd in wf.forwards], samples))
    with tempfile.TemporaryDirectory(prefix="chip-smoke-") as scratch:
        package = export_model(wf, os.path.join(scratch, "mnist.zip"))
        # what `python -m veles_tpu --serve mnist.zip:mnist` stands up
        server = InferenceServer([("mnist", package)], port=0,
                                 max_batch=MNIST["max_batch"])
        try:
            scheduler = server.registry.get("mnist").scheduler
            stats = scheduler.stats()
            ladder = bucket_sizes(MNIST["max_batch"])
            phase.require(sorted(stats["buckets"]) == ladder
                          and stats["compiles"] + stats["cache_hits"]
                          == len(ladder),
                          "all %d buckets of the ladder %s warm, none "
                          "dropped" % (len(ladder), ladder))
            worst, right = 0.0, 0
            for n in MNIST["requests"]:
                answer = _http_json("%s/api/mnist" % server.url,
                                    {"input": samples[:n].tolist()})
                out = numpy.asarray(answer["output"], numpy.float32)
                phase.require(out.shape == (n, 10)
                              and numpy.isfinite(out).all(),
                              "POST /api/mnist with %d sample(s): finite "
                              "%s output" % (n, out.shape))
                worst = max(worst, float(numpy.abs(
                    out - reference[:n]).max()))
                right = int((out.argmax(1) == labels[:n]).sum())
            phase.require(worst < 1e-3,
                          "served probabilities within 1e-3 of the trained "
                          "workflow's own forward (max err %.2e)" % worst)
            phase.require(right >= MNIST_MIN_ACCURACY * n,
                          "%d/%d served predictions on %s validation "
                          "digits are the labels"
                          % (right, n, wf.loader.provenance))
            phase.require(scheduler.stats()["post_warmup_compiles"] == 0,
                          "no compile after warm-up")
        finally:
            server.stop()
    main.launcher.stop()


# -- --multichip: the sharded paths against one chip --------------------------

def _three_steps(phase, tag, backend, extra):
    """MULTICHIP_STEPS fused AlexNet train steps; returns their losses."""
    import jax
    from veles_tpu import loader as loader_mod
    main = _alexnet_cli("fused", "float32", backend, extra)
    wf, step = main.workflow, main.workflow.fused_step
    losses = []
    while len(losses) < MULTICHIP_STEPS:
        wf.loader.run()
        if wf.loader.minibatch_class == loader_mod.TRAIN:
            step.run()
            losses.append(float(step.loss))
    phase.require(all(math.isfinite(x) for x in losses),
                  "%s: finite losses %s" % (tag, losses))
    if extra:
        n = len(jax.devices())
        params = jax.tree_util.tree_leaves(step._params_)
        spread = {d for p in params for s in p.addressable_shards
                  for d in [s.device]}
        # the step's batch-sharded output says how the step splits the
        # batch; where the loader left the input is printed beside it
        out = step.output.devmem
        out_devices = {s.device for s in out.addressable_shards}
        fed = wf.loader.minibatch_data.devmem
        phase.check(len(spread) == n and len(out_devices) == n
                    and all(d.platform == "tpu" for d in spread),
                    "%s: parameters on %d and the step's batch output on "
                    "%d distinct TPU devices (the loader hands the "
                    "minibatch over on %d)"
                    % (tag, len(spread), len(out_devices),
                       len({s.device for s in fed.addressable_shards})))
        phase.check(out.addressable_shards[0].data.shape[0] < out.shape[0],
                    "%s: the batch really is split (%s per device of %s)"
                    % (tag, out.addressable_shards[0].data.shape,
                       out.shape))
        if "model" in extra[1]:
            split = [p for p in params if
                     p.addressable_shards[0].data.shape != p.shape]
            phase.check(split, "%s: %d parameter arrays are split over "
                        "the model axis" % (tag, len(split)))
    main.launcher.stop()
    del main, wf, step
    gc.collect()
    return losses


def phase_multichip(phase, backend):
    import jax
    import jax.numpy as jnp
    import numpy
    from veles_tpu.parallel.mesh import make_mesh
    from veles_tpu.parallel.ring import ring_attention
    from veles_tpu.znicz.flash_attention import flash_attention
    n = len(jax.devices())
    phase.require(n == 4, "four devices (found %d)" % n)
    one = _three_steps(phase, "one chip", backend, ())
    for mesh in MULTICHIP_MESHES:
        extra = ["--mesh", mesh]
        if "model" in mesh:
            extra += ["--model-axis", "model"]
        losses = _three_steps(phase, mesh, backend, extra)
        worst = max(abs(a - b) for a, b in zip(one, losses))
        phase.require(worst < MULTICHIP_LOSS_TOLERANCE,
                      "%s: %d step losses within %g of one chip's from "
                      "the same seed (max diff %.2e; %s vs %s)"
                      % (mesh, MULTICHIP_STEPS, MULTICHIP_LOSS_TOLERANCE,
                         worst, losses, one))
    r = RING
    rng = numpy.random.RandomState(SEED)
    q, k, v = (jnp.asarray(rng.standard_normal(
        (r["batch"], r["t"], r["heads"], r["d"])) * 0.5, jnp.float32)
        for _ in range(3))
    seq = make_mesh({"seq": n})
    ring = jax.jit(lambda q, k, v: ring_attention(
        q, k, v, seq, causal=True, use_pallas=True)).lower(
        q, k, v).compile()
    out = ring(q, k, v)
    err = _max_err(out, jax.jit(lambda q, k, v: flash_attention(
        q, k, v, causal=True))(q, k, v))
    phase.require(err < FLASH_TOLERANCE,
                  "ring flash attention over seq=%d within %g of "
                  "single-device flash (max err %.2e)"
                  % (n, FLASH_TOLERANCE, err))
    phase.check(len({s.device for s in out.addressable_shards}) == n,
                "ring output on %d distinct devices" % n)
    text = ring.as_text()
    phase.check("tpu_custom_call" in text
                and "collective-permute" in text,
                "tpu_custom_call and collective-permute in the compiled "
                "ring")


# -- driver -------------------------------------------------------------------

PHASES = (("trainer", phase_trainer), ("kernels", phase_kernels),
          ("decode_server", phase_decode_server),
          ("export_serve", phase_export_serve))
MULTICHIP_PHASES = (("multichip", phase_multichip),)


def run(backend=BACKEND, multichip=False):
    """Run the phases against ``backend``; returns the exit code.  Only a
    test passes anything but "tpu" (a CPU rehearsal of the control flow,
    which must end in failure: the device checks see no TPU)."""
    import jax
    from veles_tpu.backends import (apply_compilation_cache_config,
                                    cache_root)
    from veles_tpu.config import root
    from veles_tpu.observability import compiles
    monitor = compiles.monitor()
    # the one cache directory: $JAX_COMPILATION_CACHE_DIR when the machine
    # sets it (JAX is already there), else the checkout's .cache/
    root.common.engine.compilation_cache_dir = cache_root()
    apply_compilation_cache_config()
    device = jax.devices()[0]
    print("chip_smoke: %d x %s (%s), jax %s, compile cache %s"
          % (len(jax.devices()), device.device_kind, device.platform,
             jax.__version__, cache_root()), flush=True)
    ok = True
    for name, fn in (MULTICHIP_PHASES if multichip else PHASES):
        phase = Phase()
        t0, c0 = time.perf_counter(), monitor.compile_seconds
        try:
            fn(phase, backend)
        except SmokeFailure:
            pass            # already on phase.failed
        except Exception:   # noqa: BLE001 — reported, and fails the run
            phase.failed.append("raised: %s"
                                % traceback.format_exc(limit=8).strip())
        ok = ok and not phase.failed
        print("phase=%s %s seconds=%.1f compile_seconds=%.1f "
              "device_kind=%r checked=[%s]%s"
              % (name, "ok" if not phase.failed else "FAILED",
                 time.perf_counter() - t0,
                 monitor.compile_seconds - c0, device.device_kind,
                 "; ".join(phase.passed),
                 " failed=[%s]" % "; ".join(phase.failed)
                 if phase.failed else ""), flush=True)
        gc.collect()
    print("persistent compile cache: %d hit(s), %d miss(es) in this run"
          % (monitor.cache_hits, monitor.cache_misses), flush=True)
    print(json.dumps({"ok": ok, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0 if ok else 1


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--multichip", action="store_true",
                        help="the four-chip phase and what it is compared "
                             "with, and no other phase")
    args = parser.parse_args(argv)
    try:
        import jax
        device = jax.devices()[0]
        found = {"platform": device.platform, "kind": device.device_kind,
                 "count": len(jax.devices())}
    except Exception as exc:  # noqa: BLE001 — JAX, or the repo, missing
        print("chip_smoke: cannot reach a device: %s: %s"
              % (type(exc).__name__, exc), file=sys.stderr)
        print(json.dumps({"ok": False, "device": None}))
        return 2
    if found["platform"] != "tpu":
        print("chip_smoke: JAX's platform is %r, not 'tpu': this script "
              "proves the system on the chip and has nothing to say "
              "about any other device" % found["platform"],
              file=sys.stderr)
        print(json.dumps({"ok": False, "device": found}))
        return 2
    return run(BACKEND, args.multichip)


if __name__ == "__main__":
    sys.exit(main())
