"""One candidate, one fresh process: measure + correctness-gate it.

``python -m veles_tpu.autotune.probe --site S --config JSON [--ctx
JSON]`` builds the site's op with the candidate configuration, checks
its output against the site's *oracle* (the dense/numpy reference the
tests already trust — NOT the default config, so a systematically
wrong pair can't vouch for itself), then times the candidate AND the
site's hand-picked default config with interleaved min-of-windows
timing in this same process.  Emits ONE JSON line::

    {"ok": true, "site": ..., "config": {...}, "gate": "passed",
     "cand_s": ..., "ref_s": ..., "score": cand_s / ref_s}

``score`` is the in-process candidate/default time ratio — the runner
ranks by it so machine-load drift between probe processes cancels.  A
gate other than ``"passed"`` disqualifies the candidate regardless of
its score.  Any exception still prints a parseable ``{"ok": false}``
line (the runner treats it as a discarded candidate).
"""

import argparse
import json
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def _timed_pair(cand_fn, ref_fn, reps, windows):
    """Interleaved min-of-windows seconds for (candidate, reference):
    alternating window order cancels monotone load drift, the min
    discards contended windows."""
    cand_fn()
    ref_fn()                    # both warm (compiles outside timing)
    cand_times, ref_times = [], []
    for w in range(max(int(windows), 1)):
        pairs = [(cand_fn, cand_times), (ref_fn, ref_times)]
        if w % 2:
            pairs.reverse()
        for fn, acc in pairs:
            t0 = time.perf_counter()
            for _ in range(max(int(reps), 1)):
                fn()
            acc.append((time.perf_counter() - t0) / max(int(reps), 1))
    return min(cand_times), min(ref_times)


def _gate(ok, detail=""):
    return "passed" if ok else "failed:%s" % (detail or "mismatch")


# -- kernel sites -------------------------------------------------------------

def probe_lrn(config, ctx, reps, windows):
    import jax
    import jax.numpy as jnp
    import numpy
    from veles_tpu.znicz import lrn as lrn_mod
    rows = int(ctx.get("rows", 2048))
    c = int(ctx.get("c", 96))
    n = int(ctx.get("n", 5))
    alpha, beta, k = 1e-4, 0.75, 2.0
    rng = numpy.random.RandomState(0)
    x = jnp.asarray(rng.standard_normal((rows, c)), jnp.float32)

    def make(cfg):
        if cfg["impl"] == "mxu":
            return jax.jit(
                lambda v: lrn_mod.lrn_mxu(v, n, alpha, beta, k))
        rows_blk = int(cfg["block_rows"])
        return jax.jit(
            lambda v: lrn_mod.pallas_lrn(v, n, alpha, beta, k,
                                         rows_blk))

    from veles_tpu.autotune.space import site
    f_cand, f_ref = make(config), make(site("lrn").default)
    out = numpy.asarray(f_cand(x))
    xs = numpy.asarray(x)
    want = xs / (k + (alpha / n)
                 * lrn_mod._window_sum(xs * xs, n, numpy)) ** beta
    err = float(numpy.max(numpy.abs(out - want)))
    cand_s, ref_s = _timed_pair(
        lambda: jax.block_until_ready(f_cand(x)),
        lambda: jax.block_until_ready(f_ref(x)), reps, windows)
    return {"gate": _gate(err <= 2e-4, "max_err=%.3g" % err),
            "cand_s": cand_s, "ref_s": ref_s}


def _probe_attention(site_name, config, ctx, reps, windows):
    import jax
    import jax.numpy as jnp
    import numpy
    from veles_tpu.autotune.space import site
    from veles_tpu.parallel.ring import attention_reference
    from veles_tpu.znicz.flash_attention import flash_attention
    b = int(ctx.get("b", 1))
    t = int(ctx.get("t", 256))
    h = int(ctx.get("h", 2))
    d = int(ctx.get("d", 64))
    causal = bool(ctx.get("causal", True))
    window = ctx.get("window") if site_name == "window_attention" \
        else None
    rng = numpy.random.RandomState(0)
    q, k, v = (jnp.asarray(rng.standard_normal((b, t, h, d)) * 0.5,
                           jnp.float32) for _ in range(3))

    def make(cfg):
        return jax.jit(lambda q, k, v: flash_attention(
            q, k, v, causal, None, cfg["block_q"], cfg["block_k"],
            window))

    f_cand, f_ref = make(config), make(site(site_name).default)
    out = numpy.asarray(f_cand(q, k, v))
    want = numpy.asarray(attention_reference(
        q, k, v, causal=causal, scale=1.0 / (d ** 0.5), window=window))
    err = float(numpy.max(numpy.abs(out - want)))
    cand_s, ref_s = _timed_pair(
        lambda: jax.block_until_ready(f_cand(q, k, v)),
        lambda: jax.block_until_ready(f_ref(q, k, v)), reps, windows)
    return {"gate": _gate(err <= 2e-3, "max_err=%.3g" % err),
            "cand_s": cand_s, "ref_s": ref_s}


def probe_flash_attention(config, ctx, reps, windows):
    return _probe_attention("flash_attention", config, ctx, reps,
                            windows)


def probe_window_attention(config, ctx, reps, windows):
    ctx = dict(ctx or {})
    ctx.setdefault("window", max(int(ctx.get("t", 256)) // 4, 32))
    return _probe_attention("window_attention", config, ctx, reps,
                            windows)


def probe_precise_gemm(config, ctx, reps, windows):
    import jax
    import jax.numpy as jnp
    import numpy
    from veles_tpu.autotune.space import site
    from veles_tpu.znicz.gemm import _matmul_impl
    m = int(ctx.get("m", 512))
    kk = int(ctx.get("k", 512))
    n = int(ctx.get("n", 512))
    level = int(ctx.get("level", 1))
    rng = numpy.random.RandomState(0)
    a = jnp.asarray(rng.standard_normal((m, kk)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((kk, n)), jnp.float32)

    def make(cfg):
        return jax.jit(lambda a, b: _matmul_impl(
            a, b, level, None, cfg["block_m"], cfg["block_n"],
            cfg["block_k"]))

    f_cand, f_ref = make(config), make(site("precise_gemm").default)
    out = numpy.asarray(f_cand(a, b))
    want = numpy.asarray(a, numpy.float64) @ numpy.asarray(
        b, numpy.float64)
    scale = float(numpy.max(numpy.abs(want))) or 1.0
    err = float(numpy.max(numpy.abs(out - want))) / scale
    cand_s, ref_s = _timed_pair(
        lambda: jax.block_until_ready(f_cand(a, b)),
        lambda: jax.block_until_ready(f_ref(a, b)), reps, windows)
    return {"gate": _gate(err <= 1e-4, "rel_err=%.3g" % err),
            "cand_s": cand_s, "ref_s": ref_s}


def probe_paged_attention(config, ctx, reps, windows):
    import jax
    import jax.numpy as jnp
    import numpy
    from veles_tpu.znicz.paged_attention import (
        paged_attention, paged_attention_reference, required_blocks)
    batch = int(ctx.get("batch", 2))
    heads = int(ctx.get("heads", 2))
    d = int(ctx.get("d", 16))
    length = int(ctx.get("length", 48))
    bs = int(config["block_size"])
    max_blocks = required_blocks(length, bs)
    num_blocks = batch * max_blocks + 1      # + reserved trash block 0
    rng = numpy.random.RandomState(0)
    k_pool, v_pool = (jnp.asarray(
        rng.standard_normal((num_blocks, bs, heads, d)) * 0.5,
        jnp.float32) for _ in range(2))
    table = numpy.zeros((batch, max_blocks), numpy.int32)
    blk = 1
    lengths = numpy.asarray(
        [length if i % 2 == 0 else max(length // 2, 1)
         for i in range(batch)], numpy.int32)
    for i in range(batch):
        used = required_blocks(int(lengths[i]), bs)
        for j in range(used):
            table[i, j] = blk
            blk += 1
    table = jnp.asarray(table)
    lengths = jnp.asarray(lengths)
    q = jnp.asarray(rng.standard_normal((batch, heads, d)) * 0.5,
                    jnp.float32)
    f_cand = jax.jit(paged_attention)
    f_ref = jax.jit(paged_attention_reference)
    out = numpy.asarray(f_cand(q, k_pool, v_pool, table, lengths))
    want = numpy.asarray(f_ref(q, k_pool, v_pool, table, lengths))
    # the kernel's contract with its dense reference is BITWISE
    bitwise = bool(numpy.array_equal(out, want))
    cand_s, ref_s = _timed_pair(
        lambda: jax.block_until_ready(
            f_cand(q, k_pool, v_pool, table, lengths)),
        lambda: jax.block_until_ready(
            f_ref(q, k_pool, v_pool, table, lengths)), reps, windows)
    return {"gate": _gate(bitwise, "not bitwise-equal to the dense "
                                   "reference"),
            "cand_s": cand_s, "ref_s": ref_s}


# -- serving sites ------------------------------------------------------------

def probe_bucket_ladder(config, ctx, reps, windows):
    """Steady-state drain time of a seeded ragged request mix.  Compile
    count differences are a one-time cost the compile cache + warmup
    manifests amortize away; what a ladder shape pays FOREVER is
    padding waste — that is what this measures."""
    import jax
    import jax.numpy as jnp
    import numpy
    from veles_tpu.autotune.space import ladder
    from veles_tpu.serving.scheduler import BucketScheduler
    mb = int(ctx.get("max_batch", 16))
    dim = int(ctx.get("dim", 64))
    n_requests = int(ctx.get("requests", 48))
    rng = numpy.random.RandomState(int(ctx.get("seed", 0)))
    w = jnp.asarray(rng.standard_normal((dim, dim)) * 0.1, jnp.float32)
    fn = jax.jit(lambda x: jnp.tanh(x @ w))
    mix = [rng.standard_normal(
        (int(rng.randint(1, mb + 1)), dim)).astype(numpy.float32)
        for _ in range(n_requests)]

    def build(shape):
        return BucketScheduler(
            fn, max_batch=mb, queue_limit=4 * n_requests * mb,
            warmup=True, name="autotune-%s" % shape,
            sample_shape=(dim,), cache=False,
            buckets=ladder(shape, mb))

    cand = build(config["shape"])
    ref = build("pow2")
    try:
        def drain(s):
            futs = [s.submit(x) for x in mix]
            return [f.result(60) for f in futs]

        outs = drain(cand)
        want = [numpy.asarray(fn(jnp.asarray(x))) for x in mix[:8]]
        ok = all(numpy.allclose(numpy.asarray(o), wv, atol=1e-5)
                 for o, wv in zip(outs[:8], want))
        cand_s, ref_s = _timed_pair(lambda: drain(cand),
                                    lambda: drain(ref), reps, windows)
    finally:
        cand.close(drain=False)
        ref.close(drain=False)
    return {"gate": _gate(ok), "cand_s": cand_s, "ref_s": ref_s,
            "ladder": ladder(config["shape"], mb)}


def probe_serving_decode(config, ctx, reps, windows):
    """Decode throughput (tokens/s over a seeded ragged prompt mix)
    under candidate geometry, gated on token-exactness vs the
    cache-free oracle."""
    import numpy
    from veles_tpu.serving import DecodeScheduler
    from veles_tpu.znicz.samples.flagship import (FlagshipDecodeModel,
                                                  generate_reference)
    max_prompt = int(ctx.get("max_prompt_len", 8))
    max_new = int(ctx.get("max_new_tokens", 8))
    n_requests = int(ctx.get("requests", 12))
    model = FlagshipDecodeModel(stages=2, experts=2, d=16, heads=2,
                                hidden=32, vocab=32, seed=0)
    rng = numpy.random.RandomState(int(ctx.get("seed", 0)))
    prompts = [[int(t) for t in rng.randint(
        0, 32, size=rng.randint(1, max_prompt + 1))]
        for _ in range(n_requests)]

    def build(cfg):
        return DecodeScheduler(
            model, max_batch=int(cfg["max_batch"]),
            block_size=int(cfg["block_size"]),
            max_prompt_len=max_prompt, max_new_tokens=max_new,
            queue_limit=4 * n_requests, warmup=True,
            name="autotune-%d-%d" % (cfg["max_batch"],
                                     cfg["block_size"]),
            cache=False)

    from veles_tpu.autotune.space import site
    cand = build(config)
    ref = build(site("serving.decode").default)
    try:
        def drain(s):
            futs = [s.submit(p, max_new) for p in prompts]
            return [f.result(120) for f in futs]

        outs = drain(cand)
        ok = all(
            outs[i]["tokens"] == generate_reference(
                model.params, prompts[i], max_new)
            for i in range(min(4, n_requests)))
        cand_s, ref_s = _timed_pair(lambda: drain(cand),
                                    lambda: drain(ref), reps, windows)
    finally:
        cand.close(drain=False)
        ref.close(drain=False)
    return {"gate": _gate(ok, "tokens diverge from the cache-free "
                              "oracle"),
            "cand_s": cand_s, "ref_s": ref_s}


def probe_prefill_chunk(config, ctx, reps, windows):
    """Short-request TTFT behind long chunked prefills — the quantity
    the chunk size actually trades (smaller chunks interleave sooner,
    but each chunk pays a dispatch) — gated on token-exactness vs the
    cache-free oracle.  Runs on the toydecode stand-in with a pinned
    per-prompt-token prefill cost so scheduling, not XLA, is what's
    measured."""
    import numpy
    from veles_tpu.serving import DecodeScheduler
    from veles_tpu.serving.toydecode import ToyDecodeModel
    max_prompt = int(ctx.get("max_prompt_len", 64))
    longs = int(ctx.get("long_prompts", 2))
    pdelay = float(ctx.get("prefill_delay", 0.001))
    model = ToyDecodeModel(vocab=97, prefill_delay=pdelay)
    rng = numpy.random.RandomState(int(ctx.get("seed", 0)))
    long_prompts = [rng.randint(1, 90, max_prompt).tolist()
                    for _ in range(longs)]
    short = [3, 1, 4, 1]

    def build(chunk):
        return DecodeScheduler(
            model, max_batch=longs + 1, block_size=4,
            max_prompt_len=max_prompt, max_new_tokens=4,
            queue_limit=64, warmup=True, cache=False,
            prefill_chunk_tokens=int(chunk),
            name="autotune-chunk%d" % chunk)

    def wave(s):
        futs = [s.submit(p, 4) for p in long_prompts]
        got = s.submit(short, 4).result(120)
        for f in futs:
            f.result(120)
        return got

    from veles_tpu.autotune.space import site
    cand = build(config["chunk_tokens"])
    ref = build(site("serving.prefill_chunk").default["chunk_tokens"])
    try:
        ok = wave(cand)["tokens"] == model.generate_reference(short, 4)
        # the _timed_pair discipline (interleaved min-of-windows)
        # applied to the short request's TTFT rather than wall time
        cand_t, ref_t = [], []
        for w in range(max(int(windows), 1)):
            pairs = [(cand, cand_t), (ref, ref_t)]
            if w % 2:
                pairs.reverse()
            for s, acc in pairs:
                vals = [wave(s)["ttft_s"]
                        for _ in range(max(int(reps), 1))]
                acc.append(sum(vals) / len(vals))
        cand_s, ref_s = min(cand_t), min(ref_t)
    finally:
        cand.close(drain=False)
        ref.close(drain=False)
    return {"gate": _gate(ok, "tokens diverge from the cache-free "
                              "oracle"),
            "cand_s": cand_s, "ref_s": ref_s}


def probe_spec_depth(config, ctx, reps, windows):
    """Decode drain time with the draft-and-verify loop at the
    candidate depth — what the depth trades is accepted tokens per
    verify pass vs wasted draft/verify work on rejections — gated on
    token-exactness vs the pure-host oracle.  Runs on the toydecode
    stand-in with a pinned per-step host delay and a pinned drafter
    agreement rate so scheduling, not XLA, is what's measured."""
    import numpy
    from veles_tpu.serving import DecodeScheduler
    from veles_tpu.serving.toydecode import ToyDecodeModel
    max_prompt = int(ctx.get("max_prompt_len", 8))
    max_new = int(ctx.get("max_new_tokens", 16))
    n_requests = int(ctx.get("requests", 8))
    agree = float(ctx.get("agreement", 0.8))
    sdelay = float(ctx.get("step_delay", 0.002))
    model = ToyDecodeModel(vocab=31, step_delay=sdelay,
                           draft_agreement=agree)
    rng = numpy.random.RandomState(int(ctx.get("seed", 0)))
    prompts = [[int(t) for t in rng.randint(
        0, 31, size=rng.randint(1, max_prompt + 1))]
        for _ in range(n_requests)]

    def build(depth):
        return DecodeScheduler(
            model, max_batch=4, block_size=4,
            max_prompt_len=max_prompt, max_new_tokens=max_new,
            queue_limit=4 * n_requests, warmup=True, cache=False,
            spec_depth=int(depth),
            name="autotune-spec%d" % depth)

    from veles_tpu.autotune.space import site
    cand = build(config["spec_depth"])
    ref = build(site("serving.spec_depth").default["spec_depth"])
    try:
        def drain(s):
            futs = [s.submit(p, max_new) for p in prompts]
            return [f.result(120) for f in futs]

        outs = drain(cand)
        ok = all(outs[i]["tokens"] == model.generate_reference(
                     prompts[i], max_new)
                 for i in range(n_requests))
        cand_s, ref_s = _timed_pair(lambda: drain(cand),
                                    lambda: drain(ref), reps, windows)
    finally:
        cand.close(drain=False)
        ref.close(drain=False)
    return {"gate": _gate(ok, "tokens diverge from the pure-host "
                              "oracle"),
            "cand_s": cand_s, "ref_s": ref_s}


def _decode_logit_rmse(model, kv_dtype, prompt, n_new):
    """Greedy-rollout logit RMSE of ``kv_dtype`` pools vs f32 pools —
    same params, same geometry, token-by-token through the model's
    ``logits_fn`` decode hook.  The error-bound gate's measurement."""
    import jax.numpy as jnp
    import numpy
    bs = 4
    # the fixed geometry below holds 4 blocks x 4 tokens per row —
    # cap the rollout so no position ever lands past the page table
    n_new = min(int(n_new), 4 * bs - len(prompt))
    per = {}
    for kvd in dict.fromkeys(("f32", kv_dtype)):
        kp, vp = model.make_pools(8, bs, kv_dtype=kvd)
        toks = jnp.zeros(8, jnp.int32).at[:len(prompt)].set(
            jnp.asarray(prompt, jnp.int32))
        block_row = jnp.asarray([1, 2, 3, 4], jnp.int32)
        tok, kp, vp = model.prefill_fn(bs, kv_dtype=kvd)(
            toks, len(prompt), kp, vp, block_row)
        table = jnp.zeros((2, 4), jnp.int32).at[0].set(block_row)
        lengths = jnp.asarray([len(prompt), 0], jnp.int32)
        logits = model.logits_fn(bs, kv_dtype=kvd)
        cur = jnp.asarray([int(tok), 0], jnp.int32)
        rows = []
        for _ in range(n_new):
            nxt, kp, vp, lg = logits(kp, vp, table, lengths, cur)
            rows.append(numpy.asarray(lg[0]))
            lengths = lengths.at[0].add(1)
            cur = cur.at[0].set(nxt[0])
        per[kvd] = numpy.stack(rows)
    if kv_dtype == "f32":
        return 0.0
    diff = per[kv_dtype] - per["f32"]
    return float(numpy.sqrt(numpy.mean(diff * diff)))


def probe_kv_dtype(config, ctx, reps, windows):
    """Decode drain time with the candidate KV-pool precision — what
    quantized pools buy is HBM (more resident blocks per byte) and
    memory-bound step time — gated on the site's DECLARED error bound:
    a lossy candidate cannot be bitwise vs the f32 oracle, so the gate
    is greedy-rollout logit RMSE <= error_bound, measured through the
    model's ``logits_fn`` hook before any timing."""
    import numpy
    from veles_tpu.autotune.space import site
    from veles_tpu.serving import DecodeScheduler
    from veles_tpu.znicz.samples.flagship import FlagshipDecodeModel
    sp = site("serving.kv_dtype")
    bound = float(ctx.get("error_bound", sp.error_bound))
    max_prompt = int(ctx.get("max_prompt_len", 8))
    max_new = int(ctx.get("max_new_tokens", 8))
    n_requests = int(ctx.get("requests", 8))
    kvd = str(config["kv_dtype"])
    model = FlagshipDecodeModel(stages=2, experts=2, d=16, heads=2,
                                hidden=32, vocab=32, seed=0)
    rng = numpy.random.RandomState(int(ctx.get("seed", 0)))
    prompts = [[int(t) for t in rng.randint(
        0, 32, size=rng.randint(1, max_prompt + 1))]
        for _ in range(n_requests)]
    rmse = _decode_logit_rmse(model, kvd, prompts[0][:3] or [1],
                              max_new)

    def build(kv_dtype, tag):
        return DecodeScheduler(
            model, max_batch=4, block_size=4,
            max_prompt_len=max_prompt, max_new_tokens=max_new,
            queue_limit=4 * n_requests, warmup=True, cache=False,
            kv_dtype=kv_dtype, name="autotune-kv-%s" % tag)

    cand = build(kvd, kvd)
    ref = build(sp.default["kv_dtype"], "ref")
    try:
        def drain(s):
            futs = [s.submit(p, max_new) for p in prompts]
            return [f.result(120) for f in futs]

        drain(cand)
        cand_s, ref_s = _timed_pair(lambda: drain(cand),
                                    lambda: drain(ref), reps, windows)
    finally:
        cand.close(drain=False)
        ref.close(drain=False)
    return {"gate": _gate(rmse <= bound,
                          "logit_rmse=%.3g > bound=%.3g"
                          % (rmse, bound)),
            "logit_rmse": round(rmse, 6), "error_bound": bound,
            "cand_s": cand_s, "ref_s": ref_s}


_IMPLS = {
    "lrn": probe_lrn,
    "flash_attention": probe_flash_attention,
    "window_attention": probe_window_attention,
    "precise_gemm": probe_precise_gemm,
    "paged_attention": probe_paged_attention,
    "serving.bucket_ladder": probe_bucket_ladder,
    "serving.decode": probe_serving_decode,
    "serving.prefill_chunk": probe_prefill_chunk,
    "serving.spec_depth": probe_spec_depth,
    "serving.kv_dtype": probe_kv_dtype,
}

#: cheap serving probes need fewer reps than μs-scale kernels
_DEFAULT_REPS = {"serving.bucket_ladder": 1, "serving.decode": 1,
                 "serving.prefill_chunk": 1, "serving.spec_depth": 1,
                 "serving.kv_dtype": 1}
_DEFAULT_WINDOWS = {"serving.bucket_ladder": 2, "serving.decode": 2,
                    "serving.prefill_chunk": 2, "serving.spec_depth": 2,
                    "serving.kv_dtype": 2}


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--site", required=True, choices=sorted(_IMPLS))
    p.add_argument("--config", required=True,
                   help="candidate configuration (JSON object)")
    p.add_argument("--ctx", default="{}",
                   help="call context: shapes/seed (JSON object)")
    p.add_argument("--reps", type=int, default=None)
    p.add_argument("--windows", type=int, default=None)
    args = p.parse_args(argv)
    config = json.loads(args.config)
    ctx = json.loads(args.ctx)
    reps = args.reps if args.reps is not None \
        else _DEFAULT_REPS.get(args.site, 3)
    windows = args.windows if args.windows is not None \
        else _DEFAULT_WINDOWS.get(args.site, 3)
    out = {"ok": True, "site": args.site, "config": config}
    try:
        out.update(_IMPLS[args.site](config, ctx, reps, windows))
        if out.get("ref_s", 0) > 0 and "cand_s" in out:
            out["score"] = round(out["cand_s"] / out["ref_s"], 4)
        out["cand_s"] = round(out.get("cand_s", 0.0), 6)
        out["ref_s"] = round(out.get("ref_s", 0.0), 6)
        # the record's key: the tuner parent stays off JAX (one process
        # per chip) and takes the environment from here
        from veles_tpu.autotune.store import environment_fingerprint
        out["fingerprint"] = environment_fingerprint()
    except Exception:  # noqa: BLE001 — the line must always print
        out = {"ok": False, "site": args.site, "config": config,
               "error": traceback.format_exc(limit=3).strip()[-500:]}
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
