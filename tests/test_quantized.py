"""Quantized serving (ISSUE 18): int8 KV pools dequantized in-kernel,
int8/fp8 weight GEMMs, and error-bounded precision autotuning.

The contract under test (acceptance):
- the quantizer is deterministic (round-half-even) so prefix-chain
  keys can commit to the quantized bytes; the int8 decode kernel is
  BITWISE against the quantized dense reference (same staging), and
  within the declared logit-RMSE bound of the f32 path end to end;
- half-specified quantized operands (one int8 pool, missing or
  misshapen scales, scales on f32 pools) are loud ValueErrors, never
  silent garbage;
- the weight GEMM crosses HBM in int8/fp8 and dequantizes AFTER the
  f32 accumulation — bitwise vs its staged oracle;
- ``kv_dtype="f32"`` (the default) is byte-identical to the prior
  scheduler: no kwarg reaches the model factories, no quant block in
  the kv dump, same manifest entries — and the int8 config gets its
  own dtype-suffixed executable tags so neither precision can hit the
  other's cache entries;
- int8 pools at a FIXED byte budget hold >= 2x the concurrent
  sessions of f32;
- quantization composes: prefix dedupe keys on the quantized bytes,
  checkpoint/restore and live migration refuse a dtype mismatch BY
  NAME (prompt-only states still migrate), speculation drafts and
  verifies through int8 pools, and a warm restart compiles NOTHING;
- ``serving.kv_dtype`` is the first LOSSY autotune site: its
  ``error_bound`` is declared on the SearchSpace (every exact site
  keeps ``None``), and the probe gates on measured logit RMSE;
- the metrics surface carries the resident-KV-bytes gauge and the
  kv_dtype info gauge, and ``GET /api/<model>/kv`` carries the
  ``quant`` block tools/kv_inspect.py renders.
"""

import time

import jax.numpy as jnp
import numpy
import pytest

from veles_tpu.serving import DecodeScheduler, ToyDecodeModel
from veles_tpu.znicz.gemm import (fp8_dtype, quantize_weight,
                                  quantized_matmul,
                                  quantized_matmul_reference)
from veles_tpu.znicz.paged_attention import (dequantize_pool,
                                             paged_attention,
                                             paged_attention_reference,
                                             quantize_pool)
from veles_tpu.znicz.samples.flagship import (FlagshipDecodeModel,
                                              _kv_arrays,
                                              generate_reference)

GEOM = dict(max_batch=4, block_size=4, max_prompt_len=8,
            max_new_tokens=8)


@pytest.fixture(scope="module")
def toy():
    return ToyDecodeModel(vocab=64)


def _rand_pools(rng, n_blocks=6, bs=4, h=2, d=8):
    shape = (n_blocks, bs, h, d)
    k = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    v = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    return k, v


# -- quantizer ----------------------------------------------------------------

def test_quantize_pool_shapes_determinism_and_bound():
    rng = numpy.random.default_rng(0)
    k, _ = _rand_pools(rng)
    q, s = quantize_pool(k)
    assert q.shape == k.shape and q.dtype == jnp.int8
    assert s.shape == (k.shape[0], k.shape[2]) and s.dtype == jnp.float32
    # deterministic: identical content -> identical int8 bytes (what
    # lets prefix-chain keys commit to the quantized pool)
    q2, s2 = quantize_pool(jnp.asarray(numpy.asarray(k)))
    assert numpy.array_equal(numpy.asarray(q), numpy.asarray(q2))
    assert numpy.array_equal(numpy.asarray(s), numpy.asarray(s2))
    # dequant error bounded by half a step per element
    err = numpy.abs(numpy.asarray(dequantize_pool(q, s) - k))
    step = numpy.asarray(s)[:, None, :, None] / 2.0 + 1e-7
    assert (err <= step).all()
    # all-zero slice quantizes to scale 1.0 (no divide-by-zero)
    qz, sz = quantize_pool(jnp.zeros((2, 4, 2, 8), jnp.float32))
    assert (numpy.asarray(sz) == 1.0).all()
    assert (numpy.asarray(qz) == 0).all()
    with pytest.raises(ValueError):
        quantize_pool(jnp.zeros((4, 2, 8), jnp.float32))


# -- decode kernel ------------------------------------------------------------

def test_paged_attention_int8_matches_quantized_reference_within_its_scales():
    """The int8 kernel's contract with the quantized dense reference
    (same dequant staging) is a tolerance (the kernel's docstring;
    ROADMAP D13), stated in the units the pool is quantized in: the two
    agree to a ten-thousandth of the largest V quantization step (2.8e-6
    here; measured 1.8e-7, a last place of outputs up to 1.5), where
    reading one wrong int8 value moves an output by whole steps.
    Padding rows (length 0, the reserved trash block) are exactly zero;
    the end-to-end error vs the f32 path stays well under the site's
    declared bound."""
    rng = numpy.random.default_rng(1)
    kp, vp = _rand_pools(rng, n_blocks=6, bs=4, h=2, d=8)
    kq, ks = quantize_pool(kp)
    vq, vs = quantize_pool(vp)
    q = jnp.asarray(rng.standard_normal((3, 2, 8)), jnp.float32)
    table = jnp.asarray([[1, 2, 3], [4, 5, 0], [0, 0, 0]], jnp.int32)
    lengths = jnp.asarray([11, 6, 0], jnp.int32)
    out = paged_attention(q, kq, vq, table, lengths,
                          k_scales=ks, v_scales=vs)
    ref = paged_attention_reference(q, kq, vq, table, lengths,
                                    k_scales=ks, v_scales=vs)
    step = float(numpy.asarray(vs).max())
    assert numpy.abs(numpy.asarray(out) -
                     numpy.asarray(ref)).max() <= 1e-4 * step
    assert (numpy.asarray(out)[2] == 0).all()     # padding row
    f32 = paged_attention(q, kp, vp, table, lengths)
    rmse = float(numpy.sqrt(numpy.mean(
        (numpy.asarray(out) - numpy.asarray(f32))[:2] ** 2)))
    assert rmse < 1e-2, rmse


def test_paged_attention_quant_args_are_validated():
    rng = numpy.random.default_rng(2)
    kp, vp = _rand_pools(rng)
    kq, ks = quantize_pool(kp)
    vq, vs = quantize_pool(vp)
    q = jnp.zeros((1, 2, 8), jnp.float32)
    table = jnp.zeros((1, 3), jnp.int32)
    lengths = jnp.asarray([4], jnp.int32)
    with pytest.raises(ValueError, match="dtypes differ"):
        paged_attention(q, kq, vp, table, lengths, k_scales=ks)
    with pytest.raises(ValueError, match="require k_scales"):
        paged_attention(q, kq, vq, table, lengths)
    with pytest.raises(ValueError, match="shape"):
        paged_attention(q, kq, vq, table, lengths,
                        k_scales=ks[:, :1], v_scales=vs)
    with pytest.raises(ValueError, match="only valid with int8"):
        paged_attention(q, kp, vp, table, lengths,
                        k_scales=ks, v_scales=vs)


# -- weight GEMM --------------------------------------------------------------

def test_quantized_matmul_bitwise_vs_staged_oracle():
    rng = numpy.random.default_rng(3)
    a = jnp.asarray(rng.standard_normal((16, 48)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((48, 24)), jnp.float32)
    w_q, scales = quantize_weight(w, "int8")
    assert w_q.dtype == jnp.int8 and scales.shape == (24,)
    out = quantized_matmul(a, w_q, scales)
    ref = quantized_matmul_reference(a, w_q, scales)
    assert numpy.array_equal(numpy.asarray(out), numpy.asarray(ref))
    # per-channel symmetric int8 keeps the product close to f32
    exact = numpy.asarray(a) @ numpy.asarray(w)
    rel = (numpy.abs(numpy.asarray(out) - exact).max()
           / numpy.abs(exact).max())
    assert rel < 0.05, rel
    with pytest.raises(ValueError):
        quantize_weight(jnp.zeros((2, 3, 4)), "int8")
    with pytest.raises(ValueError):
        quantize_weight(w, "int4")


def test_fp8_weight_path():
    w = jnp.asarray(numpy.random.default_rng(4)
                    .standard_normal((8, 8)), jnp.float32)
    w_q, scales = quantize_weight(w, "fp8")
    assert w_q.dtype == fp8_dtype()
    out = quantized_matmul(
        jnp.eye(8, dtype=jnp.float32), w_q, scales)
    rel = (numpy.abs(numpy.asarray(out) - numpy.asarray(w)).max()
           / numpy.abs(numpy.asarray(w)).max())
    assert rel < 0.1, rel


# -- flagship end to end ------------------------------------------------------

def _flagship_rollout(model, prompt, n_new, block_size=4):
    """Greedy rollout through the model's prefill/logits hooks; returns
    (tokens, stacked per-step logits, pools)."""
    kp, vp = model.make_pools(8, block_size)
    toks = jnp.zeros(8, jnp.int32).at[:len(prompt)].set(
        jnp.asarray(prompt))
    block_row = jnp.asarray([1, 2, 3, 4], jnp.int32)
    tok, kp, vp = model.prefill_fn(block_size)(
        toks, len(prompt), kp, vp, block_row)
    page_table = jnp.zeros((2, 4), jnp.int32).at[0].set(block_row)
    lengths = jnp.asarray([len(prompt), 0], jnp.int32)
    lf = model.logits_fn(block_size)
    out, logits = [int(tok)], []
    cur = jnp.asarray([int(tok), 0], jnp.int32)
    for _ in range(n_new - 1):
        nxt, kp, vp, lg = lf(kp, vp, page_table, lengths, cur)
        logits.append(numpy.asarray(lg[0]))
        out.append(int(nxt[0]))
        lengths = lengths.at[0].add(1)
        cur = cur.at[0].set(nxt[0])
    return out, numpy.stack(logits), (kp, vp)


def test_flagship_int8_kv_within_declared_bound():
    m32 = FlagshipDecodeModel(stages=2, experts=2, d=16, heads=2,
                              hidden=32, vocab=32, seed=0)
    m8 = FlagshipDecodeModel(params=m32.params, heads=2,
                             kv_dtype="int8")
    prompt = [3, 1, 2]
    o32, l32, _ = _flagship_rollout(m32, prompt, 6)
    o8, l8, pools8 = _flagship_rollout(m8, prompt, 6)
    assert o32 == generate_reference(m32.params, prompt, 6,
                                     heads=2, k=1)
    rmse = float(numpy.sqrt(numpy.mean((l32 - l8) ** 2)))
    assert rmse <= 1e-2, rmse
    kq, ks = _kv_arrays(pools8[0][0])
    assert kq.dtype == jnp.int8 and ks.dtype == jnp.float32


def test_flagship_weight_quantized_decode_matches_its_oracle():
    m32 = FlagshipDecodeModel(stages=2, experts=2, d=16, heads=2,
                              hidden=32, vocab=32, seed=0)
    mw = FlagshipDecodeModel(params=m32.params, heads=2,
                             weight_dtype="int8")
    prompt = [3, 1, 2]
    ow, lw, _ = _flagship_rollout(mw, prompt, 6)
    assert ow == generate_reference(mw.params, prompt, 6, heads=2, k=1)
    _, l32, _ = _flagship_rollout(m32, prompt, 6)
    rmse = float(numpy.sqrt(numpy.mean((l32 - lw) ** 2)))
    assert rmse <= 5e-2, rmse


# -- scheduler: default identity, capacity, composition -----------------------

class _StrictF32Toy(ToyDecodeModel):
    """Fails the test if the scheduler forwards ANY dtype kwarg to a
    factory on the default path — the f32 byte-identity contract."""

    def make_pools(self, num_blocks, block_size, **kw):
        assert not kw, "f32 default forwarded %r to make_pools" % (kw,)
        return super().make_pools(num_blocks, block_size)

    def decode_fn(self, block_size, **kw):
        assert not kw, "f32 default forwarded %r to decode_fn" % (kw,)
        return super().decode_fn(block_size)


def test_f32_default_is_byte_identical_and_unquantized(toy):
    s = DecodeScheduler(_StrictF32Toy(vocab=64), name="qf32",
                        cache=False, **GEOM)
    try:
        r = s.generate([3, 1, 2], 6, timeout=60)
        assert r["tokens"] == toy.generate_reference([3, 1, 2], 6)
        st = s.stats()
        assert st["kv_dtype"] == "f32"
        assert st.get("kv_dtype_source") is None
        assert "quant" not in s.kv_dump()
    finally:
        s.close(drain=True)


def test_toy_int8_exact_tokens_smaller_blocks(toy):
    s8 = DecodeScheduler(toy, name="qint8", cache=False,
                         kv_dtype="int8", **GEOM)
    s32 = DecodeScheduler(toy, name="qref32", cache=False, **GEOM)
    try:
        rng = numpy.random.RandomState(7)
        for _ in range(5):
            p = rng.randint(0, 64, rng.randint(1, 9)).tolist()
            n = int(rng.randint(1, 9))
            want = toy.generate_reference(p, n)
            # toy int8 stores token ids (vocab <= 127): exact, not
            # merely bounded
            assert s8.generate(p, n, timeout=60)["tokens"] == want
            assert s32.generate(p, n, timeout=60)["tokens"] == want
        st8, st32 = s8.stats(), s32.stats()
        assert st8["kv_dtype"] == "int8"
        assert st8["kv_dtype_source"] == "explicit"
        assert st8["block_bytes"] < st32["block_bytes"]
    finally:
        s8.close(drain=True)
        s32.close(drain=True)


def test_int8_doubles_sessions_at_fixed_pool_bytes(toy):
    """THE capacity claim: at one fixed byte budget the int8 pool
    geometry admits >= 2x the concurrent sessions (block 0 is the
    reserved trash block on both layouts)."""
    import jax
    bs, budget, per_seq = 8, 4096, 2
    sessions = {}
    for kvd in ("f32", "int8"):
        pools = toy.make_pools(1, bs, kv_dtype=kvd)
        bb = sum(int(numpy.prod(leaf.shape[1:])) * leaf.dtype.itemsize
                 for leaf in jax.tree_util.tree_leaves(pools))
        sessions[kvd] = (budget // bb - 1) // per_seq
    assert sessions["int8"] >= 2 * sessions["f32"], sessions


def test_unsupported_kv_dtype_is_rejected(toy):
    with pytest.raises(ValueError, match="kv_dtype"):
        DecodeScheduler(toy, name="qbad", cache=False,
                        kv_dtype="int4", **GEOM)


def test_int8_prefix_dedupe_and_kv_dump_quant_block(toy):
    s = DecodeScheduler(toy, name="qpfx", cache=False, kv_dtype="int8",
                        prefix_caching=True, prefill_chunk_tokens=4,
                        **GEOM)
    try:
        p = [5, 6, 7, 8, 1, 2]
        r1 = s.generate(p, 4, timeout=60)
        r2 = s.generate(p, 4, timeout=60)
        assert r1["tokens"] == r2["tokens"] \
            == toy.generate_reference(p, 4)
        st = s.stats()
        assert st["prefix_hits"] >= 1            # keyed on int8 bytes
        dump = s.kv_dump()
        assert dump["kv_dtype"] == "int8"
        quant = dump["quant"]
        assert quant["bytes_per_block"] == st["block_bytes"]
        scales = quant["scales"]
        assert scales["min"] <= scales["mean"] <= scales["max"]
        assert not dump["integrity"], dump["integrity"]
        assert st["kv_bytes_resident"] > 0       # published blocks
    finally:
        s.close(drain=True)


def test_int8_disk_tier_readmit_exact(tmp_path):
    """Demote/readmit carries int8 blocks + scales through the disk
    tier's existing chunk format: a chain evicted from the int8 HBM
    pool re-admits from disk (keyed on the QUANTIZED bytes) and keeps
    emitting exact tokens."""
    model = ToyDecodeModel(vocab=31)
    oracle = model.generate_reference
    s = DecodeScheduler(model, name="qdisk", cache=False,
                        kv_dtype="int8", max_batch=2, block_size=4,
                        max_prompt_len=16, max_new_tokens=8,
                        num_blocks=8, prefix_caching=True,
                        prefill_chunk_tokens=8,
                        kvtier={"disk_dir": str(tmp_path)})
    try:
        prompt = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5, 8, 9]
        cold = s.generate(prompt, 6, timeout=60)
        assert cold["tokens"] == oracle(prompt, 6)
        for i in range(4):        # churn the 7-usable-block pool
            filler = [(7 + 3 * i + j) % 31 for j in range(8)]
            assert s.generate(filler, 4, timeout=60)["tokens"] == \
                oracle(filler, 4)
        kstats = s.stats()["kvtier"]
        assert kstats["demotions"]["disk"] > 0
        warm = s.generate(prompt, 6, timeout=60)
        assert warm["tokens"] == cold["tokens"]
        assert s.stats()["kvtier"]["disk_readmits"] >= 3
        assert s._kvtier.check_integrity() == []
    finally:
        s.close(drain=True)


def test_int8_composes_with_speculation():
    toy = ToyDecodeModel(vocab=31, draft_agreement=0.75)
    s = DecodeScheduler(toy, name="qspec", cache=False,
                        kv_dtype="int8", spec_depth=2, **GEOM)
    try:
        rng = numpy.random.RandomState(11)
        for _ in range(4):
            p = rng.randint(0, 31, rng.randint(1, 9)).tolist()
            n = int(rng.randint(1, 9))
            assert s.generate(p, n, timeout=60)["tokens"] == \
                toy.generate_reference(p, n)
        assert s.metrics.draft_tokens > 0
    finally:
        s.close(drain=True)


def test_checkpoint_refuses_dtype_mismatch_by_name(toy, tmp_path):
    s8 = DecodeScheduler(toy, name="qck8", cache=False,
                         kv_dtype="int8", **GEOM)
    s8b = DecodeScheduler(toy, name="qck8b", cache=False,
                          kv_dtype="int8", **GEOM)
    s32 = DecodeScheduler(toy, name="qck32", cache=False, **GEOM)
    try:
        s8.generate([9, 8, 7], 4, timeout=60)
        path = s8.checkpoint_kv(str(tmp_path))
        s8b.restore_kv(path)                     # same dtype: fine
        with pytest.raises(ValueError, match="kv_dtype mismatch"):
            s32.restore_kv(path)
    finally:
        for s in (s8, s8b, s32):
            s.close(drain=True)


def test_migration_refuses_dtype_mismatch_then_resumes_exact():
    slow = ToyDecodeModel(vocab=64)
    slow.step_host_delay = 0.05
    kw = dict(max_batch=2, block_size=4, max_prompt_len=8,
              max_new_tokens=16, cache=False)
    src = DecodeScheduler(slow, name="qmsrc", kv_dtype="int8", **kw)
    tgt32 = DecodeScheduler(ToyDecodeModel(vocab=64), name="qmt32",
                            **kw)
    tgt8 = DecodeScheduler(slow, name="qmt8", kv_dtype="int8", **kw)
    try:
        src.submit([9, 8, 7], 12)
        time.sleep(0.3)
        states = src.export_sessions()
        assert states
        done, errors = tgt32.import_sessions(states)
        assert errors and "kv_dtype mismatch" in errors[0][1], \
            (done, errors)
        done, errors = tgt8.import_sessions(states)
        assert done and not errors, (done, errors)
        src.release_migrated(done, target="qmt8")
        _, fut = tgt8.attach(done[0])
        res = fut.result(60)
        assert res["tokens"] == slow.generate_reference([9, 8, 7], 12)
    finally:
        for s in (src, tgt32, tgt8):
            s.close(drain=True)


# -- warm restart: dtype-suffixed executable tags -----------------------------

def test_warm_restart_int8_compiles_nothing_distinct_tags(tmp_path,
                                                          toy):
    """Cold int8 populates dtype-suffixed cache entries; the warm int8
    restart deserializes every executable (compiles == 0).  A first f32
    start over the SAME populated cache still cold-compiles — neither
    precision can hit the other's entries."""
    from veles_tpu.compilecache import reset_default_caches
    from veles_tpu.config import root
    prior = root.common.compile_cache.get("dir", None)
    root.common.compile_cache.dir = str(tmp_path / "cache")
    reset_default_caches()
    kw = dict(GEOM, kv_dtype="int8")
    try:
        prompt = [5, 4, 3, 2, 1]
        s1 = DecodeScheduler(toy, name="qres", **kw)
        cold = s1.stats()
        r1 = s1.generate(prompt, 6, timeout=60)
        s1.close(drain=True)
        assert cold["compiles"] == cold["executables"] > 0
        s2 = DecodeScheduler(toy, name="qres", **kw)
        warm = s2.stats()
        r2 = s2.generate(prompt, 6, timeout=60)
        assert s2.stats()["post_warmup_compiles"] == 0
        s2.close(drain=True)
        assert warm["compiles"] == 0
        assert warm["cache_hits"] == warm["executables"] == \
            cold["executables"]
        assert r1["tokens"] == r2["tokens"] \
            == toy.generate_reference(prompt, 6)
        s3 = DecodeScheduler(toy, name="qres", **GEOM)   # f32, same name
        f32_first = s3.stats()
        s3.close(drain=True)
        assert f32_first["compiles"] > 0
    finally:
        root.common.compile_cache.dir = prior
        reset_default_caches()


# -- autotune: the first lossy site -------------------------------------------

def test_kv_dtype_site_declares_the_only_error_bound():
    from veles_tpu.autotune.space import SITES
    sp = SITES["serving.kv_dtype"]
    assert sp.error_bound == 1e-2
    assert sp.default == {"kv_dtype": "f32"}
    assert sp.candidates()[0] == {"kv_dtype": "f32"}
    assert {"kv_dtype": "int8"} in sp.candidates()
    others = {n: s.error_bound for n, s in SITES.items()
              if n != "serving.kv_dtype"}
    assert all(b is None for b in others.values()), others
    assert sp.shape_class({"max_context": 48}) == "ctx64"


def test_probe_logit_rmse_zero_for_f32_bounded_for_int8():
    from veles_tpu.autotune.probe import _decode_logit_rmse
    model = FlagshipDecodeModel(stages=2, experts=2, d=16, heads=2,
                                hidden=32, vocab=32, seed=0)
    assert _decode_logit_rmse(model, "f32", [3, 1, 2], 6) == 0.0
    rmse = _decode_logit_rmse(model, "int8", [3, 1, 2], 6)
    assert 0.0 < rmse <= 1e-2, rmse


def test_probe_gate_fails_when_bound_tightened():
    """The gate obeys the DECLARED bound: the same int8 candidate that
    passes at the site's 1e-2 fails when the ctx narrows it below the
    measured RMSE — the runner then keeps the default."""
    from veles_tpu.autotune.probe import probe_kv_dtype
    out = probe_kv_dtype({"kv_dtype": "int8"},
                         {"max_context": 32, "requests": 2,
                          "error_bound": 1e-9}, 1, 1)
    assert out["gate"] != "passed"
    assert "logit_rmse" in out.get("gate_detail", "") or \
        out.get("logit_rmse", 0) > 1e-9


# -- metrics + registry spec --------------------------------------------------

def test_quant_metrics_families_exposed(toy):
    from veles_tpu.observability.registry import REGISTRY
    s = DecodeScheduler(toy, name="qmet", cache=False,
                        kv_dtype="int8", **GEOM)
    try:
        s.generate([1, 2, 3], 4, timeout=60)
        text = REGISTRY.render_prometheus()
        assert "veles_decode_kv_bytes_resident" in text
        assert "veles_decode_kv_dtype_info" in text
        assert 'kv_dtype="int8"' in text
    finally:
        s.close(drain=True)


def test_toydecode_spec_carries_kv_dtype():
    from veles_tpu.serving.toydecode import from_spec
    m = from_spec("toydecode:vocab=64,block=4,kv_dtype=int8")
    assert m.decode_defaults["kv_dtype"] == "int8"
    # f32 is the default and must stay byte-identical: the spec key
    # vanishes rather than forwarding an explicit kwarg
    m32 = from_spec("toydecode:vocab=64,block=4,kv_dtype=f32")
    assert "kv_dtype" not in m32.decode_defaults
    with pytest.raises(ValueError, match="kv_dtype"):
        from_spec("toydecode:kv_dtype=int4")
