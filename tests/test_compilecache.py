"""veles_tpu.compilecache: persistent AOT executable cache + warmup
manifests (ISSUE 5).

The contract under test: a warm-cache restart deserializes instead of
compiling (zero bucket compiles, proven in-process AND across real
processes); a corrupted or version-mismatched entry NEVER crashes or
changes a result — it quarantines/misses and falls back to a fresh
compile; an unset cache dir reproduces pre-cache behavior exactly.
"""

import json
import os
import subprocess
import sys
import time

import numpy
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from veles_tpu import compilecache as cc                    # noqa: E402
from veles_tpu.compilecache import keys as keys_mod         # noqa: E402
from veles_tpu.config import root                           # noqa: E402
from veles_tpu.observability.registry import REGISTRY       # noqa: E402


@pytest.fixture
def cache_dir(tmp_path):
    """A cache directory wired into config, torn back down after."""
    d = str(tmp_path / "compile_cache")
    prior = root.common.compile_cache.get("dir", None)
    root.common.compile_cache.dir = d
    cc.reset_default_caches()
    try:
        yield d
    finally:
        root.common.compile_cache.dir = prior
        cc.reset_default_caches()


def _jit_and_structs(scale=2.0):
    import jax
    fn = jax.jit(lambda p, x: p["w"] * x * scale)
    structs = ({"w": jax.ShapeDtypeStruct((), numpy.float32)},
               jax.ShapeDtypeStruct((4,), numpy.float32))
    args = ({"w": numpy.float32(3.0)},
            numpy.arange(4, dtype=numpy.float32))
    return fn, structs, args


def _counter(name):
    metric = REGISTRY.get(name)
    return metric.value if metric is not None else 0.0


# -- keys ---------------------------------------------------------------------

def test_cache_key_stable_and_sensitive(monkeypatch):
    fn, structs, _ = _jit_and_structs()
    lowered = fn.lower(*structs)
    k1 = cc.cache_key(lowered)
    assert k1 == cc.cache_key(lowered)          # deterministic
    assert cc.cache_key(lowered, extra={"m": 1}) != k1
    # environment drift (jax/jaxlib version, platform, device kind)
    # must change the key — a stale entry misses instead of misloading
    monkeypatch.setattr(keys_mod, "environment_fingerprint",
                        lambda: "jax=9.9.9;other")
    assert cc.cache_key(lowered) != k1


# -- store --------------------------------------------------------------------

def test_store_roundtrip_atomic(tmp_path):
    store = cc.ExecutableStore(str(tmp_path))
    assert store.get("k" * 64) is None
    store.put("k" * 64, b"payload")
    assert store.get("k" * 64) == b"payload"
    # durability convention: no *.tmp orphan left at its final name
    assert not [n for n in os.listdir(str(tmp_path)) if ".tmp" in n]


def test_store_lru_eviction_respects_budget(tmp_path):
    store = cc.ExecutableStore(str(tmp_path), max_bytes=250)
    for i in range(4):
        store.put("key%060d" % i, b"x" * 100)
        time.sleep(0.01)            # distinct mtimes for LRU ordering
    assert store.total_bytes() <= 250
    keys = {k for k, _, _ in store.entries()}
    assert "key%060d" % 3 in keys   # newest survives
    assert "key%060d" % 0 not in keys


def test_store_quarantine_renames_aside(tmp_path):
    store = cc.ExecutableStore(str(tmp_path))
    store.put("q" * 64, b"bad")
    assert store.quarantine("q" * 64, "test")
    assert store.get("q" * 64) is None
    assert os.path.exists(store.path_for("q" * 64) + ".corrupt")
    assert not store.quarantine("q" * 64)       # idempotent


# -- the cache core -----------------------------------------------------------

def test_get_or_compile_miss_then_hit_with_metrics(tmp_path):
    fn, structs, args = _jit_and_structs()
    cache = cc.CompileCache(str(tmp_path))
    h0, m0 = _counter("veles_compile_cache_hits_total"), \
        _counter("veles_compile_cache_misses_total")
    run1, hit1 = cache.get_or_compile(fn, *structs, name="t")
    run2, hit2 = cache.get_or_compile(fn, *structs, name="t")
    assert (hit1, hit2) == (False, True)
    assert _counter("veles_compile_cache_misses_total") == m0 + 1
    assert _counter("veles_compile_cache_hits_total") == h0 + 1
    assert _counter("veles_compile_cache_bytes_total") > 0
    expected = numpy.asarray(args[1]) * 3.0 * 2.0
    numpy.testing.assert_allclose(numpy.asarray(run2(*args)), expected)
    assert cache.stats()["entries"] == 1


def test_corrupt_entry_recompiles_quarantines_logs_once(tmp_path, caplog):
    fn, structs, args = _jit_and_structs()
    cache = cc.CompileCache(str(tmp_path))
    cache.get_or_compile(fn, *structs, name="t")
    (key, _, _), = cache.store.entries()
    with open(cache.store.path_for(key), "wb") as f:
        f.write(b"\x80\x04 truncated garbage")
    with caplog.at_level("WARNING", logger="veles_tpu.compilecache"):
        run, hit = cache.get_or_compile(fn, *structs, name="t")
    # fell back to a fresh compile: no crash, correct result, evidence
    # quarantined, exactly one warning
    assert hit is False
    numpy.testing.assert_allclose(numpy.asarray(run(*args)),
                                  numpy.asarray(args[1]) * 6.0)
    assert os.path.exists(cache.store.path_for(key) + ".corrupt")
    warnings = [r for r in caplog.records if "corrupt" in r.message]
    assert len(warnings) == 1
    # the recompile re-persisted a good entry: next lookup hits
    _, hit3 = cache.get_or_compile(fn, *structs, name="t")
    assert hit3 is True


def test_version_mismatch_is_clean_miss(tmp_path, monkeypatch):
    fn, structs, args = _jit_and_structs()
    cache = cc.CompileCache(str(tmp_path))
    cache.get_or_compile(fn, *structs, name="t")
    monkeypatch.setattr(keys_mod, "environment_fingerprint",
                        lambda: "jax=0.0.0;jaxlib=0.0.0;other-device")
    run, hit = cache.get_or_compile(fn, *structs, name="t")
    assert hit is False             # different key, never a misload
    numpy.testing.assert_allclose(numpy.asarray(run(*args)),
                                  numpy.asarray(args[1]) * 6.0)
    assert len(cache.store.entries()) == 2      # both keys live


def test_entry_key_cross_check_rejects_wrong_file(tmp_path):
    """A blob copied to the wrong key (or a hash collision in the file
    namespace) is detected by the stored-key cross-check."""
    fn, structs, _ = _jit_and_structs()
    cache = cc.CompileCache(str(tmp_path))
    cache.get_or_compile(fn, *structs, name="t")
    (key, _, _), = cache.store.entries()
    blob = cache.store.get(key)
    wrong = "f" * len(key)
    cache.store.put(wrong, blob)
    assert cache._try_load(wrong, "t") is None
    assert os.path.exists(cache.store.path_for(wrong) + ".corrupt")


# -- AotStep (the fused-step adapter) ----------------------------------------

def test_aot_step_matches_jit_and_keeps_interfaces(tmp_path):
    import jax
    cache = cc.CompileCache(str(tmp_path))
    jitted = jax.jit(lambda p, x, n: p["w"] * x + n, donate_argnums=())
    step = cc.AotStep(jitted, cache, "test.step")
    args = ({"w": numpy.float32(2.0)},
            numpy.arange(3, dtype=numpy.float32), 5)    # python int arg
    out = numpy.asarray(step(*args))
    numpy.testing.assert_allclose(out, numpy.asarray(
        jitted({"w": numpy.float32(2.0)},
               numpy.arange(3, dtype=numpy.float32), 5)))
    assert step.cache_hit is False
    # the interfaces other layers rely on survive the wrap
    assert step.__wrapped__ is jitted.__wrapped__
    assert isinstance(step._cache_size(), int)
    # a second process-equivalent wrap hits
    step2 = cc.AotStep(jitted, cc.CompileCache(str(tmp_path)),
                       "test.step")
    numpy.testing.assert_allclose(numpy.asarray(step2(*args)), out)
    assert step2.cache_hit is True


def test_aot_step_keys_and_serves_an_arguments_layout(tmp_path):
    """An array placed in another layout than its device's default (the
    resident set after ``FusedTrainStep._place_data``) brings that layout
    into the lowering and so into the key: the executable cached for the
    default layout of the same shape is never handed it."""
    import jax
    from jax.experimental.layout import Format, Layout
    from veles_tpu.compilecache.keys import cache_key
    x = jax.device_put(numpy.arange(12, dtype=numpy.float32).reshape(3, 4))
    other = Layout(major_to_minor=tuple(reversed(
        x.format.layout.major_to_minor)))
    placed = jax.device_put(x, Format(other, x.sharding))
    assert placed.format.layout != x.format.layout
    # the default layout is left unnamed, as it always was
    assert cc.AotStep._leaf_struct(x).sharding is None
    assert cc.AotStep._leaf_struct(numpy.asarray(x)).sharding is None
    assert cc.AotStep._leaf_struct(placed).format == placed.format
    jitted = jax.jit(lambda a, n: a[1] * n)
    keys = {cache_key(jitted.lower(cc.AotStep._leaf_struct(a), 2))
            for a in (x, placed)}
    assert len(keys) == 2
    cache = cc.CompileCache(str(tmp_path))
    want = numpy.asarray(x)[1] * 2
    for array, hits in ((x, [False, True]), (placed, [False, True])):
        for hit in hits:
            step = cc.AotStep(jitted, cache, "test.layout")
            assert numpy.array_equal(numpy.asarray(step(array, 2)), want)
            assert step.cache_hit is hit
            assert step._cache_size() == 1
            assert step.lower(array, 2).as_text()


def test_aot_step_surfaces_a_failed_compile(tmp_path, monkeypatch):
    """A step that cannot be compiled raises: a quiet second path
    through plain jit would hide a program the device refused."""
    import jax
    cache = cc.CompileCache(str(tmp_path))

    def boom(*a, **k):
        raise RuntimeError("compiler refused")

    monkeypatch.setattr(cache, "load_or_compile", boom)
    step = cc.AotStep(jax.jit(lambda x: x * 2), cache, "test.step")
    x = numpy.arange(4, dtype=numpy.float32)
    with pytest.raises(RuntimeError, match="compiler refused"):
        step(x)
    with pytest.raises(RuntimeError, match="compiler refused"):
        step(x)                                 # and keeps raising


# -- serving scheduler integration -------------------------------------------

def _make_model():
    from veles_tpu.serving.scheduler import JaxModel
    return JaxModel(lambda p, x: x * p["scale"],
                    {"scale": numpy.float32(3.0)}, (2,))


def test_scheduler_warm_restart_zero_compiles(cache_dir):
    from veles_tpu.serving import BucketScheduler
    first = BucketScheduler(_make_model(), max_batch=8, name="cc_m1")
    s1 = first.stats()
    first.close()
    assert s1["compiles"] == 4 and s1["cache_hits"] == 0
    # "restart": a fresh scheduler + model in the same cache dir — the
    # acceptance guarantee: ZERO bucket compilations, all buckets warm
    second = BucketScheduler(_make_model(), max_batch=8, name="cc_m1")
    s2 = second.stats()
    out = second.infer(numpy.ones((3, 2), numpy.float32))
    try:
        assert s2["compiles"] == 0
        assert s2["cache_hits"] == len(s2["buckets"]) == 4
        assert s2["post_warmup_compiles"] == 0
        numpy.testing.assert_allclose(out, numpy.full((3, 2), 3.0))
        assert second.metrics.snapshot()["compile_seconds"] >= 0
    finally:
        second.close()


def test_scheduler_unset_dir_reproduces_seed_behavior():
    from veles_tpu.serving import BucketScheduler
    assert root.common.compile_cache.get("dir", None) is None
    sched = BucketScheduler(_make_model(), max_batch=8, name="cc_off")
    try:
        assert sched._cache is None and sched._manifest is None
        stats = sched.stats()
        assert stats["compiles"] == stats["warmup_compiles"] == 4
        assert stats["cache_hits"] == 0
    finally:
        sched.close()


def test_manifest_records_and_orders_warmup(cache_dir):
    from veles_tpu.serving import BucketScheduler
    sched = BucketScheduler(_make_model(), max_batch=8, name="cc_m2")
    sched.close()
    manifest = cc.default_cache().manifest
    assert manifest.buckets("cc_m2") == [1, 2, 4, 8]
    path = os.path.join(cache_dir, "warmup_manifest.json")
    assert json.load(open(path))["models"]["cc_m2"]
    # a restart consults the manifest: recorded buckets warm first
    again = BucketScheduler(_make_model(), max_batch=8, name="cc_m2",
                            warmup=False)
    try:
        assert again._warmup_order() == [1, 2, 4, 8]
        manifest.forget("cc_m2")
        manifest.record("cc_m2", 4)
        assert again._warmup_order()[0] == 4
    finally:
        again.close()


def test_background_warmup_serves_before_tail_finishes(cache_dir):
    from veles_tpu.serving import BucketScheduler
    BucketScheduler(_make_model(), max_batch=8, name="cc_m3").close()
    sched = BucketScheduler(_make_model(), max_batch=8, name="cc_m3",
                            background_warmup=True)
    try:
        # the first bucket is warm synchronously — a request is
        # servable immediately, whatever the tail is doing
        out = sched.infer(numpy.ones((1, 2), numpy.float32))
        numpy.testing.assert_allclose(out, numpy.full((1, 2), 3.0))
        assert sched.join_warmup(timeout=30.0)
        stats = sched.stats()
        assert sorted(stats["buckets"]) == [1, 2, 4, 8]
        assert len(sched._executables) == 4
        assert stats["post_warmup_compiles"] == 0
        assert stats["compiles"] == 0           # warm cache end to end
    finally:
        sched.close()


def test_corrupt_cache_never_breaks_serving(cache_dir):
    from veles_tpu.serving import BucketScheduler
    BucketScheduler(_make_model(), max_batch=4, name="cc_m4").close()
    store = cc.default_cache().store
    for key, _, _ in store.entries():
        with open(store.path_for(key), "wb") as f:
            f.write(b"not an executable")
    sched = BucketScheduler(_make_model(), max_batch=4, name="cc_m4")
    try:
        out = sched.infer(numpy.ones((2, 2), numpy.float32))
        numpy.testing.assert_allclose(out, numpy.full((2, 2), 3.0))
        stats = sched.stats()
        assert stats["cache_hits"] == 0         # every entry was bad
        assert stats["compiles"] == len(stats["buckets"])
    finally:
        sched.close()
    corrupt = [n for n in os.listdir(store.directory)
               if n.endswith(".corrupt")]
    assert len(corrupt) == len(sched.stats()["buckets"])


# -- fused train step integration --------------------------------------------

def _train_mnist_steps(n_steps, cache_dir_value):
    from veles_tpu import loader as loader_mod, prng
    from veles_tpu.backends import Device
    from veles_tpu.prng import RandomGenerator
    from veles_tpu.znicz.samples import mnist
    prng.get().seed(7)
    prior = root.common.compile_cache.get("dir", None)
    root.common.compile_cache.dir = cache_dir_value
    cc.reset_default_caches()
    try:
        wf = mnist.create_workflow(
            loader={"minibatch_size": 16, "n_train": 64, "n_valid": 16,
                    "use_fixture": False,
                    "prng": RandomGenerator().seed(3),
                    "prefetch_depth": 0},
            decision={"max_epochs": 10 ** 9, "silent": True})
        wf.initialize(device=Device(backend="cpu"))
        step = wf.fused_step
        done = 0
        while done < n_steps:
            wf.loader.run()
            if wf.loader.minibatch_class == loader_mod.TRAIN:
                step.run()
                done += 1
        step.sync_weights()
        return numpy.asarray(step.forwards[0].params["weights"]), step
    finally:
        root.common.compile_cache.dir = prior
        cc.reset_default_caches()


def test_fused_step_cache_roundtrip_bitwise_parity(tmp_path):
    """Cache off vs cold vs warm: identical weights after 5 steps —
    enabling the cache can never change training results."""
    d = str(tmp_path / "cc")
    w_off, s_off = _train_mnist_steps(5, None)
    w_cold, s_cold = _train_mnist_steps(5, d)
    w_warm, s_warm = _train_mnist_steps(5, d)
    assert numpy.array_equal(w_off, w_cold)
    assert numpy.array_equal(w_cold, w_warm)
    step_attr = ("_train_step_g_" if getattr(s_cold, "_use_gather_",
                                             False) else "_train_step_")
    assert isinstance(getattr(s_cold, step_attr), cc.AotStep)
    assert getattr(s_cold, step_attr).cache_hit is False
    assert getattr(s_warm, step_attr).cache_hit is True
    # no directory, no store: the plain step stays a jit, the gather
    # step is compiled ahead (its data argument's layout is the
    # compiler's to choose) and consults nothing
    assert not isinstance(s_off._train_step_, cc.AotStep)
    assert getattr(s_off, step_attr).cache_hit is None


# -- cross-process restart (the real thing) ----------------------------------

def test_cross_process_warm_restart_zero_compiles(tmp_path):
    """Two fresh processes share a cache dir: the second's serving
    warmup performs ZERO XLA compilations — the executable cache works
    across process lifetimes, not just within one."""
    from tools.serve_bench import build_mnist_package
    package = build_mnist_package(str(tmp_path / "pkg.zip"))
    cache_dir = str(tmp_path / "cc")
    tool = os.path.join(REPO, "tools", "cold_start.py")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"

    def probe():
        proc = subprocess.run(
            [sys.executable, tool, "--phase", "serving",
             "--package", package, "--max-batch", "4",
             "--cache-dir", cache_dir],
            capture_output=True, text=True, timeout=180, env=env,
            cwd=REPO)
        assert proc.returncode == 0, proc.stderr[-800:]
        return json.loads(proc.stdout.strip().splitlines()[-1])

    cold = probe()
    warm = probe()
    assert cold["compiles"] == len(cold["buckets"]) > 0
    assert cold["cache_hits"] == 0
    assert warm["compiles"] == 0
    assert warm["cache_hits"] == len(warm["buckets"])
    assert warm["output_rows"] == 1


# -- satellites ---------------------------------------------------------------

def test_inject_env_hands_cache_to_children(tmp_path):
    prior_cc = root.common.compile_cache.get("dir", None)
    prior_jax = root.common.engine.get("compilation_cache_dir", None)
    try:
        root.common.compile_cache.dir = None
        root.common.engine.compilation_cache_dir = None
        assert cc.inject_env({"A": "1"}) == {"A": "1"}   # unset: no-op
        root.common.compile_cache.dir = str(tmp_path / "cc")
        root.common.engine.compilation_cache_dir = str(tmp_path / "jx")
        env = cc.inject_env({})
        assert env["VELES_COMPILE_CACHE_DIR"] == \
            os.path.abspath(str(tmp_path / "cc"))
        assert env["JAX_COMPILATION_CACHE_DIR"] == \
            os.path.abspath(str(tmp_path / "jx"))
    finally:
        root.common.compile_cache.dir = prior_cc
        root.common.engine.compilation_cache_dir = prior_jax


def test_backends_apply_jax_compilation_cache_knob(tmp_path):
    import jax
    from veles_tpu.backends import apply_compilation_cache_config
    prior_dir = jax.config.jax_compilation_cache_dir
    prior_cfg = root.common.engine.get("compilation_cache_dir", None)
    try:
        root.common.engine.compilation_cache_dir = None
        assert apply_compilation_cache_config() is None
        assert jax.config.jax_compilation_cache_dir == prior_dir
        root.common.engine.compilation_cache_dir = str(tmp_path / "jx")
        root.common.engine.compilation_cache_min_entry_bytes = 128
        applied = apply_compilation_cache_config()
        assert applied == os.path.abspath(str(tmp_path / "jx"))
        assert jax.config.jax_compilation_cache_dir == applied
        assert os.path.isdir(applied)
        assert jax.config.jax_persistent_cache_min_entry_size_bytes \
            == 128
    finally:
        root.common.engine.compilation_cache_dir = prior_cfg
        del root.common.engine.compilation_cache_min_entry_bytes
        root.common.engine.compilation_cache_min_entry_bytes = 0
        jax.config.update("jax_compilation_cache_dir", prior_dir)


def test_manifest_survives_corruption(tmp_path):
    path = str(tmp_path / "m.json")
    m = cc.WarmupManifest(path)
    assert m.record("a", 4, sample_shape=(2, 3))
    assert not m.record("a", 4)                 # dedupe
    assert m.record("a", 1)
    assert cc.WarmupManifest(path).buckets("a") == [1, 4]
    with open(path, "w") as f:
        f.write("{mangled json")
    m2 = cc.WarmupManifest(path)                # no crash, starts empty
    assert m2.buckets("a") == []
    assert m2.record("b", 2)
