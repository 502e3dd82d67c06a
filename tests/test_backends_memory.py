"""Tests for the device/memory layer (patterned after the reference
multi-backend tests, /root/reference/veles/tests/accelerated_test.py)."""

import pickle

import numpy
import pytest

from veles_tpu.backends import Device, CPUDevice, NumpyDevice, resolve_dtype
from veles_tpu.memory import Array, Watcher
from veles_tpu.accelerated_units import AcceleratedUnit, DeviceBenchmark
from veles_tpu.prng import RandomGenerator, KeyTree, get
from veles_tpu.workflow import Workflow


def test_device_registry_dispatch():
    assert isinstance(Device(backend="cpu"), CPUDevice)
    assert isinstance(Device(backend="numpy"), NumpyDevice)
    with pytest.raises(ValueError):
        Device(backend="nope")


def test_device_auto_and_benchmark():
    dev = Device(backend="auto")
    assert dev.backend_name in ("tpu", "cpu")
    gflops = dev.benchmark(size=128, repeats=1)
    assert gflops > 0


def test_tpu_backend_needs_the_tpu_and_auto_says_what_it_found():
    """An explicit ``tpu`` never degrades, and ``auto`` answers from the
    one question the kernels ask too (``backends.on_tpu``)."""
    from veles_tpu import backends
    assert not backends.on_tpu()            # tests run on the CPU
    with pytest.raises(RuntimeError, match="default platform is 'cpu'"):
        Device(backend="tpu")
    assert backends.AutoDevice.pick() == "cpu"


def test_auto_device_does_not_swallow_a_jax_that_cannot_start(
        monkeypatch):
    from veles_tpu import backends

    def broken():
        raise RuntimeError("Unable to initialize backend 'tpu'")
    monkeypatch.setattr(backends, "on_tpu", broken)
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        Device(backend="auto")


def test_caches_are_placed_from_outside_or_in_the_checkout(
        monkeypatch, tmp_path):
    """$JAX_COMPILATION_CACHE_DIR places every cache and nothing sets
    another JAX cache directory; unset, everything lies under the
    checkout's fixed .cache/."""
    import os
    import jax
    from veles_tpu import backends
    from veles_tpu.config import root
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    monkeypatch.delenv(backends.CACHE_DIR_ENV, raising=False)
    assert backends.cache_root() == os.path.join(repo, ".cache")
    assert backends.cache_dir("veles_autotune") == os.path.join(
        repo, ".cache", "veles_autotune")
    placed = str(tmp_path / "placed")
    monkeypatch.setenv(backends.CACHE_DIR_ENV, placed)
    assert backends.cache_root() == placed
    assert backends.cache_dir("veles_executables") == os.path.join(
        placed, "veles_executables")
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setattr(root.common.engine, "compilation_cache_dir",
                        str(tmp_path / "elsewhere"), raising=False)
    try:
        assert backends.apply_compilation_cache_config() == placed
        assert jax.config.jax_compilation_cache_dir == before
        assert not (tmp_path / "elsewhere").exists()
    finally:
        root.common.engine.compilation_cache_dir = None


def test_children_sharing_the_tpu_are_refused_at_start():
    """Several local children on a TPU host cannot work (one process
    per chip, no chip assignment yet): the launchers ask this before
    they spawn, and get that sentence instead of a hang.  Any number of
    CPU children is fine."""
    from veles_tpu.backends import refuse_children_sharing_the_tpu
    refuse_children_sharing_the_tpu(8, "test", {"JAX_PLATFORMS": "cpu"})
    refuse_children_sharing_the_tpu(1, "test", {"JAX_PLATFORMS": "tpu"})
    with pytest.raises(RuntimeError,
                       match="a TPU chip belongs to one process"):
        refuse_children_sharing_the_tpu(2, "test",
                                        {"JAX_PLATFORMS": "tpu,cpu"})


def test_numpy_device():
    dev = NumpyDevice()
    assert not dev.exists
    assert dev.benchmark(size=64) > 0


def test_resolve_dtype():
    assert resolve_dtype("float32") == numpy.float32
    assert resolve_dtype("bfloat16").itemsize == 2


def test_array_roundtrip():
    a = Array(numpy.arange(12, dtype=numpy.float32).reshape(3, 4))
    assert a.shape == (3, 4)
    assert a.sample_size == 4
    dm = a.devmem
    assert dm is not None
    # device copy reflects host data
    assert numpy.allclose(numpy.asarray(dm), a.mem)
    # host mutation via map_write then unmap re-uploads
    a.map_write()[0, 0] = 99
    a.unmap()
    assert numpy.asarray(a.devmem)[0, 0] == 99


def test_array_device_to_host():
    import jax.numpy as jnp
    a = Array(numpy.zeros((2, 2), numpy.float32))
    a.devmem = jnp.ones((2, 2))
    # device is newer; map_read pulls
    assert a.map_read()[0, 0] == 1.0


def test_array_watcher_accounting():
    Watcher.reset()
    a = Array(numpy.zeros(1024, numpy.float32))
    _ = a.devmem
    assert Watcher.bytes_in_use >= 4096
    a.reset()
    assert Watcher.bytes_in_use == 0


def test_array_pickle_and_shallow():
    a = Array(numpy.arange(4.0))
    b = pickle.loads(pickle.dumps(a))
    assert numpy.allclose(b.mem, a.mem)
    a.shallow_pickle = True
    c = pickle.loads(pickle.dumps(a))
    assert c.mem is None


def test_prng_reproducible():
    g1 = RandomGenerator().seed(1234)
    g2 = RandomGenerator().seed(1234)
    assert numpy.allclose(g1.normal(size=8), g2.normal(size=8))
    # state save/restore determinism (snapshot semantics)
    state = pickle.dumps(g1)
    x = g1.uniform(size=4)
    g3 = pickle.loads(state)
    assert numpy.allclose(g3.uniform(size=4), x)
    assert get(0) is get(0)


def test_key_tree_deterministic():
    import jax
    kt1, kt2 = KeyTree(7), KeyTree(7)
    k1 = kt1.key_for("conv1")
    k2 = kt2.key_for("conv1")
    assert numpy.allclose(jax.random.uniform(k1, (4,)),
                          jax.random.uniform(k2, (4,)))
    # advancing produces a different stream
    k3 = kt1.key_for("conv1")
    assert not numpy.allclose(jax.random.uniform(k1, (4,)),
                              jax.random.uniform(k3, (4,)))
    # pickles with counters
    kt4 = pickle.loads(pickle.dumps(kt1))
    assert kt4.counters == kt1.counters


class _Doubler(AcceleratedUnit):
    """out = 2*x + 1 with device and numpy twins."""

    def __init__(self, workflow, **kwargs):
        super().__init__(workflow, **kwargs)
        self.input = Array()
        self.output = Array()
        self.device_inputs = ["input"]
        self.device_outputs = ["output"]

    def kernel(self, x):
        return 2 * x + 1

    def numpy_run(self):
        self.output.mem = 2 * self.input.map_read() + 1


@pytest.mark.parametrize("backend", ["cpu", "numpy"])
def test_accelerated_unit_parity(backend):
    wf = Workflow(name="w")
    u = _Doubler(wf)
    u.input.mem = numpy.arange(6, dtype=numpy.float32).reshape(2, 3)
    u.initialize(device=Device(backend=backend))
    u.run()
    assert numpy.allclose(u.output.map_read(),
                          2 * u.input.mem + 1)


def test_device_benchmark_unit():
    wf = Workflow(name="w")
    b = DeviceBenchmark(wf, size=128, repeats=1)
    b.initialize(device=Device(backend="cpu"))
    assert b.estimate() > 0


def test_precision_level_knob():
    """precision_level 0/1/2 → jax matmul precision (the reference's GEMM
    PRECISION_LEVEL plain/Kahan/multipartial knob, veles/config.py:
    245-248)."""
    import jax
    from veles_tpu.backends import Device
    from veles_tpu.config import root
    before = jax.config.jax_default_matmul_precision
    try:
        Device(backend="cpu", precision_level=2)
        assert str(jax.config.jax_default_matmul_precision) == "highest"
        root.common.engine.precision_level = 1
        Device(backend="cpu")
        assert str(jax.config.jax_default_matmul_precision) == "high"
    finally:
        root.common.engine.precision_level = 0
        jax.config.update("jax_default_matmul_precision", before)
