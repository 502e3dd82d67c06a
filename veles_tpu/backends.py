"""Device backends: the layer that binds the unit graph to hardware.

TPU-native re-design of /root/reference/veles/backends.py (Device base +
BackendRegistry :166-197, OpenCLDevice :426, CUDADevice :745, NumpyDevice
:918, AutoDevice :406).  The reference selects an OpenCL/CUDA context and
hands units raw queues; here a Device owns a set of JAX devices and a
:class:`jax.sharding.Mesh`, and hands units jit/compile services instead of
command queues.  The reference's per-device autotune database
(``device_infos.json``, backends.py:623-731) is unnecessary: XLA autotunes
tiling for the MXU at compile time, and the persistent compilation cache
plays the role of the kernel binary cache.

Backend names: ``tpu``, ``cpu`` (JAX cpu — the multi-device virtual mesh in
tests), ``numpy`` (pure-numpy pseudo-device for parity tests), ``auto``.
Selection precedence mirrors the reference (-a flag > env > auto,
backends.py:184-197): explicit name > $VELES_BACKEND > auto.
"""

import contextlib
import os
import subprocess
import sys
import threading
import time

import numpy

from .config import root


def on_tpu():
    """The one answer to "is this process driving the TPU": JAX's default
    platform is ``tpu``.  The Pallas kernels compile through Mosaic when
    it is and run in interpret mode when it is not; ``TPUDevice``,
    ``AutoDevice`` and ``resolve_use_pallas`` ask the same question."""
    import jax
    return jax.default_backend() == "tpu"


def refuse_children_sharing_the_tpu(n, what, env=None):
    """Fail at start where ``n`` local child processes, started with
    ``env``, would share the TPU — instead of letting them hang.  A chip
    belongs to one process at a time and nothing assigns children to
    chips yet (ROADMAP R6), so a second child — or any child of a parent
    that already holds the chip — fails or hangs when it starts JAX.  On
    the CPU platform any number of children is fine.

    Answered without initialising JAX here: a parent that touches JAX
    takes the chip its children need."""
    env = os.environ if env is None else env
    jax = sys.modules.get("jax")
    initialised = (jax is not None
                   and jax._src.xla_bridge.backends_are_initialized())
    holds = initialised and on_tpu()
    if n < 2 and not holds:
        return
    platforms = env.get("JAX_PLATFORMS")
    if platforms:
        tpu = platforms.split(",")[0].strip().lower() == "tpu"
    elif initialised:
        tpu = holds
    else:
        # nothing pins the platform: a short-lived child finds out, and
        # lets go of the chip as it exits
        out = subprocess.run(
            [sys.executable, "-c",
             "import jax; print(jax.default_backend())"],
            env=env, capture_output=True, text=True, timeout=300)
        tpu = out.stdout.strip().endswith("tpu")
    if tpu:
        raise RuntimeError(
            "%s: %d child process(es) would need this host's TPU%s, and "
            "a TPU chip belongs to one process at a time: more children "
            "than chips cannot work, and children are not assigned to "
            "chips yet (ROADMAP R6). Run one child from a parent that "
            "stays off JAX, or set JAX_PLATFORMS=cpu for a CPU rehearsal."
            % (what, n, ", which this process already holds," if holds
               else ""))


#: the variable JAX itself reads for its persistent compilation cache;
#: a machine that sets it places every cache of this repo (cache_root)
CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def cache_root():
    """The one directory every compile cache and tuning store lives
    under: ``$JAX_COMPILATION_CACHE_DIR`` where the machine sets it,
    else the fixed, git-ignored ``.cache/`` of this checkout.  Never a
    temporary, pid- or time-stamped path: the path is part of JAX's
    cache key, so a directory that moves never hits."""
    return os.path.abspath(os.environ.get(CACHE_DIR_ENV)
                           or os.path.join(_CHECKOUT, ".cache"))


def cache_dir(name):
    """A fixed subdirectory of :func:`cache_root` for one of the repo's
    own stores (``veles_executables``: compilecache/, ``veles_autotune``:
    the tuning store), beside JAX's entries."""
    return os.path.join(cache_root(), name)


def apply_compilation_cache_config():
    """One-knob wiring of JAX's built-in persistent compilation cache:
    ``root.common.engine.compilation_cache_dir`` (+ min-entry-size)
    applied at backend init — every ``jax.jit`` in the process then
    reuses XLA binaries across restarts, covering what the executable
    cache (veles_tpu/compilecache/) doesn't own.  Unset = untouched
    (exact default behavior).  Where ``$JAX_COMPILATION_CACHE_DIR`` is
    set JAX already keeps its cache there and nothing else is applied.
    Installs the process's compile monitor, which files what JAX traces,
    lowers, compiles and loads from a cache as the program's spans
    (``observability/compiles.py``).  Returns the directory in use or
    None."""
    from .observability import compiles
    compiles.monitor()
    if os.environ.get(CACHE_DIR_ENV):
        return cache_root()
    directory = root.common.engine.get("compilation_cache_dir", None)
    if not directory:
        return None
    import jax
    directory = os.path.abspath(str(directory))
    os.makedirs(directory, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", directory)
    jax.config.update(
        "jax_persistent_cache_min_entry_size_bytes",
        int(root.common.engine.get("compilation_cache_min_entry_bytes",
                                   0)))
    # the default 1 s floor would skip every small-model compile this
    # knob exists to persist; the entry-size knob is the filter here
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return directory


@contextlib.contextmanager
def compiles_not_persisted():
    """What is compiled inside is not written to JAX's persistent
    compilation cache (its floor on an entry's compile time is raised
    for the while), so no later process is handed it back.  For a
    program whose RESULT lies in another layout than the device's
    default: read back from the cache (JAX 0.9.0, v5e and CPU alike) its
    executable produces the default layout, whatever was compiled
    (PERF.md section 6, PR 29).  A layout asked of a PARAMETER survives
    the cache."""
    import jax
    floor_name = "jax_persistent_cache_min_compile_time_secs"
    floor = getattr(jax.config, floor_name)
    jax.config.update(floor_name, float("inf"))
    try:
        yield
    finally:
        jax.config.update(floor_name, floor)


class BackendRegistry(type):
    """Metaclass registering Device subclasses by their ``BACKEND`` name
    (reference backends.py:166-181)."""

    backends = {}

    def __init__(cls, name, bases, clsdict):
        super().__init__(name, bases, clsdict)
        backend = clsdict.get("BACKEND")
        if backend is not None:
            BackendRegistry.backends[backend] = cls


class Device(metaclass=BackendRegistry):
    """Base device.  ``Device(backend="tpu")`` dispatches to the registered
    subclass the way the reference's ``__new__`` trick does
    (backends.py:190-197)."""

    BACKEND = None

    def __new__(cls, *args, **kwargs):
        if cls is not Device:
            return super().__new__(cls)
        backend = kwargs.get("backend") or os.environ.get(
            "VELES_BACKEND", root.common.engine.get("backend", "auto"))
        if backend == "auto":
            backend = AutoDevice.pick()
        try:
            impl = BackendRegistry.backends[backend]
        except KeyError:
            raise ValueError(
                "unknown backend %r (have: %s)" %
                (backend, ", ".join(sorted(BackendRegistry.backends))))
        return super().__new__(impl)

    #: config precision_level → jax matmul precision.  The reference's
    #: GEMM PRECISION_LEVEL 0/1/2 (plain / Kahan / 32-partial summation,
    #: ocl/matrix_multiplication_precise.cl:37,119-170) maps onto the
    #: MXU's pass-decomposition knob: DEFAULT (fast bf16 passes), HIGH
    #: (3-pass), HIGHEST (6-pass / f32 accumulation) — same
    #: speed-vs-summation-error trade, implemented by the hardware.
    PRECISION_LEVELS = {0: "default", 1: "high", 2: "highest"}

    def __init__(self, **kwargs):
        self._compute_power = None
        self._lock = threading.Lock()
        level = kwargs.get("precision_level")
        if level is None:
            level = root.common.engine.get("precision_level", 0)
        level = int(level)
        if level not in self.PRECISION_LEVELS:
            raise ValueError(
                "precision_level must be one of %s, got %r"
                % (sorted(self.PRECISION_LEVELS), level))
        import jax
        # always applied — level 0 must RESET a prior device's elevated
        # precision, or every later workflow silently pays 3-6x matmuls
        jax.config.update("jax_default_matmul_precision",
                          self.PRECISION_LEVELS[level])
        apply_compilation_cache_config()

    # Devices ride along in workflow snapshots only as stubs: locks and
    # PJRT handles cannot pickle, and a restored workflow is re-attached
    # to a fresh Device by initialize(device=...) anyway (the reference
    # drops device state the same way, memory.py:284-299).
    def __getstate__(self):
        return {}

    def __setstate__(self, state):
        self._compute_power = None
        self._lock = threading.Lock()
        self._devices = []

    # -- identity ------------------------------------------------------------
    @property
    def backend_name(self):
        return self.BACKEND

    @property
    def is_attached(self):
        return True

    def __repr__(self):
        return "<%s>" % type(self).__name__

    # -- services ------------------------------------------------------------
    @property
    def jax_devices(self):
        """The JAX devices this Device drives (empty for numpy)."""
        return []

    @property
    def default_jax_device(self):
        devs = self.jax_devices
        return devs[0] if devs else None

    def sync(self):
        """Barrier until all dispatched work completes (reference
        device.sync(); CUDA ctx sync / OCL queue finish)."""

    def memory_stats(self):
        """Bytes in use / limit on the first device, when the platform
        reports them (reference Watcher accounting, memory.py:56-107)."""
        return {}

    @property
    def compute_power(self):
        """GFLOPS-ish rating used for load balancing (reference
        DeviceBenchmark "points", accelerated_units.py:843-858)."""
        if self._compute_power is None:
            self._compute_power = self.benchmark()
        return self._compute_power

    def benchmark(self, size=1024, dtype=None, repeats=4):
        raise NotImplementedError

    @property
    def exists(self):
        """False only for the numpy pseudo-device (reference
        backends.py:918)."""
        return True


class _JaxDevice(Device):
    """Shared implementation for JAX-backed devices."""

    PLATFORM = None

    def __init__(self, **kwargs):
        super().__init__(**kwargs)
        import jax
        self._jax = jax
        try:
            self._devices = jax.devices(self.PLATFORM)
        except RuntimeError as e:
            raise RuntimeError(
                "no %s devices visible to JAX: %s" % (self.PLATFORM, e))

    @property
    def jax_devices(self):
        return list(self._devices)

    def sync(self):
        # A tiny transfer to each device acts as the queue barrier.
        import jax
        for d in self._devices:
            jax.device_put(0, d).block_until_ready()

    def memory_stats(self):
        try:
            stats = self._devices[0].memory_stats()
        except Exception:
            return {}
        return stats or {}

    def benchmark(self, size=1024, dtype=None, repeats=4):
        """Time a square matmul; returns achieved GFLOP/s.  Plays the role
        of the reference DeviceBenchmark (accelerated_units.py:706-824)."""
        import jax
        import jax.numpy as jnp
        dtype = dtype or jnp.bfloat16
        a = jax.device_put(jnp.ones((size, size), dtype), self._devices[0])
        f = jax.jit(lambda x: x @ x)
        f(a).block_until_ready()  # compile outside the timed region
        t0 = time.perf_counter()
        for _ in range(repeats):
            r = f(a)
        r.block_until_ready()
        dt = (time.perf_counter() - t0) / repeats
        return 2.0 * size ** 3 / dt / 1e9


class TPUDevice(_JaxDevice):
    """The flagship backend: JAX TPU devices over PJRT.

    Requires JAX's default platform to be ``tpu`` — an explicit ``tpu``
    request must not silently degrade (the reference raises on a missing
    CUDA/OCL device, backends.py:452-467).
    """

    BACKEND = "tpu"
    PLATFORM = "tpu"

    def __init__(self, **kwargs):
        if not on_tpu():
            import jax
            raise RuntimeError(
                "backend 'tpu' requested but JAX's default platform is "
                "%r; use backend='cpu' explicitly for the virtual mesh"
                % jax.default_backend())
        super().__init__(**kwargs)


class CPUDevice(_JaxDevice):
    """JAX CPU backend — used by tests as a virtual multi-device mesh
    (XLA_FLAGS=--xla_force_host_platform_device_count=N)."""

    BACKEND = "cpu"
    PLATFORM = "cpu"


class NumpyDevice(Device):
    """Pure-numpy pseudo-device: the parity-test twin (reference
    backends.py:918-949).  Units run their ``numpy_run`` path against it."""

    BACKEND = "numpy"

    @property
    def exists(self):
        return False

    def sync(self):
        pass

    def benchmark(self, size=512, dtype=numpy.float32, repeats=2):
        a = numpy.ones((size, size), dtype)
        t0 = time.perf_counter()
        for _ in range(repeats):
            a @ a
        dt = (time.perf_counter() - t0) / repeats
        return 2.0 * size ** 3 / dt / 1e9


class AutoDevice(Device):
    """Backend auto-selection (reference backends.py:406-423)."""

    BACKEND = "auto"

    @staticmethod
    def pick():
        # a JAX that cannot start its backend raises here: carrying on
        # without a device would hide a failed bring-up
        return "tpu" if on_tpu() else "cpu"

    def __new__(cls, *args, **kwargs):
        return Device(backend=AutoDevice.pick(), **kwargs)


# -- dtype table (reference veles/opencl_types.py:39-77) ----------------------
#: mapping of the config-level dtype names onto numpy/jax dtypes
dtype_map = {
    "float16": numpy.float16,
    "bfloat16": "bfloat16",   # resolved lazily through ml_dtypes via jnp
    "float32": numpy.float32,
    "float64": numpy.float64,
    "int8": numpy.int8,
    "int16": numpy.int16,
    "int32": numpy.int32,
    "int64": numpy.int64,
    "uint8": numpy.uint8,
}


def resolve_dtype(name=None):
    """Config dtype name -> numpy dtype object (jnp understands all)."""
    name = name or root.common.engine.get("dtype", "float32")
    dt = dtype_map[name]
    if dt == "bfloat16":
        import ml_dtypes
        return numpy.dtype(ml_dtypes.bfloat16)
    return numpy.dtype(dt)
