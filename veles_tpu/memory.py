"""Array: host numpy storage paired with an HBM-resident ``jax.Array``.

TPU-native re-design of /root/reference/veles/memory.py (Array :110-511,
Watcher device-memory accounting :56-107).  The reference Array keeps one
host buffer and one OpenCL/CUDA buffer with an explicit
map_read / map_write / map_invalidate / unmap protocol.  JAX arrays are
immutable, so the protocol here tracks *validity epochs* instead of mapping:

- ``map_read``   — make the host copy current (device→host only if stale);
- ``map_write``  — make host current and mark it dirty;
- ``map_invalidate`` — mark host dirty *without* a device pull (host will be
  fully overwritten — reference memory.py:137 fast path);
- ``unmap``      — if host is dirty, push to the device (fresh jax.Array,
  sharded when a sharding is set).

Mutating through ``arr.mem[...]`` between map_write/unmap is exactly the
reference idiom (memory.py:137-141).  Device values are created lazily on
first ``devmem`` access, so graphs build host-side and pay one upload.
"""

import threading

import numpy

from .pickling import Pickleable


class Watcher:
    """Process-wide device-memory accounting (reference memory.py:56-107).

    JAX owns the allocator, so this tracks bytes of live Array devmems plus
    the platform's own ``memory_stats`` when available.
    """

    _lock = threading.RLock()  # reentrant: Array.__del__ may fire mid-GC
    #                            inside add/remove on the same thread
    bytes_in_use = 0
    peak_bytes = 0

    @classmethod
    def add(cls, nbytes):
        with cls._lock:
            cls.bytes_in_use += nbytes
            cls.peak_bytes = max(cls.peak_bytes, cls.bytes_in_use)

    @classmethod
    def remove(cls, nbytes):
        with cls._lock:
            cls.bytes_in_use -= nbytes

    @classmethod
    def reset(cls):
        with cls._lock:
            cls.bytes_in_use = 0
            cls.peak_bytes = 0


class Array(Pickleable):
    """Host numpy array + device ``jax.Array`` with validity tracking."""

    def __init__(self, data=None, shallow_pickle=False):
        super().__init__()
        self._mem = None
        self.shallow_pickle = shallow_pickle
        if data is not None:
            self.mem = data

    def init_unpickled(self):
        super().init_unpickled()
        self._devmem_ = None
        self._host_dirty_ = True
        self._device_dirty_ = False
        self._sharding_ = None
        self._accounted_ = 0

    # -- host side -----------------------------------------------------------
    @property
    def mem(self):
        return self._mem

    @mem.setter
    def mem(self, value):
        if value is None:
            self.reset()
            return
        self._mem = numpy.asarray(value)
        self._host_dirty_ = True
        self._device_dirty_ = False

    def reset(self, new_mem=None):
        """Drop both copies (reference memory.py:331)."""
        self._release_devmem()
        self._mem = new_mem
        self._host_dirty_ = new_mem is not None
        self._device_dirty_ = False

    def __bool__(self):
        return self._mem is not None or self._devmem_ is not None

    @property
    def shape(self):
        m = self._mem if self._mem is not None else self._devmem_
        return m.shape if m is not None else ()

    @property
    def dtype(self):
        m = self._mem if self._mem is not None else self._devmem_
        return m.dtype if m is not None else None

    @property
    def size(self):
        m = self._mem if self._mem is not None else self._devmem_
        return m.size if m is not None else 0

    @property
    def nbytes(self):
        m = self._mem if self._mem is not None else self._devmem_
        return m.nbytes if m is not None else 0

    @property
    def sample_size(self):
        """Elements per leading-axis sample (reference memory.py)."""
        if not self.shape:
            return 0
        return self.size // self.shape[0]

    def __len__(self):
        return self.shape[0] if self.shape else 0

    def __getitem__(self, idx):
        self.map_read()
        return self._mem[idx]

    def __setitem__(self, idx, value):
        self.map_write()
        self._mem[idx] = value

    # -- map/unmap protocol --------------------------------------------------
    def map_read(self):
        if self._device_dirty_ and self._devmem_ is not None:
            self._mem = numpy.asarray(self._devmem_)
            self._device_dirty_ = False
        return self._mem

    def map_write(self):
        self.map_read()
        self._host_dirty_ = True
        return self._mem

    def map_invalidate(self):
        if self._mem is None and self._devmem_ is not None:
            # need a host buffer of the right shape, contents irrelevant
            self._mem = numpy.empty(self._devmem_.shape,
                                    self._devmem_.dtype)
        self._host_dirty_ = True
        self._device_dirty_ = False
        return self._mem

    def unmap(self):
        if self._host_dirty_ and self._mem is not None:
            self._upload()
        return self

    # -- device side ---------------------------------------------------------
    @property
    def devmem(self):
        """The device-resident jax.Array (uploads lazily if host is newer)."""
        if self._host_dirty_ or self._devmem_ is None:
            if self._mem is None:
                return None
            self._upload()
        return self._devmem_

    @devmem.setter
    def devmem(self, value):
        """Accept a fresh device value (the output of a jitted step); the
        host copy becomes stale until map_read."""
        self._release_devmem()
        self._devmem_ = value
        if value is not None:
            self._account(value)
            self._device_dirty_ = True
            self._host_dirty_ = False

    def replace_devmem(self, value):
        """The SAME values placed differently on the device (another
        layout): which of the two copies is current does not change, so
        the next host read downloads nothing it did not before."""
        flags = self._device_dirty_, self._host_dirty_
        self.devmem = value
        self._device_dirty_, self._host_dirty_ = flags

    def swap_devmem(self, value):
        """Hot-path twin of the ``devmem`` setter (the graph compiler
        writes every traced output back each step): one combined
        accounting update under a single Watcher lock instead of
        release+add."""
        try:
            nbytes = value.nbytes
        except Exception:  # noqa: BLE001
            nbytes = 0
        with Watcher._lock:
            Watcher.bytes_in_use += nbytes - self._accounted_
            if Watcher.bytes_in_use > Watcher.peak_bytes:
                Watcher.peak_bytes = Watcher.bytes_in_use
        self._accounted_ = nbytes
        self._devmem_ = value
        self._device_dirty_ = True
        self._host_dirty_ = False

    def set_sharding(self, sharding):
        """Future uploads place the value with this jax.sharding.Sharding."""
        self._sharding_ = sharding
        if self._devmem_ is not None:
            # re-place on next access
            self.map_read()
            self._release_devmem()
            self._host_dirty_ = True

    def _upload(self):
        import jax
        self._release_devmem()
        if self._sharding_ is not None:
            self._devmem_ = jax.device_put(self._mem, self._sharding_)
        else:
            self._devmem_ = jax.device_put(self._mem)
        self._account(self._devmem_)
        self._host_dirty_ = False
        self._device_dirty_ = False

    def _account(self, value):
        try:
            nbytes = value.nbytes
        except Exception:
            nbytes = 0
        self._accounted_ = nbytes
        Watcher.add(nbytes)

    def _release_devmem(self):
        if self._devmem_ is not None:
            Watcher.remove(self._accounted_)
            self._accounted_ = 0
            self._devmem_ = None

    def __del__(self):
        try:
            self._release_devmem()
        except Exception:
            pass  # interpreter teardown

    # -- pickling ------------------------------------------------------------
    def __getstate__(self):
        """Device values are pulled to host before pickling (reference
        memory.py:284-299); shallow_pickle drops the payload for huge
        datasets.  Inside a sharded-checkpoint extraction context
        (checkpoint/tensors.py) large payloads are diverted into the
        sink instead: a device-current value is handed over zero-copy
        as its immutable jax.Array — no device→host pull on the capture
        thread — and a host-current value is snapshotted once."""
        from .checkpoint.tensors import TensorStub, active_sink
        sink = active_sink()
        if sink is not None and not self.shallow_pickle:
            if self._device_dirty_ and self._devmem_ is not None:
                payload, needs_copy = self._devmem_, False
            else:
                payload, needs_copy = self._mem, True
            nbytes = getattr(payload, "nbytes", None)  # None: already a stub
            if nbytes is not None and nbytes >= sink.min_bytes:
                state = super().__getstate__()
                state["_mem"] = TensorStub(
                    sink.add(payload, copy=needs_copy))
                return state
        self.map_read()
        state = super().__getstate__()
        if self.shallow_pickle:
            state["_mem"] = None
        return state

    def __repr__(self):
        return "<Array %s %s host_dirty=%s device=%s>" % (
            self.shape, self.dtype, self._host_dirty_,
            self._devmem_ is not None)
